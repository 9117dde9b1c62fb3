"""A cell, a configuration, a traffic kind, a per-layer metric and a
kernel's count, each added as new files and new entries with no edit to a
file that is there, and run."""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from toy import make_toy_root  # noqa: E402

from chipbench import run as runner  # noqa: E402


def test_new_files_and_entries_are_enough(tmp_path, capsys):
    root = make_toy_root(tmp_path)
    before = {
        f: f.read_bytes() for f in (root / "chipbench").rglob("*") if f.is_file()
    }
    cb = root / "chipbench"
    # A configuration: a file of its own.
    conf = json.loads((cb / "configs/internlm2-1.8b-1chip.json").read_text())
    conf["num_hidden_layers"] = 1
    (cb / "configs/extra-1layer.json").write_text(json.dumps(conf))
    # A traffic kind: a file of its own, found by name.
    (cb / "traffic/token_rows_odd.py").write_text(
        "import numpy as np\n"
        "def generate(params, seed, frame):\n"
        "    rows = int(params['steps_cap']) * int(frame['batch'])\n"
        "    rng = np.random.default_rng([int(seed), 99])\n"
        "    r = rng.integers(0, frame['vocab'] // 2, (rows, int(frame['seq'])), dtype=np.int32)\n"
        "    return {'rows': 2 * r + 1}\n"
    )
    # A cell: its traffic file.
    mix = json.loads(
        (cb / "workloads/internlm2-1.8b.pretrain-4k-1chip.json").read_text()
    )
    mix["traffic"]["kind"] = "token_rows_odd"
    (cb / "workloads/extra.odd-rows.json").write_text(json.dumps(mix))
    # A kernel's count and a per-layer metric that reads it.
    (cb / "kernels/oddness.py").write_text(
        "def rows_needed(steps, batch):\n    return steps * batch\n"
    )
    (cb / "layer_metrics/rows_read.train.py").write_text(
        "from chipbench import common\n"
        "def read(run):\n"
        "    k = common.load_named('kernels', 'oddness', run['root'])\n"
        "    return k.rows_needed(len(run['steps']), run['batch'])\n"
    )
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "extra-1layer", "source": "https://example.org/extra",
        "file": "chipbench/configs/extra-1layer.json",
        "reduced": ["num_hidden_layers"], "why": "test",
    })
    bench["workloads"].append({
        "name": "extra.odd-rows", "config": "extra-1layer",
        "traffic": "token_rows_odd", "chips": 1, "why": "test",
    })
    for m in bench["end_to_end"]:
        if m["name"] == "train.tokens_per_s":
            m["workloads"].append("extra.odd-rows")
    bench["per_layer"].append({
        "name": "rows_read.train", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "transform and batcher",
        "moves": "train.tokens_per_s", "workloads": ["extra.odd-rows"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    rc = runner.main(
        ["--workload", "extra.odd-rows", "--seed", "8", "--seconds", "0.3",
         "--trace", "1"], root=root, rehearsal=True,
    )
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rc == 0 and last["checks_passed"] is True
    assert last["cell"] == "extra.odd-rows"
    assert last["metric_names"] == ["rows_read.train"]
    # No file that was there has changed.
    for f, data in before.items():
        assert f.read_bytes() == data, f
