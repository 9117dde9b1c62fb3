"""A toy copy of the benchmark in a temporary directory: the same files,
with the configurations and the traffic cut to sizes the CPU can run. The
rehearsal path of ``chipbench/run.py`` drives it; it prints no device
metric and no verdict under a device's name."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TOY_KEYS = {
    # Heads of 128 (the kernels' lane width), two kv heads (tp=2 divides
    # them); everything else as small as it goes.
    "hidden_size": 256, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 128,
    "vocab_size": 512,
}
TOY_SERVE = {
    "slots": 4, "prompt_window": 16, "max_new": 16, "ticks_per_sync": 4,
    "commit_every": 3,
    "kv_kernel": True, "require_kernel_engaged": True,
    "compute_dtype": "float32", "param_dtype": "float32",
}
TOY_TRAIN = {"compute_dtype": "float32", "param_dtype": "float32",
             "require_flash": True}
# The open loop has no cell in BENCHMARK.json yet (PERF.md, Open
# questions): the toy keeps one, so that the traffic kind, the loop's
# open-loop path and the latency readers stay driven end to end.
OPEN_LOOP_CELL = "toy.open-loop"
OPEN_LOOP_E2E = ("serve.ttft_p95_ms", "serve.tpot_p95_ms")
OPEN_LOOP_LAYER = (
    "source.queue_wait_ms", "sched.admit_stall_ms", "tick_ms.tpot",
)
TOY_REQUESTS = {
    "deck": 16, "block": 4, "prompt_median": 6, "prompt_sigma": 0.8,
    "prompt_max": 16, "answer_median": 5, "answer_sigma": 0.8,
    "answer_max": 16,
}


def _dump(path: Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=1) + "\n", encoding="utf-8")


def add_open_loop_cell(root: Path, bench: dict) -> None:
    """The backlog cell's requests as arrivals on the wall clock, added
    the way a later PR would: a traffic file and entries."""
    drain = next(
        w for w in bench["workloads"] if w["traffic"] == "backlog"
    )
    src = root / "chipbench" / "workloads" / f"{drain['name']}.json"
    mix = json.loads(src.read_text())
    t = mix["traffic"]
    del t["records"]
    t.update(kind="open_loop", rate_per_s=6.0, burst_mean=3.0)
    mix.update(grace_s=20.0, max_sender_late_ms=50.0)
    _dump(root / "chipbench" / "workloads" / f"{OPEN_LOOP_CELL}.json", mix)
    bench["workloads"].append({
        "name": OPEN_LOOP_CELL, "config": drain["config"],
        "traffic": "open_loop", "chips": 1, "why": "toy",
    })
    for name in OPEN_LOOP_E2E:
        bench["end_to_end"].append({
            "name": name, "unit": "ms", "better": "lower", "bound": 0.1,
            "source": "host_clock", "workloads": [OPEN_LOOP_CELL],
        })
    for name in OPEN_LOOP_LAYER:
        bench["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "scheduler",
            "moves": OPEN_LOOP_E2E[0], "workloads": [OPEN_LOOP_CELL],
        })


def make_toy_root(tmp: Path) -> Path:
    root = Path(tmp) / "toyroot"
    shutil.copytree(
        REPO / "chipbench", root / "chipbench",
        ignore=shutil.ignore_patterns("__pycache__", "testdata"),
    )
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    add_open_loop_cell(root, bench)
    _dump(root / "BENCHMARK.json", bench)
    for c in bench["configs"]:
        path = root / c["file"]
        conf = json.loads(path.read_text())
        conf.update(TOY_KEYS)
        dep = conf["deployment"]
        dep.update(TOY_SERVE if dep["loop"] == "serve" else TOY_TRAIN)
        if dep["loop"] == "train":
            # tests/chipbench/toy_decoder.py: flash forced off the TPU.
            conf["model"] = "toy_decoder"
        _dump(path, conf)
    for w in bench["workloads"]:
        path = root / "chipbench" / "workloads" / f"{w['name']}.json"
        mix = json.loads(path.read_text())
        t = mix["traffic"]
        if mix["loop"] == "serve":
            t.update(TOY_REQUESTS)
            if "records" in t:
                t["records"] = 40
            if "rate_per_s" in t:
                t["rate_per_s"] = 6.0
            mix["grace_s"] = 20.0
            # A toy request has a dozen tokens: read more requests.
            mix["check"].update(max_logit_gap=0.05, sample=24)
        else:
            t.update(seq=128, steps_cap=3000)
            # Float32 on both sides: the toy's limits are float32's.
            mix["check"].update(
                head_block=128, max_loss1_rel_gap=1e-5, max_loss2_rel_gap=1e-5,
                max_grad_norm_gap=1e-4, max_change_norm_gap=1e-3,
            )
        _dump(path, mix)
    return root
