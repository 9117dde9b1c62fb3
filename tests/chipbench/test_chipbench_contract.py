"""BENCHMARK.json against the files it names and the rules of its
contract that a typo would break: every name resolves to a file, every
name and unit is made of the allowed characters, every per-layer metric
moves an end-to-end metric that its cells report."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
E2E = [m["name"] for m in BENCH["end_to_end"]]
LAYER = [m["name"] for m in BENCH["per_layer"]]
WIDTH = re.compile(
    r"(hidden_size|intermediate|latent|state_size|proj|_dim$|_rank$|head_dim|"
    r"expansion|experts_per_tok)"
)


def by_name(section: str, name: str) -> dict:
    return next(e for e in BENCH[section] if e["name"] == name)


def test_top_level_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert BENCH["command"][:2] == ["python3", "chipbench/run.py"]
    assert (REPO / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    for p in BENCH["paths"]:
        assert (REPO / p).is_dir()


def test_full_check_fits_its_budget():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_are_unique():
    for section in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[section]]
        assert len(names) == len(set(names))
    assert len(E2E + LAYER) == len(set(E2E + LAYER))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_four_chip_cells_are_at_most_a_quarter_or_one():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration(name):
    c = by_name("configs", name)
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(name) and len(c["why"]) <= 200
    assert c["source"].startswith("https://")
    path = REPO / c["file"]
    assert path.is_file() and c["file"].startswith("chipbench/configs/")
    conf = json.loads(path.read_text())
    # Every key changed from the source is listed, and none is a width.
    assert sorted(conf["changed_from_source"]) == sorted(c["reduced"])
    for key in c["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
    assert any(w["config"] == name for w in BENCH["workloads"])
    for key in ("model", "reference", "deployment", "assumed"):
        assert key in conf


def test_the_two_internlm_files_differ_in_depth_alone():
    a = json.loads((REPO / "chipbench/configs/internlm2-1.8b.json").read_text())
    b = json.loads(
        (REPO / "chipbench/configs/internlm2-1.8b-1chip.json").read_text()
    )
    published = [
        k for k in a
        if k not in ("deployment", "assumed", "changed_from_source", "notes")
    ]
    assert [k for k in published if a[k] != b[k]] == ["num_hidden_layers"]
    assert a["num_hidden_layers"] == 24 and b["num_hidden_layers"] % 2 == 0


@pytest.mark.parametrize("name", CELLS)
def test_cell(name):
    w = by_name("workloads", name)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(name) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert w["config"] in CONFIGS
    mix = json.loads(
        (REPO / "chipbench/workloads" / f"{name}.json").read_text()
    )
    assert mix["traffic"]["kind"] == w["traffic"]
    assert (REPO / "chipbench/traffic" / f"{w['traffic']}.py").is_file()
    assert (REPO / "chipbench/loops" / f"{mix['loop']}.py").is_file()
    conf = json.loads((REPO / by_name("configs", w["config"])["file"]).read_text())
    assert conf["deployment"]["loop"] == mix["loop"]
    mesh = conf["deployment"].get("mesh") or {"one": 1}
    chips = 1
    for size in mesh.values():
        chips *= size
    assert chips == w["chips"]
    # setup_s, one more end-to-end metric and a per-layer metric at least.
    reported = [
        m["name"] for m in BENCH["end_to_end"]
        if "workloads" not in m or name in m["workloads"]
    ]
    assert "setup_s" in reported and len(reported) >= 2
    assert any(name in m.get("workloads", CELLS) for m in BENCH["per_layer"])


@pytest.mark.parametrize("name", E2E)
def test_end_to_end_metric(name):
    m = by_name("end_to_end", name)
    assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    assert (REPO / "chipbench/e2e_metrics" / f"{name}.py").is_file()
    for cell in m.get("workloads", []):
        assert cell in CELLS


@pytest.mark.parametrize("name", LAYER)
def test_per_layer_metric(name):
    m = by_name("per_layer", name)
    assert set(m) <= {
        "name", "unit", "better", "source", "layer", "moves", "workloads",
    }
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    assert (REPO / "chipbench/layer_metrics" / f"{name}.py").is_file()
    moved = by_name("end_to_end", m["moves"])
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in moved.get("workloads", CELLS), (
            f"{name} lists {cell}, which does not report {m['moves']}"
        )
    if name.endswith("roofline_pct") or "mfu" in name:
        assert m["unit"] == "%"


def test_layers_are_those_perf_md_lists():
    text = (REPO / "PERF.md").read_text()
    for layer in {m["layer"] for m in BENCH["per_layer"]}:
        assert layer in text, layer


def test_files_under_paths_are_named_from_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in BENCH["paths"]:
        for f in (REPO / p).rglob("*"):
            rel = str(f.relative_to(REPO))
            if "__pycache__" in rel or not f.is_file():
                continue
            assert ok.match(rel), rel


def test_peaks_name_their_source():
    peaks = json.loads((REPO / "chipbench/peaks.json").read_text())
    for kind, row in peaks.items():
        assert row["bf16_flops"] > 0 and row["hbm_bytes_s"] > 0
        assert "source" in row and kind
