"""The reduction from the profiler's file to busy time, program time,
kernel time and the charged idle gaps, on a small trace recorded on the
v5e in PR 23: two steps of ``internlm2-1.8b.pretrain-4k-1chip`` (20
layers), trimmed to the device's program and operation lines and the
benchmark's own host spans, long operation texts cut short."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import common, xplane  # noqa: E402
from chipbench.weights import Dims  # noqa: E402

TRACE = REPO / "chipbench/testdata/train_steps_v5e.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_file(TRACE, n_devices=1)


def test_the_trace_is_small():
    assert TRACE.stat().st_size < 400_000
    others = [
        f for f in (REPO / "chipbench").rglob("*")
        if f.suffix in (".pb", ".gz") and f != TRACE
    ]
    assert not others  # no other trace, no compile cache


def test_window_and_busy(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(1.587807, abs=1e-5)
    assert reduced["busy_s"] == pytest.approx(1.582877, abs=1e-5)
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.0031, abs=2e-4)
    assert reduced["longest_gap_s"] == pytest.approx(0.004929, abs=1e-5)


def test_programs(reduced):
    step = reduced["programs"]["jit__step"]
    assert step["count"] == 2
    assert step["total_s"] == pytest.approx(1.582883, abs=1e-5)


def test_kernels_are_the_pallas_calls_under_their_program(reduced):
    k = reduced["kernels"]
    assert sorted(k) == [
        "jit__step/checkpoint.20", "jit__step/checkpoint.21",
        "jit__step/closed_call.9", "jit__step/rematted_computation.10",
    ]
    # Four flash kernels a layer (forward, the forward again under remat,
    # two backward), 20 layers, two steps.
    assert {v["count"] for v in k.values()} == {40}
    assert sum(v["total_s"] for v in k.values()) == pytest.approx(
        0.393493, abs=1e-5
    )
    for v in k.values():
        assert 'custom_call_target="tpu_custom_call"' in v["text"]
        assert v["program"] == "jit__step"


def test_containers_are_left_out_of_the_breakdown(reduced):
    names = [n for n, _s in reduced["device_ops"]]
    assert not [n for n in names if n.startswith(("while", "call"))]
    assert names[0] == "fusion:fusion"
    assert "pallas_kernel:checkpoint" in names
    # Leaves only: together they cannot exceed the busy time.
    assert sum(s for _n, s in reduced["device_ops"]) <= reduced["busy_s"]


def test_idle_gaps_are_charged_to_the_host_span_over_them(reduced):
    (name, seconds), = reduced["idle_gaps"]
    assert name == "bench_step"  # between two steps: the commit's fetch
    assert seconds == pytest.approx(0.00493, abs=1e-5)
    assert {k: len(v) for k, v in reduced["host_spans"].items()} == {
        "bench_step": 2, "bench:next_batch": 2, "bench:step_dispatch": 2,
        "bench:commit": 2,
    }
    assert reduced["collective_s"] == 0.0


@pytest.mark.parametrize("text,want", [
    ("%while.9 = (s32[]{:T(128)}, bf16[2,4096]{1,0:T(8,128)(2,1)}) while((s32[]{:T(128)}) %t), body=%b", "while"),
    ("%fusion.3 = bf16[2,8]{1,0:T(8,128)(2,1)} fusion(bf16[2,8]{1,0} %p), kind=kLoop", "fusion"),
    ("%all-reduce.7 = f32[16]{0:T(128)} all-reduce(f32[16]{0} %x), replica_groups={}", "all-reduce"),
    ("%closed_call.19 = bf16[48,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} custom-call(s32[48]{0} %g)", "custom-call"),
    ("%copy.110 = s8[32,48]{1,0:T(8,128)(4,1)} copy(s8[32,48]{1,0} %c)", "copy"),
    ("%while.9 = (s32[]{:T(128)}, bf16[2,4096,2048]{1,2,0:T(8,1", "while"),
    ("fusion.123", "fusion"),
])
def test_opcode(text, want):
    assert xplane.opcode(text) == want


@pytest.mark.parametrize("name,want", [
    ("jit_tick_block(14011707681933497497)", "jit_tick_block"),
    ("jit__step(15644412041929021176)", "jit__step"),
    ("jit_admit", "jit_admit"),
])
def test_program_name(name, want):
    assert xplane.program_name(name) == want


def fake_run(reduced):
    conf = json.loads(
        (REPO / "chipbench/configs/internlm2-1.8b-1chip.json").read_text()
    )
    return {
        "trace": reduced, "dims": Dims.from_conf(conf), "batch": 2,
        "seq": 4096, "chips": 1, "root": REPO, "conf": conf,
        "peaks": common.load_peaks("TPU v5 lite"),
        "steps": [
            {"dispatch_s": 0.0021, "commit_s": 0.7931, "batch_wait_s": 8e-5,
             "tokens": 8192, "committed": True}
        ] * 2,
        "window_s": 1.5905,
    }


def test_readers_on_the_recorded_trace(reduced):
    run = fake_run(reduced)
    read = lambda name: common.load_named("layer_metrics", name).read(run)  # noqa: E731
    assert read("step_ms.train") == pytest.approx(791.44, abs=0.01)
    # 2 steps x 3 x (2 rows x 20 layers x 16 heads x 2 x 4096^2 x 128
    # FLOPs) over 0.3935 s of kernels and 197e12: a fifth of the peak.
    need = 2 * 3 * 2 * 20 * 16 * 2 * 4096 * 4096 * 128
    assert read("flash.roofline_pct") == pytest.approx(
        100 * need / (0.393493 * 197e12), rel=1e-4
    )
    assert 15 < read("flash.roofline_pct") < 30
    assert read("commit_ms.train") == pytest.approx(795.2 - 791.44, abs=0.01)
    assert read("batch_wait_ms.train") == pytest.approx(0.08)
    assert 40 < read("mfu_pct.train") < 60
    assert read("collective_ms.train") is None  # one chip: nothing to read
    assert read("kvattn.roofline_pct") is None  # no tick program here


def test_a_reader_with_no_trace_returns_nothing(reduced):
    run = fake_run(reduced)
    run["trace"] = None
    for name in ("step_ms.train", "flash.roofline_pct", "commit_ms.train"):
        assert common.load_named("layer_metrics", name).read(run) is None
