"""The readers of what the program names itself (PR 24): its kernels by
their fixed names, its host spans, its scheduler's counts. Each on the
reduced form of a trace (a dict in the shape ``xplane.reduce`` returns,
or a few events through a fake ``ProfileData``), each returning nothing
where the program has no such name (the parent commit, a run without a
trace), and the program's own share of useful slot-ticks against the one
the benchmark rebuilds from per-request events, on the toy drain."""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from toy import make_toy_root  # noqa: E402

from chipbench import common, xplane  # noqa: E402
from chipbench import run as runner  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
DRAIN = next(w["name"] for w in BENCH["workloads"] if w["traffic"] == "backlog")
PALLAS = 'custom-call(bf16[2]{0} %x), custom_call_target="tpu_custom_call"'
NEW = [
    "sched.admit_fill_pct", "sched.slot_tick_use_pct",
    "sched.host_ms_per_sync", "source.poll_ms.serve", "commit.flush_ms.serve",
    "kvattn.call_us.tput", "flash.fwd_ms.train", "flash.bwd_ms.train",
    "stream.produce_ms.train", "commit.fetch_ms.train",
    "commit.offsets_ms.train",
]


def read(name: str, run: dict):
    return common.load_named("layer_metrics", name).read(run)


def kernel(program: str, count: float, total_s: float) -> dict:
    return {"count": count, "total_s": total_s, "program": program, "text": ""}


def serve_run() -> dict:
    """Two syncs of a drain, as ``xplane.reduce`` would hand them on."""
    spans = {
        "tk_serve:sync": [(1.0, 4.9), (8.0, 4.9)],
        "tk_serve:retire": [(5.9, 0.020), (12.9, 0.010)],
        "tk_serve:output_flush": [(5.92, 0.0004), (12.91, 0.0002)],
        "tk_serve:commit": [(5.921, 0.00002), (12.911, 0.00002)],
        "tk_serve:poll": [(5.93, 0.003), (12.92, 0.001), (12.93, 0.005)],
        "tk_serve:admit_prep": [(5.94, 0.008)],
        "tk_serve:admit": [(5.95, 0.0001)],
        "tk_serve:tick": [(5.96, 0.0002), (12.95, 0.0002)],
        "bench:serve_loop": [(0.0, 14.0)],
    }
    return {
        "trace": {
            "host_spans": spans,
            "kernels": {
                # One tick block of 128 ticks x 32 layers, and the
                # admission's flash forward, which is another kernel.
                "jit_tick_block/tk_kvattn_dynlen.19": kernel(
                    "jit_tick_block", 4096.0, 4096 * 133e-6
                ),
                "jit_admit/tk_flash_fwd.11": kernel("jit_admit", 32.0, 0.4),
            },
            "programs": {"jit_tick_block": {"count": 1.0, "total_s": 4.9}},
        },
        "counters": [
            {"scheduler": {
                "slot_ticks_run": 6144, "slot_ticks_served": 5000,
                "admit_calls": 1, "admit_rows": 3,
                "admit_rows_prefilled": 48,
            }},
            {"scheduler": {
                "slot_ticks_run": 6144 + 2 * 6144,
                "slot_ticks_served": 5000 + 9216,
                "admit_calls": 3, "admit_rows": 3 + 54,
                "admit_rows_prefilled": 48 + 96,
            }},
        ],
    }


def train_run() -> dict:
    """Two steps on 2x2: kernel times are a chip's, as the reducer
    averages them."""
    spans = {
        # An empty poll (the topic is dry), then one that a transform
        # follows: only the second is work.
        "tk_stream:poll": [(0.10, 0.050), (0.20, 0.004)],
        "tk_stream:transform": [(0.205, 0.010)],
        "tk_stream:to_device": [(0.22, 0.002), (0.83, 0.004)],
        "tk_stream:next": [(0.0, 0.0001), (0.6, 0.0001)],
        "tk_commit:wait": [(0.01, 0.59), (0.61, 0.59)],
        "tk_commit:fetch": [(0.6, 0.003), (1.2, 0.005)],
        "tk_commit:offsets": [(0.603, 0.00004), (1.205, 0.00006)],
    }
    step = "jit__step"
    return {
        "trace": {
            "host_spans": spans,
            "kernels": {
                f"{step}/tk_flash_fwd.3": kernel(step, 96.0, 0.060),
                f"{step}/tk_flash_fwd.7": kernel(step, 96.0, 0.040),
                f"{step}/tk_flash_bwd_dq.4": kernel(step, 96.0, 0.050),
                f"{step}/tk_flash_bwd_dkv.5": kernel(step, 96.0, 0.070),
                # Not flash: a kernel a later PR puts on bf16 operands.
                f"{step}/tk_qmatmul.9": kernel(step, 96.0, 0.500),
                # Not the step: the same kernel in another program.
                "jit_eval/tk_flash_fwd.2": kernel("jit_eval", 48.0, 0.030),
            },
            "programs": {step: {"count": 2.0, "total_s": 1.2}},
        },
    }


@pytest.mark.parametrize("name,want", [
    ("sched.admit_fill_pct", 100 * 54 / 96),
    ("sched.slot_tick_use_pct", 100 * 9216 / 12288),
    # retire 30 + flush 0.6 + commit 0.04 + poll 9 + prep 8 + admit 0.1 +
    # tick 0.4 ms, over two syncs; the benchmark's own span is not summed.
    ("sched.host_ms_per_sync", 48.14 / 2),
    ("source.poll_ms.serve", 9.0 / 2),  # three polls, two syncs
    ("commit.flush_ms.serve", 0.3),
    ("kvattn.call_us.tput", 133.0),
])
def test_serving_reader_on_a_reduced_trace(name, want):
    assert read(name, serve_run()) == pytest.approx(want, rel=1e-9)


def test_a_traced_part_without_a_poll_reads_zero_not_nothing():
    """One poll feeds many admissions: the traced part of a drain often
    holds none, and the metric still has to be on the line."""
    run = serve_run()
    del run["trace"]["host_spans"]["tk_serve:poll"]
    assert read("source.poll_ms.serve", run) == 0.0


@pytest.mark.parametrize("name,want", [
    ("flash.fwd_ms.train", 1e3 * 0.100 / 2),
    ("flash.bwd_ms.train", 1e3 * 0.120 / 2),
    # (the poll that a transform follows 4 + transform 10 + transfers
    # 2 + 4 ms) over the two batches shipped.
    ("stream.produce_ms.train", 20.0 / 2),
    ("commit.fetch_ms.train", 4.0),
    ("commit.offsets_ms.train", 0.05),
])
def test_training_reader_on_a_reduced_trace(name, want):
    assert read(name, train_run()) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_to_read_on_the_parent(name):
    """The parent commit's program: kernels named after the jaxpr round
    them, the five serving spans it had, no counts of the scheduler's.
    Each new reader returns nothing and does not raise; so too without
    a trace."""
    parent = {
        "trace": {
            "host_spans": {
                "tk_serve:sync": [(1.0, 4.9)], "tk_serve:tick": [(5.9, 1e-4)],
                "tk_serve:admit": [(5.8, 1e-4)],
                "tk_serve:commit": [(5.7, 1e-5)],
                "bench:commit": [(0.0, 0.8)], "bench_step": [(0.0, 0.8)],
            },
            "kernels": {
                "jit_tick_block/closed_call.19": kernel("jit_tick_block", 4096.0, 0.5),
                "jit__step/checkpoint.20": kernel("jit__step", 40.0, 0.1),
            },
            "programs": {"jit__step": {"count": 2.0, "total_s": 1.6}},
        },
        "counters": [{"ticks": 1}, {"ticks": 3}],
    }
    assert read(name, parent) is None
    assert read(name, {"trace": None, "counters": [{}, {}]}) is None


def test_named_kernels_and_spans_through_the_reducer():
    """A few events through a fake ``ProfileData``: the reducer keeps a
    named Pallas kernel under ``program/name.N``, groups the breakdown
    under the name, keeps every ``tk_*`` span and charges the idle gap
    to the program's span that covers it."""
    ev = lambda name, start_ms, dur_ms: NS(  # noqa: E731
        name=name, start_ns=int(start_ms * 1e6), duration_ns=int(dur_ms * 1e6)
    )
    ops = [
        ev(f"%tk_kvattn_dynlen.19 = bf16[2]{{0}} {PALLAS}", 0, 2),
        ev("%fusion.3 = bf16[2]{0} fusion(bf16[2]{0} %p), kind=kLoop", 2, 3),
        ev(f"%tk_kvattn_dynlen.19 = bf16[2]{{0}} {PALLAS}", 105, 2),
        ev("%fusion.3 = bf16[2]{0} fusion(bf16[2]{0} %p), kind=kLoop", 107, 3),
    ]
    profile = NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=ops),
            NS(name="XLA Modules", events=[
                ev("jit_tick_block(1)", 0, 5), ev("jit_tick_block(1)", 105, 5),
            ]),
        ]),
        NS(name="/host:CPU", lines=[NS(name="python", events=[
            ev("tk_serve:sync", 0, 6), ev("tk_serve:retire", 6, 80),
            ev("tk_serve:poll", 86, 4), ev("tk_serve:tick", 100, 1),
            ev("not_ours", 0, 200),
        ])]),
    ])
    tr = xplane.reduce(profile)
    assert set(tr["kernels"]) == {"jit_tick_block/tk_kvattn_dynlen.19"}
    assert tr["kernels"]["jit_tick_block/tk_kvattn_dynlen.19"]["count"] == 2
    assert dict(tr["device_ops"])["pallas_kernel:tk_kvattn_dynlen"] == (
        pytest.approx(0.004)
    )
    assert set(tr["host_spans"]) == {
        "tk_serve:sync", "tk_serve:retire", "tk_serve:poll", "tk_serve:tick",
    }
    assert tr["idle_gaps"] == [("tk_serve:retire", pytest.approx(0.100))]
    run = {"trace": tr, "counters": [{}, {}]}
    assert read("kvattn.call_us.tput", run) == pytest.approx(2000.0)
    assert read("source.poll_ms.serve", run) == pytest.approx(4.0)
    assert read("sched.host_ms_per_sync", run) == pytest.approx(85.0)


def test_every_new_metric_has_its_entry_and_its_reader():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] == NEW
    perf = (REPO / "PERF.md").read_text()
    for name in NEW:
        m = entries[name]
        assert (REPO / "chipbench/layer_metrics" / f"{name}.py").is_file()
        assert m["workloads"] and m["layer"] in perf
        assert f"`{name}`" in perf, f"PERF.md does not name {name}"


def test_the_programs_share_of_useful_slot_ticks_agrees_with_the_benchmarks(
    tmp_path,
):
    """The toy drain through the serving loop: the program's own counts
    (``ServeMetrics``) and the benchmark's rebuilding of the same share
    from the tracer's per-request events agree, and the dense admission's
    fill is the admitted rows over slots a call."""
    root = make_toy_root(tmp_path)
    bench, cell, conf, mix = runner.load_cell(root, DRAIN)
    import jax

    ctx = common.RunContext(
        cell=cell, conf=conf, mix=mix, seed=11, seconds=1.0, trace=True,
        devices=jax.devices()[:1], t_start=time.perf_counter(),
        rehearsal=True, root=root,
    )
    run = common.load_named("loops", mix["loop"], root).run(ctx)
    run.update(conf=conf, t0=ctx.t0, t_close=ctx.t_close, root=root)
    assert ctx.checks.correct
    use = read("sched.slot_tick_use_pct", run)
    occupancy = read("sched.occupancy_pct", run)
    assert 0 < use <= 100
    assert use == pytest.approx(occupancy, abs=1.0)
    first, last = (c["scheduler"] for c in (run["counters"][0], run["counters"][-1]))
    calls = last["admit_calls"] - first["admit_calls"]
    rows = last["admit_rows"] - first["admit_rows"]
    admitted = sum(1 for r in run["requests"] if r["active"] is not None)
    assert rows == admitted and calls > 0
    assert read("sched.admit_fill_pct", run) == pytest.approx(
        100.0 * rows / (calls * run["slots"])
    )
    # A rehearsal has no device trace: the span and kernel readers have
    # nothing to read, and say so.
    assert run["trace"] is None
    assert read("sched.host_ms_per_sync", run) is None
