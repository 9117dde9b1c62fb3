"""The benchmark's arithmetic against hand-worked cases: percentiles,
time per output token, unions and gaps of intervals, model FLOPs and MFU,
flash FLOPs, and the int8 read's bytes at valid lengths."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import common, stats  # noqa: E402
from chipbench.weights import Dims  # noqa: E402

MISTRAL = json.loads(
    (REPO / "chipbench/configs/mistral-7b-v0.3-w8.json").read_text()
)
INTERNLM = json.loads(
    (REPO / "chipbench/configs/internlm2-1.8b.json").read_text()
)


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 95, 10),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90, 9),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 50, 5),
    (list(range(1, 201)), 95, 190),
    ([7.5], 95, 7.5),
    ([3, 1, 2], 100, 3),
    ([3, 1, 2], 1, 1),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


@pytest.mark.parametrize("n,q,want", [(200, 95, 10), (100, 95, 5), (20, 95, 1), (0, 95, 0)])
def test_samples_beyond(n, q, want):
    assert stats.samples_beyond(n, q) == want


@pytest.mark.parametrize("values,want", [([1, 3, 2], 2), ([4, 1, 3, 2], 2.5)])
def test_median(values, want):
    assert stats.median(values) == want


@pytest.mark.parametrize("args,want", [
    ((10.0, 12.0, 5, 25), 0.1),      # 20 later tokens in 2 s
    ((10.0, 10.0, 5, 5), None),      # nothing after the first sync
    ((0.0, 3.0, 1, 4), 1.0),
])
def test_tpot(args, want):
    assert stats.tpot_s(*args) == want


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert stats.union_seconds(iv) == pytest.approx(3.0)
    assert stats.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert stats.gaps([], 1.0, 2.0) == [(1.0, 2.0)]
    assert stats.union_seconds([]) == 0.0


def test_published_sizes_give_the_published_parameter_counts():
    m, i = Dims.from_conf(MISTRAL), Dims.from_conf(INTERNLM)
    # Mistral-7B-v0.3: 7,248,023,552 parameters.
    assert m.params == 7_248_023_552
    # InternLM2-1.8B: 1,889,110,016 parameters.
    assert i.params == 1_889_110_016
    assert i.matmul_params == 24 * 62_914_560 + 2048 * 92544


def test_train_flops_a_token_by_hand():
    d = Dims.from_conf(INTERNLM)
    layer = 2048 * (16 + 2 * 8) * 128 + 16 * 128 * 2048 + 3 * 2048 * 8192
    assert layer == 62_914_560
    want = 6 * (24 * layer + 2048 * 92544) + 6 * 24 * 16 * 128 * 4096
    assert stats.train_flops_per_token(d, 4096) == want


def test_mfu_by_hand():
    # 10,000 tokens/s at 1e10 FLOPs a token on one chip of 2e14: 50%.
    assert stats.mfu_pct(1e4, 1e10, 1, 2e14) == pytest.approx(50.0)
    assert stats.mfu_pct(1e4, 1e10, 4, 2e14) == pytest.approx(12.5)


def test_flash_flops_by_hand():
    k = common.load_named("kernels", "flash")
    d = Dims.from_conf(INTERNLM)
    # One row, one layer, one head: two half squares of 4096 x 4096 x 128
    # multiply-adds, 2 FLOPs each.
    one = 2 * 2 * 4096 * 4096 * 128 / 2
    assert k.forward_flops(d, 1, 4096) == 24 * 16 * one
    assert k.step_flops(d, 2, 4096) == 3 * 2 * 24 * 16 * one


def test_kvattn_bytes_at_valid_lengths_by_hand():
    k = common.load_named("kernels", "kvattn")
    d = Dims.from_conf(MISTRAL)
    # One cached token: keys and values, 32 layers, 8 kv heads, 128 int8
    # and a float32 scale each: 67,584 bytes (ISSUE: "67.6 KB").
    assert k.bytes_per_position(d) == 2 * 32 * 8 * (128 + 4) == 67_584
    # Tokens 1, 2, 3 behind a 512 window read 513 + 514 + 515 positions.
    assert k.positions_of_block(512, 1, 3) == 513 + 514 + 515
    assert k.positions_of_block(512, 7, 1) == 519
    assert k.positions_of_block(512, 5, 0) == 0
    need = k.read_bytes(d, 1542, 3)
    assert need == 1542 * 67_584 + 3 * 32 * 2 * 32 * 128 * 2
    # A pool-shaped read of 1024 positions would move about twice that.
    assert 3 * 1024 * 67_584 > 1.9 * need


def test_checks_are_all_or_nothing():
    c = common.Checks()
    assert not c.correct  # nothing compared is not correct
    c.exact("same", 0)
    c.at_most("small", 0.5, 1.0)
    assert c.correct
    c.at_most("nan", float("nan"), 1.0)
    assert not c.correct
    c2 = common.Checks()
    c2.exact("off by one", -1)
    assert not c2.correct
    c3 = common.Checks()
    c3.at_least("moved", 1.9, 1.2)
    assert c3.correct


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(common.Refused):
        common.load_peaks("TPU v9 imaginary")
    assert common.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12


def two_requests() -> dict:
    """A window of 10 s over 2 slots: one request finished, one in flight
    at the close with a sync after it."""
    base = {"partition": 0, "max_new": 8, "prompt_len": 4, "committed": None}
    return {
        "t0": 100.0, "t_close": 110.0, "window_s": 10.0, "deadline": 110.0,
        "t_before_flush": 109.9,
        "slots": 2, "trace": None,
        "counters": [{"ticks": 1}, {"ticks": 4}],  # 3 blocks in the window
        "conf": {"deployment": {"ticks_per_sync": 4}},
        "requests": [
            {**base, "offset": 0, "due": 100.0, "polled": 100.5,
             "active": 101.0, "first": 103.0, "n_first": 4,
             "syncs": [(103.0, 4), (106.0, 4)], "finished": 106.0,
             "n_tokens": 8, "committed": 106.1},
            {**base, "offset": 1, "due": 102.0, "polled": 103.0,
             "active": 103.5, "first": 106.0, "n_first": 3,
             "syncs": [(106.0, 3), (111.0, 5)], "finished": None,
             "n_tokens": 0},
        ],
    }


@pytest.mark.parametrize("kind,name,want", [
    # 4 + 4 + 3 tokens surfaced inside the window; the sync at 111 is not.
    ("e2e_metrics", "serve.tokens_per_s", 1.1),
    ("e2e_metrics", "serve.ttft_p95_ms", 4000.0),   # 3 s and 4 s
    ("e2e_metrics", "serve.tpot_p95_ms", 750.0),    # 3 s for 4 later tokens
    # 3 + 4 + 2 ticks' tokens of 2 slots x 4 ticks x 3 blocks.
    ("layer_metrics", "sched.occupancy_pct", 37.5),
    ("layer_metrics", "source.queue_wait_ms", 750.0),  # 0.5 s and 1.0 s
    ("layer_metrics", "sched.admit_stall_ms", None),  # no trace, no reading
    # The 8 tokens of the one completion committed before the last flush.
    ("layer_metrics", "committed_tokens_per_s.serve", 0.8),
])
def test_serving_readers_by_hand(kind, name, want):
    got = common.load_named(kind, name).read(two_requests())
    assert got == (pytest.approx(want) if want is not None else None)


def test_a_request_never_served_counts_to_the_run_s_end():
    run = two_requests()
    run["requests"][1].update(first=None, n_first=0, syncs=[], active=None)
    ttft = common.load_named("e2e_metrics", "serve.ttft_p95_ms").read(run)
    assert ttft == pytest.approx(8000.0)  # due at 102, the run ended at 110


def test_a_completion_committed_by_the_last_flush_is_not_the_cadence_s():
    run = two_requests()
    run["requests"][0]["committed"] = 109.95  # after t_before_flush
    name = "committed_tokens_per_s.serve"
    assert common.load_named("layer_metrics", name).read(run) == 0.0


def _done(partition, offset, finished):
    return {"partition": partition, "offset": offset, "finished": finished}


def test_commit_cadence_by_hand():
    """Commits at 103 and 106 in a window from 100 to 110: two
    completions before the first, three between them, one after; the last
    commit found offsets 0 to 2 of partition 0 finished (3 is open, 4
    finished behind it) and all of partition 1 but the warm-up's record
    still to come."""
    from chipbench.loops import serve

    requests = [
        _done(0, 1, 101.0), _done(0, 2, 102.0), _done(0, 4, 104.0),
        _done(1, 1, 104.5), _done(1, 2, 105.0), _done(0, 3, 108.0),
        _done(1, 3, None), _done(1, 4, 99.0),  # before the window: not counted
    ]
    got = serve.commit_cadence(
        requests, {(0, 0), (1, 0)}, [98.0, 103.0, 106.0, 111.0],
        100.0, 110.0, {0: 6, 1: 5},
    )
    assert got == {
        "commits": 2, "between": 3, "at_close": 1, "first_open": {0: 3, 1: 3},
    }
    none = serve.commit_cadence(requests, {(0, 0), (1, 0)}, [], 100.0, 110.0,
                                {0: 6, 1: 5})
    assert none["commits"] == 0 and none["at_close"] == 6
    assert none["between"] == 0 and none["first_open"] == {0: 1, 1: 1}


def test_training_rate_counts_committed_steps_only():
    run = {"window_s": 4.0, "steps": [
        {"tokens": 8192, "committed": True}, {"tokens": 8192, "committed": True},
        {"tokens": 8192, "committed": False},
    ]}
    got = common.load_named("e2e_metrics", "train.tokens_per_s").read(run)
    assert got == pytest.approx(4096.0)
