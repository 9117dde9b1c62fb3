"""The traffic generators: pure functions of the seed, different between
seeds, and the same set of sizes and arrivals for every seed."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import common  # noqa: E402
from chipbench.traffic import _mix  # noqa: E402

SEEDS = [0, 1, 7, 2**31 + 5, 4294967295]
FRAME = {"prompt_window": 512, "max_new": 512, "vocab": 32768,
         "seconds": 40.0, "partitions": 2}
DRAIN = "mistral7b.backlog-drain"


def mix_of(cell: str) -> dict:
    return json.loads(
        (REPO / "chipbench/workloads" / f"{cell}.json").read_text()
    )["traffic"]


def open_loop_of(cell: str) -> dict:
    """The cell's requests as arrivals: no open-loop cell is in
    BENCHMARK.json yet (PERF.md, Open questions), the kind stays tested."""
    p = dict(mix_of(cell), kind="open_loop", rate_per_s=3.0, burst_mean=3.0)
    del p["records"]
    return p


MIXES = {"backlog": mix_of(DRAIN), "open_loop": open_loop_of(DRAIN)}


def digest(plan: dict):
    return [
        (r["due_s"], r["max_new"], r["key"], r["partition"],
         r["tokens"].tobytes())
        for r in plan["records"]
    ]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", sorted(MIXES))
def test_serving_traffic_is_a_pure_function_of_the_seed(kind, seed):
    p = MIXES[kind]
    gen = common.load_named("traffic", p["kind"])
    a, b = gen.generate(p, seed, FRAME), gen.generate(p, seed, FRAME)
    assert digest(a) == digest(b)
    other = gen.generate(p, seed + 1, FRAME)
    assert digest(a) != digest(other)
    # Another seed, the same sizes and arrivals in another order.
    assert sorted(r["max_new"] for r in a["records"]) == sorted(
        r["max_new"] for r in other["records"]
    )
    assert sorted(len(r["tokens"]) for r in a["records"]) == sorted(
        len(r["tokens"]) for r in other["records"]
    )
    assert len(a["records"]) == len(other["records"])
    for r in a["records"]:
        assert 1 <= len(r["tokens"]) <= 512 and 2 <= r["max_new"] <= 512
        assert r["tokens"].dtype == np.int32
        assert 0 < r["tokens"].min() and r["tokens"].max() < 32768
    # A tenant's records stay on one partition, as a keyed producer's do.
    where = {}
    for r in a["records"]:
        assert where.setdefault(r["key"], r["partition"]) == r["partition"]
    assert set(where.values()) == {0, 1}


@pytest.mark.parametrize("seed", SEEDS)
def test_every_partition_carries_the_whole_distribution(seed):
    """A server may drain one partition before it touches the next: each
    partition's stream, read in offset order, is whole decks of the same
    sizes, and every run of ``block`` records in it spans the deck."""
    p = MIXES["backlog"]
    plan = common.load_named("traffic", "backlog").generate(p, seed, FRAME)
    deck = sorted(_mix.request_deck(p, 512, 512)[1].tolist())
    for part in (0, 1):
        budgets = [r["max_new"] for r in plan["records"] if r["partition"] == part]
        assert len(budgets) > 2 * p["deck"]
        for i in range(0, len(budgets) - p["deck"] + 1, p["deck"]):
            assert sorted(budgets[i: i + p["deck"]]) == deck
        means = [
            np.mean(budgets[i: i + p["block"]])
            for i in range(0, 2 * p["deck"], p["block"])
        ]
        assert max(means) - min(means) < 0.25 * np.mean(deck)


@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_arrivals(seed):
    p = MIXES["open_loop"]
    gen = common.load_named("traffic", "open_loop")
    plan = gen.generate(p, seed, FRAME)
    due = [r["due_s"] for r in plan["records"]]
    assert plan["open_loop"] and due == sorted(due)
    assert 0 < due[0] and due[-1] < FRAME["seconds"]
    # The offered load is the file's rate, whatever the seed.
    assert len(due) / FRAME["seconds"] == pytest.approx(p["rate_per_s"], rel=0.1)
    other = gen.generate(p, seed + 13, FRAME)
    assert len(other["records"]) == len(due)


@pytest.mark.parametrize("seed", SEEDS)
def test_token_rows(seed):
    gen = common.load_named("traffic", "token_rows")
    p = {"kind": "token_rows", "seq": 64, "rows_per_step": 2, "steps_cap": 5}
    frame = {"seq": 64, "batch": 2, "vocab": 92544}
    a = gen.generate(p, seed, frame)["rows"]
    assert a.shape == (10, 64) and a.dtype == np.int32
    assert (a == gen.generate(p, seed, frame)["rows"]).all()
    assert (a != gen.generate(p, seed + 1, frame)["rows"]).any()
    assert len({r.tobytes() for r in a}) == 10  # rows that all differ
    assert 0 <= a.min() and a.max() < 92544


def test_lognormal_deck_is_the_distribution_s_quantiles():
    deck = _mix.lognormal_deck(128, 0.8, 2, 512, 256)
    assert len(deck) == 256 and (np.diff(deck) >= 0).all()
    assert abs(int(np.median(deck)) - 128) <= 1
    assert deck.min() >= 2 and deck.max() == 512
    # Mean of a lognormal cut at 512: below 128 * exp(0.32) = 176.
    assert 150 < deck.mean() < 176


def test_poisson_and_exponential_decks():
    assert _mix.poisson_deck(2.0, 10).tolist() == [0, 1, 1, 1, 2, 2, 2, 3, 3, 5]
    gaps = _mix.exponential_deck(0.5, 1000)
    assert gaps.mean() == pytest.approx(0.5, rel=0.01)


def test_zipf_counts_sum_and_order():
    counts = _mix.zipf_counts(100, 8, 1.1)
    assert counts.sum() == 100 and (np.diff(counts) <= 0).all()
    assert counts.tolist() == [40, 18, 12, 9, 7, 5, 5, 4]


@pytest.mark.parametrize("seed", [0, 5])
def test_stratified_order_spreads_every_block(seed):
    values = _mix.lognormal_deck(128, 0.8, 2, 512, 256)
    order = _mix.stratified_order(values, 16, np.random.default_rng(seed))
    assert sorted(order.tolist()) == list(range(256))
    means = [values[order[i: i + 16]].mean() for i in range(0, 256, 16)]
    # Every run of 16 carries the whole distribution: its mean is near
    # the deck's, where a plain shuffle's would swing by a third.
    assert max(means) - min(means) < 0.15 * values.mean()
