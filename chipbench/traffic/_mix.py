"""What the traffic kinds share: fixed decks of sizes, dealt by the seed.

Every seed gets the same set of sizes and arrivals in another order, so
that two seeds differ no more than two runs of one. A deck is the
distribution's own quantiles, not a sample: ``n`` values at the
probabilities ``(i + 0.5) / n``. The order is stratified: the deck, sorted,
is cut into ``block`` strata, and every run of ``block`` consecutive
records takes one from each stratum, so that each stretch of the stream
carries the whole distribution whatever the seed.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_deck(median: float, sigma: float, lo: int, hi: int, n: int):
    qs = [(i + 0.5) / n for i in range(n)]
    z = np.array([NormalDist().inv_cdf(q) for q in qs])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(np.int64)


def exponential_deck(mean: float, n: int) -> np.ndarray:
    qs = (np.arange(n) + 0.5) / n
    return -mean * np.log1p(-qs)


def poisson_deck(mean: float, n: int) -> np.ndarray:
    """Quantiles of Poisson(mean), by its cumulative distribution."""
    qs = (np.arange(n) + 0.5) / n
    out, k, cdf, pmf = np.zeros(n, np.int64), 0, 0.0, math.exp(-mean)
    i = 0
    while i < n:
        cdf += pmf
        while i < n and qs[i] <= cdf:
            out[i] = k
            i += 1
        k += 1
        pmf *= mean / k
        if k > 1000:
            out[i:] = k
            break
    return out


def zipf_counts(n: int, groups: int, s: float) -> np.ndarray:
    """``n`` records apportioned to ``groups`` by Zipf weights (largest
    remainders), so that every seed has the same tenant sizes."""
    w = np.arange(1, groups + 1, dtype=np.float64) ** -float(s)
    share = w / w.sum() * n
    counts = np.floor(share).astype(np.int64)
    for i in np.argsort(-(share - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts


def stratified_order(values, block: int, rng: np.random.Generator):
    """Indices of ``values`` in a seeded order that keeps every run of
    ``block`` records spread over the whole distribution."""
    n = len(values)
    order = np.argsort(values, kind="stable")
    strata = [rng.permutation(s) for s in np.array_split(order, block)]
    out = []
    for r in range(max(len(s) for s in strata)):
        picks = [s[r] for s in strata if r < len(s)]
        out.extend(rng.permutation(picks).tolist())
    assert len(out) == n
    return np.asarray(out, np.int64)


def request_deck(p: dict, window: int, max_new: int):
    """The fixed set of (prompt length, answer budget) pairs of a mix.
    The pairing is the file's, not the seed's."""
    n = int(p["deck"])
    prompts = lognormal_deck(
        p["prompt_median"], p["prompt_sigma"], 1,
        min(int(p["prompt_max"]), window), n,
    )
    answers = lognormal_deck(
        p["answer_median"], p["answer_sigma"], int(p.get("answer_min", 2)),
        min(int(p["answer_max"]), max_new), n,
    )
    pair = np.random.default_rng(int(p["pairing_seed"])).permutation(n)
    return prompts[pair], answers


def deal_indices(values, block: int, count: int, rng: np.random.Generator):
    """``count`` indices into the deck ``values``: one whole deck after
    another, each in a new stratified order, then an evenly spaced part of
    one (the same part for every seed)."""
    n, idx = len(values), []
    for _ in range(count // n):
        idx.extend(stratified_order(values, block, rng).tolist())
    rest = count - len(idx)
    if rest:
        by_size = np.argsort(values, kind="stable")
        part = by_size[((np.arange(rest) + 0.5) * n / rest).astype(np.int64)]
        order = stratified_order(values[part], min(block, rest), rng)
        idx.extend(part[order].tolist())
    return np.asarray(idx, np.int64)


def deal_requests(p: dict, seed: int, frame: dict, count: int):
    """``count`` requests dealt from the mix's deck by ``seed``: lists of
    prompt token arrays, answer budgets, tenant keys and partitions.

    A tenant's records go to one partition (tenant number modulo
    ``frame["partitions"]``: a keyed producer), and the sizes are dealt
    partition by partition: a server reads a partition in offset order
    and may drain one before it touches the next, so each partition's
    stream has to carry the whole distribution by itself. (Dealt over the
    topic as a whole and split by the key's hash, what the server met was
    a random part of the deck, and tokens a second swung by 2.6% between
    seeds instead of 0.6%: PERF.md.)"""
    rng = np.random.default_rng([int(seed), 0x7A11])
    prompts, answers = request_deck(p, frame["prompt_window"], frame["max_new"])
    tenants, parts = int(p["tenants"]), int(frame.get("partitions", 1))
    t_counts = zipf_counts(count, tenants, float(p["tenant_zipf"]))
    tenant_of = rng.permutation(np.repeat(np.arange(tenants), t_counts))
    partition_of = tenant_of % parts
    idx = np.zeros(count, np.int64)
    for part in range(parts):
        where = np.flatnonzero(partition_of == part)
        idx[where] = deal_indices(answers, int(p["block"]), len(where), rng)
    toks = [
        rng.integers(1, frame["vocab"], int(prompts[i]), dtype=np.int32)
        for i in idx
    ]
    keys = [f"tenant-{t:02d}".encode() for t in tenant_of]
    return toks, answers[idx].astype(int).tolist(), keys, partition_of.tolist()
