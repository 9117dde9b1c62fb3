"""The arithmetic of the metrics, kept apart so that tests can hold it to
hand-worked cases."""

from __future__ import annotations

import math
from typing import Iterable, Sequence


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank percentile (the smallest value with at least ``q``
    percent of the sample at or below it). No interpolation: a tail is a
    value some request really had."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def median(values: Iterable[float]) -> float:
    xs = sorted(values)
    if not xs:
        raise ValueError("median of nothing")
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the nearest-rank ``q``."""
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0


def tpot_s(t_first: float, t_done: float, n_first: int, n_total: int):
    """Time per output token of one request after its first tokens: the
    first host sync surfaced ``n_first`` tokens at ``t_first``, the last
    of ``n_total`` existed at ``t_done``. None where nothing followed."""
    later = n_total - n_first
    if later <= 0:
        return None
    return (t_done - t_first) / later


def union_seconds(intervals: Iterable[tuple[float, float]]) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[tuple[float, float]], lo: float, hi: float):
    """The idle stretches of ``[lo, hi]`` that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if e <= lo or s >= hi:
            continue
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def train_flops_per_token(dims, seq: int) -> float:
    """Model FLOPs a trained token: 6 a matmul parameter (forward and
    backward; the embedding is a gather) plus causal attention, 6 * L *
    heads * head_dim * seq (QK^T and PV, 2 FLOPs a multiply-add, three
    passes, the masked half not counted). Recomputation is not counted.
    A copy of benchmarks/mfu_breakdown.py::train_flops_per_step's
    arithmetic."""
    attn = 6.0 * dims.layers * dims.heads * dims.head_dim * seq
    return 6.0 * dims.matmul_params + attn


def mfu_pct(tokens_per_s: float, flops_per_token: float, chips: int,
            peak_flops: float) -> float:
    return 100.0 * tokens_per_s * flops_per_token / (chips * peak_flops)
