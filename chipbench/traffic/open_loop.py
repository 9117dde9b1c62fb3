"""An open loop on the wall clock: compound-Poisson bursts at a fixed rate.

Bursts arrive as a Poisson process at ``rate_per_s / burst_mean`` a second
and carry ``1 + Poisson(burst_mean - 1)`` requests each, due at the same
instant. The gaps and the burst sizes are fixed decks (the distributions'
quantiles), dealt by the seed; the arithmetic is a copy of
``workload/generator.py::WorkloadGenerator.schedule``'s.
"""

from __future__ import annotations

import numpy as np

from chipbench.traffic import _mix


def generate(params: dict, seed: int, frame: dict) -> dict:
    rate, burst = float(params["rate_per_s"]), float(params["burst_mean"])
    seconds = float(frame["seconds"])
    n_bursts = max(1, int(round(rate * seconds / burst)))
    rng = np.random.default_rng([int(seed), 0xA881])
    gaps = rng.permutation(_mix.exponential_deck(burst / rate, n_bursts))
    sizes = 1 + rng.permutation(_mix.poisson_deck(burst - 1.0, n_bursts))
    # The deck's gaps sum to the window but for rounding: scale them so
    # that the last burst falls inside it whatever the order.
    times = np.cumsum(gaps)
    times = times * (seconds * (n_bursts - 0.5) / n_bursts) / times[-1]
    due = np.repeat(times, sizes)
    toks, budgets, keys, parts = _mix.deal_requests(
        params, seed, frame, len(due)
    )
    return {
        "open_loop": True,
        "records": [
            {"due_s": float(d), "tokens": t, "max_new": b, "key": k,
             "partition": p}
            for d, t, b, k, p in zip(due, toks, budgets, keys, parts)
        ],
    }
