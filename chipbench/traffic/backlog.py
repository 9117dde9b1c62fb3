"""A backlog: the topic holds every record before the window opens."""

from __future__ import annotations

from chipbench.traffic import _mix


def generate(params: dict, seed: int, frame: dict) -> dict:
    count = int(params["records"])
    toks, budgets, keys, parts = _mix.deal_requests(params, seed, frame, count)
    return {
        "open_loop": False,
        "records": [
            {"due_s": 0.0, "tokens": t, "max_new": b, "key": k, "partition": p}
            for t, b, k, p in zip(toks, budgets, keys, parts)
        ],
    }
