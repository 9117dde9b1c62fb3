"""Shared by the readers of learned sparse attention's decode kernels: the
Pallas kernels ``tk_dsa_index`` and ``tk_dsa_attend`` by their own names,
inside the tick program. A program without them (the parent of the PR that
brought them, or a configuration without an indexer) or a run without a
trace gives nothing to read."""

from __future__ import annotations

from chipbench import common
from chipbench.layer_metrics import _named


def kernels(run):
    return common.load_named("kernels", "dsa", run["root"])


def total(run, name: str):
    """(seconds, calls) of the kernel ``name`` in the traced ticks; (0, 0)
    where there is nothing to read."""
    if not run.get("trace") or "sa_config" not in run["conf"]:
        return 0.0, 0.0
    return _named.kernel_total(run, name, r"tick")


def positions_served(run) -> tuple[int, int]:
    """(positions held, positions selected) summed over the (slot, tick)
    pairs of the traced part of the window that produced a served token, a
    layer: token j >= 1 of a request is a tick's, at ``window + j``
    positions held, of which the model selects ``min(that, topk)`` (a
    request's first token is its admission's)."""
    tr, window = run["trace"], run["prompt_window"]
    k = kernels(run).topk(run["conf"])
    held = selected = 0
    for r in run["requests"]:
        before = 0
        for t, n in r["syncs"]:
            if tr["host_t0"] < t <= tr["host_t1"]:
                for j in range(max(before, 1), before + n):
                    held += window + j
                    selected += min(window + j, k)
            before += n
    return held, selected
