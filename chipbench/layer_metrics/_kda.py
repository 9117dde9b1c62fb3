"""Shared by the readers of the linear-attention layers' decode step: the
Pallas kernel ``tk_kda_step`` by its own name, inside the tick program. A
program without the kernel (the parent of the PR that brought it, or a
configuration without linear-attention layers) or a run without a trace
gives nothing to read."""

from __future__ import annotations

from chipbench.layer_metrics import _named

KERNEL = "tk_kda_step"


def step_total(run):
    """(seconds, calls) of the kernel in the traced ticks; (0, 0) where
    there is nothing to read."""
    if not run.get("trace") or "layer_group_size" not in run["conf"]:
        return 0.0, 0.0
    return _named.kernel_total(run, KERNEL, r"tick")


def slot_ticks_served(run) -> int:
    """The (slot, tick) pairs of the traced part of the window that
    produced a served token: a request's first token is its admission's,
    not a tick's."""
    tr = run["trace"]
    ticks = 0
    for r in run["requests"]:
        before = 0
        for t, n in r["syncs"]:
            if tr["host_t0"] < t <= tr["host_t1"]:
                ticks += n - 1 if before == 0 else n
            before += n
    return ticks
