"""Shared by the readers of the Mamba-2 layers' decode step: the Pallas
kernel ``tk_ssd_step`` by its own name, inside the tick program. A program
without the kernel (the parent of the PR that brought it, or a
configuration without state-space layers) or a run without a trace gives
nothing to read. The slot-ticks served are counted as the delta rule's
readers count them (``_kda.slot_ticks_served``)."""

from __future__ import annotations

from chipbench.layer_metrics import _kda, _named

KERNEL = "tk_ssd_step"
slot_ticks_served = _kda.slot_ticks_served


def step_total(run):
    """(seconds, calls) of the kernel in the traced ticks; (0, 0) where
    there is nothing to read."""
    if not run.get("trace") or "mamba_d_state" not in run["conf"]:
        return 0.0, 0.0
    return _named.kernel_total(run, KERNEL, r"tick")
