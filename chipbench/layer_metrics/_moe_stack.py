"""Shared by the readers of the expert matmuls of a model that holds every
expert in stacks of every layer's (``chipbench/kernels/moe_stack.py``): the
tick program's operations that read those stacks, told by operand shape. A
configuration of another family, or a run without a trace, gives nothing
to read."""

from __future__ import annotations

from chipbench.layer_metrics import _latent_ops as L


def seconds_a_tick(run):
    """(the counts, device seconds of the expert matmuls in one tick)."""
    if not run.get("trace") or "num_experts" not in run["conf"]:
        return None, None
    k = L.kernels(run, "moe_stack")
    s = L.seconds(run, k.operand_pattern(run["conf"]))
    ticks = L.ticks_traced(run) if s else 0
    return k, (s / ticks if ticks else None)
