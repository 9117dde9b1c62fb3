"""Flash attention (``ops/flash.py``), forward and backward: the
operations the algorithm needs, from shapes.

Causal attention over ``seq`` positions multiplies, for every query head,
half of a ``seq x seq`` square twice in the forward pass (QK^T and PV) and
four times in the backward (dP, dV, dQ, dK): 2 FLOPs a multiply-add. What
the kernels recompute (the scores in the backward, the whole forward under
remat) is not counted: it is not needed, only done.
"""

from __future__ import annotations

# How the trace shows the kernels today: the step program's Pallas calls
# (named after the jaxpr around them: ``checkpoint.20``, ``closed_call.9``,
# ``rematted_computation.10``), every one of them on bfloat16 operands.
TRACE_PROGRAM = r"_step"
TRACE_OPERANDS = r"custom-call\(bf16\["


def forward_flops(dims, rows: int, seq: int) -> float:
    per_head = 2 * (2.0 * seq * seq * dims.head_dim) / 2.0
    return rows * dims.layers * dims.heads * per_head


def step_flops(dims, rows: int, seq: int) -> float:
    """Forward and backward of one training step."""
    return 3.0 * forward_flops(dims, rows, seq)
