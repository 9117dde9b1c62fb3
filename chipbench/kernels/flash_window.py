"""The sliding-window flash forward (``ops/flash.py``, the call named
``tk_flash_fwd_win``): the operations the algorithm needs, from shapes.

A query at position i multiplies with the keys j of ``i - window < j <=
i``: ``min(i + 1, window)`` of them, twice (QK^T and PV), 2 FLOPs a
multiply-add over ``head_dim``. What the kernel computes beyond that (the
masked part of a straddling key block) is not counted: it is not needed,
only done, so the share of the compute roofline cannot pass 100%.
"""

from __future__ import annotations

import re

NAME = "tk_flash_fwd_win"
PROGRAM = r"admit"
_Q = re.compile(r"custom-call\(bf16\[(\d+),(\d+),(\d+)\]")


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a causal sliding window keeps."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def call_flops(rows_heads: int, seq: int, head_dim: int, window: int) -> float:
    """One call on queries ``[rows * heads, seq, head_dim]``."""
    return rows_heads * 4.0 * window_pairs(seq, window) * head_dim


def operands(text: str):
    """(rows * heads, seq, head_dim) of the call's queries, from the
    operation's text as the trace prints it; None where it has none."""
    m = _Q.search(text)
    return tuple(int(g) for g in m.groups()) if m else None
