"""The int8 decode-attention read (``ops/kvattn.py``): what it must move.

One decode tick of one slot at valid length ``n`` reads, in every layer,
the keys and values of ``n`` cached positions: for each kv head an int8
payload of ``head_dim`` bytes and one float32 scale, for keys and for
values. The query and the output of the slot's heads are counted too.
Bytes at the VALID lengths: a pool-shaped read would move more, and the
share says so.
"""

from __future__ import annotations

# How the trace shows the kernel today. A Pallas call carries no stable
# name (the trace names it after the jaxpr around it, ``closed_call.19``),
# so it is told by the program that runs it and by its operands: the tick
# program's only Pallas call, the one that reads int8 pools.
TRACE_PROGRAM = r"tick"
TRACE_OPERANDS = r"s8\[\d+,\d+,\d+,\d+\]"


def bytes_per_position(dims) -> int:
    """One cached position of one slot, all layers, keys and values."""
    return dims.layers * 2 * dims.kv_heads * (dims.head_dim + 4)


def bytes_per_tick_slot(dims) -> int:
    """Query in, output out, bfloat16, all layers."""
    return dims.layers * 2 * dims.heads * dims.head_dim * 2


def read_bytes(dims, positions: int, slot_ticks: int) -> int:
    """``positions``: valid positions summed over every (slot, tick)
    that produced a served token; ``slot_ticks``: how many those are."""
    return (
        positions * bytes_per_position(dims)
        + slot_ticks * bytes_per_tick_slot(dims)
    )


def positions_of_block(window: int, before: int, new: int) -> int:
    """Valid positions read by the ticks that produced tokens
    ``before .. before+new-1`` of one request behind a prompt window of
    ``window``: the tick of token ``j`` reads ``window + j`` positions."""
    return new * window + new * (2 * before + new - 1) // 2
