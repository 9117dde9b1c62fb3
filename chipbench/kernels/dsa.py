"""Learned sparse attention's decode kernels and its admission's flash
forward (``torchkafka_tpu/ops/dsa.py``: ``tk_dsa_index``,
``tk_dsa_attend``; ``ops/flash.py``: ``tk_flash_fwd_sel``): what they must
move and compute.

One decode tick of one slot that holds ``n`` positions, a layer:

- the index scores need the slot's ``n`` VALID index keys,
  ``indexer_head_dim`` numbers each in the compute dtype (the queries, the
  weights and the scores written are small beside them and not counted);
- the selected read needs the ``min(n, topk)`` SELECTED rows, a position's
  K row beside its V row (``2 * kv_heads * head_dim`` numbers).

Bytes for the slot-ticks that SERVED a token alone, at the lengths those
ticks had: what the kernels fetch beyond that (a block's or a chunk's
tail, a slot past its budget) is not needed, so neither share of the HBM
roofline can pass 100% by a count of rows a kernel need not move.

The admission's flash forward runs under ``causal and selected``. Its need
is counted as the CAUSAL TRIANGLE's, as ``chipbench/kernels/flash.py``
counts the causal forward: a scattered selection empties no block under
the diagonal (2,048 of 8,192 positions spread over sixteen blocks of 512
leave every block with selected keys), so the mask saves the kernel no
block and the algorithm as built multiplies the triangle; the share says
what the mask and its fetch cost against the causal call's.
"""

from __future__ import annotations

INDEX, ATTEND, FLASH_SEL = "tk_dsa_index", "tk_dsa_attend", "tk_flash_fwd_sel"
_ITEM = {"bfloat16": 2, "float32": 4}


def layers(conf: dict) -> int:
    return int(conf["num_hidden_layers"])


def topk(conf: dict) -> int:
    return int(conf["sa_config"]["topk"])


def index_bytes(conf: dict, valid: int) -> int:
    """``valid``: positions held, summed over the served slot-ticks (a
    layer's; every layer scores them)."""
    item = _ITEM[conf["deployment"]["compute_dtype"]]
    return layers(conf) * valid * int(conf["sa_config"]["indexer_head_dim"]) * item


def attend_bytes(conf: dict, selected: int) -> int:
    """``selected``: ``min(held, topk)`` summed over the served
    slot-ticks (a layer's; every layer reads them)."""
    item = _ITEM[conf["deployment"]["compute_dtype"]]
    row = 2 * int(conf["num_key_value_heads"]) * int(conf["head_dim"]) * item
    return layers(conf) * selected * row


def flash_sel_flops(rows_heads: int, seq: int, head_dim: int) -> float:
    """One call on queries ``[rows * heads, seq, head_dim]``: the causal
    triangle twice (QK^T and PV), 2 FLOPs a multiply-add."""
    return rows_heads * 4.0 * (seq * (seq + 1) // 2) * head_dim
