"""The absorbed decode read of the latent pool (``models/mla.py``): what
it must move.

One decode tick of one slot at valid length ``n`` reads, in every layer,
``n`` cached rows of ``kv_lora_rank + qk_rope_head_dim`` bfloat16 values
(1,152 bytes at 512 + 64): once is enough for the scores and the values.
The slot's absorbed queries in and its weighted latents out are counted
too. Bytes at the VALID lengths: the XLA read moves the whole pool, twice,
and the share says so.
"""

from __future__ import annotations


def row_bytes(conf: dict) -> int:
    return (conf["kv_lora_rank"] + conf["qk_rope_head_dim"]) * 2


def bytes_per_tick_slot(conf: dict) -> int:
    """Absorbed queries in (heads x row width), weighted latents out
    (heads x rank), bfloat16, one layer."""
    h = conf["num_attention_heads"]
    return h * row_bytes(conf) + h * conf["kv_lora_rank"] * 2


def read_bytes(conf: dict, positions: int, slot_ticks: int) -> int:
    """``positions``: valid rows summed over every (slot, tick) that
    produced a served token, ONE layer's; ``slot_ticks``: how many those
    are."""
    layers = conf["num_hidden_layers"]
    return layers * (
        positions * row_bytes(conf) + slot_ticks * bytes_per_tick_slot(conf)
    )


def positions_of_block(window: int, before: int, new: int) -> int:
    """Valid rows read by the ticks that produced tokens ``before ..
    before+new-1`` of one request behind a prompt window of ``window``:
    the tick of token ``j`` reads ``window + j`` rows."""
    return new * window + new * (2 * before + new - 1) // 2


def pool_pattern(conf: dict) -> str:
    dep = conf["deployment"]
    m = dep["prompt_window"] + dep["max_new"]
    c = conf["kv_lora_rank"] + conf["qk_rope_head_dim"]
    return rf"bf16\[{conf['num_hidden_layers']},{dep['slots']},{m},{c}\]"


def scores_pattern(conf: dict) -> str:
    """The read's scores and probabilities between its two products."""
    dep = conf["deployment"]
    m = dep["prompt_window"] + dep["max_new"]
    return rf"f32\[{dep['slots']},{conf['num_attention_heads']},1,{m}\]"
