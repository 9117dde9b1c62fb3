"""The absorbed decode read of a latent pool that holds a row an attention
BLOCK (``models/mla.py`` under the double layer, ``attn_blocks`` 2): what
it must move. A block's read is ``chipbench/kernels/mla.py``'s of a layer
(a row of ``kv_lora_rank + qk_rope_head_dim`` bfloat16 values a valid
position, the slot's absorbed queries in and its weighted latents out);
there are two blocks a layer, and the pool's leading axis counts them.
Bytes at the VALID lengths: the XLA read moves every slot's whole slab,
and the share says so.
"""

from __future__ import annotations

from chipbench.kernels.mla import (  # noqa: F401 - the readers' names
    bytes_per_tick_slot, positions_of_block, row_bytes, scores_pattern,
)


def blocks(conf: dict) -> int:
    """Rows of the stacked pool: two attention blocks a layer."""
    return 2 * conf["num_hidden_layers"]


def read_bytes(conf: dict, positions: int, slot_ticks: int) -> int:
    """``positions``: valid rows summed over every (slot, tick) that
    produced a served token, ONE block's; ``slot_ticks``: how many those
    are."""
    return blocks(conf) * (
        positions * row_bytes(conf) + slot_ticks * bytes_per_tick_slot(conf)
    )


def pool_pattern(conf: dict) -> str:
    dep = conf["deployment"]
    m = dep["prompt_window"] + dep["max_new"]
    c = conf["kv_lora_rank"] + conf["qk_rope_head_dim"]
    return rf"bf16\[{blocks(conf)},{dep['slots']},{m},{c}\]"
