"""The decode read of a KV pool allocated by layer kind (``serve.py`` over
``generate.KindKVCache``): what it must move.

A layer's read of a tick fetches, for each slot that served a token, K and
V of the positions that token may attend to: ``num_key_value_heads *
head_dim`` bfloat16 values each, a position. A full layer at position p
needs p + 1 of them; a window layer ``min(p + 1, sliding_window)``, which
is what its ring holds. The program counts both itself
(``ServeMetrics.summary()["kv_pool"]``: ``window_positions_valid``,
``full_positions_valid``, summed over the kind's layers and the served
slot-ticks). Bytes at the VALID positions alone: XLA's read fetches every
slot's whole ring and slab, for idle slots too, so the share of the
roofline cannot pass 100% and says how far a fill-bounded read would go.
"""

from __future__ import annotations


def row_bytes(conf: dict) -> int:
    """K and V of one position of one layer, bfloat16."""
    return 2 * conf["num_key_value_heads"] * conf["head_dim"] * 2


def layers(conf: dict, window: bool) -> int:
    want = "sliding_attention" if window else "full_attention"
    types = conf["layer_types"][: conf["num_hidden_layers"]]
    return sum(t == want for t in types)


def read_bytes(conf: dict, positions: float) -> float:
    """``positions``: valid rows summed over the kind's layers and the
    slot-ticks that served a token."""
    return positions * row_bytes(conf)


def pool_pattern(conf: dict, window: bool) -> str:
    """How the trace shows a kind's pool: the stacked K or V tensor."""
    dep = conf["deployment"]
    rows = (
        conf["sliding_window"] if window
        else dep["prompt_window"] + dep["max_new"]
    )
    width = conf["num_key_value_heads"] * conf["head_dim"]  # heads side by side
    return rf"bf16\[{layers(conf, window)},{dep['slots']},{rows},{width}\]"
