"""The routed expert layer (``ops/moe.py``): what a decode tick must move.

A tick of 64 rows computes next to nothing against what it streams: every
expert that got at least one (token, choice) pair has its three matrices
read whole, and the shared experts are read by every tick. Bytes of the
experts the program itself counted as touched (``ServeMetrics.
moe_experts_touched``: by a slot the device held active), so the
all-experts form, which streams the untouched experts too, and rows of
idle slots lower the share: it cannot pass 100%.
"""

from __future__ import annotations


def expert_bytes(conf: dict) -> int:
    """One routed expert's gate, up and down matrices, bfloat16."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"] * 2


def shared_bytes(conf: dict) -> int:
    """A layer's shared experts (one SwiGLU of ``n_shared_experts``
    widths), bfloat16."""
    wide = conf["n_shared_experts"] * conf["moe_intermediate_size"]
    return 3 * conf["hidden_size"] * wide * 2


def expert_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def stream_bytes(conf: dict, experts_touched: float, ticks: float) -> float:
    """``experts_touched``: summed over expert layers and ticks."""
    return (
        experts_touched * expert_bytes(conf)
        + ticks * expert_layers(conf) * shared_bytes(conf)
    )


def operand_pattern(conf: dict) -> str:
    """How the trace shows the expert matmuls: operations of the tick
    program that read an expert group's stacked weights (or one layer's
    slice of them), routed or shared."""
    d, f, e = (
        conf["hidden_size"], conf["moe_intermediate_size"],
        conf["n_routed_experts"],
    )
    fs = conf["n_shared_experts"] * f
    lead = r"bf16\[(\d+,)?"
    return "|".join([
        rf"{lead}{e},{d},{f}\]", rf"{lead}{e},{f},{d}\]",
        rf"{lead}{d},{fs}\]", rf"{lead}{fs},{d}\]",
    ])
