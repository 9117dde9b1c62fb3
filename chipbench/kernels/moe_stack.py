"""The routed expert layer of a model that holds every expert, its
matrices handed to ``ops/moe.py`` as stacks of every layer's experts
(``transformer.scan_periods``: ``[L * E, ...]``): what a decode tick must
move.

A tick's rows compute next to nothing against what they stream: every
expert that got at least one (token, choice) pair has its three matrices
read whole. Bytes of the experts the program itself counted as touched
(``ServeMetrics.moe_experts_touched``): the compacted form reads those, a
tile of rows an expert at a time, and an expert whose pairs overflow one
tile is read once more, which is time spent on bytes that are not needed
and not counted: the share cannot pass 100%.
"""

from __future__ import annotations


def expert_bytes(conf: dict) -> int:
    """One expert's gate, up and down matrices, bfloat16."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"] * 2


def stream_bytes(conf: dict, experts_touched: float) -> float:
    """``experts_touched``: summed over the layers (and ticks)."""
    return experts_touched * expert_bytes(conf)


def operand_pattern(conf: dict) -> str:
    """How the trace shows the expert matmuls: operations of the tick
    program that read the experts' stacked weights, as the program holds
    them (``[L, E, ...]``), as the period scan hands them on (``[L * E,
    ...]``), or one layer's slice."""
    d, f, e = (
        conf["hidden_size"], conf["moe_intermediate_size"],
        conf["num_experts"],
    )
    lead = rf"bf16\[((\d+,)?{e}|{conf['num_hidden_layers'] * e}),"
    return rf"{lead}{d},{f}\]|{lead}{f},{d}\]"
