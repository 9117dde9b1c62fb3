"""One chip's share of a routed expert layer (``ops/moe.py`` with
``experts_held``): what a decode tick must move.

A tick's rows compute next to nothing against what they stream: every
HELD expert that got at least one (token, choice) pair has its three
matrices read whole. Bytes of the held experts the program itself counted
as touched (``ServeMetrics.moe_experts_touched``, counted over the held
range): the compacted form reads those and no others, and a form that
streamed the untouched held experts too would lower the share: it cannot
pass 100%. Zero experts have no weights and pairs of absent experts move
nothing here.
"""

from __future__ import annotations


def expert_bytes(conf: dict) -> int:
    """One held expert's gate, up and down matrices, bfloat16."""
    return 3 * conf["hidden_size"] * conf["expert_ffn_hidden_size"] * 2


def held(conf: dict) -> int:
    return conf["deployment"]["experts_held"][1]


def layers(conf: dict) -> int:
    return conf["num_hidden_layers"]


def stream_bytes(conf: dict, experts_touched: float) -> float:
    """``experts_touched``: summed over the layers (and ticks)."""
    return experts_touched * expert_bytes(conf)


def operand_pattern(conf: dict) -> str:
    """How the trace shows the held experts' matmuls: operations of the
    tick program that read the held experts' stacked weights, as the
    program holds them (``[L, E, ...]``), as the compacted form indexes
    them (``[L * E, ...]``), or one layer's slice."""
    d, f, e = conf["hidden_size"], conf["expert_ffn_hidden_size"], held(conf)
    lead = rf"bf16\[((\d+,)?{e}|{layers(conf) * e}),"
    return rf"{lead}{d},{f}\]|{lead}{f},{d}\]"
