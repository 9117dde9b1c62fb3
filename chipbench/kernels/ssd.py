"""The Mamba-2 layers' decode step over the slots' state-space state
(``torchkafka_tpu/ops/ssd.py::ssd_step``, the Pallas kernel
``tk_ssd_step``): what it must move and compute.

One decode tick of one slot reads, in every Mamba-2 layer, the layer's
state of that slot, ``heads x head_dim x state_dim`` float32 values (4 MiB
at 128 heads of 64 x 128), and writes it back: once each is enough for the
decay, the outer product, the read-out and the skip. The slot's rows in
(the decay, ``dt x`` and ``D x`` a channel, B and C) and its read-out are
counted too, all float32. Bytes for the slot-ticks that SERVED a token
alone: the kernel also runs for slots that are idle or past their budget,
and those bytes are not needed, so the share of the roofline cannot pass
100%.
"""

from __future__ import annotations


def mamba_layers(conf: dict) -> int:
    """The layers of ``layer_types`` that run and are Mamba-2 mixers."""
    kinds = conf["layer_types"][: conf["num_hidden_layers"]]
    return sum(k == "mamba" for k in kinds)


def state_bytes(conf: dict) -> int:
    """One slot's state of one layer, float32."""
    return (
        conf["mamba_n_heads"] * conf["mamba_d_head"] * conf["mamba_d_state"] * 4
    )


def row_bytes(conf: dict) -> int:
    """A slot's vectors of one layer in and out: the decay, ``dt x``, ``D
    x`` and the read-out, ``heads x head_dim`` float32 each, and B and C,
    ``state_dim`` each."""
    channels = conf["mamba_n_heads"] * conf["mamba_d_head"]
    return (4 * channels + 2 * conf["mamba_d_state"]) * 4


def step_bytes(conf: dict, slot_ticks: int) -> int:
    """``slot_ticks``: the (slot, tick) pairs that produced a served
    token; every Mamba-2 layer reads and writes the slot's state once."""
    return mamba_layers(conf) * slot_ticks * (
        2 * state_bytes(conf) + row_bytes(conf)
    )


def step_flops(conf: dict, slot_ticks: int) -> int:
    """Multiplies and adds of the step: the decay (1 a state element), the
    outer product (2) and the read-out's product with C (2)."""
    elements = (
        conf["mamba_n_heads"] * conf["mamba_d_head"] * conf["mamba_d_state"]
    )
    return mamba_layers(conf) * slot_ticks * 5 * elements


def chunk_flops(conf: dict, tokens: int, chunk: int | None = None) -> int:
    """The admission's chunked scan, a token a layer: a row of the one
    [chunk, chunk] product of C and B over ``state_dim`` (all heads share
    it), and a head's row of the masked product with ``dt x`` over the
    chunk, its product with the carried state and its term of the chunk's
    state, two operations a multiply-add."""
    q = chunk or conf["mamba_chunk_size"]
    p, n = conf["mamba_d_head"], conf["mamba_d_state"]
    per_head = 2 * (q * p + 2 * p * n)
    return mamba_layers(conf) * tokens * (
        2 * q * n + conf["mamba_n_heads"] * per_head
    )
