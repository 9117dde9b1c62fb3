"""The linear-attention (KDA) layers' decode step over the slots' recurrent
state (``torchkafka_tpu/ops/kda.py::kda_step``, the Pallas kernel
``tk_kda_step``): what it must move and compute.

One decode tick of one slot reads, in every linear layer, the layer's
state of that slot, ``heads x head_dim x head_dim`` float32 values (2 MiB
at 32 heads of 128), and writes it back: once each is enough for the
decay, the rank-one correction and the read-out. The slot's rows in (q, k,
beta k and the decay a channel, v) and its read-out are counted too, all
float32. Bytes for the slot-ticks that SERVED a token alone: the kernel
also runs for slots that are idle or past their budget, and those bytes
are not needed, so the share of the roofline cannot pass 100%.
"""

from __future__ import annotations


def linear_layers(conf: dict) -> int:
    """The leading dense layers and every layer of a period but its last."""
    dense = conf["first_k_dense_replace"]
    period = conf["layer_group_size"]
    periods = (conf["num_hidden_layers"] - dense) // period
    return dense + periods * (period - 1)


def state_bytes(conf: dict) -> int:
    """One slot's state of one layer, float32."""
    return conf["num_attention_heads"] * conf["head_dim"] ** 2 * 4


def row_bytes(conf: dict) -> int:
    """A slot's vectors of one layer in and out: q, k, beta k, exp(g), v
    and the read-out, ``heads x head_dim`` float32 each."""
    return 6 * conf["num_attention_heads"] * conf["head_dim"] * 4


def step_bytes(conf: dict, slot_ticks: int) -> int:
    """``slot_ticks``: the (slot, tick) pairs that produced a served
    token; every linear layer reads and writes the slot's state once."""
    return linear_layers(conf) * slot_ticks * (
        2 * state_bytes(conf) + row_bytes(conf)
    )


def step_flops(conf: dict, slot_ticks: int) -> int:
    """Multiplies and adds of the step: the decay (1 a state element), the
    two products with k and q (2 each) and the rank-one correction (2)."""
    elements = conf["num_attention_heads"] * conf["head_dim"] ** 2
    return linear_layers(conf) * slot_ticks * 7 * elements


def chunk_flops(conf: dict, tokens: int, chunk: int = 64) -> int:
    """The admission's chunkwise form, a token a head a layer: a row of
    the two [chunk, chunk] products over ``head_dim``, the triangular
    solve's row against ``2 head_dim`` columns, the three products with
    the carried state and the intra-chunk product, two operations a
    multiply-add."""
    e = conf["head_dim"]
    per_token_head = 2 * (2 * chunk * e + chunk * 2 * e + 3 * e * e + chunk * e)
    return linear_layers(conf) * conf["num_attention_heads"] * tokens * (
        per_token_head
    )
