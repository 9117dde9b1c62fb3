"""Bridge from a dense-decoder configuration file to the program.

A configuration file names this module under ``"model"``; a new family
brings a module of its own. It is the only place where the benchmark
builds the program's own types: ``TransformerConfig`` from the published
keys, and the program's parameter trees from ``chipbench.weights``'s
arrays (the program receives weights, it does not make them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import weights as W

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def dtype_of(name: str):
    return _DTYPES[name]


def program_config(conf: dict, max_seq_len: int, **extra):
    """The program's ``TransformerConfig`` at the file's sizes."""
    from torchkafka_tpu.models import TransformerConfig

    d = W.Dims.from_conf(conf)
    dep = conf["deployment"]
    if d.hidden != d.heads * d.head_dim:
        raise ValueError("the program derives head_dim as hidden/heads")
    return TransformerConfig(
        vocab_size=d.vocab, d_model=d.hidden, n_layers=d.layers,
        n_heads=d.heads, n_kv_heads=d.kv_heads, d_ff=d.ffn,
        max_seq_len=max_seq_len, rope_theta=d.rope_theta,
        dtype=dtype_of(dep["compute_dtype"]),
        param_dtype=dtype_of(dep["param_dtype"]), **extra,
    )


def serving_params(conf: dict, seed: int):
    """Int8 weights on the device, in one jitted call from the seed, as
    the program's ``QTensor`` tree."""
    from torchkafka_tpu.models.quant import QTensor

    dims = W.Dims.from_conf(conf)
    tree = jax.jit(lambda key: W.serving_tree(key, dims))(W.seed_key(seed))

    def wrap(node):
        if isinstance(node, dict) and set(node) == {"q", "scale"}:
            return QTensor(q=node["q"], scale=node["scale"])
        if isinstance(node, dict):
            return {k: wrap(v) for k, v in node.items()}
        return node

    return wrap(tree)


def training_params(conf: dict, seed: int, shardings):
    """Training weights from the seed, laid out by ``shardings`` (those
    of the tree the program's own ``init_fn`` returned: the layout the
    step expects)."""
    dims = W.Dims.from_conf(conf)
    dtype = dtype_of(conf["deployment"]["param_dtype"])
    draw = jax.jit(
        lambda key: W.training_tree(key, dims, dtype), out_shardings=shardings
    )
    return draw(W.seed_key(seed))


def optimizer(conf: dict):
    import optax

    o = conf["deployment"]["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"unknown optimizer {o['name']!r}")
    return optax.adamw(
        o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"],
    )
