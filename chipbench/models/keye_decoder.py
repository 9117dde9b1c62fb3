"""Bridge from a grouped-query, learned-sparse-attention, routed-expert
configuration file (``KeyeVL2``'s language model, Hugging Face keys) to the
program, and the family's weights from the seed.

The family: every layer is grouped-query attention (heads of a stated
width, no bias, rope over split halves) whose queries read the
``sa_config.topk`` cached positions that a learned INDEXER chose
(``sa_config``: ``indexer_num_heads`` heads of ``indexer_head_dim``, ONE
index key a position, a weight a head; DeepSeek-Sparse-Attention's),
followed by a routed expert layer (softmax over ``published_num_experts``
outputs, the top ``num_experts_per_tok`` renormalised, no shared expert,
no dense layer). This chip holds ``num_experts`` of each layer's experts,
``deployment.experts_held`` = [first, count] of the router's outputs.

The program receives weights, it does not make them: ``serving_params``
draws the model on the device in the program's stacked layout, and the
plain reference (``chipbench.reference.keye_decoder``) draws the same
numbers again, one layer at a time, with ``layer_weights``. Every tensor
of every layer has a key of its own, ``fold_in(fold_in(key(seed),
tensor), layer)``, and an expert's matrices a key of theirs under it by
the expert's PUBLISHED index (``fold_in(.., first + e)``): an expert's
weights depend neither on how many are drawn beside it nor on which share
holds it (the shares of one layer, summed, are the uncut layer). The
distribution is the other expert configurations': matmul weights normal
with standard deviation ``1/sqrt(fan_in)`` rounded to the parameters'
dtype, norms at one, the embedding's rows at unit variance, and the
projections that write into the residual stream (``wo``, ``w_down``)
scaled by ``1/sqrt(2 * published depth)``.

This module imports the program inside its functions only, so the
reference can share the draw and still import nothing of the program.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from chipbench import weights as W

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
TENSORS = (
    "embed", "lm_head", "wq", "wk", "wv", "wo", "wiq", "wik", "wiw",
    "router", "w_gate", "w_up", "w_down",
)
LAYER_TENSORS = TENSORS[2:]
_EXPERT = ("w_gate", "w_up", "w_down")
_WRITES_RESIDUAL = ("wo", "w_down")


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes of the family, read from a configuration file's keys."""

    hidden: int
    layers: int
    published_layers: int
    heads: int
    kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int
    topk: int
    router: int  # the router's outputs: the published experts
    first: int  # experts [first, first + experts) are held here
    experts: int
    top_k: int
    expert_ffn: int
    vocab: int
    rope_theta: float
    rms_eps: float

    @classmethod
    def from_conf(cls, conf: dict) -> "Arch":
        for key, want in (
            ("attention_bias", False), ("hidden_act", "silu"),
            ("norm_topk_prob", True), ("tie_word_embeddings", False),
            ("use_sliding_window", False), ("decoder_sparse_step", 1),
            ("mlp_only_layers", []),
        ):
            if conf.get(key, want) != want:
                raise ValueError(
                    f"{key}={conf[key]!r}: the family is built for {want!r}"
                )
        sa = conf["sa_config"]
        if int(sa["indexer_num_kv_heads"]) != 1:
            raise ValueError("the indexer is built with ONE index key")
        layers = int(conf["num_hidden_layers"])
        held = int(conf["num_experts"])
        first, count = conf["deployment"].get("experts_held", [0, held])
        if count != held:
            raise ValueError(
                f"experts_held {[first, count]} against num_experts {held}"
            )
        return cls(
            hidden=int(conf["hidden_size"]), layers=layers,
            published_layers=int(
                conf.get("published_num_hidden_layers", layers)
            ),
            heads=int(conf["num_attention_heads"]),
            kv_heads=int(conf["num_key_value_heads"]),
            head_dim=int(conf["head_dim"]),
            index_heads=int(sa["indexer_num_heads"]),
            index_dim=int(sa["indexer_head_dim"]), topk=int(sa["topk"]),
            router=int(conf.get("published_num_experts", held)),
            first=int(first), experts=held,
            top_k=int(conf["num_experts_per_tok"]),
            expert_ffn=int(conf["moe_intermediate_size"]),
            vocab=int(conf["vocab_size"]),
            rope_theta=float(conf["rope_theta"]),
            rms_eps=float(conf["rms_norm_eps"]),
        )

    def shape(self, name: str) -> tuple[int, ...]:
        d, h, k, e = self.hidden, self.heads, self.kv_heads, self.head_dim
        hi, di, n, f = (
            self.index_heads, self.index_dim, self.experts, self.expert_ffn
        )
        return {
            "embed": (self.vocab, d), "lm_head": (d, self.vocab),
            "wq": (d, h, e), "wk": (d, k, e), "wv": (d, k, e),
            "wo": (h, e, d), "wiq": (d, hi, di), "wik": (d, di),
            "wiw": (d, hi), "router": (d, self.router),
            "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
        }[name]

    def fan_in(self, name: str) -> int:
        if name == "wo":
            return self.heads * self.head_dim
        return self.expert_ffn if name == "w_down" else self.hidden

    @property
    def layer_params(self) -> int:
        return 2 * self.hidden + sum(
            math.prod(self.shape(n)) for n in LAYER_TENSORS
        )

    @property
    def params(self) -> int:
        return (
            2 * self.vocab * self.hidden + self.hidden
            + self.layers * self.layer_params
        )

    def share(self, first: int, count: int) -> "Arch":
        """The same model holding experts ``[first, first + count)``."""
        return dataclasses.replace(self, first=first, experts=count)


def tensor_key(key: jax.Array, name: str, layer) -> jax.Array:
    return jax.random.fold_in(
        jax.random.fold_in(key, TENSORS.index(name)), layer
    )


def draw(key, arch: Arch, name: str, layer, dtype):
    """One tensor of one layer (0 for the tables) in ``dtype``."""
    shape = arch.shape(name)
    k = tensor_key(key, name, layer)
    if name in _EXPERT:  # an expert's matrices by its published index
        w = jax.vmap(
            lambda e: jax.random.normal(
                jax.random.fold_in(k, e), shape[1:], jnp.float32
            )
        )(arch.first + jnp.arange(shape[0], dtype=jnp.int32))
    else:
        w = jax.random.normal(k, shape, jnp.float32)
    # A product with a constant, not a quotient (the program's draw and
    # the reference's must round alike).
    scale = 1.0 if name == "embed" else 1.0 / math.sqrt(arch.fan_in(name))
    if name in _WRITES_RESIDUAL:
        scale /= math.sqrt(2 * arch.published_layers)
    return (w * jnp.float32(scale)).astype(dtype)


def layer_weights(key, arch: Arch, layer, dtype) -> dict:
    """Layer ``layer`` as the served model stores it."""
    w = {n: draw(key, arch, n, layer, dtype) for n in LAYER_TENSORS}
    w["ln1"] = w["ln2"] = jnp.ones((arch.hidden,), dtype)
    return w


def serving_tree(key, arch: Arch, dtype) -> dict:
    """The whole model in the program's stacked layout, drawn one layer
    after another."""
    layers = jnp.arange(arch.layers, dtype=jnp.int32)
    stacked = {
        n: jax.lax.map(lambda l, n=n: draw(key, arch, n, l, dtype), layers)
        for n in LAYER_TENSORS
    }
    stacked["ln1"] = stacked["ln2"] = jnp.ones((arch.layers, arch.hidden), dtype)
    return {
        "embed": draw(key, arch, "embed", 0, dtype),
        "lm_head": draw(key, arch, "lm_head", 0, dtype),
        "ln_f": jnp.ones((arch.hidden,), dtype),
        "layers": stacked,
    }


def dtype_of(name: str):
    return _DTYPES[name]


def program_config(conf: dict, max_seq_len: int, **extra):
    """The program's ``TransformerConfig`` at the file's sizes."""
    from chipbench import common
    from chipbench.reference import keye_decoder as reference

    a = Arch.from_conf(conf)
    dep = conf["deployment"]
    # The serving loop hands the reference ``weights.Dims`` alone; the
    # family's other sizes are found by them.
    reference.register(W.Dims.from_conf(conf), a, dep)
    try:
        from torchkafka_tpu.models.transformer import TransformerConfig

        return TransformerConfig(
            vocab_size=a.vocab, d_model=a.hidden, n_layers=a.layers,
            n_heads=a.heads, n_kv_heads=a.kv_heads,
            d_ff=int(conf["intermediate_size"]), max_seq_len=max_seq_len,
            rope_theta=a.rope_theta,
            dtype=dtype_of(dep["compute_dtype"]),
            param_dtype=dtype_of(dep["param_dtype"]),
            stated_head_dim=a.head_dim, window_pattern=(False,),
            n_experts=a.router, expert_top_k=a.top_k,
            expert_d_ff=a.expert_ffn, router_score="softmax", norm_topk=True,
            experts_held=(
                None if a.experts == a.router else (a.first, a.experts)
            ),
            index_heads=a.index_heads, index_head_dim=a.index_dim,
            index_topk=a.topk, **extra,
        )
    except (ImportError, TypeError, ValueError) as e:
        # A program from before the family was built: nothing to measure.
        raise common.Refused(
            f"this program's TransformerConfig does not take the family: {e}"
        ) from e


def serving_params(conf: dict, seed: int):
    """The model on the device, in one jitted call from the seed."""
    arch = Arch.from_conf(conf)
    dtype = dtype_of(conf["deployment"]["param_dtype"])
    return jax.jit(lambda key: serving_tree(key, arch, dtype))(W.seed_key(seed))


def final_stream(cfg, params, tokens):
    """The program's own forward over ``tokens`` [B, T] (the forward an
    admission runs, one row at a time): the stream after the last layer
    [B, T, D] float32, which no slot keeps."""
    from torchkafka_tpu.models.transformer import Transformer, scan_periods

    model = Transformer(cfg)

    @jax.jit
    def one(params, row):
        from torchkafka_tpu.models.quant import embed_rows

        x = embed_rows(params["embed"], row[None], cfg.dtype)
        x, _ = scan_periods(
            cfg, params["layers"], x,
            lambda x, layer, j, _i: model._layer(x, layer, cfg.layer_kind(j)),
        )
        return x[0].astype(jnp.float32)

    return jnp.stack([one(params, jnp.asarray(r, jnp.int32)) for r in tokens])
