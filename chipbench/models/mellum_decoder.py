"""Bridge from a window/full grouped-query, routed-expert configuration
file (Hugging Face ``mellum`` keys) to the program, and the family's
weights from the seed.

The family: every layer is grouped-query attention with heads of a stated
width (``head_dim``, not ``hidden_size / num_attention_heads``) followed by
a routed expert layer (softmax over ``num_experts``, the top
``num_experts_per_tok`` renormalised, no selection bias, no shared expert,
no dense layer). ``layer_types`` says which layers slide (a query sees the
last ``sliding_window`` positions) and which are full; ``rope_parameters``
gives each kind its rotary embedding (plain for the sliding kind, YaRN for
the full kind).

The program receives weights, it does not make them: ``serving_params``
draws the whole bfloat16 model on the device in the program's stacked
layout, and the plain reference (``chipbench.reference.mellum_decoder``)
draws the same numbers again, one layer at a time, with ``layer_weights``.
Every tensor of every layer has a key of its own, ``fold_in(fold_in(
key(seed), tensor), layer)``, and an expert's matrices a key of theirs
under it (``fold_in(.., expert)``): an expert's weights do not depend on
how many experts are drawn beside it. The distribution is the other
expert configurations' (their module docstrings give the reasons): matmul
weights normal with standard deviation ``1/sqrt(fan_in)`` rounded to the
parameters' dtype, norms at one, the embedding's rows at unit variance,
and the projections that write into the residual stream (``wo``,
``w_down``) scaled by ``1/sqrt(2 * published depth)``.

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
    "embed", "lm_head", "wq", "wk", "wv", "wo", "router", "w_gate", "w_up",
    "w_down",
)
LAYER_TENSORS = TENSORS[2:]
_EXPERT = ("w_gate", "w_up", "w_down")
_WRITES_RESIDUAL = ("wo", "w_down")


@dataclasses.dataclass(frozen=True)
class Rope:
    """One kind's ``rope_parameters`` as the file gives them."""

    theta: float
    factor: float = 1.0
    original: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    @classmethod
    def from_conf(cls, p: dict) -> "Rope":
        if p["rope_type"] == "default":
            return cls(theta=float(p["rope_theta"]))
        if p["rope_type"] != "yarn":
            raise ValueError(f"rope_type={p['rope_type']!r} is not built")
        return cls(
            theta=float(p["rope_theta"]), factor=float(p["factor"]),
            original=int(p["original_max_position_embeddings"]),
            beta_fast=float(p["beta_fast"]), beta_slow=float(p["beta_slow"]),
            attention_factor=float(p["attention_factor"]),
        )


def pattern_of(types: list[str]) -> tuple[bool, ...]:
    """The shortest period of ``layer_types`` as (slides?, ...)."""
    slides = [t == "sliding_attention" for t in types]
    for p in range(1, len(slides) + 1):
        if len(slides) % p == 0 and slides == slides[:p] * (len(slides) // p):
            return tuple(slides[:p])
    raise AssertionError("unreachable: the whole list is a period")


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes of the family, read from a configuration file's
    published keys."""

    hidden: int
    layers: int
    published_layers: int
    heads: int
    kv_heads: int
    head_dim: int
    unused_ffn: int
    experts: int
    top_k: int
    expert_ffn: int
    vocab: int
    window: int
    pattern: tuple[bool, ...]
    rope_window: Rope
    rope_full: Rope
    rms_eps: float

    @classmethod
    def from_conf(cls, conf: dict) -> "Arch":
        for key, want in (
            ("attention_bias", False), ("hidden_act", "silu"),
            ("norm_topk_prob", True), ("tie_word_embeddings", False),
            ("use_sliding_window", True),
        ):
            if conf.get(key, want) != want:
                raise ValueError(
                    f"{key}={conf[key]!r}: the family is built for {want!r}"
                )
        layers = int(conf["num_hidden_layers"])
        if set(conf["mlp_layer_types"][:layers]) != {"sparse"}:
            raise ValueError("the family is built with every layer sparse")
        types = conf["layer_types"][:layers]
        if not set(types) <= {"sliding_attention", "full_attention"}:
            raise ValueError(f"layer_types {sorted(set(types))} are not built")
        ropes = conf["rope_parameters"]
        return cls(
            hidden=int(conf["hidden_size"]), layers=layers,
            published_layers=int(
                conf.get("published_num_hidden_layers", layers)
            ),
            heads=int(conf["num_attention_heads"]),
            kv_heads=int(conf["num_key_value_heads"]),
            head_dim=int(conf["head_dim"]),
            unused_ffn=int(conf["intermediate_size"]),
            experts=int(conf["num_experts"]),
            top_k=int(conf["num_experts_per_tok"]),
            expert_ffn=int(conf["moe_intermediate_size"]),
            vocab=int(conf["vocab_size"]),
            window=int(conf["sliding_window"]),
            pattern=pattern_of(types),
            rope_window=Rope.from_conf(ropes["sliding_attention"]),
            rope_full=Rope.from_conf(ropes["full_attention"]),
            rms_eps=float(conf["rms_norm_eps"]),
        )

    def slides(self, layer: int) -> bool:
        return self.pattern[layer % len(self.pattern)]

    def kind_layers(self, slides: bool) -> int:
        return sum(self.slides(l) == slides for l in range(self.layers))

    def shape(self, name: str) -> tuple[int, ...]:
        d, h, k, e = self.hidden, self.heads, self.kv_heads, self.head_dim
        n, f = self.experts, self.expert_ffn
        return {
            "embed": (self.vocab, d), "lm_head": (d, self.vocab),
            "wq": (d, h, e), "wk": (d, k, e), "wv": (d, k, e),
            "wo": (h, e, d), "router": (d, n),
            "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
        }[name]

    def fan_in(self, name: str) -> int:
        d = self.hidden
        return {
            "embed": d, "lm_head": d, "wq": d, "wk": d, "wv": d,
            "wo": self.heads * self.head_dim, "router": d, "w_gate": d,
            "w_up": d, "w_down": self.expert_ffn,
        }[name]

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

    def pool_bytes(self, slides: bool, slots: int, positions: int,
                   itemsize: int = 2) -> int:
        """K and V of one kind's layers: a window layer holds a ring of
        ``window`` rows a slot, a full layer every position."""
        rows = self.window if slides else positions
        return (
            2 * self.kind_layers(slides) * slots * rows * self.kv_heads
            * self.head_dim * itemsize
        )


def tensor_key(key: jax.Array, name: str, layer) -> jax.Array:
    return jax.random.fold_in(
        jax.random.fold_in(key, TENSORS.index(name)), layer
    )


def draw(key, arch: Arch, name: str, layer, dtype):
    """One tensor of one layer (0 for the tables) in ``dtype``."""
    shape = arch.shape(name)
    k = tensor_key(key, name, layer)
    if name in _EXPERT:  # an expert's matrices by its index
        w = jax.vmap(
            lambda e: jax.random.normal(
                jax.random.fold_in(k, e), shape[1:], jnp.float32
            )
        )(jnp.arange(shape[0], dtype=jnp.int32))
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
    after another (a layer's float32 normals are 1.6 GB before they are
    rounded)."""
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
    from chipbench.reference import mellum_decoder as reference

    a = Arch.from_conf(conf)
    dep = conf["deployment"]
    # The serving loop hands the reference ``weights.Dims`` alone; the
    # family's other sizes are found by them.
    reference.register(W.Dims.from_conf(conf), a, dep)
    try:
        from torchkafka_tpu.models.transformer import (
            RopeKind, TransformerConfig,
        )

        def kind(r: Rope):
            if r == Rope(theta=a.rope_window.theta):
                return None  # plain, at ``rope_theta``
            return RopeKind(
                theta=r.theta, factor=r.factor, original_len=r.original,
                beta_fast=r.beta_fast, beta_slow=r.beta_slow,
                attention_factor=r.attention_factor,
            )

        return TransformerConfig(
            vocab_size=a.vocab, d_model=a.hidden, n_layers=a.layers,
            n_heads=a.heads, n_kv_heads=a.kv_heads, d_ff=a.unused_ffn,
            max_seq_len=max_seq_len, rope_theta=a.rope_window.theta,
            dtype=dtype_of(dep["compute_dtype"]),
            param_dtype=dtype_of(dep["param_dtype"]),
            stated_head_dim=a.head_dim,
            sliding_window=a.window if any(a.pattern) else 0,
            window_pattern=a.pattern, rope_window=kind(a.rope_window),
            rope_full=kind(a.rope_full), n_experts=a.experts,
            expert_top_k=a.top_k, expert_d_ff=a.expert_ffn,
            router_score="softmax", norm_topk=True, **extra,
        )
    except (ImportError, TypeError) as e:
        # A program from before the family was built: nothing to measure.
        raise common.Refused(
            f"this program's TransformerConfig does not take the family: {e}"
        ) from e


def serving_params(conf: dict, seed: int):
    """The model on the device, in one jitted call from the seed."""
    arch = Arch.from_conf(conf)
    dtype = dtype_of(conf["deployment"]["param_dtype"])
    return jax.jit(lambda key: serving_tree(key, arch, dtype))(W.seed_key(seed))
