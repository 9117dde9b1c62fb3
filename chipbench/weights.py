"""Weights from the seed: the benchmark's own draw, shared by the model
builders (which hand the arrays to the program) and the plain references
(which draw the same arrays again, one layer at a time).

Every tensor of every layer has a key of its own,
``fold_in(fold_in(key(seed), tensor), layer)``, so one layer's weights can
be drawn without the others and a stacked draw (``vmap`` over the layers)
gives the same numbers. The distribution is the one the program's own
initialisers use (a copy of the arithmetic in ``models/transformer.py::
init_params`` and ``models/zoo.py::random_serving_params``): matmul weights
with standard deviation ``1/sqrt(fan_in)``, norms at one. Served int8
weights are uniform integers in [-127, 127] with one constant scale a
tensor that gives that standard deviation after dequantisation.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

TENSORS = (
    "embed", "lm_head", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
)
LAYER_TENSORS = TENSORS[2:]
# Uniform integers over [-127, 127] have this standard deviation.
_UNIFORM_INT8_STD = 127.0 / math.sqrt(3.0)


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense decoder, read from a configuration file's
    published keys (Hugging Face ``config.json`` names)."""

    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    rms_eps: float

    @classmethod
    def from_conf(cls, conf: dict) -> "Dims":
        heads = int(conf["num_attention_heads"])
        hidden = int(conf["hidden_size"])
        return cls(
            hidden=hidden,
            layers=int(conf["num_hidden_layers"]),
            heads=heads,
            kv_heads=int(conf["num_key_value_heads"]),
            head_dim=int(conf.get("head_dim") or hidden // heads),
            ffn=int(conf["intermediate_size"]),
            vocab=int(conf["vocab_size"]),
            rope_theta=float(conf["rope_theta"]),
            rms_eps=float(conf["rms_norm_eps"]),
        )

    def shape(self, name: str) -> tuple[int, ...]:
        """One layer's shape of a layer tensor, or a table's shape."""
        d, h, k, e, f, v = (
            self.hidden, self.heads, self.kv_heads, self.head_dim, self.ffn,
            self.vocab,
        )
        return {
            "embed": (v, d), "lm_head": (d, v),
            "wq": (d, h, e), "wk": (d, k, e), "wv": (d, k, e),
            "wo": (h, e, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
        }[name]

    def fan_in(self, name: str) -> int:
        return {
            "embed": self.hidden, "lm_head": self.hidden,
            "wq": self.hidden, "wk": self.hidden, "wv": self.hidden,
            "wo": self.heads * self.head_dim,
            "w_gate": self.hidden, "w_up": self.hidden, "w_down": self.ffn,
        }[name]

    def scale_shape(self, name: str) -> tuple[int, ...]:
        """Shape of an int8 tensor's scale: one on the axes the matmul
        contracts over (the embedding is scaled by row)."""
        contract = {
            "embed": (1,), "lm_head": (0,), "wq": (0,), "wk": (0,),
            "wv": (0,), "wo": (0, 1), "w_gate": (0,), "w_up": (0,),
            "w_down": (0,),
        }[name]
        return tuple(
            1 if ax in contract else s
            for ax, s in enumerate(self.shape(name))
        )

    @property
    def matmul_params(self) -> int:
        """Parameters every token multiplies with: the layers and the
        head (the embedding is a gather)."""
        d, f = self.hidden, self.ffn
        attn = d * (self.heads + 2 * self.kv_heads) * self.head_dim
        attn += self.heads * self.head_dim * d
        return self.layers * (attn + 3 * d * f) + d * self.vocab

    @property
    def params(self) -> int:
        return (
            self.matmul_params + self.vocab * self.hidden
            + (2 * self.layers + 1) * self.hidden
        )


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number: ``--seed`` may pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def tensor_key(key: jax.Array, name: str, layer: int | jax.Array) -> jax.Array:
    return jax.random.fold_in(
        jax.random.fold_in(key, TENSORS.index(name)), layer
    )


def int8_scale(dims: Dims, name: str) -> float:
    return 1.0 / (_UNIFORM_INT8_STD * math.sqrt(dims.fan_in(name)))


def draw_int8(key: jax.Array, dims: Dims, name: str, layer) -> jax.Array:
    return jax.random.randint(
        tensor_key(key, name, layer), dims.shape(name), -127, 128,
        dtype=jnp.int8,
    )


def draw_normal(key: jax.Array, dims: Dims, name: str, layer, dtype):
    w = jax.random.normal(
        tensor_key(key, name, layer), dims.shape(name), jnp.float32
    )
    return (w / math.sqrt(dims.fan_in(name))).astype(dtype)


def _stacked(draw, key, dims: Dims, name: str, *extra):
    layers = jnp.arange(dims.layers, dtype=jnp.int32)
    return jax.vmap(lambda l: draw(key, dims, name, l, *extra))(layers)


def serving_tree(key: jax.Array, dims: Dims) -> dict:
    """The whole int8 model in the program's stacked layout, as plain
    arrays: a quantised tensor is ``{"q": int8, "scale": float32}``."""

    def qleaf(name: str, q: jax.Array, lead: tuple[int, ...] = ()) -> dict:
        scale = jnp.full(
            lead + dims.scale_shape(name), int8_scale(dims, name), jnp.float32
        )
        return {"q": q, "scale": scale}

    layers: dict = {
        "ln1": jnp.ones((dims.layers, dims.hidden), jnp.float32),
        "ln2": jnp.ones((dims.layers, dims.hidden), jnp.float32),
    }
    for name in LAYER_TENSORS:
        layers[name] = qleaf(
            name, _stacked(draw_int8, key, dims, name), (dims.layers,)
        )
    return {
        "embed": qleaf("embed", draw_int8(key, dims, "embed", 0)),
        "layers": layers,
        "ln_f": jnp.ones((dims.hidden,), jnp.float32),
        "lm_head": qleaf("lm_head", draw_int8(key, dims, "lm_head", 0)),
    }


def training_tree(key: jax.Array, dims: Dims, dtype) -> dict:
    """The whole model for training in the program's stacked layout."""
    layers: dict = {
        "ln1": jnp.ones((dims.layers, dims.hidden), dtype),
        "ln2": jnp.ones((dims.layers, dims.hidden), dtype),
    }
    for name in LAYER_TENSORS:
        layers[name] = _stacked(draw_normal, key, dims, name, dtype)
    return {
        "embed": draw_normal(key, dims, "embed", 0, dtype),
        "layers": layers,
        "ln_f": jnp.ones((dims.hidden,), dtype),
        "lm_head": draw_normal(key, dims, "lm_head", 0, dtype),
    }
