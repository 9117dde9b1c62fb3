"""Bridge from a latent-attention, routed-expert configuration file
(Hugging Face ``deepseek_v3`` keys) to the program, and the family's
weights from the seed.

The program receives weights, it does not make them: ``serving_params``
draws the whole bfloat16 model on the device in the program's stacked
layout (``params["dense_layers"]`` for the leading dense layers, then
``params["layers"]`` for the expert layers), and the plain reference
(``chipbench.reference.mla_moe_decoder``) draws the same numbers again,
one layer at a time, with ``layer_weights``. Every tensor of every layer
has a key of its own, ``fold_in(fold_in(key(seed), tensor), layer)``,
``layer`` counted over both groups. Matmul weights are normal with
standard deviation ``1/sqrt(fan_in)`` rounded to the parameters' dtype,
norms are one, and the selection bias (``e_score_correction_bias``, which
``config.json`` does not give) is normal with the file's ``assumed``
sigma of 0.01. Two departures from that rule, both the file's ``assumed``
and both for the conditioning of a RANDOM model with discrete routing
(PERF.md, PR 27): the embedding's rows have unit variance (a trained
model's residual stream has entries of order one; at ``1/sqrt(hidden)``
the stream is 0.02 and the first layer's output replaces it), and the
projections that write into the residual stream (``wo``, ``w_down``,
``ws_down``) are scaled by ``1/sqrt(2 * published depth)``, GPT-2's and
Megatron's initialiser. With ``1/sqrt(fan_in)`` everywhere every layer
rewrites the stream, one flipped top-6 near-tie moves a token's state by
a fifth, the flips compound (94% of routings agree with the float32
reference in the first expert layer, 49% in the sixth), and two bfloat16
formulations of the SAME function differ by 2.7 in a logit: no comparison
could then tell a sound run from a broken one.

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
BIAS_SIGMA = 0.01
TENSORS = (
    "embed", "lm_head", "wq", "wkva", "wkvb", "wo", "w_gate", "w_up",
    "w_down", "router", "router_bias", "ws_gate", "ws_up", "ws_down",
)
_ATTN = ("wq", "wkva", "wkvb", "wo")
_MLP = ("w_gate", "w_up", "w_down")
_EXPERT = ("router", "router_bias", "ws_gate", "ws_up", "ws_down")
_WRITES_RESIDUAL = ("wo", "w_down", "ws_down")


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes of the family, read from a configuration file's
    published keys."""

    hidden: int
    layers: int
    published_layers: int
    dense_layers: int
    heads: int
    rank: int
    nope: int
    rope: int
    v: int
    ffn: int
    experts: int
    top_k: int
    expert_ffn: int
    shared: int
    vocab: int
    rope_theta: float
    rope_interleave: bool
    rms_eps: float
    scaling: float

    @classmethod
    def from_conf(cls, conf: dict) -> "Arch":
        for key, want in (
            ("q_lora_rank", None), ("rope_scaling", None), ("n_group", 1),
            ("topk_group", 1), ("scoring_func", "sigmoid"),
            ("norm_topk_prob", True), ("moe_layer_freq", 1),
            ("attention_bias", False), ("hidden_act", "silu"),
        ):
            if conf.get(key, want) != want:
                raise ValueError(
                    f"{key}={conf[key]!r}: the family is built for {want!r}"
                )
        return cls(
            hidden=int(conf["hidden_size"]),
            layers=int(conf["num_hidden_layers"]),
            published_layers=int(
                conf.get("published_num_hidden_layers", conf["num_hidden_layers"])
            ),
            dense_layers=int(conf["first_k_dense_replace"]),
            heads=int(conf["num_attention_heads"]),
            rank=int(conf["kv_lora_rank"]),
            nope=int(conf["qk_nope_head_dim"]),
            rope=int(conf["qk_rope_head_dim"]),
            v=int(conf["v_head_dim"]),
            ffn=int(conf["intermediate_size"]),
            experts=int(conf["n_routed_experts"]),
            top_k=int(conf["num_experts_per_tok"]),
            expert_ffn=int(conf["moe_intermediate_size"]),
            shared=int(conf["n_shared_experts"]),
            vocab=int(conf["vocab_size"]),
            rope_theta=float(conf["rope_theta"]),
            rope_interleave=bool(conf["rope_interleave"]),
            rms_eps=float(conf["rms_norm_eps"]),
            scaling=float(conf["routed_scaling_factor"]),
        )

    @property
    def latent(self) -> int:
        return self.rank + self.rope

    def is_expert_layer(self, layer: int) -> bool:
        return layer >= self.dense_layers

    def tensors(self, expert_layer: bool) -> tuple[str, ...]:
        return _ATTN + _MLP + (_EXPERT if expert_layer else ())

    def shape(self, name: str, expert_layer: bool) -> tuple[int, ...]:
        d, h, e = self.hidden, self.heads, self.experts
        f = self.expert_ffn if expert_layer else self.ffn
        fs = self.shared * self.expert_ffn
        lead = (e,) if expert_layer else ()
        return {
            "embed": (self.vocab, d), "lm_head": (d, self.vocab),
            "wq": (d, h, self.nope + self.rope),
            "wkva": (d, self.latent),
            "wkvb": (self.rank, h, self.nope + self.v),
            "wo": (h, self.v, d),
            "w_gate": lead + (d, f), "w_up": lead + (d, f),
            "w_down": lead + (f, d),
            "router": (d, e), "router_bias": (e,),
            "ws_gate": (d, fs), "ws_up": (d, fs), "ws_down": (fs, d),
        }[name]

    def fan_in(self, name: str, expert_layer: bool) -> int:
        d = self.hidden
        f = self.expert_ffn if expert_layer else self.ffn
        return {
            "embed": d, "lm_head": d, "wq": d, "wkva": d, "wkvb": self.rank,
            "wo": self.heads * self.v, "w_gate": d, "w_up": d, "w_down": f,
            "router": d, "ws_gate": d, "ws_up": d,
            "ws_down": self.shared * self.expert_ffn,
        }[name]

    def layer_params(self, expert_layer: bool) -> int:
        norms = 2 * self.hidden + self.rank
        return norms + sum(
            math.prod(self.shape(n, expert_layer))
            for n in self.tensors(expert_layer)
        )

    @property
    def params(self) -> int:
        return (
            2 * self.vocab * self.hidden + self.hidden
            + self.dense_layers * self.layer_params(False)
            + (self.layers - self.dense_layers) * self.layer_params(True)
        )


def tensor_key(key: jax.Array, name: str, layer) -> jax.Array:
    return jax.random.fold_in(
        jax.random.fold_in(key, TENSORS.index(name)), layer
    )


def draw(key, arch: Arch, name: str, layer, dtype, expert_layer: bool = False):
    """One tensor of one layer (``layer`` over both groups; 0 for the
    tables) in ``dtype``."""
    shape = arch.shape(name, expert_layer)
    w = jax.random.normal(tensor_key(key, name, layer), shape, jnp.float32)
    # A product with a constant, not a quotient: the compiler may turn a
    # division into a reciprocal's product in one program and not in
    # another, and the program's draw and the reference's must round alike.
    if name == "router_bias":
        scale = BIAS_SIGMA
    elif name == "embed":
        scale = 1.0
    else:
        scale = 1.0 / math.sqrt(arch.fan_in(name, expert_layer))
        if name in _WRITES_RESIDUAL:
            scale /= math.sqrt(2 * arch.published_layers)
    return (w * jnp.float32(scale)).astype(dtype)


def layer_weights(key, arch: Arch, layer, dtype, expert: bool) -> dict:
    """Layer ``layer`` (an expert layer or a leading dense one) as the
    served model stores it."""
    w = {
        n: draw(key, arch, n, layer, dtype, expert) for n in arch.tensors(expert)
    }
    w["ln1"] = w["ln2"] = jnp.ones((arch.hidden,), dtype)
    w["kv_norm"] = jnp.ones((arch.rank,), dtype)
    return w


def _group(key, arch: Arch, first: int, count: int, dtype) -> dict:
    """``count`` layers from ``first`` on, stacked, drawn one layer after
    another (an expert layer's float32 normals are 2.4 GB before they are
    rounded: the layers must not be drawn at once)."""
    expert = arch.is_expert_layer(first)
    layers = first + jnp.arange(count, dtype=jnp.int32)
    out = {
        n: jax.lax.map(lambda l, n=n: draw(key, arch, n, l, dtype, expert), layers)
        for n in arch.tensors(expert)
    }
    out["ln1"] = out["ln2"] = jnp.ones((count, arch.hidden), dtype)
    out["kv_norm"] = jnp.ones((count, arch.rank), dtype)
    return out


def serving_tree(key, arch: Arch, dtype) -> dict:
    tree = {
        "embed": draw(key, arch, "embed", 0, dtype),
        "lm_head": draw(key, arch, "lm_head", 0, dtype),
        "ln_f": jnp.ones((arch.hidden,), dtype),
        "layers": _group(
            key, arch, arch.dense_layers, arch.layers - arch.dense_layers, dtype
        ),
    }
    if arch.dense_layers:
        tree["dense_layers"] = _group(key, arch, 0, arch.dense_layers, dtype)
    return tree


def dtype_of(name: str):
    return _DTYPES[name]


def program_config(conf: dict, max_seq_len: int, **extra):
    """The program's ``TransformerConfig`` at the file's sizes."""
    from torchkafka_tpu.models import TransformerConfig

    from chipbench.reference import mla_moe_decoder as reference

    a = Arch.from_conf(conf)
    # The serving loop hands the reference ``weights.Dims`` alone; the
    # family's other sizes are found by them.
    reference.register(W.Dims.from_conf(conf), a, conf["deployment"])
    dep = conf["deployment"]
    try:
        return _program_config(TransformerConfig, a, dep, max_seq_len, extra)
    except TypeError as e:
        # A program from before the family was built: nothing to measure.
        from chipbench import common

        raise common.Refused(
            f"this program's TransformerConfig does not take the family: {e}"
        ) from e


def _program_config(TransformerConfig, a: Arch, dep: dict, max_seq_len, extra):
    return TransformerConfig(
        vocab_size=a.vocab, d_model=a.hidden, n_layers=a.layers,
        n_heads=a.heads, n_kv_heads=a.heads, d_ff=a.ffn,
        max_seq_len=max_seq_len, rope_theta=a.rope_theta,
        dtype=dtype_of(dep["compute_dtype"]),
        param_dtype=dtype_of(dep["param_dtype"]),
        kv_lora_rank=a.rank, qk_nope_dim=a.nope, qk_rope_dim=a.rope,
        v_head_dim=a.v, rope_interleave=a.rope_interleave,
        first_dense_layers=a.dense_layers, n_experts=a.experts,
        expert_top_k=a.top_k, expert_d_ff=a.expert_ffn,
        n_shared_experts=a.shared, router_score="sigmoid",
        routed_scaling=a.scaling, **extra,
    )


def serving_params(conf: dict, seed: int):
    """The model on the device, in one jitted call from the seed."""
    arch = Arch.from_conf(conf)
    dtype = dtype_of(conf["deployment"]["param_dtype"])
    return jax.jit(lambda key: serving_tree(key, arch, dtype))(W.seed_key(seed))
