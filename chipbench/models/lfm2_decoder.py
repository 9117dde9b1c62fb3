"""Bridge from an ``lfm2_moe`` configuration file (LFM2-8B-A1B:
``config.json``'s own keys) to the program, and the family's weights from
the seed.

The family: ``layer_types`` names each layer's sequence mixer, a GATED
SHORT CONVOLUTION (``torchkafka_tpu/ops/gconv.py``: ``hidden_size``
channels, ``conv_L_cache`` taps, no bias, no activation, gated on both
sides, and no state) or grouped-query attention whose q and k are
RMS-normed a head before the rotation; the first ``num_dense_layers``
layers close with a dense SwiGLU of ``intermediate_size``, every other
with ``num_experts`` experts of ``moe_intermediate_size`` under a SIGMOID
router whose bias moves the selection alone (``use_expert_bias``), the
``num_experts_per_tok`` chosen scores divided by their sum
(``norm_topk_prob``); no shared expert; a TIED head. The file describes
the FIRST PIPELINE STAGE of a deployment: ``num_hidden_layers`` in the
file is the number of layers this chip runs, in published order from layer
0, ``published_num_hidden_layers`` the model's; every width, every expert
and the whole vocabulary are the source's.

The program receives weights, it does not make them: ``serving_params``
draws the stage on the device in the program's layout, stacked by kind
(``models/transformer.py::scan_hybrid``; the leading dense layers a group
of their own), and the plain reference
(``chipbench.reference.lfm2_decoder``) draws the same numbers again, a
layer at a time. Every tensor of every layer has a key of its own,
``fold_in(fold_in(key(seed), tensor), layer)``, an expert's matrices one
more ``fold_in(.., expert)``. Matmul weights are normal with standard
deviation ``1/sqrt(fan_in)`` rounded to the parameters' dtype, the three
projections back into the stream (``g_out``, ``wo``, ``w_down`` /
``we_down``) times ``1/sqrt(2 * published layers)`` more, the taps normal
at ``1/sqrt(taps)``, norms one, the selection bias normal at
``BIAS_SIGMA``. The file's ``assumed`` says why each.

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
# A tenth of the median gap between the 4th and the 5th largest of 32
# sigmoids of unit normals (0.0194 over two million draws): the bias
# decides the selection where the scores nearly tie, and nowhere else.
BIAS_SIGMA = 0.002
TENSORS = (
    "embed", "g_in", "g_conv", "g_out", "wq", "wk", "wv", "wo", "w_gate",
    "w_up", "w_down", "router", "router_bias", "we_gate", "we_up", "we_down",
)
CONV = ("g_in", "g_conv", "g_out")
ATTENTION = ("wq", "wk", "wv", "wo")
DENSE = ("w_gate", "w_up", "w_down")
EXPERT = ("we_gate", "we_up", "we_down")
ROUTER = ("router", "router_bias")
# The projections back into the stream, scaled down by the depth.
OUTWARD = ("g_out", "wo", "w_down", "we_down")


def dims_conf(conf: dict) -> dict:
    """``conf`` with the key ``weights.Dims`` reads the norm's eps by (the
    family's ``config.json`` calls it ``norm_eps``)."""
    return {**conf, "rms_norm_eps": conf["norm_eps"]}


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes of the family, read from a configuration file."""

    hidden: int
    layers: int
    published_layers: int
    kinds: tuple[bool, ...]  # a layer: True the gated convolution
    dense_layers: int
    heads: int
    kv_heads: int
    head: int
    taps: int
    experts: int
    top_k: int
    dense_ffn: int
    expert_ffn: int
    vocab: int
    rms_eps: float
    rope_theta: float
    scaling: float

    @classmethod
    def from_conf(cls, conf: dict) -> "Arch":
        for key, want in (
            ("conv_bias", False), ("norm_topk_prob", True),
            ("use_expert_bias", True), ("model_type", "lfm2_moe"),
        ):
            if conf.get(key, want) != want:
                raise ValueError(
                    f"{key}={conf[key]!r}: the family is built for {want!r}"
                )
        layers = int(conf["num_hidden_layers"])
        names = conf["layer_types"]
        if len(names) < layers or set(names) - {"conv", "full_attention"}:
            raise ValueError(
                f"layer_types names {len(names)} layers of "
                f"{sorted(set(names))}; the file runs {layers}"
            )
        hidden = int(conf["hidden_size"])
        heads = int(conf["num_attention_heads"])
        return cls(
            hidden=hidden, layers=layers,
            published_layers=int(conf["published_num_hidden_layers"]),
            kinds=tuple(t == "conv" for t in names[:layers]),
            dense_layers=int(conf["num_dense_layers"]), heads=heads,
            kv_heads=int(conf["num_key_value_heads"]),
            head=int(conf.get("head_dim") or hidden // heads),
            taps=int(conf["conv_L_cache"]),
            experts=int(conf["num_experts"]),
            top_k=int(conf["num_experts_per_tok"]),
            dense_ffn=int(conf["intermediate_size"]),
            expert_ffn=int(conf["moe_intermediate_size"]),
            vocab=int(conf["vocab_size"]),
            rms_eps=float(conf["norm_eps"]),
            rope_theta=float(conf["rope_theta"]),
            scaling=float(conf["routed_scaling_factor"]),
        )

    @property
    def kv_row(self) -> int:
        """A position's K (or V) row: the kv heads side by side."""
        return self.kv_heads * self.head

    @property
    def pattern(self) -> tuple[bool, ...]:
        """The period of the layers after the leading dense ones: the
        shortest prefix of their kinds that repeats."""
        kinds = self.kinds[self.dense_layers:]
        for p in range(1, len(kinds) + 1):
            if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
                return kinds[:p]
        return kinds

    def is_linear(self, layer: int) -> bool:
        return self.kinds[layer]

    def is_dense(self, layer: int) -> bool:
        return layer < self.dense_layers

    def kind_layers(self, linear: bool, dense: bool) -> list[int]:
        """The layers of one kind of mixer among the leading dense layers
        (``dense``) or among the expert layers."""
        return [
            l for l in range(self.layers)
            if self.kinds[l] == linear and self.is_dense(l) == dense
        ]

    @staticmethod
    def tensors(linear: bool, dense: bool) -> tuple[str, ...]:
        """The drawn tensors of a layer of a kind but its experts'
        (``EXPERT``, drawn an expert at a time)."""
        return (CONV if linear else ATTENTION) + (DENSE if dense else ROUTER)

    def shape(self, name: str) -> tuple[int, ...]:
        """A tensor of one layer, or ONE expert's matrix."""
        d, h, k, e = self.hidden, self.heads, self.kv_heads, self.head
        return {
            "embed": (self.vocab, d),
            "g_in": (d, 3 * d), "g_conv": (self.taps, d), "g_out": (1, d, d),
            "wq": (d, h, e), "wk": (d, k, e), "wv": (d, k, e),
            "wo": (h, e, d),
            "w_gate": (d, self.dense_ffn), "w_up": (d, self.dense_ffn),
            "w_down": (self.dense_ffn, d),
            "router": (d, self.experts), "router_bias": (self.experts,),
            "we_gate": (d, self.expert_ffn), "we_up": (d, self.expert_ffn),
            "we_down": (self.expert_ffn, d),
        }[name]

    def sigma(self, name: str) -> float:
        """The standard deviation a tensor is drawn at."""
        if name == "router_bias":
            return BIAS_SIGMA
        fan_in = {
            "g_conv": self.taps, "wo": self.heads * self.head,
            "w_down": self.dense_ffn, "we_down": self.expert_ffn,
        }.get(name, self.hidden)
        depth = 2 * self.published_layers if name in OUTWARD else 1
        return 1.0 / math.sqrt(fan_in * depth)

    def mixer_params(self, linear: bool) -> int:
        """A mixer of one kind as the source counts it (the attention's
        two norms a head with it; the layer's two norms apart)."""
        own = 0 if linear else 2 * self.head
        return own + sum(
            math.prod(self.shape(n)) for n in (CONV if linear else ATTENTION)
        )

    @property
    def expert_params(self) -> int:
        return sum(math.prod(self.shape(n)) for n in EXPERT)

    def layer_params(self, layer: int) -> int:
        ffn = (
            sum(math.prod(self.shape(n)) for n in DENSE)
            if self.is_dense(layer) else
            self.experts * self.expert_params
            + sum(math.prod(self.shape(n)) for n in ROUTER)
        )
        return self.mixer_params(self.kinds[layer]) + 2 * self.hidden + ffn

    @property
    def params(self) -> int:
        """The stage: the tied matrix once, the final norm."""
        return self.vocab * self.hidden + self.hidden + sum(
            self.layer_params(l) for l in range(self.layers)
        )


def _key(key, name: str, layer, expert=None):
    k = jax.random.fold_in(jax.random.fold_in(key, TENSORS.index(name)), layer)
    return k if expert is None else jax.random.fold_in(k, expert)


def draw(key, arch: Arch, name: str, layer, dtype, expert=None):
    """One tensor of layer ``layer`` (0 for the table) in ``dtype``; with
    ``expert`` one expert's matrix. A product with a constant, not a
    quotient: the program's draw and the reference's must round alike."""
    w = jax.random.normal(
        _key(key, name, layer, expert), arch.shape(name), jnp.float32
    )
    return (w * jnp.float32(arch.sigma(name))).astype(dtype)


def experts_of(key, arch: Arch, layer, dtype) -> dict:
    """Every expert of layer ``layer``, stacked ``[experts, ..]``."""
    every = jnp.arange(arch.experts, dtype=jnp.int32)
    return {
        n: jax.lax.map(
            lambda e, n=n: draw(key, arch, n, layer, dtype, expert=e), every
        )
        for n in EXPERT
    }


def layer_weights(key, arch: Arch, layer, dtype, kind=None) -> dict:
    """Layer ``layer`` as the reference reads it: its mixer's tensors,
    its norms at one, its dense SwiGLU or its router, bias and experts
    (``we_*`` stacked). ``kind``: the layer's ``(linear, dense)``, given
    where ``layer`` is a traced value."""
    linear, dense = kind or (arch.kinds[layer], arch.is_dense(layer))
    w = {
        n: draw(key, arch, n, layer, dtype) for n in arch.tensors(linear, dense)
    }
    w["ln1"] = w["ln2"] = jnp.ones((arch.hidden,), dtype)
    if not linear:
        w["q_head_norm"] = w["k_head_norm"] = jnp.ones((arch.head,), dtype)
    if not dense:
        w.update(experts_of(key, arch, layer, dtype))
    return w


def _group(key, arch: Arch, dtype, dense: bool) -> dict:
    """One stacked group of the program's tree: the norms and the FFN's
    tensors over every layer of the group, each kind's own over its
    layers; a layer after another (an expert layer's float32 normals are
    gigabytes before they are rounded)."""

    def stacked(names, over):
        at = jnp.asarray(over, jnp.int32)
        return {
            n: jax.lax.map(lambda l, n=n: draw(key, arch, n, l, dtype), at)
            for n in names
        } if over else {}

    lin, att = arch.kind_layers(True, dense), arch.kind_layers(False, dense)
    every = sorted(lin + att)
    group = {**stacked(CONV, lin), **stacked(ATTENTION, att)}
    if att:
        group["q_head_norm"] = group["k_head_norm"] = jnp.ones(
            (len(att), arch.head), dtype
        )
    group["ln1"] = group["ln2"] = jnp.ones((len(every), arch.hidden), dtype)
    if dense:
        return {**group, **stacked(DENSE, every)}
    group.update(stacked(ROUTER, every))
    mats = jax.lax.map(
        lambda l: experts_of(key, arch, l, dtype),
        jnp.asarray(every, jnp.int32),
    )
    # The program's names for an expert layer's experts.
    group.update({f"w_{n[3:]}": mats[n] for n in EXPERT})
    return group


def serving_tree(key, arch: Arch, dtype) -> dict:
    """The stage in the program's layout."""
    return {
        "embed": draw(key, arch, "embed", 0, dtype),
        "ln_f": jnp.ones((arch.hidden,), dtype),
        "dense_layers": _group(key, arch, dtype, True),
        "layers": _group(key, arch, dtype, False),
    }


def dtype_of(name: str):
    return _DTYPES[name]


def program_config(conf: dict, max_seq_len: int, **extra):
    """The program's ``TransformerConfig`` at the file's sizes."""
    from torchkafka_tpu.models import TransformerConfig

    from chipbench.reference import lfm2_decoder as reference

    a = Arch.from_conf(conf)
    if any(not k for k in a.kinds[: a.dense_layers]):
        raise ValueError("the leading dense layers are convolutions")
    # The serving loop hands the reference ``weights.Dims`` alone; the
    # family's other sizes are found by them.
    reference.register(W.Dims.from_conf(dims_conf(conf)), a, conf["deployment"])
    dep = conf["deployment"]
    try:
        return TransformerConfig(
            vocab_size=a.vocab, d_model=a.hidden, n_layers=a.layers,
            n_heads=a.heads, n_kv_heads=a.kv_heads, d_ff=a.dense_ffn,
            stated_head_dim=a.head, max_seq_len=max_seq_len,
            rope_theta=a.rope_theta,
            dtype=dtype_of(dep["compute_dtype"]),
            param_dtype=dtype_of(dep["param_dtype"]),
            n_experts=a.experts, expert_top_k=a.top_k,
            expert_d_ff=a.expert_ffn, first_dense_layers=a.dense_layers,
            router_score="sigmoid", norm_topk=True, routed_scaling=a.scaling,
            linear_pattern=a.pattern, linear_kind="conv", linear_conv=a.taps,
            qk_norm=True, tie_embeddings=True, norm_eps=a.rms_eps, **extra,
        )
    except TypeError as e:
        # A program from before the family was built: nothing to measure.
        from chipbench import common

        raise common.Refused(
            f"this program's TransformerConfig does not take the family: {e}"
        ) from e


def serving_params(conf: dict, seed: int):
    """The stage on the device, in one jitted call from the seed."""
    arch = Arch.from_conf(conf)
    dtype = dtype_of(conf["deployment"]["param_dtype"])
    return jax.jit(lambda key: serving_tree(key, arch, dtype))(W.seed_key(seed))


def final_stream(cfg, params, tokens, rows: int = 4):
    """The program's forward over ``tokens`` [B, T], the one an admission
    runs (``generate.prefill``'s), ``rows`` rows a call → (its stream after
    the LAST layer, before the final norm, [B, T, D]; the K rows it would
    cache [L_att, B, T, K * Dh]; the conv tails it would leave after the T
    tokens [L_lin, B, taps - 1, D]), float32 on the host. What the last
    layer adds no slot keeps: the serving loop holds the stream against
    the reference's, by the last layer's parts, and finds a prompt's slot
    by the rows."""
    import numpy as np

    from torchkafka_tpu.models import Transformer
    from torchkafka_tpu.models.linear_attn import hybrid_forward
    from torchkafka_tpu.models.transformer import embed_tokens

    model = Transformer(cfg)

    @jax.jit
    def some(params, toks):
        x = embed_tokens(params, cfg, toks)
        x, (tails, k_rows, _v), _chosen = hybrid_forward(params, model, x)
        # (the program keeps a slot's conv tail in one row)
        tails = tails.reshape(*tails.shape[:2], cfg.linear_conv - 1, -1)
        return tuple(a.astype(jnp.float32) for a in (x, k_rows, tails))

    rows = math.gcd(len(tokens), rows)
    got = [
        jax.device_get(some(params, jnp.asarray(tokens[i:i + rows], jnp.int32)))
        for i in range(0, len(tokens), rows)
    ]
    return (np.concatenate([g[0] for g in got]),) + tuple(
        np.concatenate([g[i] for g in got], axis=1) for i in (1, 2)
    )


def router_of(params, layer: int):
    """The router and its bias of expert layer ``layer`` (its index among
    the expert layers) of the served tree: all ``route_rows`` needs, so
    that the tree can be freed."""
    group = params["layers"]
    return group["router"][layer], group["router_bias"][layer]


def route_rows(cfg, router, rows):
    """The program's own router (``ops/moe.py::route``, the function a
    tick and an admission call) with ``router`` = ``router_of``'s pair on
    ``rows`` [N, D] → (chosen [N, K], gates [N, K]), on the host."""
    from torchkafka_tpu.ops import moe

    @jax.jit
    def some(weight, bias, rows):
        return moe.route(
            rows, weight, bias, top_k=cfg.expert_top_k,
            scaling=cfg.routed_scaling, score=cfg.router_score,
            norm_topk=cfg.norm_topk,
        )

    return jax.device_get(some(*router, jnp.asarray(rows, cfg.dtype)))
