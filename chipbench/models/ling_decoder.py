"""Bridge from a ``bailing_hybrid`` configuration file (Ling-3.0-flash:
``config.json``'s own keys) to the program, and the family's weights from
the seed.

The family: periods of ``layer_group_size`` layers, the last of a period a
latent-attention (MLA) layer, the others linear-attention layers of the
delta rule with a decay a channel (KDA: ``torchkafka_tpu/ops/kda.py``);
the leading ``first_k_dense_replace`` layers are linear with a dense
SwiGLU, every other layer routes over ``published_num_experts`` sigmoid
scores with a selection bias, CHOSEN BY GROUP (``n_group`` groups of
consecutive experts, the ``topk_group`` best by the sum of their two best
scores), beside one shared expert; both kinds of attention gate their
output by one scalar a head. The file describes ONE CHIP'S SHARE of a
deployment in which ``n_group`` chips share each layer, a group a chip:
``num_experts`` in the file is the number of experts whose weights this
chip holds (``deployment.experts_held`` says which), ``published_num_
experts`` the number the router scores.

The program receives weights, it does not make them: ``serving_params``
draws the whole bfloat16 share on the device in the program's layout,
stacked by kind (``models/transformer.py::scan_hybrid``), and the plain
reference (``chipbench.reference.ling_decoder``) draws the same numbers
again, a layer at a time. Every tensor of every layer has a key of its
own, ``fold_in(fold_in(key(seed), tensor), layer)``, and an expert's
matrices one more ``fold_in(.., expert)`` with the expert's PUBLISHED
index, so an expert's weights do not depend on which chip holds it.
Matmul weights are normal with standard deviation ``1/sqrt(fan_in)``
rounded to the parameters' dtype (the convolution's four taps a channel
likewise, fan-in 4) and norms are one. The file's ``assumed`` says what
else: the embedding's rows at unit variance and every projection that
writes into the residual stream scaled by ``1/sqrt(2 * published depth)``
(as the other latent-attention family, PERF.md, PR 27); the selection
bias normal with ``BIAS_SIGMA``; and the decay's parameters, which
``config.json`` does not give: ``dt_bias`` uniform over ``DT_RANGE`` a
channel and ``A_log`` uniform over ``[0, ln 2]`` a head, so that with
``a = W_f x`` of unit variance the log-decay ``-5 sigmoid(e^A_log (a +
dt_bias))`` spreads over three decades, a channel's half-life from under
a token to the whole context.

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
DT_RANGE = (-6.0, 2.0)
TENSORS = (
    "embed", "lm_head", "lqkv", "lconv", "lf", "l_alog", "l_dt", "lb", "lg",
    "lo", "wq", "wkva", "wkvb", "wo", "wg", "w_gate", "w_up", "w_down",
    "router", "router_bias", "we_gate", "we_up", "we_down", "ws_gate",
    "ws_up", "ws_down",
)
LINEAR = ("lqkv", "lconv", "lf", "l_alog", "l_dt", "lb", "lg", "lo")
LATENT = ("wq", "wkva", "wkvb", "wo", "wg")
DENSE = ("w_gate", "w_up", "w_down")
EXPERT = ("we_gate", "we_up", "we_down")
BRANCH = ("router", "router_bias", "ws_gate", "ws_up", "ws_down")
_WRITES_RESIDUAL = ("lo", "wo", "w_down", "we_down", "ws_down")


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes of the family, read from a configuration file."""

    hidden: int
    layers: int
    published_layers: int
    dense_layers: int
    period: int
    heads: int
    head: int  # a linear head's width
    conv: int
    lower: float
    rank: int
    nope: int
    rope: int
    v: int
    ffn: int
    experts: int  # the router's outputs (published)
    groups: int
    top_groups: int
    held_first: int
    held_count: int
    top_k: int
    expert_ffn: int
    shared_ffn: int
    vocab: int
    rope_theta: float
    rms_eps: float
    scaling: float

    @classmethod
    def from_conf(cls, conf: dict) -> "Arch":
        for key, want in (
            ("q_lora_rank", None), ("rope_scaling", None),
            ("scoring_func", "sigmoid"), ("norm_topk_prob", True),
            ("rope_interleave", True), ("linear_silu", True),
            ("kda_safe_gate", True), ("use_kda_lora", False),
            ("gated_attention_proj_granularity_type", "head_wise"),
            ("group_norm_size", 1), ("use_qk_norm", True),
            ("value_norm", False), ("up_proj_norm", False),
            ("use_nGPT", False), ("use_mla_nope", False),
            ("use_bias", False), ("use_qkv_bias", False),
            ("hidden_act", "silu"), ("tie_word_embeddings", False),
            ("num_kv_heads_for_linear_attn", 0),
            ("moe_router_enable_expert_bias", True),
        ):
            if conf.get(key, want) != want:
                raise ValueError(
                    f"{key}={conf[key]!r}: the family is built for {want!r}"
                )
        first, count = conf["deployment"]["experts_held"]
        if count != conf["num_experts"]:
            raise ValueError(
                f"num_experts={conf['num_experts']} are the experts held "
                f"here; deployment.experts_held says {count}"
            )
        layers = int(conf["num_hidden_layers"])
        dense = int(conf["first_k_dense_replace"])
        period = int(conf["layer_group_size"])
        if (layers - dense) % period or layers <= dense:
            raise ValueError(
                f"{layers} layers after {dense} dense ones are not whole "
                f"periods of {period}"
            )
        return cls(
            hidden=int(conf["hidden_size"]), layers=layers,
            published_layers=int(conf["published_num_hidden_layers"]),
            dense_layers=dense, period=period,
            heads=int(conf["num_attention_heads"]),
            head=int(conf["head_dim"]),
            conv=int(conf["short_conv_kernel_size"]),
            lower=float(conf["kda_lower_bound"]),
            rank=int(conf["kv_lora_rank"]),
            nope=int(conf["qk_nope_head_dim"]),
            rope=int(conf["qk_rope_head_dim"]),
            v=int(conf["v_head_dim"]),
            ffn=int(conf["intermediate_size"]),
            experts=int(conf["published_num_experts"]),
            groups=int(conf["n_group"]), top_groups=int(conf["topk_group"]),
            held_first=int(first), held_count=int(count),
            top_k=int(conf["num_experts_per_tok"]),
            expert_ffn=int(conf["moe_intermediate_size"]),
            shared_ffn=int(conf["num_shared_experts"])
            * int(conf["moe_shared_expert_intermediate_size"]),
            vocab=int(conf["vocab_size"]),
            rope_theta=float(conf["rope_theta"]),
            rms_eps=float(conf["rms_norm_eps"]),
            scaling=float(conf["routed_scaling_factor"]),
        )

    @property
    def latent(self) -> int:
        return self.rank + self.rope

    @property
    def channels(self) -> int:
        """q, k and v of a linear layer side by side."""
        return 3 * self.heads * self.head

    def is_expert_layer(self, layer: int) -> bool:
        return layer >= self.dense_layers

    def is_linear(self, layer: int) -> bool:
        """The leading dense layers are linear; of each period after them
        every layer but the last."""
        if layer < self.dense_layers:
            return True
        return (layer - self.dense_layers) % self.period != self.period - 1

    @property
    def pattern(self) -> tuple[bool, ...]:
        return (True,) * (self.period - 1) + (False,)

    def kind_layers(self, linear: bool) -> list[int]:
        return [l for l in range(self.layers) if self.is_linear(l) == linear]

    def hold(self, first: int, count: int) -> "Arch":
        """The same model, another chip's share of its experts."""
        return dataclasses.replace(self, held_first=first, held_count=count)

    def kind(self, layer: int) -> tuple[bool, bool]:
        """(linear attention, an expert layer)."""
        return self.is_linear(layer), self.is_expert_layer(layer)

    @staticmethod
    def tensors(linear: bool, expert: bool) -> tuple[str, ...]:
        """The drawn tensors of a layer of a kind but its experts'
        (``EXPERT``, drawn an expert at a time)."""
        return (LINEAR if linear else LATENT) + (BRANCH if expert else DENSE)

    def shape(self, name: str) -> tuple[int, ...]:
        """A tensor of one layer, or ONE expert's matrix."""
        d, h, e = self.hidden, self.heads, self.head
        return {
            "embed": (self.vocab, d), "lm_head": (d, self.vocab),
            "lqkv": (d, self.channels), "lconv": (self.conv, self.channels),
            "lf": (d, h, e), "l_alog": (h,), "l_dt": (h, e),
            "lb": (d, h), "lg": (d, h), "lo": (h, e, d),
            "wq": (d, h, self.nope + self.rope), "wkva": (d, self.latent),
            "wkvb": (self.rank, h, self.nope + self.v),
            "wo": (h, self.v, d), "wg": (d, h),
            "w_gate": (d, self.ffn), "w_up": (d, self.ffn),
            "w_down": (self.ffn, d),
            "router": (d, self.experts), "router_bias": (self.experts,),
            "we_gate": (d, self.expert_ffn), "we_up": (d, self.expert_ffn),
            "we_down": (self.expert_ffn, d),
            "ws_gate": (d, self.shared_ffn), "ws_up": (d, self.shared_ffn),
            "ws_down": (self.shared_ffn, d),
        }[name]

    def fan_in(self, name: str) -> int:
        d = self.hidden
        return {
            "lconv": self.conv, "lo": self.heads * self.head,
            "wkvb": self.rank, "wo": self.heads * self.v,
            "w_down": self.ffn, "we_down": self.expert_ffn,
            "ws_down": self.shared_ffn,
        }.get(name, d)

    def block_params(self, linear: bool) -> int:
        """An attention block of one kind with the layer's two norms and
        the norm it has of its own (a head's read-out, or the latent)."""
        own = self.head if linear else self.rank
        return 2 * self.hidden + own + sum(
            math.prod(self.shape(n)) for n in (LINEAR if linear else LATENT)
        )

    @property
    def expert_params(self) -> int:
        return sum(math.prod(self.shape(n)) for n in EXPERT)

    @property
    def router_params(self) -> int:
        return math.prod(self.shape("router")) + self.experts

    @property
    def dense_ffn_params(self) -> int:
        return sum(math.prod(self.shape(n)) for n in DENSE)

    def layer_params(self, layer: int) -> int:
        """A layer as held here."""
        block = self.block_params(self.is_linear(layer))
        if not self.is_expert_layer(layer):
            return block + self.dense_ffn_params
        shared = sum(
            math.prod(self.shape(n)) for n in ("ws_gate", "ws_up", "ws_down")
        )
        return block + self.router_params + shared + (
            self.held_count * self.expert_params
        )

    @property
    def params(self) -> int:
        return 2 * self.vocab * self.hidden + self.hidden + sum(
            self.layer_params(l) for l in range(self.layers)
        )


def _key(key, name: str, layer, expert=None):
    k = jax.random.fold_in(jax.random.fold_in(key, TENSORS.index(name)), layer)
    return k if expert is None else jax.random.fold_in(k, expert)


def draw(key, arch: Arch, name: str, layer, dtype, expert=None):
    """One tensor of layer ``layer`` (0 for the tables) in ``dtype``;
    with ``expert`` (the PUBLISHED index) one expert's matrix."""
    k, shape = _key(key, name, layer, expert), arch.shape(name)
    if name == "l_dt":
        return jax.random.uniform(
            k, shape, jnp.float32, *DT_RANGE
        ).astype(dtype)
    if name == "l_alog":
        return jax.random.uniform(
            k, shape, jnp.float32, 0.0, math.log(2.0)
        ).astype(dtype)
    if name == "router_bias":
        scale = BIAS_SIGMA
    elif name == "embed":
        scale = 1.0
    else:
        scale = 1.0 / math.sqrt(arch.fan_in(name))
        if name in _WRITES_RESIDUAL:
            scale /= math.sqrt(2 * arch.published_layers)
    # A product with a constant, not a quotient: the program's draw and
    # the reference's must round alike.
    w = jax.random.normal(k, shape, jnp.float32)
    return (w * jnp.float32(scale)).astype(dtype)


def held_experts(key, arch: Arch, layer, dtype) -> dict:
    """The experts of layer ``layer`` held here, stacked ``[count, ..]``."""
    held = arch.held_first + jnp.arange(arch.held_count, dtype=jnp.int32)
    return {
        n: jax.lax.map(
            lambda e, n=n: draw(key, arch, n, layer, dtype, expert=e), held
        )
        for n in EXPERT
    }


def layer_weights(key, arch: Arch, layer, dtype, kind=None) -> dict:
    """Layer ``layer`` as the served model stores it: its attention's
    tensors, its norms at one, its dense SwiGLU or its branch (router,
    bias, shared expert, the held experts ``we_*`` stacked). ``kind``:
    ``arch.kind(layer)``, given where ``layer`` is a traced value."""
    linear, expert = arch.kind(layer) if kind is None else kind
    w = {
        n: draw(key, arch, n, layer, dtype)
        for n in arch.tensors(linear, expert)
    }
    w["ln1"] = w["ln2"] = jnp.ones((arch.hidden,), dtype)
    if linear:
        w["lnorm"] = jnp.ones((arch.head,), dtype)
    else:
        w["kv_norm"] = jnp.ones((arch.rank,), dtype)
    if expert:
        w.update(held_experts(key, arch, layer, dtype))
    return w


def _group(key, arch: Arch, layers: list[int], dtype) -> dict:
    """``layers`` (a stacked group of the program) in the program's
    layout: the norms and the MLP's tensors over every layer, each
    kind's own over the group's layers of the kind; a layer after
    another (an expert layer's float32 normals are gigabytes before they
    are rounded)."""
    expert = arch.is_expert_layer(layers[0])

    def stacked(names, over):
        at = jnp.asarray(over, jnp.int32)
        return {
            n: jax.lax.map(lambda l, n=n: draw(key, arch, n, l, dtype), at)
            for n in names
        }

    out = {}
    lin = [l for l in layers if arch.is_linear(l)]
    lat = [l for l in layers if not arch.is_linear(l)]
    if lin:
        out.update(stacked(LINEAR, lin))
        out["lnorm"] = jnp.ones((len(lin), arch.head), dtype)
    if lat:
        out.update(stacked(LATENT, lat))
        out["kv_norm"] = jnp.ones((len(lat), arch.rank), dtype)
    out["ln1"] = out["ln2"] = jnp.ones((len(layers), arch.hidden), dtype)
    if not expert:
        out.update(stacked(DENSE, layers))
        return out
    out.update(stacked(BRANCH, layers))
    mats = jax.lax.map(
        lambda l: held_experts(key, arch, l, dtype),
        jnp.asarray(layers, jnp.int32),
    )
    # The program's names for an expert layer's experts.
    out.update({f"w_{n[3:]}": mats[n] for n in EXPERT})
    return out


def serving_tree(key, arch: Arch, dtype) -> dict:
    tree = {
        "embed": draw(key, arch, "embed", 0, dtype),
        "lm_head": draw(key, arch, "lm_head", 0, dtype),
        "ln_f": jnp.ones((arch.hidden,), dtype),
        "layers": _group(
            key, arch, list(range(arch.dense_layers, arch.layers)), dtype
        ),
    }
    if arch.dense_layers:
        tree["dense_layers"] = _group(
            key, arch, list(range(arch.dense_layers)), dtype
        )
    return tree


def dtype_of(name: str):
    return _DTYPES[name]


def program_config(conf: dict, max_seq_len: int, **extra):
    """The program's ``TransformerConfig`` at the file's sizes."""
    from torchkafka_tpu.models import TransformerConfig

    from chipbench.reference import ling_decoder as reference

    a = Arch.from_conf(conf)
    # The serving loop hands the reference ``weights.Dims`` alone; the
    # family's other sizes are found by them.
    reference.register(W.Dims.from_conf(conf), a, conf["deployment"])
    dep = conf["deployment"]
    try:
        return TransformerConfig(
            vocab_size=a.vocab, d_model=a.hidden, n_layers=a.layers,
            n_heads=a.heads, n_kv_heads=a.heads, d_ff=a.ffn,
            max_seq_len=max_seq_len, rope_theta=a.rope_theta,
            dtype=dtype_of(dep["compute_dtype"]),
            param_dtype=dtype_of(dep["param_dtype"]),
            kv_lora_rank=a.rank, qk_nope_dim=a.nope, qk_rope_dim=a.rope,
            v_head_dim=a.v, rope_interleave=True,
            first_dense_layers=a.dense_layers, n_experts=a.experts,
            expert_top_k=a.top_k, expert_d_ff=a.expert_ffn,
            n_shared_experts=a.shared_ffn // a.expert_ffn,
            router_score="sigmoid",
            routed_scaling=a.scaling,
            experts_held=(a.held_first, a.held_count),
            linear_pattern=a.pattern, linear_head_dim=a.head,
            linear_conv=a.conv, linear_lower_bound=a.lower, attn_gate=True,
            n_group=a.groups, topk_group=a.top_groups, **extra,
        )
    except TypeError as e:
        # A program from before the family was built: nothing to measure.
        from chipbench import common

        raise common.Refused(
            f"this program's TransformerConfig does not take the family: {e}"
        ) from e


def serving_params(conf: dict, seed: int):
    """The share on the device, in one jitted call from the seed."""
    arch = Arch.from_conf(conf)
    dtype = dtype_of(conf["deployment"]["param_dtype"])
    return jax.jit(lambda key: serving_tree(key, arch, dtype))(W.seed_key(seed))


def final_stream(cfg, params, tokens, rows: int = 4):
    """The program's forward over ``tokens`` [B, T], the one an admission
    runs (``generate.prefill``'s), ``rows`` rows a call → (its stream after
    the LAST layer, before the final norm, [B, T, D]; the latent rows it
    would cache [L_lat, B, T, rank + rope]), float32 on the host. What the
    last layer adds no slot keeps: the serving loop holds the stream
    against the reference's, by the last layer's parts, and finds a
    prompt's slot by the rows."""
    import numpy as np

    from torchkafka_tpu.models import Transformer
    from torchkafka_tpu.models.linear_attn import hybrid_forward
    from torchkafka_tpu.models.quant import embed_rows

    model = Transformer(cfg)

    @jax.jit
    def some(params, toks):
        x = embed_rows(params["embed"], toks, cfg.dtype)
        x, (_states, _tails, latents), _chosen = hybrid_forward(params, model, x)
        return x.astype(jnp.float32), latents.astype(jnp.float32)

    rows = math.gcd(len(tokens), rows)
    got = [
        jax.device_get(some(params, jnp.asarray(tokens[i:i + rows], jnp.int32)))
        for i in range(0, len(tokens), rows)
    ]
    return (np.concatenate([g[0] for g in got]),
            np.concatenate([g[1] for g in got], axis=1))
