"""Bridge from a LongCat-Flash configuration file (the language model of
LongCat-Flash-Omni: ``config.json``'s own keys) to the program, and the
family's weights from the seed.

The family: a layer is the shortcut-connected DOUBLE layer, two latent
attention (MLA) blocks with compressed queries and two dense SwiGLUs in a
row, and one expert branch that leaves the first block's normed output
and rejoins after the second SwiGLU; the router is a softmax over
``n_routed_experts + zero_expert_num`` outputs whose last
``zero_expert_num`` are identity experts; the top-k probabilities are not
renormalised. The file describes ONE CHIP'S SHARE of an expert-parallel
deployment: ``n_routed_experts`` in the file is the number of experts
whose weights this chip holds (``deployment.experts_held`` says which),
``published_n_routed_experts`` the number the router scores.

The program receives weights, it does not make them: ``serving_params``
draws the whole bfloat16 share on the device in the program's stacked
layout, and the plain reference (``chipbench.reference.longcat_decoder``)
draws the same numbers again, a block or a branch at a time. Every tensor
has a key of its own: ``fold_in(fold_in(key(seed), tensor), 2 * layer +
block)`` for what a block owns, ``fold_in(.., layer)`` for the router,
and one more ``fold_in(.., expert)`` with the expert's PUBLISHED index for
an expert's matrices, so an expert's weights do not depend on which chip
holds it. Matmul weights are normal with standard deviation
``1/sqrt(fan_in)`` rounded to the parameters' dtype and norms are one.
Three departures, the file's ``assumed``, as the other latent-attention
family has them and for its reasons (PERF.md, PR 27): the embedding's rows
have unit variance; the attention's and the dense SwiGLUs' projections
that write into the residual stream (``wo``, ``w_down``) are scaled by
``1/sqrt(their writes in the published model)``, four a layer; the
selection bias (``e_score_correction_bias``, not in ``config.json``) is
normal with ``BIAS_SIGMA``. The held experts' ``we_down`` is NOT scaled
down: a zero expert returns its input at unit gain, and its peers in the
same weighted sum are drawn at a comparable one (at ``1/sqrt(fan_in)`` an
expert returns 0.6 of its input's norm; scaled down with the other writes
it would lie under bfloat16's grain of the zero experts' term).

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
# A tenth of the median selected score: the top 12 of a softmax over 768
# unit-variance logits have a median probability of 0.0088.
BIAS_SIGMA = 0.0009
TENSORS = (
    "embed", "lm_head", "wqa", "wqb", "wkva", "wkvb", "wo", "w_gate",
    "w_up", "w_down", "router", "router_bias", "we_gate", "we_up",
    "we_down",
)
BLOCK = ("wqa", "wqb", "wkva", "wkvb", "wo", "w_gate", "w_up", "w_down")
EXPERT = ("we_gate", "we_up", "we_down")
_WRITES_RESIDUAL = ("wo", "w_down")
RESIDUAL_WRITES_A_LAYER = 4  # two attention blocks, two dense SwiGLUs


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes of the family, read from a configuration file."""

    hidden: int
    layers: int
    published_layers: int
    heads: int
    q_rank: int
    rank: int
    nope: int
    rope: int
    v: int
    ffn: int
    experts: int  # the router's real experts (published)
    zero: int  # identity experts behind them
    held_first: int
    held_count: int
    top_k: int
    expert_ffn: int
    vocab: int
    rope_theta: float
    rms_eps: float
    scaling: float
    scale_q: bool
    scale_kv: bool

    @classmethod
    def from_conf(cls, conf: dict) -> "Arch":
        for key, want in (
            ("attention_method", "MLA"), ("zero_expert_type", "identity"),
            ("attention_bias", False),
        ):
            if conf.get(key, want) != want:
                raise ValueError(
                    f"{key}={conf[key]!r}: the family is built for {want!r}"
                )
        first, count = conf["deployment"]["experts_held"]
        if count != conf["n_routed_experts"]:
            raise ValueError(
                f"n_routed_experts={conf['n_routed_experts']} are the "
                f"experts held here; deployment.experts_held says {count}"
            )
        return cls(
            hidden=int(conf["hidden_size"]),
            # ``num_hidden_layers`` and ``intermediate_size`` are the
            # harness's names (``weights.Dims``) of ``num_layers`` and
            # ``ffn_hidden_size``; the file holds both, equal.
            layers=int(conf["num_hidden_layers"]),
            published_layers=int(conf["published_num_layers"]),
            heads=int(conf["num_attention_heads"]),
            q_rank=int(conf["q_lora_rank"]),
            rank=int(conf["kv_lora_rank"]),
            nope=int(conf["qk_nope_head_dim"]),
            rope=int(conf["qk_rope_head_dim"]),
            v=int(conf["v_head_dim"]),
            ffn=int(conf["intermediate_size"]),
            experts=int(conf["published_n_routed_experts"]),
            zero=int(conf["zero_expert_num"]),
            held_first=int(first), held_count=int(count),
            top_k=int(conf["moe_topk"]),
            expert_ffn=int(conf["expert_ffn_hidden_size"]),
            vocab=int(conf["vocab_size"]),
            rope_theta=float(conf["rope_theta"]),
            rms_eps=float(conf["rms_norm_eps"]),
            scaling=float(conf["routed_scaling_factor"]),
            scale_q=bool(conf["mla_scale_q_lora"]),
            scale_kv=bool(conf["mla_scale_kv_lora"]),
        )

    @property
    def latent(self) -> int:
        return self.rank + self.rope

    @property
    def router_width(self) -> int:
        return self.experts + self.zero

    def hold(self, first: int, count: int) -> "Arch":
        """The same model, another chip's share of its experts."""
        return dataclasses.replace(self, held_first=first, held_count=count)

    def shape(self, name: str) -> tuple[int, ...]:
        """A tensor of one block, the router of one layer, or ONE
        expert's matrix."""
        d, h = self.hidden, self.heads
        return {
            "embed": (self.vocab, d), "lm_head": (d, self.vocab),
            "wqa": (d, self.q_rank),
            "wqb": (self.q_rank, h, self.nope + self.rope),
            "wkva": (d, self.latent),
            "wkvb": (self.rank, h, self.nope + self.v),
            "wo": (h, self.v, d),
            "w_gate": (d, self.ffn), "w_up": (d, self.ffn),
            "w_down": (self.ffn, d),
            "router": (d, self.router_width),
            "router_bias": (self.router_width,),
            "we_gate": (d, self.expert_ffn), "we_up": (d, self.expert_ffn),
            "we_down": (self.expert_ffn, d),
        }[name]

    def fan_in(self, name: str) -> int:
        d = self.hidden
        return {
            "embed": d, "lm_head": d, "wqa": d, "wqb": self.q_rank,
            "wkva": d, "wkvb": self.rank, "wo": self.heads * self.v,
            "w_gate": d, "w_up": d, "w_down": self.ffn, "router": d,
            "we_gate": d, "we_up": d, "we_down": self.expert_ffn,
        }[name]

    @property
    def block_params(self) -> int:
        """One attention block with its dense SwiGLU and its four norms."""
        norms = 2 * self.hidden + self.q_rank + self.rank
        return norms + sum(math.prod(self.shape(n)) for n in BLOCK)

    @property
    def expert_params(self) -> int:
        return sum(math.prod(self.shape(n)) for n in EXPERT)

    @property
    def layer_params(self) -> int:
        """A double layer as held here: two blocks, the router with its
        bias, the held experts."""
        router = math.prod(self.shape("router")) + self.router_width
        return 2 * self.block_params + router + (
            self.held_count * self.expert_params
        )

    @property
    def params(self) -> int:
        return (
            2 * self.vocab * self.hidden + self.hidden
            + self.layers * self.layer_params
        )


def _scale(arch: Arch, name: str) -> float:
    if name == "router_bias":
        return BIAS_SIGMA
    if name == "embed":
        return 1.0
    scale = 1.0 / math.sqrt(arch.fan_in(name))
    if name in _WRITES_RESIDUAL:
        scale /= math.sqrt(RESIDUAL_WRITES_A_LAYER * arch.published_layers)
    return scale


def draw(key, arch: Arch, name: str, index, dtype, expert=None):
    """One tensor in ``dtype``. ``index``: ``2 * layer + block`` for a
    block's tensor, the layer for the router, its bias and (with
    ``expert``, the PUBLISHED index) an expert's matrix, 0 for the
    tables."""
    k = jax.random.fold_in(
        jax.random.fold_in(key, TENSORS.index(name)), index
    )
    if expert is not None:
        k = jax.random.fold_in(k, expert)
    w = jax.random.normal(k, arch.shape(name), jnp.float32)
    # A product with a constant, not a quotient: the program's draw and
    # the reference's must round alike.
    return (w * jnp.float32(_scale(arch, name))).astype(dtype)


def block_weights(key, arch: Arch, layer, block, dtype) -> dict:
    """Attention block ``block`` of layer ``layer`` with its dense
    SwiGLU, as the served model stores it."""
    w = {n: draw(key, arch, n, 2 * layer + block, dtype) for n in BLOCK}
    w["ln1"] = w["ln2"] = jnp.ones((arch.hidden,), dtype)
    w["q_norm"] = jnp.ones((arch.q_rank,), dtype)
    w["kv_norm"] = jnp.ones((arch.rank,), dtype)
    return w


def branch_weights(key, arch: Arch, layer, dtype) -> dict:
    """Layer ``layer``'s router, selection bias and the experts held
    here, stacked."""
    held = arch.held_first + jnp.arange(arch.held_count, dtype=jnp.int32)
    w = {
        n: jax.lax.map(
            lambda e, n=n: draw(key, arch, n, layer, dtype, expert=e), held
        )
        for n in EXPERT
    }
    w["router"] = draw(key, arch, "router", layer, dtype)
    w["router_bias"] = draw(key, arch, "router_bias", layer, dtype)
    return w


def serving_tree(key, arch: Arch, dtype) -> dict:
    """The share in the program's stacked layout: a block's tensors
    ``[L, 2, ...]``, the branch's ``[L, ...]``; drawn a layer after
    another (a layer's float32 normals are gigabytes before they are
    rounded)."""

    def layer_tree(layer):
        blocks = [block_weights(key, arch, layer, b, dtype) for b in (0, 1)]
        tree = {n: jnp.stack([b[n] for b in blocks]) for n in blocks[0]}
        tree.update(branch_weights(key, arch, layer, dtype))
        return tree

    return {
        "embed": draw(key, arch, "embed", 0, dtype),
        "lm_head": draw(key, arch, "lm_head", 0, dtype),
        "ln_f": jnp.ones((arch.hidden,), dtype),
        "layers": jax.lax.map(
            layer_tree, jnp.arange(arch.layers, dtype=jnp.int32)
        ),
    }


def dtype_of(name: str):
    return _DTYPES[name]


def program_config(conf: dict, max_seq_len: int, **extra):
    """The program's ``TransformerConfig`` at the file's sizes."""
    from torchkafka_tpu.models import TransformerConfig

    from chipbench.reference import longcat_decoder as reference

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
            v_head_dim=a.v, rope_interleave=True, q_lora_rank=a.q_rank,
            mla_scale_q_lora=a.scale_q, mla_scale_kv_lora=a.scale_kv,
            attn_blocks=2, n_experts=a.experts, zero_experts=a.zero,
            expert_top_k=a.top_k, expert_d_ff=a.expert_ffn,
            router_score="softmax", norm_topk=False,
            routed_scaling=a.scaling,
            experts_held=(a.held_first, a.held_count), **extra,
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
