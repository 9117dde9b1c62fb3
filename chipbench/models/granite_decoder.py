"""Bridge from a ``granitemoehybrid`` configuration file (granite-4.0-h:
``config.json``'s own keys) to the program, and the family's weights from
the seed.

The family: ``layer_types`` names each layer's mixer, a Mamba-2
state-space mixer (``torchkafka_tpu/ops/ssd.py``: ``mamba_n_heads`` heads
of ``mamba_d_head`` with a state of ``mamba_d_state``, ONE group of B and
C, a causal conv of ``mamba_d_conv`` taps with a bias, a gated RMSNorm
over all the heads' channels) or grouped-query attention WITHOUT
positions (``position_embedding_type`` "nope"); after the mixer of EVERY
layer a routed expert layer, softmax over the chosen
``num_experts_per_tok`` of ``published_num_local_experts`` logits, beside
one shared SwiGLU of ``shared_intermediate_size``; four multipliers
(``embedding_multiplier`` on the embedding's rows, ``residual_multiplier``
on both branches of every layer, ``attention_multiplier`` in place of
``1 / sqrt(head_dim)``, the logits over ``logits_scaling``) and a TIED
head. The file describes ONE CHIP'S SHARE of a deployment in which
``deployment.chips_sharing_a_layer`` chips share each layer:
``num_local_experts`` in the file is the number of experts whose weights
this chip holds (``deployment.experts_held`` says which),
``published_num_local_experts`` the number the router scores,
``vocab_size`` this chip's rows of the one matrix that is the embedding
and the head.

The program receives weights, it does not make them: ``serving_params``
draws the whole bfloat16 share on the device in the program's layout,
stacked by kind (``models/transformer.py::scan_hybrid``), and the plain
reference (``chipbench.reference.granite_decoder``) draws the same numbers
again, a layer at a time. Every tensor of every layer has a key of its
own, ``fold_in(fold_in(key(seed), tensor), layer)``, and an expert's
matrices one more ``fold_in(.., expert)`` with the expert's PUBLISHED
index, so an expert's weights do not depend on which chip holds it.
Matmul weights are normal with standard deviation ``1/sqrt(fan_in)``
rounded to the parameters' dtype and norms are one. The file's
``assumed`` says what else: the embedding's rows at ``EMBED_SIGMA`` (a
tied head scores a token's own row, see there), and the mixer's
parameters, which ``config.json`` does not give, by Mamba-2's own
initialisers: ``A`` uniform over ``A_RANGE`` a head (``A_log`` its
logarithm), ``dt_bias`` the inverse softplus of a step log-uniform over
``DT_RANGE``, ``D`` one, the conv's taps and bias uniform over ``+-1 /
sqrt(taps)``.

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
EMBED_SIGMA = 1.0 / 1024.0
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)
TENSORS = (
    "embed", "s_in", "s_in_dt", "s_conv", "s_conv_b", "s_dt", "s_alog",
    "s_out", "wq", "wk", "wv", "wo", "router", "we_gate", "we_up",
    "we_down", "ws_gate", "ws_up", "ws_down",
)
MAMBA = ("s_in", "s_in_dt", "s_conv", "s_conv_b", "s_dt", "s_alog", "s_out")
ATTENTION = ("wq", "wk", "wv", "wo")
EXPERT = ("we_gate", "we_up", "we_down")
BRANCH = ("router", "ws_gate", "ws_up", "ws_down")


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes of the family, read from a configuration file."""

    hidden: int
    layers: int
    published_layers: int
    kinds: tuple[bool, ...]  # a layer: True the Mamba-2 mixer
    heads: int
    kv_heads: int
    head: int
    m_heads: int
    m_head: int
    m_state: int
    conv: int
    chunk: int
    experts: int  # the router's outputs (published)
    held_first: int
    held_count: int
    top_k: int
    expert_ffn: int
    shared_ffn: int
    vocab: int
    rms_eps: float
    embed_mult: float
    residual_mult: float
    attn_mult: float
    logits_scaling: float

    @classmethod
    def from_conf(cls, conf: dict) -> "Arch":
        for key, want in (
            ("position_embedding_type", "nope"), ("mamba_n_groups", 1),
            ("mamba_conv_bias", True), ("mamba_proj_bias", False),
            ("attention_bias", False), ("tie_word_embeddings", True),
            ("hidden_act", "silu"), ("normalization_function", "rmsnorm"),
            ("rope_scaling", None), ("mamba_expand", 2),
        ):
            if conf.get(key, want) != want:
                raise ValueError(
                    f"{key}={conf[key]!r}: the family is built for {want!r}"
                )
        first, count = conf["deployment"]["experts_held"]
        if count != conf["num_local_experts"]:
            raise ValueError(
                f"num_local_experts={conf['num_local_experts']} are the "
                f"experts held here; deployment.experts_held says {count}"
            )
        layers = int(conf["num_hidden_layers"])
        kinds = tuple(t == "mamba" for t in conf["layer_types"][:layers])
        if len(kinds) != layers or set(conf["layer_types"]) - {
            "mamba", "attention"
        }:
            raise ValueError(
                f"layer_types names {len(conf['layer_types'])} layers of "
                f"{sorted(set(conf['layer_types']))}; the file runs {layers}"
            )
        hidden = int(conf["hidden_size"])
        heads = int(conf["num_attention_heads"])
        m_heads, m_head = int(conf["mamba_n_heads"]), int(conf["mamba_d_head"])
        if m_heads * m_head != int(conf["mamba_expand"]) * hidden:
            raise ValueError("mamba_n_heads * mamba_d_head != expand * hidden")
        return cls(
            hidden=hidden, layers=layers,
            published_layers=int(conf["published_num_hidden_layers"]),
            kinds=kinds, heads=heads,
            kv_heads=int(conf["num_key_value_heads"]),
            head=int(conf.get("head_dim") or hidden // heads),
            m_heads=m_heads, m_head=m_head,
            m_state=int(conf["mamba_d_state"]),
            conv=int(conf["mamba_d_conv"]),
            chunk=int(conf["mamba_chunk_size"]),
            experts=int(conf["published_num_local_experts"]),
            held_first=int(first), held_count=int(count),
            top_k=int(conf["num_experts_per_tok"]),
            expert_ffn=int(conf["intermediate_size"]),
            shared_ffn=int(conf["shared_intermediate_size"]),
            vocab=int(conf["vocab_size"]),
            rms_eps=float(conf["rms_norm_eps"]),
            embed_mult=float(conf["embedding_multiplier"]),
            residual_mult=float(conf["residual_multiplier"]),
            attn_mult=float(conf["attention_multiplier"]),
            logits_scaling=float(conf["logits_scaling"]),
        )

    @property
    def inner(self) -> int:
        """The mixer's channels: every head's."""
        return self.m_heads * self.m_head

    @property
    def channels(self) -> int:
        """What the mixer's convolution runs over: x beside B and C."""
        return self.inner + 2 * self.m_state

    @property
    def kv_row(self) -> int:
        """A position's K (or V) row: the kv heads side by side."""
        return self.kv_heads * self.head

    def is_linear(self, layer: int) -> bool:
        return self.kinds[layer]

    @property
    def pattern(self) -> tuple[bool, ...]:
        """The period: the shortest prefix of the kinds that repeats."""
        for p in range(1, self.layers + 1):
            if self.layers % p == 0 and self.kinds == self.kinds[:p] * (
                self.layers // p
            ):
                return self.kinds[:p]
        return self.kinds

    def kind_layers(self, linear: bool) -> list[int]:
        return [l for l in range(self.layers) if self.kinds[l] == linear]

    def hold(self, first: int, count: int) -> "Arch":
        """The same model, another chip's share of its experts."""
        return dataclasses.replace(self, held_first=first, held_count=count)

    def slice_vocab(self, rows: int) -> "Arch":
        return dataclasses.replace(self, vocab=rows)

    @staticmethod
    def tensors(linear: bool) -> tuple[str, ...]:
        """The drawn tensors of a layer of a kind but its experts'
        (``EXPERT``, drawn an expert at a time)."""
        return (MAMBA if linear else ATTENTION) + BRANCH

    def shape(self, name: str) -> tuple[int, ...]:
        """A tensor of one layer, or ONE expert's matrix."""
        d, h, k, e = self.hidden, self.heads, self.kv_heads, self.head
        return {
            "embed": (self.vocab, d),
            "s_in": (d, self.inner + self.channels),
            "s_in_dt": (d, self.m_heads),
            "s_conv": (self.conv, self.channels),
            "s_conv_b": (self.channels,), "s_dt": (self.m_heads,),
            "s_alog": (self.m_heads,),
            "s_out": (self.m_heads, self.m_head, d),
            "wq": (d, h, e), "wk": (d, k, e), "wv": (d, k, e),
            "wo": (h, e, d), "router": (d, self.experts),
            "we_gate": (d, self.expert_ffn), "we_up": (d, self.expert_ffn),
            "we_down": (self.expert_ffn, d),
            "ws_gate": (d, self.shared_ffn), "ws_up": (d, self.shared_ffn),
            "ws_down": (self.shared_ffn, d),
        }[name]

    def fan_in(self, name: str) -> int:
        return {
            "s_out": self.inner, "wo": self.heads * self.head,
            "we_down": self.expert_ffn, "ws_down": self.shared_ffn,
        }.get(name, self.hidden)

    def block_params(self, linear: bool) -> int:
        """A mixer of one kind as the source counts it (the mixer's norm
        and skip weights with it; the layer's two norms apart)."""
        own = self.inner + self.m_heads if linear else 0  # s_norm, D
        return own + sum(
            math.prod(self.shape(n)) for n in (MAMBA if linear else ATTENTION)
        )

    @property
    def expert_params(self) -> int:
        return sum(math.prod(self.shape(n)) for n in EXPERT)

    @property
    def shared_params(self) -> int:
        return sum(
            math.prod(self.shape(n)) for n in ("ws_gate", "ws_up", "ws_down")
        )

    @property
    def router_params(self) -> int:
        return math.prod(self.shape("router"))

    def layer_params(self, layer: int, experts: int | None = None) -> int:
        """A layer as held here (``experts``: with that many instead)."""
        held = self.held_count if experts is None else experts
        return (
            self.block_params(self.kinds[layer]) + 2 * self.hidden
            + self.router_params + self.shared_params
            + held * self.expert_params
        )

    @property
    def params(self) -> int:
        """The share: the tied matrix once."""
        return self.vocab * self.hidden + self.hidden + sum(
            self.layer_params(l) for l in range(self.layers)
        )


def _key(key, name: str, layer, expert=None):
    k = jax.random.fold_in(jax.random.fold_in(key, TENSORS.index(name)), layer)
    return k if expert is None else jax.random.fold_in(k, expert)


def draw(key, arch: Arch, name: str, layer, dtype, expert=None):
    """One tensor of layer ``layer`` (0 for the table) in ``dtype``; with
    ``expert`` (the PUBLISHED index) one expert's matrix."""
    k, shape = _key(key, name, layer, expert), arch.shape(name)
    if name == "s_alog":
        a = jax.random.uniform(k, shape, jnp.float32, *A_RANGE)
        return jnp.log(a).astype(dtype)
    if name == "s_dt":
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name in ("s_conv", "s_conv_b"):
        bound = 1.0 / math.sqrt(arch.conv)
        return jax.random.uniform(
            k, shape, jnp.float32, -bound, bound
        ).astype(dtype)
    # A product with a constant, not a quotient: the program's draw and
    # the reference's must round alike. (The table: ``embed_rows``.)
    w = jax.random.normal(k, shape, jnp.float32)
    return (w * jnp.float32(1.0 / math.sqrt(arch.fan_in(name)))).astype(dtype)


def embed_rows(key, arch: Arch, dtype, first: int = 0, count: int | None = None):
    """Rows ``[first, first + count)`` of the tied matrix (default this
    chip's ``[0, vocab)``): a row's numbers do not depend on the slice."""
    count = arch.vocab if count is None else count
    rows = first + jnp.arange(count, dtype=jnp.int32)
    k = _key(key, "embed", 0)

    def one(r):
        w = jax.random.normal(
            jax.random.fold_in(k, r), (arch.hidden,), jnp.float32
        )
        return (w * jnp.float32(EMBED_SIGMA)).astype(dtype)

    return jax.vmap(one)(rows)


def held_experts(key, arch: Arch, layer, dtype) -> dict:
    """The experts of layer ``layer`` held here, stacked ``[count, ..]``."""
    held = arch.held_first + jnp.arange(arch.held_count, dtype=jnp.int32)
    return {
        n: jax.lax.map(
            lambda e, n=n: draw(key, arch, n, layer, dtype, expert=e), held
        )
        for n in EXPERT
    }


def layer_weights(key, arch: Arch, layer, dtype, linear=None) -> dict:
    """Layer ``layer`` as the served model stores it: its mixer's
    tensors, its norms and skip at one, its branch (router, shared expert,
    the held experts ``we_*`` stacked). ``linear``: the layer's kind,
    given where ``layer`` is a traced value."""
    linear = arch.kinds[layer] if linear is None else linear
    w = {n: draw(key, arch, n, layer, dtype) for n in arch.tensors(linear)}
    w["ln1"] = w["ln2"] = jnp.ones((arch.hidden,), dtype)
    if linear:
        w["s_norm"] = jnp.ones((arch.inner,), dtype)
        w["s_d"] = jnp.ones((arch.m_heads,), dtype)
    w.update(held_experts(key, arch, layer, dtype))
    return w


def serving_tree(key, arch: Arch, dtype) -> dict:
    """The share in the program's layout: ONE stacked group, the norms and
    the branch over every layer, each kind's own over its layers; a layer
    after another (an expert layer's float32 normals are gigabytes before
    they are rounded)."""

    def stacked(names, over):
        at = jnp.asarray(over, jnp.int32)
        return {
            n: jax.lax.map(lambda l, n=n: draw(key, arch, n, l, dtype), at)
            for n in names
        }

    lin, att = arch.kind_layers(True), arch.kind_layers(False)
    every = list(range(arch.layers))
    layers = {**stacked(MAMBA, lin), **stacked(ATTENTION, att),
              **stacked(BRANCH, every)}
    layers["s_norm"] = jnp.ones((len(lin), arch.inner), dtype)
    layers["s_d"] = jnp.ones((len(lin), arch.m_heads), dtype)
    layers["ln1"] = layers["ln2"] = jnp.ones((arch.layers, arch.hidden), dtype)
    mats = jax.lax.map(
        lambda l: held_experts(key, arch, l, dtype),
        jnp.asarray(every, jnp.int32),
    )
    # The program's names for an expert layer's experts.
    layers.update({f"w_{n[3:]}": mats[n] for n in EXPERT})
    return {
        "embed": embed_rows(key, arch, dtype),
        "ln_f": jnp.ones((arch.hidden,), dtype), "layers": layers,
    }


def dtype_of(name: str):
    return _DTYPES[name]


def program_config(conf: dict, max_seq_len: int, **extra):
    """The program's ``TransformerConfig`` at the file's sizes."""
    from torchkafka_tpu.models import TransformerConfig

    from chipbench.reference import granite_decoder as reference

    a = Arch.from_conf(conf)
    # The serving loop hands the reference ``weights.Dims`` alone; the
    # family's other sizes are found by them.
    reference.register(W.Dims.from_conf(conf), a, conf["deployment"])
    dep = conf["deployment"]
    try:
        return TransformerConfig(
            vocab_size=a.vocab, d_model=a.hidden, n_layers=a.layers,
            n_heads=a.heads, n_kv_heads=a.kv_heads, d_ff=a.expert_ffn,
            stated_head_dim=a.head, max_seq_len=max_seq_len,
            dtype=dtype_of(dep["compute_dtype"]),
            param_dtype=dtype_of(dep["param_dtype"]),
            n_experts=a.experts, expert_top_k=a.top_k,
            expert_d_ff=a.expert_ffn,
            n_shared_experts=a.shared_ffn // a.expert_ffn,
            router_score="softmax", norm_topk=True,
            experts_held=(a.held_first, a.held_count),
            linear_pattern=a.pattern, linear_kind="ssd",
            linear_conv=a.conv, ssd_heads=a.m_heads, ssd_head_dim=a.m_head,
            ssd_state_dim=a.m_state, ssd_chunk=a.chunk,
            embedding_multiplier=a.embed_mult,
            residual_multiplier=a.residual_mult,
            attention_multiplier=a.attn_mult,
            logits_scaling=a.logits_scaling, use_rope=False,
            tie_embeddings=True, norm_eps=a.rms_eps, **extra,
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
    the LAST layer, before the final norm, [B, T, D]; the K rows it would
    cache [L_att, B, T, K * Dh]; the states [L_lin, B, H, P, N] and the
    conv tails it would leave after the T tokens), float32 on the host.
    What the last layer adds no slot keeps: the serving loop holds the
    stream against the reference's, by the last layer's part, finds a
    prompt's slot by the rows, and holds the admission's state by these."""
    import numpy as np

    from torchkafka_tpu.models import Transformer
    from torchkafka_tpu.models.linear_attn import hybrid_forward
    from torchkafka_tpu.models.transformer import embed_tokens

    model = Transformer(cfg)

    @jax.jit
    def some(params, toks):
        x = embed_tokens(params, cfg, toks)
        x, (states, tails, k_rows, _v), _chosen = hybrid_forward(
            params, model, x
        )
        # (the program keeps a slot's conv tail in one row)
        tails = tails.reshape(*tails.shape[:2], cfg.linear_conv - 1, -1)
        return tuple(
            a.astype(jnp.float32) for a in (x, k_rows, states, tails)
        )

    rows = math.gcd(len(tokens), rows)
    got = [
        jax.device_get(some(params, jnp.asarray(tokens[i:i + rows], jnp.int32)))
        for i in range(0, len(tokens), rows)
    ]
    return (np.concatenate([g[0] for g in got]),) + tuple(
        np.concatenate([g[i] for g in got], axis=1) for i in (1, 2, 3)
    )
