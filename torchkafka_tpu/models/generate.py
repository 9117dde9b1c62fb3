"""KV-cache autoregressive decoding for the flagship transformer.

BASELINE config 5's consumer: prompts stream in from a topic, the model
generates continuations, and the prompts' offsets commit only after
generation completes (commit-after-step, extended to a multi-step op).

TPU/XLA shape discipline: the caches are preallocated to a static
``max_len = prompt_len + max_new`` and written with
``lax.dynamic_update_slice``; the decode loop is a ``lax.scan`` over
``max_new`` steps (trace once, no per-step recompilation); attention masks by
position against the static cache. Greedy (temperature=0) or categorical
sampling.

The prefill math intentionally reuses the exact layer code of
``Transformer.__call__`` (one implementation, no drift); only the
single-token decode step is specialised here.

Model-sharded decode: pass ``mesh`` (and commit params to
``serving_shardings``) to run tp/fsdp/data-sharded inference — kv heads
shard over tp, the batch over data, and weights keep their training
layouts, so anything too big for one chip (bf16 8B+, long KV budgets)
serves across a slice. BASELINE config 5 names Llama-3-8B on v5e-8; the
multichip dryrun (``__graft_entry__.dryrun_multichip``) proves this path
end-to-end on a virtual mesh.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchkafka_tpu.models.quant import QTensor, embed_rows, load_weight, quantize_specs
from torchkafka_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
    _arch_refusal,
    _double_scan,
    _dense_mlp,
    _layer_groups,
    _moe_mlp,
    _rms_norm,
    _rope,
    embed_tokens,
    head_product,
    join_residual,
    param_specs,
    qk_head_norm,
    scan_periods,
    shardings_for_mesh,
)
from torchkafka_tpu.utils import tracing


class KVCache(NamedTuple):
    k: jax.Array  # [L, B, max_len, K, Dh]
    v: jax.Array  # [L, B, max_len, K, Dh]


class KindKVCache(NamedTuple):
    """The cache of a config with kinds of layer (``window_pattern``),
    allocated by kind: the full layers hold every position, a window layer
    a RING of ``sliding_window`` rows, position p at row ``p mod window``
    (keys are cached roped, so a ring's order does not matter to a read).
    Layer ``j`` of period ``i`` is row ``i * count + rank`` of its kind's
    tensors (``TransformerConfig.kind_rank``). A position's kv heads lie
    side by side in ONE row of ``K * Dh`` columns, so the decode read is
    two matrix products a layer against the slab in place
    (``_attend_merged``)."""

    k_full: jax.Array  # [Lf, B, max_len, K * Dh]
    v_full: jax.Array
    k_win: jax.Array  # [Lw, B, window, K * Dh]
    v_win: jax.Array


@tracing.scope(tracing.SCOPE_KV_WRITE)
def ring_rows(rows: jax.Array, window: int) -> jax.Array:
    """A window layer's rows over positions [0, S), [L, B, S, C], as its
    ring holds them once S positions are written: the last ``window`` of
    them, position p at row ``p mod window`` (S is static, so this is two
    static slices); rows no position has reached yet are zero."""
    s = rows.shape[2]
    if s <= window:
        return jnp.pad(rows, ((0, 0), (0, 0), (0, window - s), (0, 0)))
    return jnp.roll(rows[:, :, s - window:], (s - window) % window, axis=2)


# --------------------------------------------------------------- sampling


def filter_logits(
    logits: jax.Array,
    *,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """Temperature → top-k → top-p filtering over [..., V] logits, the
    standard composition order; masked-out entries go to -inf so a
    categorical draw never selects them. STATIC shapes throughout — top-k
    is a ``lax.top_k`` threshold compare, top-p a full sort + exclusive
    cumulative-probability mask — so the serving tick stays one compiled
    program for any (k, p). Ties at either threshold are kept (>= the
    boundary value), the rule the NumPy reference in tests/test_sampling.py
    mirrors bit-for-bit at f32."""
    logits = logits.astype(jnp.float32) / jnp.float32(temperature)
    neg = jnp.float32(-jnp.inf)
    if top_k is not None and 0 < top_k < logits.shape[-1]:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if top_p is not None and top_p < 1.0:
        srt = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(srt, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep a token while the cumulative probability BEFORE it is still
        # < p: the minimal prefix whose mass reaches p, never empty.
        keep = (cum - probs) < jnp.float32(top_p)
        n_keep = jnp.sum(keep.astype(jnp.int32), axis=-1, keepdims=True)
        kth = jnp.take_along_axis(srt, n_keep - 1, axis=-1)
        logits = jnp.where(logits < kth, neg, logits)
    return logits


@tracing.scope(tracing.SCOPE_HEAD)
def sample_logits(
    logits: jax.Array,
    key: jax.Array,
    *,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """[..., V] logits → [...] int32 token ids. ``temperature == 0`` is
    greedy argmax (top_k/top_p ignored — the filter cannot change the
    argmax); otherwise a categorical draw over ``filter_logits``. One
    sampling definition serves the lockstep ``generate`` and the
    continuous-batching server, so their sampled paths cannot drift."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    filtered = filter_logits(
        logits, temperature=temperature, top_k=top_k, top_p=top_p
    )
    return jax.random.categorical(key, filtered, axis=-1).astype(jnp.int32)


def check_sampling_params(top_k: int | None, top_p: float | None) -> None:
    """Shared eager validation: a bad knob should fail at construction, not
    as an XLA shape error three dispatches later."""
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


# ------------------------------------------------------------ mesh-sharded
# Model-sharded decode (BASELINE config 5 names an 8-chip v5e slice): the
# same tp/fsdp layouts training uses (param_specs) carry into inference,
# the KV cache shards its kv-head axis over tp (each shard attends over its
# own heads' cache — attention is head-local until wo's psum), and the
# batch/slot axis shards over data. XLA inserts the megatron collectives
# (psum after wo and w_down, logit all-gather) from the layouts alone —
# no hand-written collectives, same design rule as the train step.


def check_serving_mesh(cfg: TransformerConfig, mesh: Mesh, *, batch: int | None = None) -> None:
    """Divisibility guards for model-sharded decode, covering every dim the
    ``serving_shardings`` layouts split: device_put requires EVEN shards,
    so each sharded dim must divide its axis or the placement fails deep in
    JAX internals instead of here. tp shards heads (wq's H, the cache's K),
    the vocab (embed rows / lm_head columns) and d_ff (w_gate/w_down); fsdp
    shards d_model; ep shards experts; data shards the batch/slot axis."""
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and (cfg.n_heads % tp or cfg.n_kv_heads % tp):
        raise ValueError(
            f"tp={tp} must divide n_heads={cfg.n_heads} and "
            f"n_kv_heads={cfg.n_kv_heads} for sharded decode"
        )
    if tp > 1 and (cfg.vocab_size % tp or cfg.d_ff % tp):
        raise ValueError(
            f"tp={tp} must divide vocab_size={cfg.vocab_size} and "
            f"d_ff={cfg.d_ff} (embed/lm_head/MLP shard those dims over tp)"
        )
    fsdp = mesh.shape.get("fsdp", 1)
    if fsdp > 1 and cfg.d_model % fsdp:
        raise ValueError(
            f"fsdp={fsdp} must divide d_model={cfg.d_model} "
            "(weight fan-in dims shard over fsdp)"
        )
    ep = mesh.shape.get("ep", 1)
    if ep > 1 and cfg.is_moe and cfg.n_experts % ep:
        raise ValueError(
            f"ep={ep} must divide n_experts={cfg.n_experts}"
        )
    pp = mesh.shape.get("pp", 1)
    if pp > 1 and cfg.n_layers % pp:
        raise ValueError(
            f"pp={pp} must divide n_layers={cfg.n_layers} (layer-stacked "
            "weights shard over pp; decode is layer-sharded storage, not a "
            "pipelined schedule)"
        )
    if mesh.shape.get("sp", 1) > 1:
        raise ValueError(
            "serving meshes must not carry an sp axis: decode is one token "
            "per step (nothing to sequence-shard) and prefill under sp "
            "would engage ring attention against an unsharded prompt — "
            "shard kv heads over tp and slots over data instead"
        )
    dp = mesh.shape.get("data", 1)
    if batch is not None and dp > 1 and batch % dp:
        raise ValueError(
            f"batch/slots={batch} must divide by the data axis ({dp})"
        )


def serving_shardings(cfg: TransformerConfig, mesh: Mesh, params) -> dict:
    """NamedShardings for a serving param tree — plain (bf16/f32) or
    int8-quantized (QTensor leaves get quantize_specs' scale handling).
    The layouts are exactly the training ``param_specs``: a checkpoint
    trained tp/fsdp-sharded serves in place."""
    specs = param_specs(cfg)
    if isinstance(params["lm_head"], QTensor):
        specs = quantize_specs(specs, cfg)
    return shardings_for_mesh(mesh, specs)


def kv_sharding(mesh: Mesh) -> NamedSharding:
    """KVCache [L, B, M, K, Dh]: slots/batch over data, kv heads over tp."""
    return shardings_for_mesh(mesh, P(None, "data", None, "tp", None))


def kv_scale_sharding(mesh: Mesh) -> NamedSharding:
    """int8-KV scale tensors [L, B, M, K] (the payload layout minus the
    head_dim axis): slots over data, kv heads over tp."""
    return shardings_for_mesh(mesh, P(None, "data", None, "tp"))


def slot_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Per-slot vectors [B, ...] (tokens, positions, masks): over data."""
    return shardings_for_mesh(mesh, P("data", *([None] * (ndim - 1))))


def kv_kmajor_sharding(mesh: Mesh) -> NamedSharding:
    """K-MAJOR dense int8 pool [L, B, K, M, Dh] (the Pallas dynamic-
    length kernel's layout): slots over data, kv heads over tp — the
    same axes as ``kv_sharding``, transposed with the layout."""
    return shardings_for_mesh(mesh, P(None, "data", "tp", None, None))


def kv_kmajor_scale_sharding(mesh: Mesh) -> NamedSharding:
    """K-major int8 scale tensors [L, B, K, M]."""
    return shardings_for_mesh(mesh, P(None, "data", "tp", None))


def paged_pool_sharding(mesh: Mesh) -> NamedSharding:
    """Paged block pool [L, NB, bs, K, Dh]: kv heads over tp, blocks
    REPLICATED over data — blocks are shared storage (any slot's table
    may reference any block, and radix prefix blocks are read by slots
    on every data shard), so the slot axis that shards over data in the
    dense pool has no analog here; each data shard holds the full pool
    for its K/tp heads and XLA all-gathers the per-shard scatter
    updates to keep the replicas coherent."""
    return shardings_for_mesh(mesh, P(None, None, None, "tp", None))


def paged_pool_kmajor_sharding(mesh: Mesh) -> NamedSharding:
    """K-major-per-block int8 paged payloads [L, NB, K, bs, Dh]: kv
    heads over tp, blocks replicated over data (see
    ``paged_pool_sharding``)."""
    return shardings_for_mesh(mesh, P(None, None, "tp", None, None))


def paged_scale_kmajor_sharding(mesh: Mesh) -> NamedSharding:
    """K-major-per-block int8 paged scales [L, NB, K, bs]."""
    return shardings_for_mesh(mesh, P(None, None, "tp", None))


def _constrain_cache(cache: KVCache, mesh: Mesh | None) -> KVCache:
    if mesh is None:
        return cache
    s = kv_sharding(mesh)
    return KVCache(
        lax.with_sharding_constraint(cache.k, s),
        lax.with_sharding_constraint(cache.v, s),
    )


def _attend_cached(
    x, q, cache_k, cache_v, valid, layer, cfg,
    k_scale=None, v_scale=None, routing=False,
):
    """Shared decode tail: grouped-query attention over the kv cache,
    masked softmax, output projection and the MLP residual. x: [B, S, D];
    q: [B, S, H, Dh]; caches [B, M, K, Dh]; valid: [M], [B, M], or
    [B, S, M] (per-query masks — the multi-query verify step of
    speculative decoding) bool mask of readable cache positions. Single
    source of truth for the lockstep decode (scalar position,
    generate.py), the continuous-batching server's per-slot decode
    (serve.py), and spec decode's verify (spec_decode.py), in BOTH
    cache dtypes.

    GQA runs as a grouped einsum — q reshaped [B, S, K, rep, Dh] contracts
    directly against the [B, M, K, Dh] cache. Decode is cache-bandwidth
    bound, so never materialising a repeated H-head cache copy is the
    difference between reading K heads and reading H heads per token.

    ``k_scale``/``v_scale`` ([B, M, K] f32): int8-KV mode — the caches
    hold int8 payloads and the per-position scales are FOLDED onto the
    small score/prob tensors (exact: scales are constant along the Dh
    contraction), so the big operands carry only an int8→compute cast:

        scores[..., m] = (q · k_int8[m]) · k_scale[m]
        out            = (probs · v_scale) @ v_int8

    Measured caveat (PERF.md): on v5e XLA still materialises the
    converted operand as a buffer rather than fusing the cast into the
    dot's HBM read, so int8 KV trades ~20% equal-slot throughput for
    ~2× pool capacity; a Pallas decode kernel streaming int8 directly
    is the known fix."""
    attn = _read_cached(q, cache_k, cache_v, valid, cfg, k_scale, v_scale)
    if routing:  # (x, the routed expert layer's choices or None)
        return _attn_tail_routing(x, attn, layer, cfg)
    return _attn_tail(x, attn, layer, cfg)


@tracing.scope(tracing.SCOPE_KV_READ)
def _read_cached(q, cache_k, cache_v, valid, cfg, k_scale, v_scale):
    """``_attend_cached``'s read: scores, masked softmax and values →
    [B, S, H, Dh]."""
    b, s, h, dh = q.shape
    kk = cache_k.astype(cfg.dtype)
    vv = cache_v.astype(cfg.dtype)
    n_kv = kk.shape[2]
    rep = h // n_kv
    qg = q.reshape(b, s, n_kv, rep, dh)
    scores = jnp.einsum(
        "bskre,bmke->bkrsm", qg, kk, preferred_element_type=jnp.float32
    )
    if k_scale is not None:
        # [B, M, K] → [B, K, 1, 1, M] over [B, K, rep, S, M] scores.
        scores = scores * k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    scores = scores / jnp.sqrt(jnp.float32(cfg.head_dim))
    if valid.ndim == 1:
        valid = valid[None, :]
    if valid.ndim == 2:  # [B, M]: one mask for every query position
        vmask = valid[:, None, None, None, :]
    else:  # [B, S, M]: per-query masks (multi-query verify, spec decode)
        vmask = valid[:, None, None, :, :]
    scores = jnp.where(vmask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if v_scale is not None:
        probs = probs * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    return jnp.einsum(
        "bkrsm,bmke->bskre", probs.astype(cfg.dtype), vv,
        preferred_element_type=jnp.float32,
    ).astype(cfg.dtype).reshape(b, s, h, dh)


def _attend_merged(x, q, slab_k, slab_v, valid, layer, cfg, scope):
    """``_attend_cached`` for ONE query a slot against slabs whose rows
    hold a position's kv heads side by side, [B, M, K * Dh] (a pool by
    layer kind, ``KindKVCache``): the queries are laid block-diagonal,
    head h in the columns of its kv head and zero elsewhere, so scores
    and values are two plain matrix products a layer that read the slab
    where it lies (``mla.attend_absorbed``'s shape). The products cost K
    times the FLOPs of the grouped form and move the same bytes; the
    grouped einsum over [B, M, K, Dh] has the compiler copy a layer's
    slab out of the pool and re-tile it every tick (PERF.md, PR 34).
    x: [B, 1, D]; q: [B, 1, H, Dh]; valid: [B, M]; ``scope``: the read's
    name by the pool's kind (``tracing.SCOPE_KV_READ_WINDOW`` or
    ``_FULL``). Returns (x, the routed expert layer's choices or None)."""
    with tracing.scope(scope):
        attn = _read_merged(q, slab_k, slab_v, valid, cfg)
    return _attn_tail_routing(x, attn, layer, cfg)


def _read_merged(q, slab_k, slab_v, valid, cfg):
    """``_attend_merged``'s read → [B, 1, H, Dh]. The scores are over
    ``sqrt(Dh)``, or times the config's ``attention_multiplier``."""
    b, _s, h, dh = q.shape
    n_kv = cfg.n_kv_heads
    own = jnp.arange(h)[:, None] // (h // n_kv) == jnp.arange(n_kv)[None, :]
    q_wide = jnp.where(
        own[None, :, :, None], q[:, 0, :, None, :], 0
    ).reshape(b, h, n_kv * dh)
    scores = jnp.einsum(
        "bhc,bmc->bhm", q_wide, slab_k.astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )
    if cfg.attention_multiplier:
        scores = scores * jnp.float32(cfg.attention_multiplier)
    else:
        scores = scores / jnp.sqrt(jnp.float32(dh))
    probs = jax.nn.softmax(
        jnp.where(valid[:, None, :], scores, -1e30), axis=-1
    )
    wide = jnp.einsum(
        "bhm,bmc->bhc", probs.astype(cfg.dtype), slab_v.astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    ).reshape(b, h, n_kv, dh)
    return jnp.sum(
        jnp.where(own[None, :, :, None], wide, 0.0), axis=2
    ).astype(cfg.dtype)[:, None]


def _attn_tail(x, attn, layer, cfg):
    """Post-attention residual: output projection + the MLP block. Shared
    by the bf16 cache read (``_attend_cached``), the dense int8 kernel's
    read (slot_pool._slot_layer_step_q) and the latent read
    (slot_pool._slot_layer_step_latent), so the layer math has one
    definition."""
    return _attn_tail_routing(x, attn, layer, cfg)[0]


def _attn_tail_routing(x, attn, layer, cfg):
    """``_attn_tail`` and the expert choices it made: (x, routing [B, S,
    top_k] for a routed expert layer (ops/moe.py), else None)."""
    with tracing.scope(tracing.SCOPE_ATTN_PROJ):
        x = join_residual(x, jnp.einsum(
            "bshe,hed->bsd", attn, load_weight(layer["wo"], cfg.dtype)
        ), cfg)
        h = _rms_norm(x, layer["ln2"], cfg.norm_eps)
    if "router" not in layer:
        return join_residual(x, _dense_mlp(h, layer, cfg), cfg), None
    if cfg.routed_moe:
        # The one routed expert layer, prefill's too (ops/moe.py): its
        # form follows the static row count, and no token is dropped.
        from torchkafka_tpu.ops.moe import routed_moe_mlp

        mlp_out, routing = routed_moe_mlp(h, layer, cfg)
        return join_residual(x, mlp_out, cfg), routing
    # Decode always routes EXACTLY (dense dispatch) regardless of
    # cfg.moe_dispatch: capacity drops are a training
    # throughput/regularization tradeoff; at inference every token
    # gets its routed experts (standard MoE serving semantics — see
    # the moe_dispatch config comment).
    mlp_out, _stats = _moe_mlp(h, layer, cfg)
    return x + mlp_out, None


@tracing.scope(tracing.SCOPE_ATTN_PROJ)
def _project_qkv(x, layer, cfg):
    """RMSNorm + q/k/v projections for decode queries. x: [B, S, D] —
    S=1 for a decode tick, S=k+1 for spec decode's multi-query verify."""
    h = _rms_norm(x, layer["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhe->bshe", h, load_weight(layer["wq"], cfg.dtype))
    k = jnp.einsum("bsd,dke->bske", h, load_weight(layer["wk"], cfg.dtype))
    v = jnp.einsum("bsd,dke->bske", h, load_weight(layer["wv"], cfg.dtype))
    return (*qk_head_norm(q, k, layer, cfg), v)


@tracing.scope(tracing.SCOPE_HEAD)
def head_logits(params, cfg, x, at: int):
    """The final norm and the head's product at position ``at`` of x
    [B, S, D] → float32 logits [B, V]."""
    x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    return head_product(params, cfg, x[:, at])


def _layer_step(x, layer, cache_k, cache_v, pos, cfg):
    """One token through one layer. x: [B, 1, D]; caches [B, max_len, K, Dh];
    pos: scalar current position. Returns (x, new_cache_k, new_cache_v)."""
    q, k, v = _project_qkv(x, layer, cfg)
    positions = pos[None] if pos.ndim == 0 else pos
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    with tracing.scope(tracing.SCOPE_KV_WRITE):
        cache_k = lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0))
        cache_v = lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0))
    valid = jnp.arange(cache_k.shape[1]) <= pos  # attend to cache[0..pos]
    x = _attend_cached(x, q, cache_k, cache_v, valid, layer, cfg)
    return x, cache_k, cache_v


def prefill(
    params, cfg: TransformerConfig, tokens: jax.Array, max_len: int,
    mesh: Mesh | None = None, routing: bool = False,
):
    """Full forward over the prompt, capturing k/v into static caches.

    tokens: [B, S] → (last-position logits [B, V], KVCache with [0,S) filled).
    Uses Transformer.__call__ for the logits (single source of truth) and an
    auxiliary scan to capture per-layer k/v. ``routing``: a third result,
    the routed expert layers' choices ``[L_moe, B, S, top_k]`` (None for a
    config without such a layer), for the caller that counts them.

    With ``mesh``, the prompt batch is constrained over data and the cache
    over (data, tp) — weights are assumed committed to ``serving_shardings``
    layouts. Prefill attention under a mesh dispatches through the model's
    own rules: on TPU the Pallas flash kernels run under shard_map
    (``flash_attention_sharded`` — a Pallas call is opaque to GSPMD, but
    batch/head-parallel attention needs no collectives), falling back to
    the dense XLA body off-TPU or when the batch/heads don't split evenly.
    """
    # A training config that requested a sequence-parallel attn_impl
    # ('ring'/'ulysses') must still be servable from its checkpoint, so
    # fall back to the adaptive spelling rather than tripping the
    # constructor's misconfigured-mesh guard. An explicit 'dense' or
    # 'flash' passes through unchanged — a deliberate kernel opt-out (or
    # opt-in) is the user's call, mesh or not.
    if cfg.attn_impl in ("ring", "ulysses"):
        model = Transformer(dataclasses.replace(cfg, attn_impl="auto"), mesh)
    else:
        model = Transformer(cfg, mesh)
    if cfg.linear_pattern:
        # Linear and latent layers: what a slot keeps is (states, conv
        # tails, latent rows), each over its kind's layers.
        from torchkafka_tpu.models.linear_attn import hybrid_forward

        with tracing.scope(tracing.SCOPE_EMBED):
            x = embed_tokens(params, cfg, tokens)
        x, kept, chosen = hybrid_forward(params, model, x)
        out = head_logits(params, cfg, x, -1), kept, chosen
        return out if routing else out[:2]
    if cfg.is_mla:
        out = latent_forward(params, model, tokens)
        return out if routing else out[:2]
    if cfg.is_sparse:
        out = _prefill_indexed(params, model, tokens)
        return out if routing else out[:2]
    if cfg.window_pattern:
        out = _prefill_kinds(params, model, tokens, max_len)
        return out if routing else out[:2]
    if mesh is not None:
        tokens = lax.with_sharding_constraint(
            tokens, slot_sharding(mesh, tokens.ndim)
        )
    batch, seq = tokens.shape
    with tracing.scope(tracing.SCOPE_EMBED):
        x = embed_rows(params["embed"], tokens, cfg.dtype)
    positions = jnp.arange(seq)

    def capture(x, layer):
        # Same math as Transformer._layer, but returns k/v for the cache.
        with tracing.scope(tracing.SCOPE_ATTN_PROJ):
            h = _rms_norm(x, layer["ln1"])
            k = jnp.einsum("bsd,dke->bske", h, load_weight(layer["wk"], cfg.dtype))
            v = jnp.einsum("bsd,dke->bske", h, load_weight(layer["wv"], cfg.dtype))
            k = _rope(k, positions, cfg.rope_theta)
        x, _stats, (_latent, chosen) = model._layer_capture(x, layer)
        return x, (k, v, chosen)

    x, (ks, vs, chosen) = lax.scan(capture, x, params["layers"])
    logits = head_logits(params, cfg, x, -1)
    nl, kh, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    with tracing.scope(tracing.SCOPE_KV_WRITE):
        cache_k = jnp.zeros((nl, batch, max_len, kh, dh), cfg.dtype)
        cache_v = jnp.zeros((nl, batch, max_len, kh, dh), cfg.dtype)
        cache_k = lax.dynamic_update_slice(cache_k, ks.astype(cfg.dtype), (0, 0, 0, 0, 0))
        cache_v = lax.dynamic_update_slice(cache_v, vs.astype(cfg.dtype), (0, 0, 0, 0, 0))
    cache = _constrain_cache(KVCache(cache_k, cache_v), mesh)
    return (logits, cache, chosen) if routing else (logits, cache)


def _prefill_kinds(params, model: Transformer, tokens: jax.Array, max_len: int):
    """``prefill`` for a config with kinds of layer: (last-position logits
    [B, V], ``KindKVCache`` with the full layers' [0, S) filled and each
    window layer's ring holding its last ``sliding_window`` positions, the
    routed expert layers' choices [L, B, S, top_k] or None)."""
    cfg = model.cfg
    batch, seq = tokens.shape
    with tracing.scope(tracing.SCOPE_EMBED):
        x = embed_rows(params["embed"], tokens, cfg.dtype)
    positions = jnp.arange(seq)

    def capture(x, layer, j, _i):
        # Transformer._layer's k and v once more, beside it (as ``prefill``).
        kind = cfg.layer_kind(j)
        with tracing.scope(tracing.SCOPE_ATTN_PROJ):
            h = _rms_norm(x, layer["ln1"])
            k = jnp.einsum("bsd,dke->bske", h, load_weight(layer["wk"], cfg.dtype))
            v = jnp.einsum("bsd,dke->bske", h, load_weight(layer["wv"], cfg.dtype))
        x, _stats, (_latent, chosen) = model._layer_capture(x, layer, kind)
        return x, (_rope(k, positions, kind[1]), v, chosen)

    x, kv = scan_periods(cfg, params["layers"], x, capture)
    logits = head_logits(params, cfg, x, -1)

    def pool(window: bool, t: int):
        """One kind's k (t 0) or v (t 1): [periods, count, B, S, K, Dh]
        as [L, B, S, K * Dh]."""
        js = [j for j, w in enumerate(cfg.window_pattern) if w == window]
        width = cfg.n_kv_heads * cfg.head_dim
        if not js:
            return jnp.zeros((0, batch, seq, width), cfg.dtype)
        rows = jnp.stack([kv[j][t] for j in js], axis=1)
        return rows.reshape(-1, batch, seq, width).astype(cfg.dtype)

    grow = ((0, 0), (0, 0), (0, max_len - seq), (0, 0))
    chosen = None
    if kv[0][2] is not None:  # [periods, B, S, K] a layer of the period
        chosen = jnp.stack([y[2] for y in kv], axis=1)
        chosen = chosen.reshape(-1, *chosen.shape[2:])
    with tracing.scope(tracing.SCOPE_KV_WRITE):
        cache = KindKVCache(
            jnp.pad(pool(False, 0), grow), jnp.pad(pool(False, 1), grow),
            ring_rows(pool(True, 0), cfg.sliding_window),
            ring_rows(pool(True, 1), cfg.sliding_window),
        )
    return logits, cache, chosen


def _prefill_indexed(params, model: Transformer, tokens: jax.Array):
    """``prefill`` for learned sparse attention: (last-position logits [B,
    V]; what the indexed pool keeps of the S positions, ``(rows [L, B, S,
    W], index keys [L, B, Di, S])``: a position's K row beside its V row
    as ``ops.dsa.pack_rows`` lays them, its index key transposed; the
    routed expert layers' choices [L, B, S, top_k] or None). S long, not a
    pool: the caller writes them where its pool keeps them."""
    from torchkafka_tpu.models.transformer import index_key
    from torchkafka_tpu.ops.dsa import pack_rows

    cfg = model.cfg
    with tracing.scope(tracing.SCOPE_EMBED):
        x = embed_rows(params["embed"], tokens, cfg.dtype)
    positions = jnp.arange(tokens.shape[1])

    def capture(x, layer, j, _i):
        # Transformer._layer's k, v and index key once more, beside it.
        kind = cfg.layer_kind(j)
        with tracing.scope(tracing.SCOPE_ATTN_PROJ):
            h = _rms_norm(x, layer["ln1"])
            k = jnp.einsum("bsd,dke->bske", h, load_weight(layer["wk"], cfg.dtype))
            v = jnp.einsum("bsd,dke->bske", h, load_weight(layer["wv"], cfg.dtype))
            k = _rope(k, positions, kind[1])
        ki = index_key(h, layer, cfg, positions, kind[1])
        x, _stats, (_latent, chosen) = model._layer_capture(x, layer, kind)
        with tracing.scope(tracing.SCOPE_KV_WRITE):
            kept = pack_rows(k, v), ki.swapaxes(1, 2).astype(cfg.dtype)
        return x, (*kept, chosen)

    x, ((rows, keys, chosen),) = scan_periods(cfg, params["layers"], x, capture)
    return head_logits(params, cfg, x, -1), (rows, keys), chosen


def latent_forward(params, model: Transformer, tokens: jax.Array):
    """A latent-attention config's forward over ``tokens`` [B, S], with
    what serving and the benchmark keep of it: (last-position logits [B,
    V]; the rows the cache holds, [L, B, S, rank + rope], L over the
    leading dense layers and then the expert layers (over the blocks, 2 a
    layer, of the double layer); the expert layers'
    routing [L_moe, B, S, top_k], or None for a config without experts).
    Unlike ``prefill``'s ``KVCache`` the rows are S long, not a pool: the
    caller writes them where its pool keeps them."""
    cfg = model.cfg
    with tracing.scope(tracing.SCOPE_EMBED):
        x = embed_rows(params["embed"], tokens, cfg.dtype)

    def capture(x, layer):
        x, _stats, cached = model._layer_capture(x, layer)
        return x, cached

    latents, routing = [], None
    for key, _nl, expert_mlp in _layer_groups(cfg):
        xs, step = params[key], capture
        if cfg.attn_blocks == 2:
            xs, layer_of = _double_scan(params[key])
            step = lambda x, s, f=layer_of: capture(x, f(s))  # noqa: E731
        x, (lat, rt) = lax.scan(step, x, xs)
        if cfg.attn_blocks == 2:  # [L, 2, ...]: block i of layer l at 2l + i
            lat = lat.reshape(-1, *lat.shape[2:])
        latents.append(lat)
        if expert_mlp:
            routing = rt
    logits = head_logits(params, cfg, x, -1)
    latents = latents[0] if len(latents) == 1 else jnp.concatenate(latents)
    return logits, latents.astype(cfg.dtype), routing


def _decode_one(
    params, cfg, cache: KVCache, token: jax.Array, pos: jax.Array,
    mesh: Mesh | None = None,
):
    """token: [B] → logits [B, V], updated cache. pos: scalar position."""
    with tracing.scope(tracing.SCOPE_EMBED):
        x = embed_rows(params["embed"], token, cfg.dtype)[:, None, :]  # [B,1,D]

    def body(x, inputs):
        layer, ck, cv = inputs
        x, ck, cv = _layer_step(x, layer, ck, cv, pos, cfg)
        return x, (ck, cv)

    x, (ck, cv) = lax.scan(body, x, (params["layers"], cache.k, cache.v))
    logits = head_logits(params, cfg, x, 0)
    return logits, _constrain_cache(KVCache(ck, cv), mesh)


def generate(
    params,
    cfg: TransformerConfig,
    prompt: jax.Array,
    max_new: int,
    *,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    rng: jax.Array | None = None,
    mesh: Mesh | None = None,
):
    """prompt: [B, S] int32 → generated [B, max_new] int32 (greedy when
    temperature == 0). Jit-friendly: static prompt length and max_new.

    ``top_k``/``top_p``: nucleus/top-k filtering applied per step when
    sampling (``sample_logits``) — static-shape, same definition the
    serving path uses, differential-tested against a NumPy reference.

    ``mesh``: model-sharded decode — params must be committed to
    ``serving_shardings`` layouts (kv heads shard over tp, batch over
    data); token-exact vs the mesh-less path (differential-tested)."""
    check_sampling_params(top_k, top_p)
    why = _arch_refusal(cfg, "generate()'s lockstep decode")
    if why:
        raise ValueError(why)
    batch, seq = prompt.shape
    if mesh is not None:
        check_serving_mesh(cfg, mesh, batch=batch)
        params = lax.with_sharding_constraint(
            params, serving_shardings(cfg, mesh, params)
        )
    max_len = seq + max_new
    logits, cache = prefill(params, cfg, prompt, max_len, mesh)
    if rng is None:
        rng = jax.random.key(0)

    def pick(logits, key):
        return sample_logits(
            logits, key, temperature=temperature, top_k=top_k, top_p=top_p
        )

    first = pick(logits, rng)

    def step(carry, i):
        token, cache, key = carry
        key, sub = jax.random.split(key)
        logits, cache = _decode_one(params, cfg, cache, token, seq + i, mesh)
        nxt = pick(logits, sub)
        return (nxt, cache, key), token

    (_, _, _), tokens = lax.scan(
        step, (first, cache, rng), jnp.arange(max_new)
    )
    return jnp.transpose(tokens, (1, 0))  # [B, max_new]
