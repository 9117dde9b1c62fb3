"""The slot pool of the dense (non-paged) server: what a layout MEANS.

``kvcache.resolve_kv_backend`` names a layout (``KVBackend.layout`` with
``int8``, ``kernel``, ``partner``); ``make_slot_pool`` picks its class here,
and ``StreamingGenerator._build`` asks that object and branches on nothing.
A pool answers, and nothing else in the package does: its tensors and their
shardings under a mesh (``shapes``, ``zeros``, ``shardings``), an admission
trip's rows (``rows``, ``merged_put``), a decode tick's walk over the layers
with the layout's own step (``tick_layers``), the static metrics payload
(``static``), what a sync adds to the position meters (``count_reads``) and
the resume prefill's write (``resume_put``). serve → kvcache → models → ops.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from torchkafka_tpu.models import linear_attn, mla
from torchkafka_tpu.models.generate import (
    _attend_cached,
    _attend_merged,
    _attn_tail,
    _attn_tail_routing,
    _project_qkv,
    kv_kmajor_scale_sharding,
    kv_kmajor_sharding,
    kv_scale_sharding,
    kv_sharding,
)
from torchkafka_tpu.models.quant import quant_kv_groups
from torchkafka_tpu.models.transformer import (
    _double_layer,
    _double_scan,
    _layer_groups,
    _rms_norm,
    _rope,
    hybrid_groups,
    index_project,
    scan_hybrid,
    scan_periods,
)
from torchkafka_tpu.ops import dsa
from torchkafka_tpu.ops.kvattn import (
    dynlen_block,
    int8_decode_attention_dynlen,
    int8_decode_attention_dynlen_sharded,
)
from torchkafka_tpu.utils import tracing as xprof

__all__ = ["SlotPool", "make_slot_pool"]


@xprof.scope(xprof.SCOPE_KV_WRITE)
def _quant_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric absmax int8 over the last (head_dim) axis:
    [..., Dh] → (int8 [..., Dh], f32 scale [...]). The shared
    ``models.quant.quant_kv_groups`` scheme — the int8 PAGED pool
    quantizes through the same (position, head) groups, which is what
    keeps int8-paged serving token-exact vs int8-dense serving."""
    return quant_kv_groups(x)


def _layer_of(pool, l):
    """Layer ``l``'s slab of a stacked pool, for a read XLA can see into:
    the dynamic slice fuses into the read's first operation."""
    return lax.dynamic_index_in_dim(pool, l, keepdims=False)


def _slot_layer_step(x, layer, cache_k, cache_v, l, pos_b, cfg, kind=None):
    """One decode token through layer ``l`` with a DIFFERENT position per
    slot. x: [B, 1, D]; caches: the STACKED pool [L, B, M, K, Dh], which
    the caller carries through its layer loop — written in place here, one
    row per slot, and read at index ``l``; pos_b: [B]. Only the rope and
    the cache write differ from the lockstep ``generate._layer_step``; the
    attention/MLP tail is the shared ``_attend_cached``. (Sibling:
    spec_decode._multi_step generalizes this to S queries per row —
    update in step if the write/mask discipline changes.)

    ``kind`` (``cfg.layer_kind(j)``, a config with kinds of layer): the
    layer's ``(window or None, rope)``, the caches its KIND's pool, a
    position's kv heads in one row [L, B, M, K * Dh]
    (``generate.KindKVCache``), and ``l`` its row there. A window layer's
    pool is a ring [Lw, B, W, K * Dh]: the row goes to ``pos mod W`` and
    the read takes the rows ``< min(pos + 1, W)``, which hold the last W
    positions in some order (keys are cached roped, so the order does not
    matter). Returns (x, cache_k, cache_v, the routed expert layer's
    choices [B, 1, top_k] or None)."""
    window, rope = kind or (None, cfg.rope_theta)
    q, k, v = _project_qkv(x, layer, cfg)
    if cfg.use_rope:  # (a config without positions rotates nothing)
        q = _rope(q, pos_b[:, None], rope)
        k = _rope(k, pos_b[:, None], rope)
    # Per-row cache write as a SCATTER (.at[l, rows, pos].set), not a masked
    # select: the select rewrites the whole pool every layer while the
    # scatter writes one row per slot. The scatter goes into the stacked
    # pool and not into a layer's slab: a pool that is a scan's input and
    # output is sliced, written back and copied whole every tick (PERF.md,
    # PR 25); a carry is written in place.
    rows = jnp.arange(cache_k.shape[1])
    at, last = pos_b, pos_b
    if window is not None:
        at, last = pos_b % window, jnp.minimum(pos_b, window - 1)
    with xprof.scope(xprof.SCOPE_KV_WRITE):
        if kind is not None:  # a position's kv heads side by side in one row
            k, v = (a.reshape(*a.shape[:2], -1) for a in (k, v))
        cache_k = cache_k.at[l, rows, at].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[l, rows, at].set(v[:, 0].astype(cache_v.dtype))
    valid = jnp.arange(cache_k.shape[2])[None, :] <= last[:, None]  # [B, M]
    slabs = _layer_of(cache_k, l), _layer_of(cache_v, l)
    if kind is None:
        x, routing = _attend_cached(x, q, *slabs, valid, layer, cfg, routing=True)
    else:
        x, routing = _attend_merged(
            x, q, *slabs, valid, layer, cfg,
            xprof.SCOPE_KV_READ_FULL if window is None
            else xprof.SCOPE_KV_READ_WINDOW,
        )
    return x, cache_k, cache_v, routing


def _slot_layer_step_q(
    x, layer, ck_q, ck_s, cv_q, cv_s, l, pos_b, cfg, use_kernel=False,
    mesh=None, act=None,
):
    """int8-KV variant of ``_slot_layer_step``, over the same STACKED pool
    at layer index ``l`` (written in place, read at ``l``): the pool stores
    int8 payloads + per-(position, head) f32 absmax scales over Dh —
    (Dh+4)/(2·Dh) ≈ 52% of bf16 pool bytes at Dh=128 — read through
    ``_attend_cached``'s scale-folded mode (scales land on the small
    score/prob tensors; the big operands carry only a cast). A capacity
    lever: ~2× the slot/context headroom. Quantization error is bounded by
    absmax/127 per group; OPT-IN because token-exactness vs the bf16 path is
    deliberately given up."""
    q, k, v = _project_qkv(x, layer, cfg)
    q = _rope(q, pos_b[:, None], cfg.rope_theta)
    k = _rope(k, pos_b[:, None], cfg.rope_theta)
    kq, ks = _quant_kv(k[:, 0])  # [B, K, Dh] int8, [B, K]
    vq, vs = _quant_kv(v[:, 0])
    if use_kernel:
        # Pallas DYNAMIC-LENGTH int8 decode attention (ops/kvattn.py; its
        # docstring has the why of each point), which is this layer's
        # WRITE as well as its read. The pool is K-MAJOR ([L, B, K, M, Dh]
        # / [L, B, K, M]); the kernel DMAs M-blocks itself, so HBM traffic
        # follows each slot's ACTUAL fill. It takes the pool WHOLE with
        # the layer's index (``pool[l]`` outside an opaque call would
        # materialise the slab every layer) and returns it ALIASED to what
        # came in, with this tick's rows at [l, b, :, pos_b[b]] (as four
        # XLA scatters that write cost more than the read: PERF.md, PR
        # 30). A slot that is not live (``act``: idle, or latched done
        # inside this block) costs the kernel no HBM traffic: nothing
        # fetched, no row written, zeros out. A DMA has no bounds check
        # where a scatter drops: the tick's latch holds pos_b <= P +
        # max_new - 2 < M, and the call clamps. Under a mesh the call runs
        # per (data, tp) shard inside shard_map (the capability probe
        # gated the divisibilities).
        fresh = (kq, ks, vq, vs)
        with xprof.scope(xprof.SCOPE_KV_READ):
            if mesh is not None:
                attn, ck_q, ck_s, cv_q, cv_s = int8_decode_attention_dynlen_sharded(
                    q, ck_q, ck_s, cv_q, cv_s, pos_b, mesh, layer=l,
                    rows=fresh, live=act,
                )
            else:
                attn, ck_q, ck_s, cv_q, cv_s = int8_decode_attention_dynlen(
                    q, ck_q, ck_s, cv_q, cv_s, pos_b, layer=l, rows=fresh,
                    live=act,
                )
        x = _attn_tail(x, attn, layer, cfg)
    else:
        # The XLA read has nothing to write inside: scatters, like the
        # bf16 path (see _slot_layer_step's note), into the position-major
        # pool, payload [L, B, M, K, Dh] and scale [L, B, M, K] alike.
        rows = jnp.arange(ck_q.shape[1])
        with xprof.scope(xprof.SCOPE_KV_WRITE):
            ck_q, ck_s, cv_q, cv_s = (
                c.at[l, rows, pos_b].set(row)
                for c, row in ((ck_q, kq), (ck_s, ks), (cv_q, vq), (cv_s, vs))
            )
        valid = jnp.arange(ck_q.shape[2])[None, :] <= pos_b[:, None]  # [B, M]
        x = _attend_cached(
            x, q, _layer_of(ck_q, l), _layer_of(cv_q, l), valid, layer, cfg,
            k_scale=_layer_of(ck_s, l), v_scale=_layer_of(cv_s, l),
        )
    return x, ck_q, ck_s, cv_q, cv_s


def _slot_layer_step_latent(x, layer, pool, l, pos_b, cfg):
    """``_slot_layer_step`` for a latent-attention config: the stacked pool
    is ONE tensor [L, B, M, rank + rope]. The slot's row (the normed
    latent beside the roped shared key, models/mla.py) is scattered into
    layer ``l`` in place, and the read is ABSORBED: the heads' queries meet
    the cached rows themselves, nothing is up-projected for the pool's
    positions. The double layer (``attn_blocks`` 2) does so twice, block
    ``i`` against pool row ``2l + i``, the pool ``[2L, ...]``. Returns (x,
    pool, routing [B, 1, top_k] | None)."""
    rows = jnp.arange(pool.shape[1])
    if cfg.attn_blocks == 2:
        held = [pool]

        def attend(i, h, blk):
            q_nope, q_rope, latent = mla.project(h, blk, cfg, pos_b[:, None])
            with xprof.scope(xprof.SCOPE_KV_WRITE):
                held[0] = held[0].at[2 * l + i, rows, pos_b].set(
                    latent[:, 0].astype(pool.dtype)
                )
            return mla.attend_absorbed(
                q_nope, q_rope, held[0], 2 * l + i, pos_b, blk, cfg
            )

        x, routing = _double_layer(x, layer, cfg, attend)
        return x, held[0], routing
    with xprof.scope(xprof.SCOPE_ATTN_PROJ):
        h = _rms_norm(x, layer["ln1"])
    q_nope, q_rope, latent = mla.project(h, layer, cfg, pos_b[:, None])
    with xprof.scope(xprof.SCOPE_KV_WRITE):
        pool = pool.at[l, rows, pos_b].set(latent[:, 0].astype(pool.dtype))
    attn = mla.attend_absorbed(q_nope, q_rope, pool, l, pos_b, layer, cfg)
    x, routing = _attn_tail_routing(x, attn, layer, cfg)
    return x, pool, routing


def _slot_layer_step_indexed(x, layer, rows, keys, l, pos_b, act, cfg, rope):
    """``_slot_layer_step`` under learned sparse attention (ops/dsa.py),
    over the indexed pool: ``rows`` [L, B, M, W / 128, 128] (a position's K
    row beside its V row, ``dsa.pack_rows``, a tile) and the index keys ``keys`` [L, B, Di,
    M]. The token's row and index key are scattered into layer ``l`` in
    place; ``tk_dsa_index`` scores the slot's ``pos + 1`` valid keys, the
    ``index_topk`` best are selected (``lax.top_k``: exact, ties to the
    lower position), and ``tk_dsa_attend`` fetches those rows alone. A
    slot that is not live (``act``) has no valid position: nothing of it is
    fetched, what it computes is never read. Returns (x, rows, keys,
    routing)."""
    q, k, v = _project_qkv(x, layer, cfg)
    q = _rope(q, pos_b[:, None], rope)
    k = _rope(k, pos_b[:, None], rope)
    with xprof.scope(xprof.SCOPE_ATTN_PROJ):
        h = _rms_norm(x, layer["ln1"], cfg.norm_eps)
    qi, ki, w = index_project(h, layer, cfg, pos_b[:, None], rope)
    slots = jnp.arange(rows.shape[1])
    with xprof.scope(xprof.SCOPE_KV_WRITE):
        rows = rows.at[l, slots, pos_b].set(
            dsa.pack_rows(k[:, 0], v[:, 0]).reshape(-1, *rows.shape[3:])
        )
        keys = keys.at[l, slots, :, pos_b].set(ki[:, 0].astype(keys.dtype))
    with xprof.scope(xprof.SCOPE_KV_READ_FULL):
        n = jnp.where(act, pos_b + 1, 0)
        scores = dsa.index_scores(qi[:, 0], w[:, 0], keys, l, n)
        topk = min(cfg.index_topk, scores.shape[-1])
        _best, chosen = lax.top_k(scores, topk)
        attn = dsa.attend_selected(
            q[:, 0], rows, l, chosen, jnp.minimum(n, topk),
            n_kv=cfg.n_kv_heads, scale=cfg.attn_scale,
        )
    x, routing = _attn_tail_routing(x, attn[:, None], layer, cfg)
    return x, rows, keys, routing


@xprof.scope(xprof.SCOPE_MOE_ROUTE)
def _count_routing(stats, routing, act, cfg):
    """(experts touched, pairs by expert[, pairs by fate]) with one expert
    layer's routing [B, 1, top_k] of a tick added (None, a layer without
    one: ``stats`` as it came): a pair counts where the device holds the
    slot active, an expert is touched where it got at least one. Where
    the layer has zero-compute experts or holds a share (``stats`` then
    has a third member), the load is over the HELD experts and the fates
    are (zero, local, absent)."""
    if routing is None:
        return stats
    touched, load, *fates = stats
    flat = routing.reshape(-1)
    live = jnp.repeat(act, routing.shape[-1]).astype(load.dtype)
    if not fates:
        pairs = jnp.zeros_like(load).at[flat].add(live)
        return touched + jnp.sum(pairs > 0), load + pairs
    first, count = cfg.held_experts
    zero = flat >= cfg.n_experts
    local = (flat >= first) & (flat < first + count)
    pairs = jnp.zeros_like(load).at[
        jnp.where(local, flat - first, count)
    ].add(live, mode="drop")
    fate = jnp.stack([
        jnp.sum(live * zero), jnp.sum(live * local),
        jnp.sum(live * ~(zero | local)),
    ])
    return touched + jnp.sum(pairs > 0), load + pairs, fates[0] + fate


def _count_slabs(metrics, kind, layers, spans, window, read):
    """``layers`` layers of one kind at a sync: the rows their served ticks
    needed (token j >= 1 read ``window + j``) against the ``read`` fetched."""
    needed = sum(
        (cnt - j0) * window + (cnt - j0) * (j0 + cnt - 1) // 2
        for j0, cnt, _ran in spans
    )
    getattr(metrics, f"{kind}_positions_valid").add(needed * layers)
    getattr(metrics, f"{kind}_positions_read").add(read * layers)


class SlotPool:
    """Layout ``dense`` in the compute dtype, and the base of the others:
    K and V, each ``[L, B, M, K, Dh]``, written by a scatter a tick and
    read whole by XLA."""

    merged_put = False  # (StatePool says when ``admit`` puts through a view)

    def __init__(self, cfg, backend, slots: int, max_len: int, mesh=None):
        self.cfg, self.backend, self.mesh = cfg, backend, mesh
        self.slots, self.max_len = slots, max_len

    def shapes(self) -> tuple:
        """((shape, dtype), ...) of the pool's tensors, in their order."""
        cfg = self.cfg
        kv = (cfg.n_layers, self.slots, self.max_len, cfg.n_kv_heads, cfg.head_dim)
        return ((kv, cfg.dtype),) * 2

    def zeros(self) -> tuple:
        return tuple(jnp.zeros(shape, dtype) for shape, dtype in self.shapes())

    def nbytes(self, which=slice(None)) -> int:
        return sum(
            math.prod(shape) * jnp.dtype(dtype).itemsize
            for shape, dtype in self.shapes()[which]
        )

    def shardings(self) -> tuple:
        """Each tensor's sharding under the mesh: kv heads over tp, slots
        over data; an int8 pool's 4-D scales the payloads' axes minus
        head_dim, kernel mode both K-MAJOR. (The others serve unsharded.)"""
        kv, scale = (
            (kv_kmajor_sharding, kv_kmajor_scale_sharding)
            if self.backend.kernel else (kv_sharding, kv_scale_sharding)
        )
        return tuple(
            (kv if len(shape) == 5 else scale)(self.mesh)
            for shape, _ in self.shapes()
        )

    def static(self) -> dict:
        """``ServeMetrics``' static payloads of this pool, by attribute."""
        return {}

    def rows(self, fresh) -> tuple:
        """One admission trip's rows [L, R, ...] in the pool's layout, of
        what ``generate.prefill`` kept: (k, v) as they are; by kind the
        full layers' window [0, P) and the rings as P positions leave them."""
        return tuple(fresh)

    def step(self, x, layer, caches, l, pos, act):
        """One layer of ``tick_layers``' walk → (x, caches, routing)."""
        x, ck, cv, routing = _slot_layer_step(x, layer, *caches, l, pos, self.cfg)
        return x, (ck, cv), routing

    def tick_layers(self, params, x, caches, stats, pos, act):
        """One decode tick's layers over x [B, 1, D] → (x, caches, stats).
        The pool rides the layer loop as its CARRY, as it rides the tick
        loop: each layer writes its rows into the stacked pool in place (a
        scatter, or the dense int8 kernel's aliased write) and reads at
        its own index; as a scan's xs and ys the pool is copied whole
        every tick (PERF.md, PR 25). The layer index runs over the leading
        dense layers and then the expert layers."""
        cfg = self.cfg

        def body(carry, inputs):
            x, caches, stats = carry
            layer, l = inputs
            x, caches, routing = self.step(x, layer, caches, l, pos, act)
            return (x, caches, _count_routing(stats, routing, act, cfg)), None

        first = 0
        for key, n, _expert_mlp in _layer_groups(cfg):
            xs, step = (params[key], jnp.arange(first, first + n)), body
            if cfg.attn_blocks == 2:
                # The blocks' tensors stay stacked: _double_scan.
                xs, layer_of = _double_scan(params[key], first)
                step = lambda c, s, f=layer_of: body(c, (f(s), s[1]))  # noqa: E731
            (x, caches, stats), _ = lax.scan(step, (x, caches, stats), xs)
            first += n
        return x, caches, stats

    def count_reads(self, metrics, spans, window: int, ticks: int) -> None:
        """What a sync adds to ``*_positions_valid`` / ``*_positions_read``.
        ``spans``: (j0, cnt, ran) an active slot, the tokens [j0, cnt) its
        served ticks produced, ``ran`` those the device held it live for;
        ``ticks``: slot-ticks the block ran. Nothing for this pool."""

    def resume_put(self, caches, fresh, slot):
        """The resume prefill's K and V [L, 1, M, K, Dh] into ``slot``."""
        return tuple(
            lax.dynamic_update_slice(c, a.astype(c.dtype), (0, slot, 0, 0, 0))
            for c, a in zip(caches, (fresh.k, fresh.v))
        )


class Int8Pool(SlotPool):
    """Layout ``dense`` with ``int8``: K and V payloads with a float32
    scale a (position, head) each, K-MAJOR for the Pallas read, which
    fetches a live slot's rows by blocks (``_slot_layer_step_q``);
    position-major for the XLA read, whose block is the slab."""

    @property
    def read_block(self) -> int:  # rows the read fetches at a time
        return dynlen_block(self.max_len) if self.backend.kernel else self.max_len

    def shapes(self):
        cfg, kh, M = self.cfg, self.cfg.n_kv_heads, self.max_len
        rows = (kh, M) if self.backend.kernel else (M, kh)
        return tuple(
            ((cfg.n_layers, self.slots, *rows, *tail), dtype)
            for _ in "kv"
            for tail, dtype in (((cfg.head_dim,), jnp.int8), ((), jnp.float32))
        )

    def static(self):
        return {"kv_pool_static": {
            "full_layers": self.cfg.n_layers, "bytes_full": self.nbytes(),
            "read": "kernel" if self.backend.kernel else "xla",
            "block": self.read_block,
        }}

    def rows(self, fresh):
        rows = (*_quant_kv(fresh.k), *_quant_kv(fresh.v))
        if self.backend.kernel:
            # Kernel mode stores the pool K-major: transpose the chunk's
            # freshly-quantized [L, R, P, K, ·] rows (the per-tick read
            # this layout accelerates runs max_new times an admission).
            with xprof.scope(xprof.SCOPE_KV_WRITE):
                rows = tuple(jnp.swapaxes(a, 2, 3) for a in rows)
        return rows

    def step(self, x, layer, caches, l, pos, act):
        x, *caches = _slot_layer_step_q(
            x, layer, *caches, l, pos, self.cfg,
            use_kernel=self.backend.kernel, mesh=self.mesh, act=act,
        )
        return x, tuple(caches), None

    def count_reads(self, metrics, spans, window, ticks):
        block = self.read_block
        # The XLA read fetches every slot's slab, every tick; the kernel a
        # live tick's whole blocks up to its row (a slot whose budget is 1
        # is live for the one tick that latches it), else nothing.
        fetched = ticks * block
        if self.backend.kernel:
            live = (window + np.arange(j0, max(ran, j0 + (cnt == 1))) for j0, cnt, ran in spans)
            fetched = sum(int((-(-j // block) * block).sum()) for j in live)
        _count_slabs(metrics, "full", self.cfg.n_layers, spans, window, fetched)


class LatentPool(SlotPool):
    """Layout ``latent``: ONE tensor [L, B, M, rank + rope] in the compute
    dtype, a row an attention block ([2L, ...] for the double layer), read
    absorbed (models/mla.py)."""

    def shapes(self):
        cfg = self.cfg
        rows = (cfg.cache_layers, self.slots, self.max_len, cfg.latent_dim)
        return ((rows, cfg.dtype),)

    def static(self):
        return {"attn_blocks": self.cfg.attn_blocks}

    def rows(self, fresh):
        return (fresh,)  # [L, R, P, C]

    def step(self, x, layer, caches, l, pos, act):
        x, pool, routing = _slot_layer_step_latent(x, layer, *caches, l, pos, self.cfg)
        return x, (pool,), routing

    def count_reads(self, metrics, spans, window, ticks):
        # The XLA read fetches the whole slab of every slot, every tick.
        read = ticks * self.max_len
        _count_slabs(metrics, "latent", self.cfg.cache_layers, spans, window, read)


class ByKindPool(SlotPool):
    """Layout ``by_kind`` (``window_pattern``): ``KindKVCache``'s four
    tensors, the full layers' K and V [Lf, B, M, K * Dh] and the window
    layers' rings [Lw, B, W, K * Dh], both the period scan's carry."""

    def shapes(self):
        cfg, w = self.cfg, self.cfg.sliding_window
        width = cfg.n_kv_heads * cfg.head_dim
        return tuple(
            ((cfg.kind_layers(window), self.slots, rows, width), cfg.dtype)
            for window, rows in (
                (False, self.max_len), (False, self.max_len), (True, w), (True, w)
            )
        )

    def static(self):
        cfg = self.cfg
        return {"kv_pool_static": {
            "window": cfg.sliding_window, "window_layers": cfg.kind_layers(True),
            "full_layers": cfg.kind_layers(False),
            "bytes_window": self.nbytes(slice(2, None)),
            "bytes_full": self.nbytes(slice(2)),
        }}

    def tick_layers(self, params, x, caches, stats, pos, act):
        cfg = self.cfg

        def body(carry, layer, j, i):
            # Layer j of period i: its kind's pool, its row there.
            x, caches, stats = carry
            rank, count = cfg.kind_rank(j)
            at = 2 if cfg.window_pattern[j] else 0
            x, ck, cv, routing = _slot_layer_step(
                x, layer, caches[at], caches[at + 1], i * count + rank, pos,
                cfg, cfg.layer_kind(j),
            )
            caches = caches[:at] + (ck, cv) + caches[at + 2:]
            return (x, caches, _count_routing(stats, routing, act, cfg)), None

        for key, _n, _expert_mlp in _layer_groups(cfg):
            (x, caches, stats), _ = scan_periods(
                cfg, params[key], (x, caches, stats), body
            )
        return x, caches, stats

    def count_reads(self, metrics, spans, window, ticks):
        # The XLA reads fetch every slot's ring and slab, every tick.
        cfg, ring = self.cfg, self.cfg.sliding_window
        n_win = cfg.kind_layers(True)
        metrics.window_positions_valid.add(n_win * sum(
            int(np.minimum(window + np.arange(j0, cnt), ring).sum())
            for j0, cnt, _ran in spans
        ))
        metrics.window_positions_read.add(n_win * ticks * ring)
        read = ticks * self.max_len
        _count_slabs(metrics, "full", cfg.kind_layers(False), spans, window, read)


class IndexedPool(SlotPool):
    """Layout ``indexed`` (learned sparse attention, ``index_topk``): a
    position's K row beside its V row in ONE row of 32-bit words, a tile
    of its own [L, B, M, W / 128, 128], and its index key [L, B, Di, M]
    (ops/dsa.py has why each lies so),
    both the layer scan's carry; a tick scores a live slot's valid index
    keys and fetches the selected rows alone."""

    def shapes(self):
        cfg = self.cfg
        tile, words = dsa.row_spec(cfg.n_kv_heads, cfg.head_dim, cfg.dtype)
        return (
            ((cfg.n_layers, self.slots, self.max_len, *tile), words),
            ((cfg.n_layers, self.slots, cfg.index_head_dim, self.max_len),
             cfg.dtype),
        )

    @property
    def topk(self) -> int:  # what a query selects, at most
        return min(self.cfg.index_topk, self.max_len)

    def rows(self, fresh):
        rows, keys = fresh  # [L, R, P, W] as a tile a position
        return rows.reshape(*rows.shape[:3], *self.shapes()[0][0][3:]), keys

    def static(self):
        cfg = self.cfg
        return {"kv_pool_static": {
            "full_layers": cfg.n_layers, "bytes_full": self.nbytes(slice(1)),
            "read": "kernel", "index_layers": cfg.n_layers,
            "bytes_index": self.nbytes(slice(1, 2)), "topk": self.topk,
        }}

    def tick_layers(self, params, x, caches, stats, pos, act):
        cfg = self.cfg

        def body(carry, layer, j, i):
            x, (rows, keys), stats = carry
            x, rows, keys, routing = _slot_layer_step_indexed(
                x, layer, rows, keys, i, pos, act, cfg, cfg.layer_kind(j)[1]
            )
            return (x, (rows, keys), _count_routing(stats, routing, act, cfg)), None

        for key, _n, _expert_mlp in _layer_groups(cfg):
            (x, caches, stats), _ = scan_periods(
                cfg, params[key], (x, caches, stats), body
            )
        return x, caches, stats

    def count_reads(self, metrics, spans, window, ticks):
        # A served tick at position p holds p + 1 = window + j rows and
        # selects min(that, topk) of them; the kernels fetch, for a tick
        # the device held the slot live, the index keys by whole blocks up
        # to the row and the selected rows by whole chunks.
        layers, topk = self.cfg.n_layers, self.topk
        blk = dsa.index_block(self.max_len)
        chunk = min(dsa.ATTEND_CHUNK, topk)
        up = lambda a, b: -(-a // b) * b  # noqa: E731
        for j0, cnt, ran in spans:
            held = window + np.arange(j0, cnt)
            live = window + np.arange(j0, max(ran, j0 + (cnt == 1)))
            metrics.index_positions_valid.add(layers * int(held.sum()))
            metrics.index_positions_read.add(layers * int(up(live, blk).sum()))
            metrics.sparse_positions_valid.add(layers * int(held.sum()))
            metrics.sparse_positions_selected.add(
                layers * int(np.minimum(held, topk).sum())
            )
            metrics.sparse_positions_read.add(
                layers * int(up(np.minimum(live, topk), chunk).sum())
            )


class StatePool(SlotPool):
    """Layout ``state`` (``linear_pattern``, models/linear_attn.py): what a
    slot keeps of a linear layer, its recurrent state in float32 ([L_lin,
    B, H, E, E] the delta rule's, [L_lin, B, H, P, N] the Mamba-2 mixer's,
    NONE the gated convolution's) and its conv tail
    (``linear_attn.slot_shapes``), then the attention layers' pool by
    ``partner``: the latent rows, or K and V rows [L_att, B, M, K * Dh]."""

    def __init__(self, *args):
        super().__init__(*args)
        self.latent = self.backend.partner == "latent"
        # The tensors of the linear layers lead: (states, tails) or (tails,).
        self.kept = linear_attn.kept_tensors(self.cfg)
        # The state-space state [H, P, N] leaves the chunked scan's
        # product laid out otherwise than the pool (P before H), and the
        # compiler lays the POOL out again to take it: a copy of the whole
        # state in and out of every admission (4.5 GiB at 128 slots: the
        # program no longer fits the chip). Through a view with a slot's
        # axes merged only one layout makes the view free, and the rows
        # are laid out again instead. The K and V rows likewise: each pool
        # is still copied in and out of an admission, but in a GiB less of
        # temporaries (figures: the benchmark's configuration file).
        self.merged_put = self.cfg.linear_kind == "ssd"

    def shapes(self):
        cfg, B = self.cfg, self.slots
        n_lin, n_att = cfg.hybrid_layers(True), cfg.cache_layers
        state, conv = linear_attn.slot_shapes(cfg)
        pools = (
            ((n_att, B, self.max_len, cfg.latent_dim),) if self.latent
            else ((n_att, B, self.max_len, cfg.n_kv_heads * cfg.head_dim),) * 2
        )
        states = () if state is None else (((n_lin, B, *state), jnp.float32),)
        return (
            *states, ((n_lin, B, *conv), cfg.dtype),
            *((shape, cfg.dtype) for shape in pools),
        )

    def static(self):
        cfg, kept = self.cfg, self.kept
        chunk = linear_attn.prefill_chunk(cfg)
        payload = {"linear_state": {
            "kind": cfg.linear_kind, "layers": cfg.hybrid_layers(True),
            "bytes_state": self.nbytes(slice(kept - 1)),
            "bytes_conv": self.nbytes(slice(kept - 1, kept)),
            "state_dtype": "float32" if kept == 2 else None,
            "step": linear_attn.step_form() if kept == 2 else "xla",
            **({"prefill": "chunked", "chunk": chunk} if chunk
               else {"prefill": "shifted_sum"}),
        }}
        if not self.latent:
            payload["kv_pool_static"] = {
                "full_layers": cfg.cache_layers,
                "bytes_full": self.nbytes(slice(kept, None)), "read": "xla",
            }
        return payload

    def tick_layers(self, params, x, caches, stats, pos, act):
        cfg, kept = self.cfg, self.kept

        def body(carry, layer, linear, row):
            # Its row in its kind's tensors; a slot that is not active
            # keeps its state. The grouped-query layer is the one the dense
            # path steps, over K and V rows as a pool by kind's full layers.
            x, caches, stats = carry
            if linear or self.latent:
                x, caches, routing = linear_attn.slot_layer_step(
                    x, layer, linear, row, caches, pos, act, cfg
                )
            else:
                x, ck, cv, routing = _slot_layer_step(
                    x, layer, *caches[kept:], row, pos, cfg,
                    (None, cfg.rope_theta),
                )
                caches = (*caches[:kept], ck, cv)
            return (x, caches, _count_routing(stats, routing, act, cfg)), None

        # Every kind's tensors are the period scan's carry.
        for key, pattern, lin0, lat0 in hybrid_groups(cfg):
            (x, caches, stats), _ = scan_hybrid(
                cfg, params[key], pattern, (x, caches, stats), body, lin0, lat0
            )
        return x, caches, stats

    def count_reads(self, metrics, spans, window, ticks):
        # The attention layers' XLA read fetches every slot's slab, every
        # tick; nothing of a linear layer is indexed by position.
        _count_slabs(
            metrics, "latent" if self.latent else "full", self.cfg.cache_layers,
            spans, window, ticks * self.max_len,
        )


def make_slot_pool(cfg, backend, *, slots: int, max_len: int, mesh=None) -> SlotPool:
    """The pool of the layout that ``backend`` (a resolved ``KVBackend``) names."""
    kind = {
        "dense": Int8Pool if backend.int8 else SlotPool, "latent": LatentPool,
        "by_kind": ByKindPool, "state": StatePool, "indexed": IndexedPool,
    }[backend.layout]
    return kind(cfg, backend, slots, max_len, mesh)
