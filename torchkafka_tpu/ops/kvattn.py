"""Pallas decode-attention reads of the int8 KV pools, and the XLA
block-table reads of the paged pool.

Two kernels, one for each pool layout a ``StreamingGenerator`` builds:

- ``int8_decode_attention_dynlen`` (``tk_kvattn_dynlen``) reads the DENSE
  slot pool, payloads [B, K, M, Dh] int8 with scales [B, K, M] f32 (or the
  stacked pool [L, B, ...] with ``layer=``), up to each slot's watermark.
  With ``rows=`` it is the decode tick's WRITE too (below).
- ``int8_paged_decode_attention`` (``tk_kvattn_paged``) reads the PAGED
  pool, blocks [NB, K, bs, Dh] with scales [NB, K, bs], through per-slot
  block tables, up to each slot's watermark.

Both are held by differential tests to the scale-folded XLA read
(``models.generate._attend_cached`` with ``k_scale``/``v_scale``), exact
up to f32 reduction order.

Why the layout and the structure:

- **K-major.** With the kv-head axis ahead of the position axis every
  head's [M, Dh] tile is a contiguous leading-axis slice, and both dots
  are ONE K-batched ``dot_general`` whose batch dims sit at position 0 on
  each operand (Mosaic's batched-dot rule). A position-major pool
  [B, M, K, Dh] makes every per-head slice strided and forces a static
  per-head loop of tiny [rep, Dh] dots over relaid-out operands.
- **Watermarks by scalar prefetch.** The pool stays in HBM
  (``memory_space=ANY``); the per-slot watermarks (and, paged, the block
  tables) arrive as scalar-prefetch arguments, and the kernel DMAs
  M-blocks itself, double-buffered, through a flash-style online-softmax
  recurrence. The block loop runs ``ceil((pos+1)/mb)`` times, so
  positions past a slot's fill are never fetched: HBM traffic follows the
  fill, which no XLA spelling can do (static shapes make every read
  pool-shaped).
- **Global buffer parity.** Which of the two VMEM buffers block (slot, j)
  uses is ``(blocks of all earlier slots + j) % 2``, the watermarks'
  prefix sum (the dense kernel is handed it with the watermarks, the
  paged one sums it a program), not restarted per program, so that
- **the predecessor prefetches.** The grid is sequential ("arbitrary")
  and scratch persists across programs, so program i starts the DMA of
  program i+1's first block during its own last block's compute; without
  it every slot opens with a DMA stall.
- **A slot that is not live costs the dense kernel nothing** (``live=``;
  PR 47). A decode tick runs every slot, whether it serves a request or
  not: idle ones, and ones the tick latched done earlier in its block (by
  EOS, or at the request's answer budget: a quarter of the slot-ticks of
  a heavy-tailed deck). The grid walks one more scalar-prefetch operand,
  an ORDER with the live slots first (the query, fresh-row and output
  blocks are indexed through it), so the first ``n_live`` programs are the
  kernel above over a permutation, with the chain of prefetches, buffer
  parities and staged row writes among them alone, and every program
  past them starts no DMA, waits on none, writes no row and stores zeros
  (0 / 0 must not reach the residual). "Earlier slots" above reads
  "earlier programs".

- **The dense kernel owns the tick's row write** (``rows=``; PR 30). A
  decode tick puts one new position into each slot: 8 heads x 132 bytes.
  As four XLA scatters into the K-major pool that is 384 separately
  indexed updates each, run one after another, and cost more than this
  read. The kernel takes the freshly quantised rows beside the query and
  returns the four pool tensors ALIASED to the four it was given
  (``input_output_aliases``; the operands stay in ``memory_space=ANY``, so
  nothing is copied and the pool stays the carry of the tick's loops).
  Inside, the row is MERGED INTO THE FETCHED TILE in VMEM before the dots:
  column ``pos`` always lies in the slot's last block, and HBM cannot
  serve it, since slot b's first block is fetched by program b - 1,
  before program b writes anything. What goes back to HBM is the tile's
  aligned group round the row — 32 positions of payload (int8 packs four
  positions a word, (32, 128) a tile: one position is not a DMA's to
  address) and 128 lanes of scales — from a staging scratch of its own,
  waited one program later, so the tile's buffer is free for the next
  prefetch at once. Every other byte of the group is what was fetched, so
  the pool is bit for bit the scatters'. A DMA has no bounds check where
  a scatter drops: ``pos <= M - 1`` is the caller's (the tick's latch
  holds it) and the call clamps. A slot that is not live writes
  nothing, where the scatters wrote a stale row at its frozen position
  that nothing read: its pool stays as it was.

Numbers (time a call, roofline share): PERF.md §5.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchkafka_tpu.ops.flash import _default_interpret, tpu_compiler_params

_NEG_INF = -1e30


def kernel_applicable(head_dim: int, max_len: int) -> bool:
    """Shape gate of the dense-pool kernels on every backend:
    lane-aligned head_dim (Dh is the lane dim of the payload blocks) and
    a pool length that tiles by 8. Compiled Mosaic needs more for the
    dynamic-length read — its [K, mb] scale slices put the M-block on the
    LANE dim, so mb must be a multiple of 128 — which the serving probe
    (kvcache/backend.py) enforces on TPU by requiring ``dynlen_block`` >=
    256. Interpret mode accepts anything; tests force it."""
    return head_dim % 128 == 0 and max_len % 8 == 0


# ------------------------------------------------------- paged (block-table)
# Block-table attention for the paged slot pool (torchkafka_tpu/kvcache):
# the cache is a SHARED pool of fixed-size blocks [NB, bs, K, Dh] and each
# slot maps logical positions to physical blocks through a per-slot block
# table [B, nblk] — multiple slots may map the same physical prefix blocks
# (radix-tree sharing), which is what decouples pool bytes from
# slots × max_context. Static shapes throughout, the XLA discipline: the
# write is a scatter at (table[pos // bs], pos % bs), the read a gather of
# each slot's nblk blocks into a contiguous [B, nblk·bs, K, Dh] logical
# view, masked to the live length. The gather materialises the per-slot
# view each call (read bytes match the dense pool read); the wins are
# STORAGE (shared prefixes held once; pool sized to live tokens, not
# slots × max_len) and PREFILL compute (cached prefixes skip re-prefill).


def paged_gather(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Gather a per-slot logical cache view from a block pool.

    pool: [NB, bs, ...rest]; table: [B, nblk] int32 physical block ids →
    [B, nblk * bs, ...rest] — logical position p of slot b lands at
    index p (block table order), so position masks apply unchanged."""
    b, nblk = table.shape
    return pool[table].reshape(b, nblk * pool.shape[1], *pool.shape[2:])


def paged_scatter(
    pool: jax.Array, table: jax.Array, positions: jax.Array,
    values: jax.Array,
) -> jax.Array:
    """Write ``values`` [B, S, ...rest] at logical ``positions`` [B, S]
    through ``table`` [B, nblk] into ``pool`` [NB, bs, ...rest].

    Live slots write only blocks they own privately (sharing is limited
    to whole blocks strictly below any written position — the radix
    contract), so no two live slots ever collide. Idle slots' table rows
    point every entry at the sink block (kvcache.SINK_BLOCK), which no
    live table references — their unconditional frozen-position writes
    land there harmlessly (masking the write would cost a pool-sized
    select per layer; slot_pool._slot_layer_step's lesson)."""
    bs = pool.shape[1]
    blk = jnp.take_along_axis(table, positions // bs, axis=1)  # [B, S]
    off = positions % bs
    return pool.at[blk, off].set(values.astype(pool.dtype))


def paged_gather_kmajor(pool: jax.Array, table: jax.Array) -> jax.Array:
    """``paged_gather`` for K-MAJOR-PER-BLOCK pools.

    pool: [NB, K, bs, ...rest] (payload rest=(Dh,), scales rest=());
    table: [B, nblk] int32 → [B, nblk * bs, K, ...rest]. The int8 paged
    pool stores each block K-major so the Pallas block-table kernel's
    per-block tiles are the dyn-len kernel's [K, bs, Dh] shape (one batched
    dot over (slot, head), no per-head relayout); the XLA read pays one
    transpose of the gathered view to recover the logical
    [B, M', K, ...] layout ``_attend_cached`` expects."""
    b, nblk = table.shape
    v = jnp.swapaxes(pool[table], 2, 3)  # [B, nblk, bs, K, ...rest]
    return v.reshape(b, nblk * pool.shape[2], *v.shape[3:])


def paged_scatter_kmajor(
    pool: jax.Array, table: jax.Array, positions: jax.Array,
    values: jax.Array,
) -> jax.Array:
    """``paged_scatter`` for K-major-per-block pools: ``values``
    [B, S, K, ...rest] written at logical ``positions`` [B, S] through
    ``table`` into ``pool`` [NB, K, bs, ...rest]. Same ownership rules
    as ``paged_scatter`` (sink-routed idle writes, private-block-only
    live writes)."""
    bs = pool.shape[2]
    blk = jnp.take_along_axis(table, positions // bs, axis=1)  # [B, S]
    off = positions % bs
    # Advanced indices separated by the K slice broadcast to the front:
    # pool[blk, :, off] is [B, S, K, ...rest], matching ``values``.
    return pool.at[blk, :, off].set(values.astype(pool.dtype))


def block_table_attention_q8(
    x, q, k_new, v_new, pool_kq, pool_ks, pool_vq, pool_vs, table,
    positions, layer, cfg,
):
    """``block_table_attention`` over the INT8 paged pool: fresh k/v are
    quantized into the shared group-wise scheme (``models.quant.
    quant_kv_groups`` — one absmax scale per (position, head), the same
    groups the dense int8 slot pool stores, which is what makes
    int8-paged serving token-exact vs int8-DENSE serving), scattered
    K-major-per-block (payload [NB, K, bs, Dh] + scales [NB, K, bs]),
    and read back through the scale-folded ``_attend_cached`` on the
    gathered logical view. Returns (x, pool_kq, pool_ks, pool_vq,
    pool_vs). The Pallas block-table kernel replaces only this READ on
    the decode path (``int8_paged_decode_attention``); the write
    half is shared."""
    from torchkafka_tpu.models.generate import _attend_cached
    from torchkafka_tpu.models.quant import quant_kv_groups

    kq, ks = quant_kv_groups(k_new)  # [B, S, K, Dh] int8, [B, S, K] f32
    vq, vs = quant_kv_groups(v_new)
    pool_kq = paged_scatter_kmajor(pool_kq, table, positions, kq)
    pool_ks = paged_scatter_kmajor(pool_ks, table, positions, ks)
    pool_vq = paged_scatter_kmajor(pool_vq, table, positions, vq)
    pool_vs = paged_scatter_kmajor(pool_vs, table, positions, vs)
    ck = paged_gather_kmajor(pool_kq, table)  # [B, M', K, Dh] int8
    cv = paged_gather_kmajor(pool_vq, table)
    cks = paged_gather_kmajor(pool_ks, table)  # [B, M', K] f32
    cvs = paged_gather_kmajor(pool_vs, table)
    valid = (
        jnp.arange(ck.shape[1])[None, None, :] <= positions[:, :, None]
    )  # [B, S, M']
    x = _attend_cached(
        x, q, ck, cv, valid, layer, cfg, k_scale=cks, v_scale=cvs
    )
    return x, pool_kq, pool_ks, pool_vq, pool_vs


def block_table_attention(
    x, q, k_new, v_new, pool_k, pool_v, table, positions, layer, cfg,
):
    """One layer of write-then-attend over a paged pool.

    x: [B, S, D]; q/k_new/v_new: [B, S, ·, Dh] (already rope'd);
    pools: [NB, bs, K, Dh]; table: [B, nblk]; positions: [B, S] the
    logical positions of the S queries. Writes k/v at ``positions``
    (write-before-attend, the serving discipline), gathers each slot's
    logical view, masks per query to [0, positions[b, s]] and runs the
    shared ``_attend_cached`` tail — the SAME math as the dense slot
    pool on a gathered operand, so paged serving stays token-comparable
    with the dense path. Returns (x, pool_k, pool_v)."""
    from torchkafka_tpu.models.generate import _attend_cached

    pool_k = paged_scatter(pool_k, table, positions, k_new)
    pool_v = paged_scatter(pool_v, table, positions, v_new)
    ck = paged_gather(pool_k, table)  # [B, M', K, Dh]
    cv = paged_gather(pool_v, table)
    valid = (
        jnp.arange(ck.shape[1])[None, None, :] <= positions[:, :, None]
    )  # [B, S, M'] per-query masks, live-length bounded
    x = _attend_cached(x, q, ck, cv, valid, layer, cfg)
    return x, pool_k, pool_v


# ------------------------------------------------ dense pool (dyn-len)
# Dynamic-length read: the capability XLA's static shapes cannot express.
# Every XLA spelling of decode attention reads the FULL pool and discards
# masked positions; per-slot fills vary in continuous batching, so the
# discarded bytes are real HBM traffic. The kernel takes the per-slot
# watermark as a SCALAR-PREFETCH argument, keeps the pool in HBM
# (memory_space=ANY), and manually DMAs M-blocks with double buffering,
# running the per-block online-softmax (flash) recurrence — the fori_loop
# bound is ceil((pos+1)/mb), so blocks beyond a slot's fill are never
# fetched.


def _kvattn_dynlen_kernel(
    pos_ref, base_ref, order_ref, par_ref, q_ref, *refs, mb: int,
    inv_sqrt_dh: float, rg: int, lg: int,
):
    # ``rg``/``lg`` > 0: the WRITING form, whose refs carry this tick's rows
    # ([1, K, 1, Dh] int8, [1, K, 1] f32), the pool a second time as the
    # aliased outputs, the staging scratch and its semaphores.
    write = rg > 0
    if write:
        (nkq_ref, nks_ref, nvq_ref, nvs_ref, kq_hbm, ks_hbm, vq_hbm, vs_hbm,
         o_ref, kq_out, ks_out, vq_out, vs_out, kt, st, vt, wt, sems,
         gk, gks, gv, gvs, wsems) = refs
    else:
        kq_hbm, ks_hbm, vq_hbm, vs_hbm, o_ref, kt, st, vt, wt, sems = refs
    i = pl.program_id(0)
    # THE GRID WALKS AN ORDER, LIVE SLOTS FIRST: program t serves slot
    # ``order[t]`` (the query, fresh-row and output blocks are indexed
    # through it), and the first ``n_live`` programs are the live slots in
    # slot order. Those are the whole chain of fetches, prefetches and row
    # writes below; a program past them starts no DMA, waits on none,
    # writes no row and stores zeros.
    n_live = base_ref[1]
    live = i < n_live
    b = order_ref[i]
    # Slot b's rows lie at ``base + b`` of the pool operands: 0 for one
    # layer's slab, ``layer * B`` for the stacked pool taken whole.
    base = base_ref[0]

    # The watermark of this program's slot, held inside the pool by the
    # caller (a DMA is unchecked where XLA's gather and scatter clamp or
    # drop), for the read and the write.
    pos = pos_ref[i]
    # ceil((pos + 1) / mb), pos >= 0; none for a slot that is not live.
    n_blocks = jnp.where(live, (pos + mb) // mb, 0)
    q = q_ref[0]  # [K, rep, Dh] compute dtype
    n_kv, rep, dh = q.shape

    # CROSS-PROGRAM PREFETCH. Grid programs run sequentially (semantics
    # "arbitrary") and scratch persists across them, so each live
    # program's FIRST block is DMA'd by its predecessor during that
    # predecessor's last-block compute — without this, every slot begins
    # with a DMA stall.
    # Buffer parity must therefore be GLOBAL over the whole run, not
    # per-program: block (program, j) uses parity
    # (blocks of all earlier programs + j) % 2. The prefix sums come with
    # the watermarks (a program summing them itself walks every earlier
    # program's, on the scalar core, ahead of its first DMA).
    parity0 = par_ref[i]

    def dmas(slot, t, j):  # block j of program t's slot into buffer ``slot``
        row = base + order_ref[t]
        return (
            pltpu.make_async_copy(
                kq_hbm.at[row, :, pl.ds(j * mb, mb), :], kt.at[slot],
                sems.at[slot, 0],
            ),
            pltpu.make_async_copy(
                ks_hbm.at[row, :, pl.ds(j * mb, mb)], st.at[slot],
                sems.at[slot, 1],
            ),
            pltpu.make_async_copy(
                vq_hbm.at[row, :, pl.ds(j * mb, mb), :], vt.at[slot],
                sems.at[slot, 2],
            ),
            pltpu.make_async_copy(
                vs_hbm.at[row, :, pl.ds(j * mb, mb)], wt.at[slot],
                sems.at[slot, 3],
            ),
        )

    def row_writes(r0, c0):  # the staged groups into slot b's pool row
        row = base + b
        return (
            pltpu.make_async_copy(
                gk, kq_out.at[row, :, pl.ds(r0, rg), :], wsems.at[0],
            ),
            pltpu.make_async_copy(
                gks, ks_out.at[row, :, pl.ds(c0, lg)], wsems.at[1],
            ),
            pltpu.make_async_copy(
                gv, vq_out.at[row, :, pl.ds(r0, rg), :], wsems.at[2],
            ),
            pltpu.make_async_copy(
                gvs, vs_out.at[row, :, pl.ds(c0, lg)], wsems.at[3],
            ),
        )

    def write_row(slot):
        """Merge this tick's row into the fetched LAST tile (column
        ``pos`` always lies there) and send its aligned group home.

        The merge is in VMEM because HBM cannot serve it: slot b's block
        0 was fetched by program b - 1, before anything this program
        writes. The group (``rg`` payload rows: one packed int8 tile; ``lg``
        scale lanes) goes out from a staging scratch of its own, so the
        tile buffer is free for the next prefetch at once; the staging
        scratch is waited where it is next filled, one live program later
        (the last live program waits its own at the end)."""
        off = pos - (n_blocks - 1) * mb
        r0 = pl.multiple_of(off // rg * rg, rg)
        c0 = pl.multiple_of(off // lg * lg, lg)

        @pl.when(i > 0)
        def _():
            for d in row_writes(0, 0):
                d.wait()

        hit = jax.lax.broadcasted_iota(jnp.int32, (n_kv, rg, dh), 1) == off - r0
        for tile, stage, new in ((kt, gk, nkq_ref), (vt, gv, nvq_ref)):
            old = tile[slot, :, pl.ds(r0, rg), :]
            grp = jnp.where(hit, new[0], old)  # new[0]: [K, 1, Dh]
            tile[slot, :, pl.ds(r0, rg), :] = grp
            stage[...] = grp
        hit = jax.lax.broadcasted_iota(jnp.int32, (n_kv, lg), 1) == off - c0
        for tile, stage, new in ((st, gks, nks_ref), (wt, gvs, nvs_ref)):
            old = tile[slot, :, pl.ds(c0, lg)]
            grp = jnp.where(hit, new[0], old)  # new[0]: [K, 1]
            tile[slot, :, pl.ds(c0, lg)] = grp
            stage[...] = grp
        for d in row_writes((n_blocks - 1) * mb + r0, (n_blocks - 1) * mb + c0):
            d.start()

    @pl.when((i == 0) & live)
    def _():  # no predecessor: start our own first block
        for d in dmas(parity0 % 2, i, 0):
            d.start()

    m0 = jnp.full((n_kv, rep), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_kv, rep), jnp.float32)
    a0 = jnp.zeros((n_kv, rep, dh), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        slot = (parity0 + j) % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            for d in dmas((parity0 + j + 1) % 2, i, j + 1):
                d.start()

        @pl.when((j + 1 == n_blocks) & (i + 1 < n_live))
        def _():  # prefetch the NEXT LIVE program's first block
            for d in dmas((parity0 + n_blocks) % 2, i + 1, 0):
                d.start()

        for d in dmas(slot, i, j):
            d.wait()
        if write:
            pl.when(j + 1 == n_blocks)(lambda: write_row(slot))
        kk = kt[slot].astype(q.dtype)  # [K, mb, Dh]
        s = jax.lax.dot_general(
            q, kk, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [K, rep, mb]
        s = s * st[slot][:, None, :] * inv_sqrt_dh
        col = jax.lax.broadcasted_iota(jnp.int32, (n_kv, rep, mb), 2) + j * mb
        s = jnp.where(col <= pos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)  # first block: exp(-inf - m) = 0
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        pw = (p * wt[slot][:, None, :]).astype(q.dtype)
        vv = vt[slot].astype(q.dtype)
        acc = acc * alpha[..., None] + jax.lax.dot_general(
            pw, vv, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    # A slot that is not live read nothing: its sum is 0 over 0, and what
    # reaches the residual is zeros.
    l = jnp.where(live, l, 1.0)
    o_ref[0] = (acc / l[..., None]).astype(o_ref.dtype)
    if write:
        @pl.when(i + 1 == n_live)
        def _():
            for d in row_writes(0, 0):
                d.wait()


def _live_first(live: jax.Array | None, b: int):
    """(order [b] int32, n_live): the live slots in slot order, then the
    others. A rank by prefix sums and a one-hot sum, not a sort: the tick
    asks once a layer."""
    slots = jnp.arange(b, dtype=jnp.int32)
    if live is None:
        return slots, jnp.int32(b)
    live = live.astype(bool)
    ahead = jnp.cumsum(live, dtype=jnp.int32)  # live slots up to and with b
    n_live = ahead[-1]
    rank = jnp.where(live, ahead - 1, n_live + slots - ahead)
    order = jnp.sum(
        jnp.where(rank[:, None] == slots[None, :], slots[:, None], 0), axis=0
    )
    return order.astype(jnp.int32), n_live


def dynlen_block(max_len: int) -> int:
    """Largest of (512, 256, 128, 64, 8) dividing the pool length, and a
    512 only where the pool holds four of them — the M-block granularity
    of the dynamic-length read (skipping works at block granularity;
    smaller blocks skip more but issue more DMAs). A pool of 1,024 reads
    by 256: behind a prompt window of 512 a block of 512 is half the pool
    and every slot fetches all of it from its first tick. Read on the
    chip at that pool, a call: 113.9 us by 256, 120.1 by 512, 137.2 by
    128 (PERF.md §5, PR 47); longer pools were not read and keep 512."""
    for mb in (512, 256, 128, 64, 8):
        if max_len % mb == 0 and (mb <= 256 or 4 * mb <= max_len):
            return mb
    return 0  # no tiling → caller must fall back


def int8_decode_attention_dynlen(
    q: jax.Array,
    ck_q: jax.Array,
    ck_s: jax.Array,
    cv_q: jax.Array,
    cv_s: jax.Array,
    pos: jax.Array,
    *,
    layer: jax.Array | int | None = None,
    rows: tuple[jax.Array, jax.Array, jax.Array, jax.Array] | None = None,
    live: jax.Array | None = None,
    block: int | None = None,
    interpret: bool | None = None,
):
    """q [B, 1, H, Dh] against a K-MAJOR int8 cache ck_q/cv_q
    [B, K, M, Dh] with scales [B, K, M] (f32), reading ONLY positions
    [0, pos[b]] per slot (pos: [B] int32 watermarks) → attn
    [B, 1, H, Dh]. HBM traffic scales with the actual fill, not the
    pool size — inexpressible in XLA, where every read is pool-shaped.

    With ``layer`` (a scalar, traced or not) the caches are the STACKED
    pool, [L, B, K, M, Dh] and [L, B, K, M], and the read is layer
    ``layer``'s. The pool stays where it lies: a Pallas operand is opaque
    to XLA, so a ``pool[layer]`` outside the call would materialise the
    slab. The kernel sees the pool with L and B merged (a bitcast) and
    DMAs from row ``layer * B + b``.

    With ``rows`` = (kq, ks, vq, vs), this tick's quantised rows (kq/vq
    [B, K, Dh] int8, ks/vs [B, K] f32), the call is the tick's WRITE as
    well: it puts slot b's row at position ``pos[b]`` of the pool (of
    layer ``layer``), attends over [0, pos[b]] with that row in place,
    and returns (attn, ck_q, ck_s, cv_q, cv_s), the pools aliased to the
    ones passed in — bit for bit what
    ``c.at[layer, b, :, pos[b]].set(row)`` on each, then the read, gives.
    ``pos`` must lie inside the pool (the call clamps it to M - 1: a DMA
    has no bounds check, where a scatter drops what is out of range).

    With ``live`` ([B] bool; all live without it) a slot that is not live
    costs no HBM traffic: nothing of its pool is fetched, its row (with
    ``rows``) is not written, so that its pool stays as it was, and its
    ``attn`` is zeros. A live slot's result and pool row are what they
    are without the mask.

    Exact w.r.t. the scale-folded read restricted to valid positions
    (flash-style online softmax; differential-tested against
    ``_attend_cached`` with ``valid = arange(M) <= pos[:, None]``).
    """
    b, s, h, dh = q.shape
    if s != 1:
        raise ValueError(f"decode attention is one token per slot, got S={s}")
    pool = (ck_q, ck_s.astype(jnp.float32), cv_q, cv_s.astype(jnp.float32))
    shapes = [c.shape for c in pool]
    if layer is None:
        base = jnp.int32(0)
    else:
        if ck_q.ndim != 5 or ck_q.shape[1] != b:
            raise ValueError(
                f"layer= takes the stacked pool [L, {b}, K, M, Dh], got "
                f"{ck_q.shape}"
            )
        base = jnp.asarray(layer, jnp.int32) * b
        pool = tuple(c.reshape(-1, *c.shape[2:]) for c in pool)
    n_kv, m = pool[0].shape[1:3]
    rep = h // n_kv
    mb = block or dynlen_block(m)
    if not mb or m % mb:
        raise ValueError(f"block {mb} must divide pool length {m}")
    if interpret is None:
        interpret = _default_interpret()
    qg = q[:, 0].reshape(b, n_kv, rep, dh)
    # What the programs read from SMEM, in program order: the slot each
    # serves, its watermark (inside the pool) and its first block's buffer.
    order, n_live = _live_first(live, b)
    pos = jnp.clip(pos.astype(jnp.int32), 0, m - 1)[order]
    blocks = jnp.where(jnp.arange(b) < n_live, (pos + mb) // mb, 0)
    parity = (jnp.cumsum(blocks) - blocks) % 2
    # SEQUENTIAL grid ("arbitrary"): the cross-program prefetch scheme
    # relies on program i+1's first block being DMA'd by program i, so
    # the order must be the textual one.
    kw = {} if interpret else tpu_compiler_params(("arbitrary",))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    # Program i serves slot ``order[i]``: every per-slot block goes through
    # the order.
    q_spec = pl.BlockSpec(
        (1, n_kv, rep, dh), lambda i, pos, base, order, par: (order[i], 0, 0, 0)
    )
    in_specs = [q_spec]
    operands = [qg]
    out_specs = q_spec
    out_shape = jax.ShapeDtypeStruct((b, n_kv, rep, dh), q.dtype)
    scratch = [
        pltpu.VMEM((2, n_kv, mb, dh), jnp.int8),   # k tiles
        pltpu.VMEM((2, n_kv, mb), jnp.float32),    # k scales
        pltpu.VMEM((2, n_kv, mb, dh), jnp.int8),   # v tiles
        pltpu.VMEM((2, n_kv, mb), jnp.float32),    # v scales
        pltpu.SemaphoreType.DMA((2, 4)),
    ]
    rg = lg = 0
    if rows is not None:
        kq, ks, vq, vs = rows
        # What goes back to HBM is the aligned group round the row: 32
        # positions of payload (one packed int8 tile: a single position is
        # not a DMA's to address) and 128 lanes of scales, or the whole
        # block where it is smaller than those (interpret-mode sizes).
        rg = 32 if mb % 32 == 0 else mb
        lg = 128 if mb % 128 == 0 else mb
        in_specs += [
            pl.BlockSpec(
                (1, n_kv, 1, dh), lambda i, pos, base, order, par: (order[i], 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, n_kv, 1), lambda i, pos, base, order, par: (order[i], 0, 0)
            ),
        ] * 2
        operands += [
            kq.astype(jnp.int8)[:, :, None, :], ks.astype(jnp.float32)[..., None],
            vq.astype(jnp.int8)[:, :, None, :], vs.astype(jnp.float32)[..., None],
        ]
        out_specs = [q_spec] + [any_spec] * 4
        out_shape = [out_shape] + [
            jax.ShapeDtypeStruct(c.shape, c.dtype) for c in pool
        ]
        scratch += [
            pltpu.VMEM((n_kv, rg, dh), jnp.int8),      # staged k group
            pltpu.VMEM((n_kv, lg), jnp.float32),       # staged k scales
            pltpu.VMEM((n_kv, rg, dh), jnp.int8),      # staged v group
            pltpu.VMEM((n_kv, lg), jnp.float32),       # staged v scales
            pltpu.SemaphoreType.DMA((4,)),
        ]
        # Operand numbers count the four scalar-prefetch arguments: the
        # pool is operands 9..12 and outputs 1..4.
        kw["input_output_aliases"] = {9 + i: 1 + i for i in range(4)}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=in_specs + [any_spec] * 4,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _kvattn_dynlen_kernel, mb=mb,
            inv_sqrt_dh=float(1.0 / np.sqrt(dh)), rg=rg, lg=lg,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="tk_kvattn_dynlen",
        **kw,
    )(pos, jnp.stack([base, n_live]), order, parity.astype(jnp.int32),
      *operands, *pool)
    if rows is None:
        return out.reshape(b, 1, h, dh)
    attn, *pool = out
    return (attn.reshape(b, 1, h, dh),
            *(c.reshape(sh) for c, sh in zip(pool, shapes)))


# The two sharded wrappers below are manual over EVERY mesh axis: compiled
# Mosaic refuses a kernel under a partially manual shard_map region ("Mosaic
# kernels cannot be automatically partitioned"; met on a 2x2 v5e mesh, PR
# 21). Their specs name only ``data`` (slots) and ``tp`` (kv/q heads) — the
# dense slot pool's ``kv_sharding`` axes — and simply do not mention
# ``fsdp``/``ep``/``pp``: weight-only axes across which the kernel's
# operands are replicated, so each of their shards runs the same read.


def int8_decode_attention_dynlen_sharded(
    q: jax.Array,
    ck_q: jax.Array,
    ck_s: jax.Array,
    cv_q: jax.Array,
    cv_s: jax.Array,
    pos: jax.Array,
    mesh,
    *,
    layer: jax.Array | int,
    rows: tuple[jax.Array, jax.Array, jax.Array, jax.Array] | None = None,
    live: jax.Array | None = None,
    block: int | None = None,
    interpret: bool | None = None,
):
    """``int8_decode_attention_dynlen`` of layer ``layer`` of the STACKED
    pool ([L, B, K, M, Dh] / [L, B, K, M]) under a serving mesh.

    A Pallas call is opaque to GSPMD (the ``flash_attention_sharded``
    lesson), but the decode read is (slot, head)-parallel with no
    collectives — each shard attends its own slots' watermarked pool
    over its own kv heads — so ``shard_map`` splits it exactly like the
    XLA read's layouts: q/pos/caches batch over ``data``, kv heads over
    ``tp``. Requirements (the capability probe gates on these): B
    divisible by data, H and K by tp. The pool enters the region 5-D and
    L merges with the SHARD's slots inside it: an unsharded L cannot
    merge with a ``data``-sharded B outside. With ``rows`` each shard
    writes its own slots' and heads' rows, and the pools come back under
    the specs they came in with. ``live`` splits over ``data`` like
    ``pos``: each shard orders its own slots."""
    from jax.sharding import PartitionSpec as P

    bspec = "data" if "data" in mesh.shape else None
    tp = "tp" if "tp" in mesh.shape else None
    qspec = P(bspec, None, tp, None)         # [B, 1, H, Dh]
    cspec = P(None, bspec, tp, None, None)   # [L, B, K, M, Dh] payloads
    sspec = P(None, bspec, tp, None)         # [L, B, K, M] scales
    pool_specs = (cspec, sspec, cspec, sspec)
    if live is None:
        live = jnp.ones(pos.shape, bool)
    in_specs = (qspec, *pool_specs, P(bspec), P(bspec), P())
    args = (q, ck_q, ck_s, cv_q, cv_s, pos, live,
            jnp.asarray(layer, jnp.int32))
    out_specs = qspec
    if rows is not None:
        rspec = P(bspec, tp, None)           # [B, K, Dh] fresh payloads
        rsspec = P(bspec, tp)                # [B, K] fresh scales
        in_specs += (rspec, rsspec, rspec, rsspec)
        args += tuple(rows)
        out_specs = (qspec, *pool_specs)

    def read(q, ck_q, ck_s, cv_q, cv_s, pos, live, layer, *rows):
        return int8_decode_attention_dynlen(
            q, ck_q, ck_s, cv_q, cv_s, pos, layer=layer, rows=rows or None,
            live=live, block=block, interpret=interpret,
        )

    fn = jax.shard_map(
        read, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return fn(*args)


def int8_paged_decode_attention_sharded(
    q: jax.Array,
    pool_kq: jax.Array,
    pool_ks: jax.Array,
    pool_vq: jax.Array,
    pool_vs: jax.Array,
    table: jax.Array,
    pos: jax.Array,
    mesh,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """``int8_paged_decode_attention`` under a serving mesh.

    Sharded over ``tp`` ONLY — kv/q heads split per shard, the block
    pools per-block over tp (``generate.paged_pool_kmajor_sharding``'s
    per-layer slice), and slots/tables/watermarks REPLICATED across
    every other axis. That matches the paged serving program's
    invariant (serve.py ``pin_paged``): the data axis stays out of the
    paged path entirely — block pools are shared storage with no slot
    axis to split, and re-introducing data sharding at this kernel's
    boundary re-triggers the jax-0.4.x partitioned-concat miscompile
    the rest of the program avoids. The region is still manual over
    every mesh axis (note above): the specs name only ``tp``, so each
    data shard runs the same read on its replica. Each tp shard DMAs
    only live blocks for its own heads; no collectives."""
    from jax.sharding import PartitionSpec as P

    tp = "tp" if "tp" in mesh.shape else None
    if tp is None:
        # No tp axis: nothing to split — the plain kernel call inside
        # the (data-replicated) paged program is already correct.
        return int8_paged_decode_attention(
            q, pool_kq, pool_ks, pool_vq, pool_vs, table, pos,
            interpret=interpret,
        )
    qspec = P(None, None, tp, None)    # [B, 1, H, Dh]
    pspec = P(None, tp, None, None)    # [NB, K, bs, Dh] payload pools
    sspec = P(None, tp, None)          # [NB, K, bs] scale pools
    fn = jax.shard_map(
        functools.partial(int8_paged_decode_attention, interpret=interpret),
        mesh=mesh,
        in_specs=(qspec, pspec, sspec, pspec, sspec, P(None, None),
                  P(None)),
        out_specs=qspec,
        check_vma=False,
    )
    return fn(q, pool_kq, pool_ks, pool_vq, pool_vs, table, pos)


# ------------------------------------------ paged pool (block-table kernel)
# Block-table read: the dyn-len kernel's watermark-DMA structure extended
# to read THROUGH per-slot block tables (the int8 PAGED pool). Both the pool
# watermarks (pos) and the block tables arrive by scalar prefetch; the
# per-slot block loop DMAs exactly ceil((pos+1)/bs) physical blocks —
# ``pool_kq.at[table[b, j]]`` — so HBM traffic scales with each slot's
# live length AND the host-side indirection (which physical block backs
# which logical position) never materialises a gathered per-slot view
# the way the XLA spelling must (paged_gather copies the view every
# layer, every tick). The pool is K-MAJOR-PER-BLOCK ([NB, K, bs, Dh] /
# [NB, K, bs]) so each block tile is exactly the dyn-len kernel's [K, mb,
# Dh] shape: one batched dot over (slot, head), no per-head relayout.
# Cross-program first-block prefetch and global buffer parity are the
# dyn-len kernel's — parity is the prefix-sum of per-slot block counts,
# computable by any program from the prefetched watermarks.


def _kvattn_paged_kernel(
    pos_ref, table_ref, q_ref, kq_hbm, ks_hbm, vq_hbm, vs_hbm, o_ref,
    kt, st, vt, wt, sems, *, bs: int, inv_sqrt_dh: float,
):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    pos = pos_ref[b]
    n_blocks = (pos + bs) // bs  # ceil((pos + 1) / bs), pos >= 0
    q = q_ref[0]  # [K, rep, Dh] compute dtype
    n_kv, rep, dh = q.shape

    def blocks_of(t):
        return (pos_ref[t] + bs) // bs

    parity0 = jax.lax.fori_loop(
        0, b, lambda t, acc: acc + blocks_of(t), jnp.int32(0)
    ) % 2

    def dmas(slot, row, j):
        blk = table_ref[row, j]  # physical block id — the indirection
        return (
            pltpu.make_async_copy(
                kq_hbm.at[blk], kt.at[slot], sems.at[slot, 0],
            ),
            pltpu.make_async_copy(
                ks_hbm.at[blk], st.at[slot], sems.at[slot, 1],
            ),
            pltpu.make_async_copy(
                vq_hbm.at[blk], vt.at[slot], sems.at[slot, 2],
            ),
            pltpu.make_async_copy(
                vs_hbm.at[blk], wt.at[slot], sems.at[slot, 3],
            ),
        )

    @pl.when(b == 0)
    def _():  # no predecessor: start our own first block
        for d in dmas(parity0 % 2, b, 0):
            d.start()

    m0 = jnp.full((n_kv, rep), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_kv, rep), jnp.float32)
    a0 = jnp.zeros((n_kv, rep, dh), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        slot = (parity0 + j) % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            for d in dmas((parity0 + j + 1) % 2, b, j + 1):
                d.start()

        @pl.when((j + 1 == n_blocks) & (b + 1 < nb))
        def _():  # prefetch the NEXT program's first block
            for d in dmas((parity0 + n_blocks) % 2, b + 1, 0):
                d.start()

        for d in dmas(slot, b, j):
            d.wait()
        kk = kt[slot].astype(q.dtype)  # [K, bs, Dh]
        s = jax.lax.dot_general(
            q, kk, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [K, rep, bs]
        s = s * st[slot][:, None, :] * inv_sqrt_dh
        col = jax.lax.broadcasted_iota(jnp.int32, (n_kv, rep, bs), 2) + j * bs
        s = jnp.where(col <= pos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)  # first block: exp(-inf - m) = 0
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        pw = (p * wt[slot][:, None, :]).astype(q.dtype)
        vv = vt[slot].astype(q.dtype)
        acc = acc * alpha[..., None] + jax.lax.dot_general(
            pw, vv, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    o_ref[0] = (acc / l[..., None]).astype(o_ref.dtype)


def paged_kernel_applicable(head_dim: int, block_size: int) -> bool:
    """Compiled-Mosaic tiling constraints for the block-table read:
    lane-aligned head_dim (Dh is the lane dim of the payload tiles) AND
    lane-aligned block size — the scale pools are [NB, K, bs], so bs is
    the lane dim of every scale tile the kernel DMAs. Read off the v5e
    (PR 21): bs = 256 and 384 compile; 264, 272, 288 and 320 are refused
    ("Slice shape along dimension 2 must be aligned to tiling (128)").
    Interpret mode accepts anything; tests force it. Callers should
    additionally require a reasonable block size (>= 256) on TPU —
    skipping works at block granularity, but tiny blocks drown in
    per-block DMA/recurrence overhead (the dynlen_block lesson)."""
    return head_dim % 128 == 0 and block_size % 128 == 0


def int8_paged_decode_attention(
    q: jax.Array,
    pool_kq: jax.Array,
    pool_ks: jax.Array,
    pool_vq: jax.Array,
    pool_vs: jax.Array,
    table: jax.Array,
    pos: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """q [B, 1, H, Dh] against the int8 PAGED pool — K-major-per-block
    payloads pool_kq/pool_vq [NB, K, bs, Dh] with scales pool_ks/pool_vs
    [NB, K, bs] (f32) — read through per-slot block tables ``table``
    [B, nblk] (int32) at per-slot watermarks ``pos`` [B] (positions
    [0, pos[b]] readable) → attn [B, 1, H, Dh].

    Only ceil((pos+1)/bs) physical blocks are DMA'd per slot, each by
    table indirection, so HBM traffic scales with live tokens and no
    gathered per-slot view is ever materialised (the XLA block-table
    read copies one per layer per tick). Exact w.r.t. the scale-folded
    gathered read restricted to valid positions (flash-style online
    softmax; differential-tested against ``paged_gather_kmajor`` +
    ``_attend_cached``)."""
    b, s, h, dh = q.shape
    if s != 1:
        raise ValueError(f"decode attention is one query per slot, got S={s}")
    n_kv, bs = pool_kq.shape[1], pool_kq.shape[2]
    rep = h // n_kv
    if interpret is None:
        interpret = _default_interpret()
    qg = q[:, 0].reshape(b, n_kv, rep, dh)
    # SEQUENTIAL grid ("arbitrary"): cross-program prefetch, as the
    # dyn-len kernel.
    kw = {} if interpret else tpu_compiler_params(("arbitrary",))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # watermarks AND block tables
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, n_kv, rep, dh), lambda i, pos, tbl: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, n_kv, rep, dh), lambda i, pos, tbl: (i, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, n_kv, bs, dh), jnp.int8),   # k tiles
            pltpu.VMEM((2, n_kv, bs), jnp.float32),    # k scales
            pltpu.VMEM((2, n_kv, bs, dh), jnp.int8),   # v tiles
            pltpu.VMEM((2, n_kv, bs), jnp.float32),    # v scales
            pltpu.SemaphoreType.DMA((2, 4)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kvattn_paged_kernel, bs=bs,
            inv_sqrt_dh=float(1.0 / np.sqrt(dh)),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, rep, dh), q.dtype),
        interpret=interpret,
        name="tk_kvattn_paged",
        **kw,
    )(pos.astype(jnp.int32), table.astype(jnp.int32), qg, pool_kq,
      pool_ks.astype(jnp.float32), pool_vq, pool_vs.astype(jnp.float32))
    return out.reshape(b, 1, h, dh)
