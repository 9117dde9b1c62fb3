"""Learned sparse attention: an indexer scores every cached position, the
top ``index_topk`` are selected, and attention reads those rows alone.

The layer (``TransformerConfig.index_heads`` > 0; DeepSeek-Sparse-
Attention's indexer beside grouped-query attention). From the layer's
normed input ``h``: index queries ``qI`` (``index_heads`` heads of
``index_head_dim``), ONE index key ``kI`` a position and a weight a head
``w``; ``qI`` and ``kI`` roped. The score of an earlier position ``s <= t``
is ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` in float32, the
selected set ``S_t`` the ``min(topk, t + 1)`` positions of largest score,
ties to the lower position, and the attention's softmax runs over ``S_t``
alone. Positive constants on the score change no set and are left out.

What a slot keeps of a position, a layer (``kvcache/slot_pool.py::
IndexedPool``): its K row beside its V row in ONE row of 32-bit words
(``pack_rows``: in bfloat16 two numbers a word, a head's channel ``c``
beside its channel ``c + Dh/2``), laid as a tile of its own, ``[L, B, M,
W / 128, 128]``: the position is no tiled axis, so a row is one DMA of a
whole tile at any position (Mosaic slices a tiled axis by whole tiles
alone: a row of ``[.., M, W]`` cannot be fetched); and its index key,
stored TRANSPOSED, ``[L, B, Di, M]``,
positions along the lanes, so that a slot's keys stream as dense tiles
and the scores come out as a row.

A decode tick, a layer (the program, ``IndexedPool.step``):

- ``index_scores``: the Pallas kernel ``tk_dsa_index`` streams the slot's
  VALID index keys by blocks of positions (a block past its length is
  not fetched; a slot that is not live fetches nothing: its programs
  name the block the program before them read last) and scores them
  against the token's index queries -> ``[B, M]`` float32, ``-inf`` past
  the length;
- the selection is ``lax.top_k`` over that row (exact, ties to the lower
  position);
- ``attend_selected``: the Pallas kernel ``tk_dsa_attend`` fetches the
  selected rows by index, one DMA a row out of the pool where it lies,
  ``ATTEND_CHUNK`` rows in flight while the chunk before them is
  multiplied, online softmax over the chunks; nothing else of the slot is
  read. A row costs the instruction stream its START and nothing else:
  the list comes in clipped, its entries past the slot's count naming the
  last counted row, so a start reads one index out of SMEM (no clamp, no
  compare), and a chunk is waited for ONCE, by a descriptor whose
  destination is the chunk's whole buffer (a DMA semaphore counts bytes:
  the one wait takes what the chunk's starts gave), and the start is
  compiled without Mosaic's own bounds check of its two addresses, which
  cost more than the start itself: the wrapper has clipped the list and
  the layer and holds the query batch to the pool's slots, everything
  else is a loop counter. The queries are laid block-diagonal (head ``h``
  in its kv head's columns, ``generate._read_merged``'s form), so a
  chunk's scores and values are plain products over the chunk's rows
  flattened to ``[chunk, W]``, the K words then the V words. (The
  flattening re-lays a row's sublanes as lane groups; it read 1-2% of the
  kernel on the chip, PERF.md PR 50, and is not worth a second way of
  reading the buffer.)

An admission (``sparse_prefill_attention``): the index scores of a block
of queries against every key, the threshold of each query's row by
bisection over the scores' bits (32 counts; exact, no sort), the mask
``causal and selected`` as int8, and ``ops/flash.py::
flash_forward_selected`` (``tk_flash_fwd_sel``) under it. The scores of a
block of ``SELECT_BLOCK`` queries exist at a time, never the matrix.
Queries before position ``topk`` select every earlier position and take
no scores at all.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchkafka_tpu.ops.flash import (
    _default_interpret,
    flash_forward_selected,
    tpu_compiler_params,
)

__all__ = [
    "ATTEND_CHUNK", "attend_selected", "attend_selected_reference",
    "index_block", "index_scores", "index_scores_dense", "kth_largest_key",
    "order_key", "pack_rows", "row_spec", "row_tile", "select_mask",
    "sparse_prefill_attention", "unpack_rows",
]

# Rows of a slot the selected read has in flight at a time (a chunk is
# multiplied while the next one's rows arrive), the most rows whose DMAs
# one trip of its fetch loop starts (unrolled: 32 read 566 us a call at the
# cell's sizes, 8 629, 64 558; PERF.md, PR 50), and the queries whose index
# scores an admission holds at a time (the configuration's 512).
ATTEND_CHUNK = 256
_ROWS_A_TRIP = 32
SELECT_BLOCK = 512
_INDEX_BLOCK_MAX = 2048


# ---------------------------------------------------------------- the rows


def _packed(dtype) -> bool:
    return jnp.dtype(dtype).itemsize == 2


def pack_rows(k: jax.Array, v: jax.Array) -> jax.Array:
    """k, v [..., K, Dh] in the compute dtype -> a position's row of
    32-bit words [..., W]: the K part then the V part. A 16-bit dtype puts
    two numbers a word, channel ``c`` of a head in the low half beside
    channel ``c + Dh/2`` in the high half (int32, ``W = K * Dh``); a
    32-bit dtype is kept as it is (``W = 2 * K * Dh``)."""
    if not _packed(k.dtype):
        return jnp.concatenate(
            [a.reshape(*a.shape[:-2], -1) for a in (k, v)], axis=-1
        )

    def words(a):
        bits = lax.bitcast_convert_type(a, jnp.uint16).astype(jnp.uint32)
        lo, hi = jnp.split(bits, 2, axis=-1)
        w = lax.bitcast_convert_type(lo | (hi << 16), jnp.int32)
        return w.reshape(*w.shape[:-2], -1)

    return jnp.concatenate([words(k), words(v)], axis=-1)


def row_tile(width: int) -> tuple[int, int]:
    """How the pool lays a row of ``width`` words: whole lanes of 128
    where they divide it, else one short row (the tests' sizes)."""
    return (width // 128, 128) if width % 128 == 0 else (1, width)


def row_spec(n_kv: int, head_dim: int, dtype) -> tuple[tuple[int, int], object]:
    """(the tile, the dtype) of a position's packed row in the pool, for K
    and V of ``n_kv`` heads of ``head_dim`` in the compute ``dtype``."""
    if _packed(dtype):
        return row_tile(n_kv * head_dim), jnp.int32
    return row_tile(2 * n_kv * head_dim), dtype


def unpack_rows(rows: jax.Array, n_kv: int, head_dim: int, dtype):
    """``pack_rows``' inverse: rows [..., W] -> (k, v) [..., K, Dh]."""
    kw, vw = jnp.split(rows, 2, axis=-1)
    if not _packed(dtype):
        return tuple(
            a.reshape(*a.shape[:-1], n_kv, head_dim).astype(dtype)
            for a in (kw, vw)
        )

    def numbers(w):
        w = lax.bitcast_convert_type(w, jnp.uint32)
        w = w.reshape(*w.shape[:-1], n_kv, head_dim // 2)
        both = jnp.concatenate([w & 0xFFFF, w >> 16], axis=-1)
        return lax.bitcast_convert_type(both.astype(jnp.uint16), dtype)

    return numbers(kw), numbers(vw)


def _expand(words, dtype):
    """A block of row words [n, half] -> its numbers as the parts the
    products run over: ``[lo, hi]`` of a packed row (each [n, half] in
    ``dtype``, exact: a bfloat16 is the high half of its float32), the
    words themselves otherwise."""
    if not _packed(dtype):
        return [words]
    lo = lax.bitcast_convert_type(words << 16, jnp.float32)
    hi = lax.bitcast_convert_type(words & jnp.int32(-65536), jnp.float32)
    return [lo.astype(dtype), hi.astype(dtype)]


def _own(n_heads: int, n_kv: int):
    """[H, K] bool: head h reads kv head h // (H / K)."""
    return (
        jnp.arange(n_heads)[:, None] // (n_heads // n_kv)
        == jnp.arange(n_kv)[None, :]
    )


def _wide_queries(q, n_kv: int):
    """q [B, H, Dh] -> block-diagonal [B, H, parts * half] in the rows'
    order of columns: head h in its kv head's columns, zero elsewhere."""
    b, h, dh = q.shape
    own = _own(h, n_kv)[None, :, :, None]
    halves = jnp.split(q, 2, axis=-1) if _packed(q.dtype) else [q]
    return jnp.concatenate([
        jnp.where(own, part[:, :, None, :], 0).reshape(b, h, -1)
        for part in halves
    ], axis=-1)


def _narrow_values(wide, n_kv: int, dtype):
    """The read's [B, H, parts * half] float32 -> [B, H, Dh]: each head
    keeps its kv head's columns."""
    b, h, _ = wide.shape
    own = _own(h, n_kv)[None, :, :, None]
    parts = jnp.split(wide, 2, axis=-1) if _packed(dtype) else [wide]
    return jnp.concatenate([
        jnp.sum(jnp.where(own, p.reshape(b, h, n_kv, -1), 0.0), axis=2)
        for p in parts
    ], axis=-1).astype(dtype)


# ------------------------------------------------------ decode: the scores


def index_block(max_len: int) -> int:
    """Positions a grid step of ``tk_dsa_index`` fetches: the largest
    divisor of the pool's length that is a multiple of 128 and at most
    ``_INDEX_BLOCK_MAX``; the whole length where there is none (the
    interpreter's small pools)."""
    for blk in range(min(_INDEX_BLOCK_MAX, max_len) // 128 * 128, 0, -128):
        if max_len % blk == 0:
            return blk
    return max_len


def _index_kernel(layer_ref, meta_ref, q_ref, w_ref, k_ref, o_ref, *, blk):
    b, j = pl.program_id(0), pl.program_id(1)
    n = meta_ref[2, b]
    reached = j * blk < n

    @pl.when(reached)
    def _score():
        s = jnp.dot(
            q_ref[...], k_ref[...], preferred_element_type=jnp.float32
        )  # [Hi, blk]
        tot = jnp.sum(
            jnp.maximum(s, 0.0) * w_ref[...], axis=0, keepdims=True
        )
        at = j * blk + lax.broadcasted_iota(jnp.int32, tot.shape, 1)
        o_ref[...] = jnp.where(at < n, tot, -jnp.inf)

    @pl.when(jnp.logical_not(reached))
    def _past():
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)


def index_scores(qi, w, pool, layer, n, *, interpret: bool | None = None):
    """The Pallas kernel ``tk_dsa_index``. qi [B, Hi, Di] (compute dtype),
    w [B, Hi] float32, pool [L, B, Di, M] the stacked index keys taken
    WHOLE with ``layer`` (a slice outside an opaque call would be copied
    out), n [B] int32 the positions a slot holds (0: the slot is not live)
    -> scores [B, M] float32, ``-inf`` from ``n`` on, a ``-0.0`` as 0.0
    (``lax.top_k`` orders the two). Only the blocks under ``n`` are
    fetched."""
    if interpret is None:
        interpret = _default_interpret()
    b, hi, di = qi.shape
    m = pool.shape[-1]
    blk = index_block(m)
    n = jnp.clip(n.astype(jnp.int32), 0, m)
    # A program names a block of ITS slot under its length, else the block
    # the program before it read last: consecutive programs that name one
    # block fetch it once. (Leading slots that are not live name the first
    # live slot's first block, which that slot then finds fetched.)
    live = n > 0
    slot = jnp.arange(b, dtype=jnp.int32)
    last = jnp.where(live, (n - 1) // blk, 0)
    seen = lax.cummax(jnp.where(live, slot, -1), axis=0)
    first_live = jnp.argmax(live).astype(jnp.int32)
    src = jnp.where(seen >= 0, seen, first_live)
    src_last = jnp.where(seen >= 0, last[jnp.maximum(seen, 0)], 0)
    meta = jnp.stack([src, jnp.where(live, last, src_last), n])
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def key_block(s, j, layer_ref, meta_ref):
        return (
            layer_ref[0], meta_ref[0, s], 0, jnp.minimum(j, meta_ref[1, s])
        )

    out = pl.pallas_call(
        functools.partial(_index_kernel, blk=blk),
        out_shape=jax.ShapeDtypeStruct((b, 1, m), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, m // blk),
            in_specs=[
                pl.BlockSpec((None, hi, di), lambda s, j, *_: (s, 0, 0)),
                pl.BlockSpec((None, hi, 1), lambda s, j, *_: (s, 0, 0)),
                pl.BlockSpec((None, None, di, blk), key_block),
            ],
            out_specs=pl.BlockSpec((None, 1, blk), lambda s, j, *_: (s, 0, j)),
        ),
        interpret=interpret,
        name="tk_dsa_index",
        **({} if interpret else tpu_compiler_params(("arbitrary", "arbitrary"))),
    )(layer, meta, qi, w.astype(jnp.float32)[..., None], pool)
    return jnp.where(out == 0, 0.0, out)[:, 0]


def index_scores_dense(qi, ki, w):
    """The scores in ``jax.numpy``: qi [B, S, Hi, Di], ki [B, T, Di], w
    [B, S, Hi] -> [B, S, T] float32 (the admission's blocks; the tests'
    form of the kernel)."""
    s = jnp.einsum("bshd,btd->bsht", qi, ki, preferred_element_type=jnp.float32)
    return jnp.einsum(
        "bsht,bsh->bst", jnp.maximum(s, 0.0), w.astype(jnp.float32)
    )


# ------------------------------------------------- decode: the selected read


def _attend_kernel(meta_ref, idx_ref, q_ref, pool_ref, o_ref, buf, sem,
                   m_ref, l_ref, acc_ref, *, chunk, scale, dtype):
    b = pl.program_id(0)
    n = meta_ref[1 + b]
    n_chunks = (n + chunk - 1) // chunk
    width = buf.shape[-2] * buf.shape[-1]
    half = width // 2
    slab = pool_ref.at[meta_ref[0], b]  # the slot's rows of this layer
    m_ref[...] = jnp.full(m_ref.shape, -1e30, m_ref.dtype)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # (Mosaic's loops unroll wholly or not at all: ``group`` rows a trip,
    # the largest power of two up to ``_ROWS_A_TRIP`` that divides a chunk,
    # as an inner loop unrolled whole: the same code as a Python loop over
    # the rows, traced once and not ``group`` times.)
    group = math.gcd(chunk, _ROWS_A_TRIP)

    def fetch(c, slot):
        # A row is its index and nothing else: ``attend_selected`` has
        # clipped the list and written the last counted entry over the
        # entries past ``n`` (a chunk's rows are fetched whole; the softmax
        # masks them). The trip's first index and its destinations are
        # taken once, so that a row adds a constant to each.
        def trip(g, _):
            at = pl.multiple_of(g * group, group)
            first = c * chunk + at
            dst = buf.at[slot, pl.ds(at, group)]

            def row(i, _):
                pltpu.make_async_copy(
                    slab.at[idx_ref[0, first + i]], dst.at[i], sem.at[slot]
                ).start()
                return _

            return lax.fori_loop(0, group, row, None, unroll=True)

        lax.fori_loop(0, chunk // group, trip, None)

    def wait(slot):
        # ONE wait for the chunk: a DMA semaphore counts bytes, and this
        # descriptor's destination is the chunk's bytes, what its ``chunk``
        # starts gave between them (only the destination's size is read).
        pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()

    @pl.when(n_chunks > 0)
    def _first():
        fetch(0, 0)

    def step(c, _):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _next():
            fetch(c + 1, 1 - slot)

        wait(slot)
        rows = buf[slot].reshape(chunk, width)  # words
        q = q_ref[...]  # [H, parts * half]
        ks = _expand(rows[:, :half], dtype)
        vs = _expand(rows[:, half:], dtype)
        s = sum(
            lax.dot_general(
                q[:, p * half:(p + 1) * half], kp, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) for p, kp in enumerate(ks)
        ) * scale  # [H, chunk]
        at = c * chunk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = at < n
        s = jnp.where(keep, s, -1e30)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.concatenate([
            jnp.dot(p.astype(dtype), vp, preferred_element_type=jnp.float32)
            for vp in vs
        ], axis=-1)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[:, :1] = m_new
        return _

    lax.fori_loop(0, n_chunks, step, None)
    o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)


def attend_selected(q, pool, layer, idx, n, *, n_kv: int, scale: float,
                    interpret: bool | None = None):
    """The Pallas kernel ``tk_dsa_attend``. q [B, H, Dh] (compute dtype),
    pool [L, B, M, W / 128, 128] the stacked rows (``pack_rows`` laid by
    ``row_tile``) taken whole with ``layer``, idx [B, k] int32 the selected positions, n [B] int32 how
    many of them count (the leading ones; 0: the slot is not live) ->
    attention [B, H, Dh] over those rows alone, zeros where n is 0. The
    kernel fetches ``ceil(n / chunk) * chunk`` rows of a slot, each by its
    own DMA, a chunk behind ONE wait, and nothing else of the pool; an
    entry past ``n`` is never fetched, whatever it holds."""
    if interpret is None:
        interpret = _default_interpret()
    b, h, dh = q.shape
    k = idx.shape[1]
    m = pool.shape[2]
    dtype = q.dtype
    chunk = min(ATTEND_CHUNK, k)
    # Every address the kernel forms is in range by what is done HERE (a
    # program's slot is under the pool's by the shapes held below; the
    # list and the layer are clipped where XLA's gather would clamp; the
    # buffer's half and the row of a chunk are loop counters), so the
    # kernel is compiled without Mosaic's own check of every DMA's two
    # addresses, which was 13 of a start's 18 instruction bundles. The
    # entries past ``n`` name the slot's last counted row, so that the row
    # loop reads an index and neither clamps nor compares (a slot of none
    # fetches no chunk: what its entries hold is never read).
    assert pool.ndim == 5 and pool.shape[1] >= b, (pool.shape, q.shape)
    assert idx.shape == (b, k) and n.shape == (b,), (idx.shape, n.shape)
    n = jnp.clip(n.astype(jnp.int32), 0, k)
    idx = jnp.clip(idx.astype(jnp.int32), 0, m - 1)
    last = jnp.take_along_axis(idx, jnp.maximum(n - 1, 0)[:, None], axis=1)
    idx = jnp.where(jnp.arange(k)[None, :] < n[:, None], idx, last)
    layer = jnp.clip(jnp.asarray(layer, jnp.int32), 0, pool.shape[0] - 1)
    wide = _wide_queries(q, n_kv)
    meta = jnp.concatenate([layer.reshape(1), n])
    vmem = pltpu.VMEM
    out = pl.pallas_call(
        functools.partial(
            _attend_kernel, chunk=chunk, scale=scale, dtype=dtype
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, wide.shape[-1]), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, 1, k), lambda s, *_: (s, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((None, h, wide.shape[-1]), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (None, h, wide.shape[-1]), lambda s, *_: (s, 0, 0)
            ),
            scratch_shapes=[
                vmem((2, chunk, *pool.shape[3:]), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                vmem((h, 128), jnp.float32), vmem((h, 128), jnp.float32),
                vmem((h, wide.shape[-1]), jnp.float32),
            ],
        ),
        interpret=interpret,
        name="tk_dsa_attend",
        **({} if interpret else tpu_compiler_params(
            ("arbitrary",), disable_bounds_checks=True
        )),
    )(meta, idx[:, None, :], wide, pool)
    return _narrow_values(out, n_kv, dtype)


def attend_selected_reference(q, pool, layer, idx, n, *, n_kv: int,
                              scale: float):
    """``attend_selected`` in ``jax.numpy`` (XLA's gather of the selected
    rows, a dense softmax over them): the tests' form, and the form the
    kernel was read against on the chip."""
    b, h, dh = q.shape
    rows = jnp.take_along_axis(pool[layer], idx[:, :, None, None], axis=1)
    k, v = unpack_rows(
        rows.reshape(*rows.shape[:2], -1), n_kv, dh, q.dtype
    )  # [B, k, K, Dh]
    rep = h // n_kv
    qg = q.reshape(b, n_kv, rep, dh)
    s = jnp.einsum(
        "bgrd,bkgd->bgrk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    keep = (jnp.arange(idx.shape[1])[None, :] < n[:, None])[:, None, None, :]
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    p = jnp.where(keep, p, 0.0)
    out = jnp.einsum(
        "bgrk,bkgd->bgrd", p.astype(q.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, h, dh).astype(q.dtype)


# ------------------------------------------------ admission: the selection


def order_key(scores: jax.Array) -> jax.Array:
    """float32 -> uint32 that orders as the floats do (a real score is
    never 0 there, which marks a position that does not count)."""
    scores = scores.astype(jnp.float32)
    scores = jnp.where(scores == 0, 0.0, scores)  # -0.0 is 0.0
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    return jnp.where(
        bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000)
    )


def kth_largest_key(keys: jax.Array, k: int) -> jax.Array:
    """keys [..., T] uint32 -> [...] the largest x with at least ``k``
    keys >= x (the k-th largest key; 0 where fewer than k are non-zero),
    by bisection over the 32 bits: 32 counts, no sort."""
    def bit(i, x):
        cand = x | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(
            keys >= cand[..., None], axis=-1, dtype=jnp.int32
        ) >= k
        return jnp.where(enough, cand, x)

    return lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32)
    )


def _select_block(scores, q_pos, topk: int):
    """scores [B, R, T] float32 of the queries at ``q_pos`` [R] -> int8
    [B, R, T]: causal and among the row's ``topk`` largest causal scores,
    ties to the lower position."""
    t = scores.shape[-1]
    causal = jnp.arange(t)[None, :] <= q_pos[:, None]  # [R, T]
    keys = jnp.where(causal[None], order_key(scores), jnp.uint32(0))
    thr = kth_largest_key(keys, topk)[..., None]
    above = keys > thr
    level = keys == thr
    need = topk - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)

    def split_ties(_):
        # More keys at the threshold than places left: the lowest
        # positions among them.
        return level & (jnp.cumsum(level, axis=-1, dtype=jnp.int32) <= need)

    level = lax.cond(
        jnp.any(jnp.sum(level, axis=-1, keepdims=True, dtype=jnp.int32) > need),
        split_ties, lambda _: level, None,
    )
    return ((above | level) & causal[None]).astype(jnp.int8)


def select_mask(qi, ki, w, topk: int, block: int = SELECT_BLOCK):
    """The admission's selection: qi [B, S, Hi, Di], ki [B, S, Di], w [B,
    S, Hi] -> int8 [B, S, S], 1 where query t attends to position s. A
    block of ``block`` queries is scored at a time; the blocks that end
    under ``topk`` select every earlier position and are scored not at
    all."""
    b, s = qi.shape[:2]
    # (the largest power-of-two cut of ``block`` that divides S, from 64)
    block = next(
        (blk for blk in (block, block // 2, block // 4, block // 8)
         if blk >= 64 and s % blk == 0), s,
    )
    n_blocks = s // block
    easy = min(topk // block, n_blocks)  # blocks whose last query < topk
    pos = jnp.arange(s)
    parts = []
    if easy:
        rows = pos[: easy * block]
        parts.append(jnp.broadcast_to(
            (pos[None, :] <= rows[:, None]).astype(jnp.int8)[None],
            (b, easy * block, s),
        ))
    if easy < n_blocks:
        def one(i):
            at = i * block
            cut = lambda a: lax.dynamic_slice_in_dim(a, at, block, axis=1)  # noqa: E731
            return _select_block(
                index_scores_dense(cut(qi), ki, cut(w)),
                at + jnp.arange(block), topk,
            )

        hard = lax.map(one, jnp.arange(easy, n_blocks))  # [n, B, block, S]
        parts.append(hard.swapaxes(0, 1).reshape(b, -1, s))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def sparse_prefill_attention(q, k, v, qi, ki, w, *, topk: int, scale: float,
                             use_kernel: bool, interpret: bool | None = None):
    """The admission's attention under ``causal and selected``. q [B, S,
    H, Dh], k, v [B, S, K, Dh], the indexer's qi, ki, w as ``select_mask``
    takes them -> [B, S, H, Dh]. ``use_kernel``: the flash forward
    ``tk_flash_fwd_sel`` where S tiles; else (and where it does not) a
    dense masked softmax."""
    b, s, h, dh = q.shape
    if topk >= s:  # every query selects its whole past
        mask = jnp.broadcast_to(
            jnp.tril(jnp.ones((s, s), jnp.int8))[None], (b, s, s)
        )
    else:
        mask = select_mask(qi, ki, w, topk)
    if use_kernel:
        out = flash_forward_selected(
            q, k, v, mask, scale=scale, interpret=interpret
        )
        if out is not None:
            return out
    rep = h // k.shape[2]
    qg = q.reshape(b, s, k.shape[2], rep, dh)
    scores = jnp.einsum(
        "bsgrd,btgd->bgrst", qg, k, preferred_element_type=jnp.float32
    ) * scale
    keep = (mask != 0)[:, None, None]
    p = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
    out = jnp.einsum(
        "bgrst,btgd->bsgrd", p.astype(q.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, s, h, dh).astype(q.dtype)
