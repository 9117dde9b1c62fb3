"""Flash attention: Pallas TPU kernels for the ingest consumers' hot op.

Net-new vs the reference (no tensor ops in its tree, SURVEY.md §2). The XLA
``mha`` in attention.py materialises the [B,H,Sq,Sk] score tensor in HBM;
these kernels never do — scores live in VMEM one (block_q × block_k) tile at
a time, combined with the online-softmax recurrence (running max m, running
normaliser l), so attention memory is O(S·D) instead of O(S²) and the
matmuls stay hot in the MXU.

Layout: [B, S, H, D] api (matching ``mha``), computed as [B·H, S, D] with a
(batch·head, q-block, k-block) grid; the innermost grid axis is sequential on
TPU, and the f32 accumulators persist in VMEM scratch across its iterations.
Causal blocks strictly above the diagonal are skipped via ``pl.when`` (half
the FLOPs of the naive mask for long sequences).

Training: the custom VJP is a real flash backward (the FlashAttention-2
formulation). The forward saves only (q, k, v, o, lse) — lse is the per-row
log-sum-exp ``m + log l`` emitted by the forward kernel — and the backward
runs two Pallas kernels that recompute probabilities per tile from lse:

  delta = rowsum(dO ∘ O)                       (XLA, O(S·D))
  P  = exp(S·scale − lse)                      (per VMEM tile, never in HBM)
  dV = Pᵀ dO      dS = P ∘ (dP − delta)·scale
  dQ = dS K       dK = dSᵀ Q

so ``jax.grad`` through ``flash_attention`` allocates O(S·D), never O(S²).

Under ``jax.checkpoint``: the forward rule names the two residuals that only
the forward kernel can rebuild, the [B·H, S, D] output ``"tk_flash_out"`` and
the log-sum-exp ``"tk_flash_lse"`` (``REMAT_SAVED``, ``checkpoint_name``). A
policy that saves those names (``models/transformer.py::_remat_layer``) keeps
them, 2·B·S·H·D + 4·B·S·H bytes a call in bf16, and the backward pass runs the
dq and dkv kernels on them without a second forward kernel; q, k and v are
recomputed from the layer's input as before. Without such a policy the names
do nothing, and the primal functions (``flash_attention``'s own body,
``flash_forward``) carry none: a program that is not differentiated is
unchanged.

Per-row vectors (lse, delta) are carried as [BH, S, 1] arrays with
(1, block_q, 1) blocks: Mosaic accepts a minor block dim equal to the array
dim, and the kernels get natural [block_q, 1] columns that broadcast against
[block_q, block_k] score tiles with no sublane↔lane relayout. (jax's own TPU
flash kernel instead replicates lse across a 128-lane minor dim — 128× the
residual bytes for the same broadcast.)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchkafka_tpu.ops.attention import mha

_NEG_INF = -1e30

# The names ``_flash_fwd`` gives its output and its log-sum-exp (header).
REMAT_SAVED = ("tk_flash_out", "tk_flash_lse")


# ------------------------------------------------------------------ forward


def _flash_kernel(
    q_ref, k_ref, v_ref, qoff_ref, koff_ref, o_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    window: int | None = None,
):
    qi = pl.program_id(1)
    ki = step = pl.program_id(2)
    if window is not None:
        # A sliding window (offsets 0): the grid's last axis runs over the
        # key blocks from the first one the q-block's window reaches
        # (``_window_first``), not from 0; blocks wholly under the window
        # are never fetched.
        ki = _window_first(qi, block_q, block_k, window) + step
    qoff = qoff_ref[0]  # global position of q row 0 (ring shard offset)
    koff = koff_ref[0]

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: a k-block strictly above the q-block's last row contributes
    # nothing — skip its matmuls entirely. With ring offsets this also
    # skips every block of a kv shard that lies wholly in the future.
    run = (
        (koff + ki * block_k <= qoff + qi * block_q + block_q - 1)
        if causal
        else True
    )

    @pl.when(run)
    def _block():
        q = q_ref[0]  # [block_q, D]
        k = k_ref[0]  # [block_k, D]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]
        if causal:
            q_pos = qoff + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = koff + ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = q_pos >= k_pos
            if window is not None:  # a straddling block is masked
                keep &= q_pos - k_pos < window
            s = jnp.where(keep, s, _NEG_INF)
        m_prev = m_ref[:, :1]  # [block_q, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if window is not None:
            # A row's keys of a straddling block may all lie under its
            # window while its maximum is still the mask's: exp(0) is 1.
            p = jnp.where(keep, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[:, :1] = m_new

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # Rows that saw no allowed key (possible for a ring block wholly in
        # the future): l == 0 → lse ≈ -1e30, o = 0; the partial-merge
        # weight exp(lse - lse_new) underflows to exactly 0.
        lse_ref[0] = m_ref[:, :1] + jnp.log(l)  # [block_q, 1]


def _window_first(qi, block_q: int, block_k: int, window: int):
    """The first key block a q-block's sliding window reaches: its first
    row ``qi * block_q`` sees the keys from ``qi * block_q - window + 1``
    on."""
    return jnp.maximum(qi * block_q - window + 1, 0) // block_k


def _window_blocks(sq: int, block_q: int, block_k: int, window: int) -> int:
    """Key blocks a q-block visits under a sliding window, at most: from
    ``_window_first`` to the block of its last row."""
    return max(
        (qi * block_q + block_q - 1) // block_k
        - max(qi * block_q - window + 1, 0) // block_k + 1
        for qi in range(-(-sq // block_q))
    )


def _scratch(shapes):
    return [pltpu.VMEM(sh, jnp.float32) for sh in shapes]


def _smem_spec():
    return pl.BlockSpec((1,), lambda b, i, j: (0,), memory_space=pltpu.SMEM)


def _offsets(q_offset, k_offset):
    return (
        jnp.asarray(q_offset, jnp.int32).reshape(1),
        jnp.asarray(k_offset, jnp.int32).reshape(1),
    )


def _kv_index(n_q_heads: int, n_kv_heads: int):
    """Grid-row → kv array row for grouped-query attention.

    q rows are laid out [batch·H + h]; the matching kv row is
    [batch·K + h // (H/K)]. With H == K this is the identity. Computed in
    the BlockSpec index map, so the kernel reads the SMALL kv tensors
    directly — no jnp.repeat materialising H/K× the kv bytes in HBM.
    """
    if n_q_heads == n_kv_heads:
        return lambda b: b
    rep = n_q_heads // n_kv_heads
    return lambda b: (b // n_q_heads) * n_kv_heads + (b % n_q_heads) // rep


def _flash_fwd_bhsd(
    q, k, v, *, causal: bool, block_q: int, block_k: int, interpret: bool,
    q_offset=0, k_offset=0, n_q_heads: int = 1, n_kv_heads: int = 1,
    scale: float | None = None, window: int | None = None,
):
    """q: [B·H, Sq, D]; k: [B·K, Sk, D]; v: [B·K, Sk, Dv] → ([B·H, Sq,
    Dv], lse f32). Dv is D everywhere but in ``flash_forward`` (latent
    attention's heads are wider in q/k than in v); ``scale`` defaults to
    ``1/sqrt(D)``. ``window`` (static, causal, offsets 0, forward only): a
    sliding window; the call is named ``tk_flash_fwd_win``, its grid
    visits ``_window_blocks`` key blocks a q-block, and the causal call's
    program is untouched.

    ``q_offset``/``k_offset`` are the global positions of row 0 (traced i32
    scalars, SMEM) — this is what lets the same kernel serve the single-chip
    path (offsets 0) and one block step of ring attention (shard offsets),
    mirroring ``mha``'s offset contract (attention.py). K < H (GQA) is
    served by the kv index map, not by materialising repeated heads.
    """
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    grid = (bh, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k))
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k
    )
    vmem = {"memory_space": pltpu.VMEM}
    qoff, koff = _offsets(q_offset, k_offset)
    kv = _kv_index(n_q_heads, n_kv_heads)
    if window is not None:
        return _flash_fwd_window(
            q, k, v, qoff, koff, kv, scale=scale, block_q=block_q,
            block_k=block_k, interpret=interpret, window=window,
        )
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0), **vmem),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), j, 0), **vmem),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j: (kv(b), j, 0), **vmem),
            _smem_spec(),
            _smem_spec(),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0), **vmem),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0), **vmem),
        ],
        scratch_shapes=_scratch([(block_q, dv), (block_q, 128), (block_q, 128)]),
        interpret=interpret,
        name="tk_flash_fwd",
    )(q, k, v, qoff, koff)


def _flash_fwd_window(q, k, v, qoff, koff, kv, *, scale, block_q, block_k,
                      interpret, window):
    """``_flash_fwd_bhsd`` under a sliding window: the same kernel, its
    last grid axis over the key blocks the window reaches. A step past the
    q-block's own diagonal names the last block again (no new fetch) and
    is skipped by the causal test."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    last = pl.cdiv(sk, block_k) - 1
    vmem = {"memory_space": pltpu.VMEM}

    def key_block(b, i, j):
        first = _window_first(i, block_q, block_k, window)
        return (kv(b), jnp.minimum(first + j, last), 0)

    return pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=True, block_q=block_q,
            block_k=block_k, window=window,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        grid=(
            bh, pl.cdiv(sq, block_q),
            _window_blocks(sq, block_q, block_k, window),
        ),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0), **vmem),
            pl.BlockSpec((1, block_k, d), key_block, **vmem),
            pl.BlockSpec((1, block_k, dv), key_block, **vmem),
            _smem_spec(),
            _smem_spec(),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0), **vmem),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0), **vmem),
        ],
        scratch_shapes=_scratch([(block_q, dv), (block_q, 128), (block_q, 128)]),
        interpret=interpret,
        name="tk_flash_fwd_win",
    )(q, k, v, qoff, koff)


def _flash_sel_kernel(
    q_ref, k_ref, v_ref, mask_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, block_q: int, block_k: int,
):
    """``_flash_kernel`` (causal, offsets 0, no log-sum-exp) under a mask
    a (query, key) pair that the caller made: a row may keep no key of a
    block, so a masked probability is set to 0 and not left to exp()."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki * block_k <= qi * block_q + block_q - 1)
    def _block():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        keep = mask_ref[0] != 0  # [block_q, block_k]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(keep, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, :1] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_forward_selected(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array, *,
    scale: float, interpret: bool | None = None,
    block_q: int | None = None, block_k: int | None = None,
) -> jax.Array | None:
    """Forward-only flash under a SELECTION (learned sparse attention's
    admission, ``ops/dsa.py``): q [B, S, H, D]; k, v [B, S, K, D]; mask [B,
    S, S] int8, 1 where query i attends to key j, which the caller holds
    inside the causal triangle (a block above the diagonal is skipped and
    its mask not read) -> [B, S, H, D], or None where S does not tile. A
    scattered selection empties no block under the diagonal, so the call,
    named ``tk_flash_fwd_sel``, visits the causal call's blocks; the
    causal call's own program is untouched."""
    b, s, h, d = q.shape
    block_q, block_k, interpret = _resolve(s, block_q, block_k, interpret)
    if not _supported(s, block_q, block_k):
        return None
    kv = _kv_index(h, k.shape[2])
    vmem = {"memory_space": pltpu.VMEM}
    out = pl.pallas_call(
        functools.partial(
            _flash_sel_kernel, scale=scale, block_q=block_q, block_k=block_k
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        grid=(b * h, s // block_q, s // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0), **vmem),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (kv(g), j, 0), **vmem),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (kv(g), j, 0), **vmem),
            pl.BlockSpec(
                (1, block_q, block_k), lambda g, i, j: (g // h, i, j), **vmem
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, d), lambda g, i, j: (g, i, 0), **vmem
        ),
        scratch_shapes=_scratch([(block_q, d), (block_q, 128), (block_q, 128)]),
        interpret=interpret,
        name="tk_flash_fwd_sel",
    )(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v), mask)
    return _from_bhsd(out, b, h)


# ----------------------------------------------------------------- backward


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qoff_ref, koff_ref,
    dq_ref, dq_acc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
):
    """Grid (bh, qi, ki), ki innermost: accumulate dQ for one q block."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    qoff = qoff_ref[0]
    koff = koff_ref[0]

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = (
        (koff + ki * block_k <= qoff + qi * block_q + block_q - 1)
        if causal
        else True
    )

    @pl.when(run)
    def _block():
        q = q_ref[0]  # [block_q, D]
        k = k_ref[0]  # [block_k, D]
        v = v_ref[0]
        do = do_ref[0]  # [block_q, D]
        lse = lse_ref[0]  # [block_q, 1]
        delta = delta_ref[0]  # [block_q, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]
        p = jnp.exp(s - lse)
        if causal:
            q_pos = qoff + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = koff + ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        ds = p * (dp - delta) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, D]

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qoff_ref, koff_ref,
    dk_ref, dv_ref, dk_acc, dv_acc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
):
    """Grid (bh, ki, qi), qi innermost: accumulate dK, dV for one k block."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    qoff = qoff_ref[0]
    koff = koff_ref[0]

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (
        (qoff + qi * block_q + block_q - 1 >= koff + ki * block_k)
        if causal
        else True
    )

    @pl.when(run)
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]  # [block_q, 1]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]
        p = jnp.exp(s - lse)
        if causal:
            q_pos = qoff + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = koff + ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        # dV += Pᵀ dO: contract the q (sublane) dim.
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_k, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_k, D]

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_bhsd(
    q, k, v, o, lse, do, *, causal: bool, block_q: int, block_k: int,
    interpret: bool, q_offset=0, k_offset=0, n_q_heads: int = 1,
    n_kv_heads: int = 1,
):
    """q,o,do [BH, Sq, D]; k,v [BH, Sk, D]; lse [BH, Sq, 1] →
    (dq [BH, Sq, D], dk, dv [BH, Sk, D])."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    # delta = rowsum(dO ∘ O): O(S·D) elementwise — XLA fuses this fine.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [BH, Sq, 1]

    vmem = {"memory_space": pltpu.VMEM}
    qoff, koff = _offsets(q_offset, k_offset)
    kv = _kv_index(n_q_heads, n_kv_heads)

    def qd(idx):
        return pl.BlockSpec((1, block_q, d), idx, **vmem)

    def kd(idx):
        return pl.BlockSpec((1, block_k, d), idx, **vmem)

    def col(idx):
        return pl.BlockSpec((1, block_q, 1), idx, **vmem)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k
        ),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        grid=(bh, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)),
        in_specs=[
            qd(lambda b, i, j: (b, i, 0)),  # q
            kd(lambda b, i, j: (kv(b), j, 0)),  # k
            kd(lambda b, i, j: (kv(b), j, 0)),  # v
            qd(lambda b, i, j: (b, i, 0)),  # do
            col(lambda b, i, j: (b, i, 0)),  # lse
            col(lambda b, i, j: (b, i, 0)),  # delta
            _smem_spec(),
            _smem_spec(),
        ],
        out_specs=qd(lambda b, i, j: (b, i, 0)),
        scratch_shapes=_scratch([(block_q, d)]),
        interpret=interpret,
        name="tk_flash_bwd_dq",
    )(q, k, v, do, lse, delta, qoff, koff)

    # dk/dv are written PER Q-HEAD (grid rows would race on a shared kv row
    # otherwise); under GQA the caller group-sums the rep partials.
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        grid=(bh, pl.cdiv(sk, block_k), pl.cdiv(sq, block_q)),
        in_specs=[
            qd(lambda b, j, i: (b, i, 0)),  # q
            kd(lambda b, j, i: (kv(b), j, 0)),  # k
            kd(lambda b, j, i: (kv(b), j, 0)),  # v
            qd(lambda b, j, i: (b, i, 0)),  # do
            col(lambda b, j, i: (b, i, 0)),  # lse
            col(lambda b, j, i: (b, i, 0)),  # delta
            _smem_spec(),
            _smem_spec(),
        ],
        out_specs=[
            kd(lambda b, j, i: (b, j, 0)),
            kd(lambda b, j, i: (b, j, 0)),
        ],
        scratch_shapes=_scratch([(block_k, d), (block_k, d)]),
        interpret=interpret,
        name="tk_flash_bwd_dkv",
    )(q, k, v, do, lse, delta, qoff, koff)
    return dq, dk, dv


# ------------------------------------------------------------------ public


def _supported(s: int, block_q: int, block_k: int) -> bool:
    return block_q > 0 and block_k > 0 and s % block_q == 0 and s % block_k == 0


def _auto_block(s: int) -> int:
    """Largest of (512, 256, 128) dividing S — 512 benches ~5-25x faster
    than 128 (fewer grid steps, better MXU occupancy), but any non-divisor
    would silently lose the flash path for that S entirely."""
    for b in (512, 256, 128):
        if s % b == 0:
            return b
    return 0  # no tiling → dense fallback


def _default_interpret() -> bool:
    """Interpret mode off-TPU: the kernels run under the Pallas interpreter
    (tests on the CPU mesh); compiled Mosaic on the real chip."""
    return jax.default_backend() != "tpu"


def tpu_compiler_params(
    dimension_semantics: tuple, vmem_limit_bytes: int | None = None,
    disable_bounds_checks: bool = False,
) -> dict:
    """``{"compiler_params": ...}`` kwargs for a compiled-Mosaic
    pallas_call — shared by the qmatmul, kvattn, grouped-matmul and
    sparse-attention kernels. ``vmem_limit_bytes``: the scoped VMEM a
    kernel whose blocks outgrow the compiler's default asks for (None: the
    default). ``disable_bounds_checks``: Mosaic's own check of every DMA's
    addresses left out, for a kernel whose caller holds every address in
    range itself (``ops/dsa.py::attend_selected``)."""
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=dimension_semantics,
            vmem_limit_bytes=vmem_limit_bytes,
            disable_bounds_checks=disable_bounds_checks,
        )
    }


def _resolve(s: int, block_q: int | None, block_k: int | None, interpret):
    block_q = _auto_block(s) if block_q is None else min(block_q, s)
    block_k = _auto_block(s) if block_k is None else min(block_k, s)
    if interpret is None:
        interpret = _default_interpret()
    return block_q, block_k, interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused attention. q,k,v: [B, S, H, D] → [B, S, H, D].

    Differentiable with O(S·D) memory (flash backward). Block sizes default
    to the largest of (512, 256, 128) dividing S. Falls back to the XLA
    path — forward and backward — when the sequence does not tile (no
    candidate block divides S, e.g. S < 128 or odd sizes).
    """
    return _flash_impl(q, k, v, causal, block_q, block_k, interpret)


def flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float,
    causal: bool = True, interpret: bool | None = None,
    window: int | None = None, block_q: int | None = None,
    block_k: int | None = None,
) -> jax.Array | None:
    """Forward-only flash: for heads wider in q/k than in v (latent
    attention's prefill: q/k 192, v 128), and for a sliding-window layer
    (``window``: a query at i sees the keys j with ``i - window < j <= i``;
    causal). q, k: [B, S, H, D]; v: [B, S, K, Dv] → [B, S, H, Dv], or
    None where S does not tile (the caller has its dense form). q and k
    are zero-padded to the lanes' multiple of 128, which leaves every
    score as it was; ``scale`` is the caller's, since it follows the
    unpadded width. The kernel and its arithmetic are
    ``flash_attention``'s."""
    b, s, h, d = q.shape
    block_q, block_k, interpret = _resolve(s, block_q, block_k, interpret)
    if not _supported(s, block_q, block_k):
        return None
    pad = -d % 128
    if pad:
        q, k = (jnp.pad(a, ((0, 0),) * 3 + ((0, pad),)) for a in (q, k))
    out, _ = _flash_fwd_bhsd(
        _to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
        n_q_heads=h, n_kv_heads=k.shape[2], scale=scale, window=window,
    )
    return _from_bhsd(out, b, h)


def _to_bhsd(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bhsd(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _repeat_kv(q, k, v):
    rep = q.shape[2] // k.shape[2]
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _check_heads(q, k):
    h, kh = q.shape[2], k.shape[2]
    if h % kh:
        raise ValueError(
            f"q heads ({h}) must be a multiple of kv heads ({kh}) for GQA"
        )


def _flash_impl(q, k, v, causal, block_q, block_k, interpret):
    _check_heads(q, k)
    b, s, h, d = q.shape
    block_q, block_k, interpret = _resolve(s, block_q, block_k, interpret)
    if not _supported(s, block_q, block_k):
        kk, vv = _repeat_kv(q, k, v)
        return mha(q, kk, vv, causal=causal)
    out, _ = _flash_fwd_bhsd(
        _to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
        n_q_heads=h, n_kv_heads=k.shape[2],
    )
    return _from_bhsd(out, b, h)


def _name_bits(x, name):
    """``checkpoint_name`` on an unsigned view of ``x``'s bits. A floating
    residual that the forward pass goes on to use (the output is: the layer's
    ``wo`` product reads it) is passed by ``jax.checkpoint`` through a
    ``reduce_precision`` to its own precision: nothing to a kernel's bf16
    output, but XLA keeps it as a pass over the tensor (4.9 ms of a 757 ms
    step, PERF.md §6, PR 32). An integer residual is exempt, and the two
    bitcasts fuse into their readers."""
    bits = jnp.dtype(f"uint{8 * x.dtype.itemsize}")
    named = checkpoint_name(jax.lax.bitcast_convert_type(x, bits), name)
    return jax.lax.bitcast_convert_type(named, x.dtype)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    _check_heads(q, k)
    b, s, h, d = q.shape
    block_q, block_k, interpret = _resolve(s, block_q, block_k, interpret)
    if not _supported(s, block_q, block_k):
        # Residuals (o=None, lse=None) route the backward to the dense vjp.
        kk, vv = _repeat_kv(q, k, v)
        return mha(q, kk, vv, causal=causal), (q, k, v, None, None)
    out, lse = _flash_fwd_bhsd(
        _to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
        n_q_heads=h, n_kv_heads=k.shape[2],
    )
    out = _name_bits(out, REMAT_SAVED[0])
    lse = checkpoint_name(lse, REMAT_SAVED[1])
    return _from_bhsd(out, b, h), (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, o_bhsd, lse = res
    if lse is None:  # untileable shape: dense fallback, matching the forward
        def dense(q, k, v):
            kk, vv = _repeat_kv(q, k, v)
            return mha(q, kk, vv, causal=causal)

        _, vjp = jax.vjp(dense, q, k, v)
        return vjp(g)
    b, s, h, d = q.shape
    n_kv = k.shape[2]
    block_q, block_k, interpret = _resolve(s, block_q, block_k, interpret)
    dq, dk, dv = _flash_bwd_bhsd(
        _to_bhsd(q), _to_bhsd(k), _to_bhsd(v), o_bhsd, lse, _to_bhsd(g),
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
        n_q_heads=h, n_kv_heads=n_kv,
    )
    if n_kv != h:
        # dk/dv came back as per-q-head partials [B·H, S, D]: kv grads sum
        # over each group of H/K consecutive q heads (the transpose of the
        # kv broadcast), then land in [B, S, K, D] layout.
        rep = h // n_kv
        dk = dk.reshape(b, n_kv, rep, s, d).sum(axis=2).transpose(0, 2, 1, 3)
        dv = dv.reshape(b, n_kv, rep, s, d).sum(axis=2).transpose(0, 2, 1, 3)
    else:
        dk = _from_bhsd(dk, b, n_kv)
        dv = _from_bhsd(dv, b, n_kv)
    return _from_bhsd(dq, b, h), dk, dv


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    causal: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash attention under an auto-sharded {data, fsdp, tp} mesh.

    A Pallas call is OPAQUE to GSPMD: inside a jit with sharded operands
    the partitioner cannot split the kernel the way it splits einsums, so
    plain ``flash_attention`` on a multi-device mesh either replicates the
    work or fails to partition. But batch/head-parallel attention needs NO
    communication — each (batch-shard, head-shard) attends over its own
    full sequence independently — so this wraps the kernel in
    ``shard_map``: batch over (data, fsdp), q heads AND kv heads over tp
    (the GQA group ratio is preserved per shard). Differentiable like the
    unsharded kernel (shard_map composes with the custom VJP).

    Requirements (the caller gates on these — Transformer falls back to
    the dense path otherwise): B divisible by data·fsdp, H and K by tp.
    Per-shard sequences that don't tile fall back to dense INSIDE the
    shard, same math. The region is manual over EVERY mesh axis: compiled
    Mosaic refuses a kernel under a partially manual region ("cannot be
    automatically partitioned"). Axes the spec does not name (sp/pp/ep)
    see the inputs replicated, and each of their shards runs the same
    attention.
    """
    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.shape)
    tp = "tp" if "tp" in mesh.shape else None
    spec = P(batch_axes if batch_axes else None, None, tp, None)
    fn = jax.shard_map(
        functools.partial(
            flash_attention, causal=causal, interpret=interpret
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
