"""Attention: dense (XLA), ring, and Ulysses (sequence-parallel) implementations.

Net-new vs the reference (SURVEY.md §2: no attention anywhere in its tree);
built TPU-first:

- ``mha``: one fused einsum-softmax-einsum chain. XLA fuses the mask/softmax
  elementwise work into the two MXU matmuls; for moderate sequence lengths
  this is the fastest thing you can write without a custom kernel.
- ``ring_attention``: blockwise attention with online softmax over a
  sequence-parallel mesh axis. Each device holds a [B, S/n, H, D] shard of
  q/k/v; k/v shards rotate around the ring via ``lax.ppermute`` (ICI
  neighbour hops — the cheapest collective on a TPU torus) while every
  device's q stays resident. Memory per device is O(S/n), enabling contexts
  n× longer than a single chip's HBM would allow. Numerics follow the
  flash-attention online-softmax recurrence (running max m, running
  normalizer l) so the result is exact, not approximate.
- ``ulysses_attention``: the all-to-all alternative — two ``lax.all_to_all``
  exchanges convert the sequence split into a head split and back, so each
  device runs one full-sequence flash call over H/n heads. Same exact
  result, different comm/compute shape (see its docstring for the
  ring-vs-ulysses tradeoff).

Both are differentiable (``ppermute`` and ``lax.scan`` have transpose rules),
so ring attention composes with ``jax.value_and_grad`` in the training step.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30  # finite sentinel: avoids -inf - -inf = nan in the recurrence


def axis_is_manual(name: str) -> bool:
    """True when tracing inside a shard_map manual region over ``name`` —
    the guard the ring/ulysses wrappers and RoPE positioning use to avoid
    nesting a second shard_map on a bound axis."""
    return name in jax.sharding.get_abstract_mesh().manual_axes


def mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int | jax.Array = 0,
    k_offset: int | jax.Array = 0,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Dense multi-head attention.

    q: [B, Sq, H, D]; k, v: [B, Sk, H, D]  →  [B, Sq, H, D].

    ``q_offset``/``k_offset`` are the global positions of the first row of
    each block — this is what lets the same kernel serve both the single-chip
    path (offsets 0) and one block step of ring attention (shard offsets).
    ``window`` (with ``causal``): a sliding-window layer, a query at i sees
    the keys j with ``i - window < j <= i``. ``scale`` multiplies the
    scores (None: ``1 / sqrt(D)``).
    """
    dim = q.shape[-1]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * (1.0 / math.sqrt(dim) if scale is None else scale)
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(v.dtype), v, preferred_element_type=jnp.float32
    ).astype(v.dtype)


def _ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    causal: bool,
    use_flash: bool | None = None,
) -> jax.Array:
    """Per-device body (runs under shard_map). q/k/v: local [B, Sl, H, D].

    Dispatch: on TPU, when the local shard tiles (Sl a multiple of a flash
    block), each ring step runs the Pallas flash kernels — O(Sl·D)
    VMEM-tile memory and MXU-rate matmuls, forward AND backward (custom
    VJP below). Elsewhere (and for ragged shards) the dense blockwise body
    runs: it materialises the local [B, H, Sl, Sl] score tile per step but
    is exact and compiled XLA — far faster than interpret-mode kernels on
    CPU/GPU. ``use_flash=True`` forces the kernel path (tests exercise it
    in interpret mode); ``False`` forces dense.
    """
    from torchkafka_tpu.ops.flash import _auto_block

    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    block = _auto_block(q.shape[1])
    if use_flash and block:
        return _ring_flash(q, k, v, axis_name, axis_size, causal, block)
    return _ring_dense_local(
        q, k, v, axis_name=axis_name, axis_size=axis_size, causal=causal
    )


def _ring_dense_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    causal: bool,
) -> jax.Array:
    batch, s_local, heads, dim = q.shape
    my_idx = lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(dim)
    q_pos = my_idx * s_local + jnp.arange(s_local)  # global positions, [Sl]

    def block_step(carry, step):
        out, m, l, k_cur, v_cur = carry
        # Which shard k_cur holds now: it started at (my_idx + step) ... each
        # hop moves shard j's data to device j+1, so after `step` hops device
        # my_idx holds the shard originally on device (my_idx - step).
        src = (my_idx - step) % axis_size
        k_pos = src * s_local + jnp.arange(s_local)
        # Inputs stay in their compute dtype (bf16 on the MXU); accumulation
        # is f32 via preferred_element_type — flash-kernel numerics at
        # native matmul speed (f32 inputs run the MXU in multi-pass mode).
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_cur, preferred_element_type=jnp.float32
        ) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, _NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))  # [B,H,Sq]
        p = jnp.exp(scores - m_new[..., None])  # [B,H,Sq,Sk]
        corr = jnp.exp(m - m_new)  # [B,H,Sq]
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(v_cur.dtype), v_cur,
            preferred_element_type=jnp.float32,
        )
        out_new = out * corr.transpose(0, 2, 1)[..., None] + pv
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (out_new, m_new, l_new, k_nxt, v_nxt), None

    out0 = jnp.zeros((batch, s_local, heads, dim), jnp.float32)
    m0 = jnp.full((batch, heads, s_local), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((batch, heads, s_local), jnp.float32)
    (out, _, l, _, _), _ = lax.scan(
        block_step, (out0, m0, l0, k, v), jnp.arange(axis_size)
    )
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (out / denom).astype(v.dtype)


# ------------------------------------------------- ring over flash kernels


def _ring_perm(x, axis_name: str, axis_size: int):
    return lax.ppermute(
        x, axis_name, [(j, (j + 1) % axis_size) for j in range(axis_size)]
    )


def _ring_flash_run(q, k, v, axis_name, axis_size, causal, block):
    """Forward scan: one flash-kernel call per ring step, partial results
    merged with the standard two-softmax combine
    (lse_new = logaddexp; o weighted by exp(lse − lse_new)).
    Returns (o [BH, Sl, D] f32, lse [BH, Sl, 1] f32)."""
    from torchkafka_tpu.ops.flash import _default_interpret, _flash_fwd_bhsd, _to_bhsd

    b, sl, h, d = q.shape
    # Non-causal steps ignore the shard offsets entirely (no position mask,
    # no block-skip predicate), so the axis_index that feeds them would be a
    # dead PartitionId op — which jax 0.4.x's SPMD partitioner rejects once
    # DCE strands it outside the manual region. Skip it: offsets are only
    # meaningful under the causal mask.
    my = lax.axis_index(axis_name) if causal else 0
    interpret = _default_interpret()
    qb, kb, vb = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)

    def step(carry, t):
        o, lse, k_cur, v_cur = carry
        src = (my - t) % axis_size  # shard k_cur holds after t hops
        o_p, lse_p = _flash_fwd_bhsd(
            qb, k_cur, v_cur, causal=causal, block_q=block, block_k=block,
            interpret=interpret, q_offset=my * sl, k_offset=src * sl,
        )
        lse_new = jnp.logaddexp(lse, lse_p)
        o = (
            jnp.exp(lse - lse_new) * o
            + jnp.exp(lse_p - lse_new) * o_p.astype(jnp.float32)
        )
        return (
            o, lse_new,
            _ring_perm(k_cur, axis_name, axis_size),
            _ring_perm(v_cur, axis_name, axis_size),
        ), None

    o0 = jnp.zeros((b * h, sl, d), jnp.float32)
    lse0 = jnp.full((b * h, sl, 1), _NEG_INF, jnp.float32)
    (o, lse, _, _), _ = lax.scan(step, (o0, lse0, kb, vb), jnp.arange(axis_size))
    return o, lse


def _from_bhsd(x, b, h, dtype):
    from torchkafka_tpu.ops.flash import _from_bhsd as _fb

    return _fb(x, b, h).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name, axis_size, causal, block):
    b, _, h, _ = q.shape
    o, _ = _ring_flash_run(q, k, v, axis_name, axis_size, causal, block)
    return _from_bhsd(o, b, h, v.dtype)


def _ring_flash_fwd(q, k, v, axis_name, axis_size, causal, block):
    b, _, h, _ = q.shape
    o, lse = _ring_flash_run(q, k, v, axis_name, axis_size, causal, block)
    return _from_bhsd(o, b, h, v.dtype), (q, k, v, o, lse)


def _ring_flash_bwd(axis_name, axis_size, causal, block, res, g):
    """Ring backward: dq accumulates locally; dk/dv accumulators travel WITH
    their k/v shard (contributions are added by whichever device currently
    holds the shard) and arrive home after the full cycle of hops."""
    from torchkafka_tpu.ops.flash import _default_interpret, _flash_bwd_bhsd, _to_bhsd

    q, k, v, o, lse = res
    b, sl, h, d = q.shape
    # Same dead-PartitionId guard as _ring_flash_run: the dq/dkv kernels
    # read the offsets only under the causal mask.
    my = lax.axis_index(axis_name) if causal else 0
    interpret = _default_interpret()
    qb, kb, vb, gb = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v), _to_bhsd(g)

    def step(carry, t):
        dq, dk_cur, dv_cur, k_cur, v_cur = carry
        src = (my - t) % axis_size
        dq_p, dk_p, dv_p = _flash_bwd_bhsd(
            qb, k_cur, v_cur, o, lse, gb,
            causal=causal, block_q=block, block_k=block, interpret=interpret,
            q_offset=my * sl, k_offset=src * sl,
        )
        dq = dq + dq_p.astype(jnp.float32)
        dk_cur = dk_cur + dk_p.astype(jnp.float32)
        dv_cur = dv_cur + dv_p.astype(jnp.float32)
        return (
            dq,
            _ring_perm(dk_cur, axis_name, axis_size),
            _ring_perm(dv_cur, axis_name, axis_size),
            _ring_perm(k_cur, axis_name, axis_size),
            _ring_perm(v_cur, axis_name, axis_size),
        ), None

    zeros = jnp.zeros((b * h, sl, d), jnp.float32)
    (dq, dk, dv, _, _), _ = lax.scan(
        step, (zeros, zeros, zeros, kb, vb), jnp.arange(axis_size)
    )
    return (
        _from_bhsd(dq, b, h, q.dtype),
        _from_bhsd(dk, b, h, k.dtype),
        _from_bhsd(dv, b, h, v.dtype),
    )


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


# ------------------------------------------------- Ulysses (all-to-all) SP


def _ulysses_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    causal: bool,
    use_flash: bool | None,
) -> jax.Array:
    """Per-device body (runs under shard_map). q/k/v: local [B, Sl, H, D].

    Two all-to-alls re-partition the problem: the first trades the sequence
    split for a head split ([B, Sl, H, D] → [B, S, H/n, D]), so each device
    runs FULL-sequence attention over its head subset — one flash kernel
    call instead of a ring of n — and the second trades back. Both
    all-to-alls move the same volume a ring moves in total, but as two
    dense exchanges XLA schedules across ICI instead of n dependent
    neighbour hops; ``lax.all_to_all`` has a transpose rule, so the
    backward differentiates through the same pattern reversed.
    """
    from torchkafka_tpu.ops.flash import _auto_block, flash_attention

    a2a = functools.partial(lax.all_to_all, axis_name=axis_name, tiled=True)
    qh = a2a(q, split_axis=2, concat_axis=1)  # [B, S, Hq/n, D]
    kh = a2a(k, split_axis=2, concat_axis=1)  # [B, S, Hkv/n, D]
    vh = a2a(v, split_axis=2, concat_axis=1)
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if use_flash and _auto_block(qh.shape[1]):
        out = flash_attention(qh, kh, vh, causal)  # GQA-native kv reads
    else:
        from torchkafka_tpu.ops.flash import _repeat_kv

        kh, vh = _repeat_kv(qh, kh, vh)  # dense path: repeat kv for GQA
        out = mha(qh, kh, vh, causal=causal)
    return a2a(out, split_axis=1, concat_axis=2)  # back to [B, Sl, H, D]


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = True,
    use_flash: bool | None = None,
) -> jax.Array:
    """Exact sequence-parallel attention via all-to-all head re-partitioning
    (the DeepSpeed-Ulysses pattern, built from ``lax.all_to_all`` over the
    mesh axis rather than any NCCL analog).

    Same contract as ``ring_attention`` — global [B, S, H, D] arrays,
    seq-sharded over ``axis_name`` — but a different comm/compute shape:
    2 all-to-alls bracketing ONE full-sequence attention per device,
    versus n dependent ppermute hops each bracketing a shard-sized
    attention. Ulysses needs head counts divisible by the axis size
    (heads are the re-partition currency); ring has no head constraint
    and GQA kv travels unrepeated. Pick per model: many-headed dense
    models → ulysses; few-kv-head GQA at extreme context → ring.
    """
    axis_size = mesh.shape[axis_name]
    if axis_size == 1:
        return mha(q, k, v, causal=causal) if q.shape[2] == k.shape[2] else (
            _gqa_dense(q, k, v, causal)
        )
    if q.shape[2] % axis_size or k.shape[2] % axis_size:
        raise ValueError(
            f"ulysses_attention re-partitions heads over {axis_name!r} "
            f"(size {axis_size}): q heads {q.shape[2]} and kv heads "
            f"{k.shape[2]} must both be divisible by it — use "
            "ring_attention for indivisible head counts"
        )
    body = functools.partial(
        _ulysses_local, axis_name=axis_name, axis_size=axis_size,
        causal=causal, use_flash=use_flash,
    )
    if axis_is_manual(axis_name):
        return body(q, k, v)
    spec = P(None, axis_name, None, None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=frozenset({axis_name}),
        check_vma=False,
    )(q, k, v)


def _gqa_dense(q, k, v, causal):
    from torchkafka_tpu.ops.flash import _repeat_kv

    k, v = _repeat_kv(q, k, v)
    return mha(q, k, v, causal=causal)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = True,
    batch_axes: tuple[str, ...] | str | None = None,
    use_flash: bool | None = None,
) -> jax.Array:
    """Exact sequence-parallel attention over ``mesh[axis_name]``.

    q/k/v are *global* [B, S, H, D] arrays (inside jit, sharded along S over
    ``axis_name`` and along B over ``batch_axes``); the shard_map body sees
    the local shards and exchanges k/v around the ring. ``use_flash``:
    None = Pallas flash kernels per ring step on TPU, dense XLA elsewhere;
    True/False forces.
    """
    if q.shape[2] != k.shape[2]:
        raise ValueError(
            "ring_attention requires equal q/kv head counts — repeat kv "
            "heads before the ring (GQA-native reads are a flash_attention "
            "feature; the ring rotates whatever kv it is given)"
        )
    axis_size = mesh.shape[axis_name]
    if axis_size == 1:
        return mha(q, k, v, causal=causal)
    if axis_is_manual(axis_name):
        # Already inside a manual region over axis_name (e.g. a pipeline
        # stage that bound 'sp' alongside 'pp'): q/k/v are local shards and
        # the collectives can run directly — nesting a second shard_map on
        # the same axis is illegal.
        return _ring_attention_local(
            q, k, v, axis_name=axis_name, axis_size=axis_size, causal=causal,
            use_flash=use_flash,
        )
    # Partial-manual shard_map: only the sequence axis is manual here; batch
    # (data/fsdp) sharding stays automatic, so the specs mention ONLY
    # axis_name.
    spec = P(None, axis_name, None, None)
    body = functools.partial(
        _ring_attention_local, axis_name=axis_name, axis_size=axis_size,
        causal=causal, use_flash=use_flash,
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=frozenset({axis_name}),
        check_vma=False,
    )(q, k, v)
