"""Pallas int8 weight-dequant matmul: y = x @ (q · scale).

Weight-only-quantized decode is HBM-bandwidth-bound: every step streams the
full weight set for a few rows of activations (models/quant.py rationale).
Two properties make this kernel worth having next to XLA's dequant matmul:

- **Structural int8 streaming**: int8 weight tiles feed `dot_general`
  directly (Mosaic's mixed bf16×int8 MXU path — no bf16 weight copy even
  in VMEM); XLA's `(q*scale) @ x` relies on discretionary fusion for the
  same property.
- **Better numerics**: the per-output-channel scale applies ONCE to the
  f32 accumulator (scale is constant along the contraction), where the
  XLA path rounds every dequantized element to bf16 before the MXU.

XLA does fuse the dequant, so the default serving path stays XLA (the
compiler-friendly design the build contract prescribes) and this kernel is
the opt-in; neither has been timed on the current chip (PERF.md). The grid
MUST declare
``dimension_semantics=(parallel, parallel, arbitrary)``: without it Mosaic
assumes cross-iteration dependence and serializes the pipeline (measured
60× slower).

Net-new vs the reference (no kernels of any kind in its tree, SURVEY.md
§2); the TPU analog of the CUDA dequant-GEMM kernels weight-only-quant
serving stacks ship.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from torchkafka_tpu.ops.flash import (
    _default_interpret,
    _scratch,
    tpu_compiler_params,
)


def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk: int, mixed: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 operand straight into the MXU (Mosaic's mixed-precision dot) —
    # the weight tile is never materialized in bf16, not even in VMEM. The
    # interpreter (CPU tests) has no mixed path, so it converts first.
    xb = x_ref[...]
    qb = q_ref[...] if mixed else q_ref[...].astype(xb.dtype)
    acc_ref[...] += jax.lax.dot_general(
        xb, qb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _auto_block_mm(d: int) -> int:
    """Like flash's _auto_block but prefers 1024 — measured fastest for
    the weight-streaming matmul (fewer grid steps, bigger DMA bursts)."""
    for b in (1024, 512, 256, 128):
        if d % b == 0:
            return b
    return 0


def _xla_fallback(x2, q, scale, dtype):
    # q·scale in f32 then ONE cast — a bf16 scale would round to 8 mantissa
    # bits before the multiply (the load_weight rule, models/quant.py).
    return (x2 @ (q * scale.astype(jnp.float32)).astype(dtype)).astype(dtype)


def quantized_matmul(
    x: jax.Array,
    q: jax.Array,
    scale: jax.Array,
    *,
    block_m: int | None = None,
    block_k: int | None = None,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """x [.., K] (bf16/f32) @ int8 q [K, N] with per-column scale → [.., N].

    ``scale`` broadcasts as [1, N] (or [N]) — one scale per output channel,
    the layout ``models.quant.quantize`` produces for 2-D weights
    (contract axis 0). Shapes that don't tile (K or N not divisible by a
    128-multiple block, row count not divisible by 8) fall back to the XLA
    dequant matmul — same math, discretionary fusion.
    """
    if scale.ndim == 1:
        scale = scale[None, :]
    *lead, k = x.shape
    # Validate the operand contract up front: the Pallas path would run on
    # mismatched shapes and return silent garbage (blocks index whatever is
    # there), where a plain matmul raises.
    if q.ndim != 2 or q.shape[0] != k:
        raise ValueError(
            f"q must be [K={k}, N], got {q.shape} — quantize() with "
            "contract_axes=(0,) for 2-D weights"
        )
    n = q.shape[1]
    if scale.shape != (1, n):
        raise ValueError(
            f"scale must broadcast as [1, N={n}] (one per output channel), "
            f"got {scale.shape}"
        )
    m = int(np.prod(lead)) if lead else 1
    x2 = x.reshape(m, k)
    if interpret is None:
        interpret = _default_interpret()
    bk = _auto_block_mm(k) if block_k is None else block_k
    bn = _auto_block_mm(n) if block_n is None else block_n
    if block_m is not None:
        bm = block_m
    elif m % 8 == 0 and m <= 512:
        bm = m  # decode shapes: a handful of rows, one m-block
    else:
        bm = _auto_block_mm(m)
    ok = bool(bk and bn and bm and k % bk == 0 and n % bn == 0 and m % bm == 0)
    if not ok:
        return _xla_fallback(x2, q, scale, x.dtype).reshape(*lead, n)
    # Without parallel semantics Mosaic serializes the whole grid
    # (measured 60x slower) — m/n blocks are independent; only the k
    # (accumulation) dim carries state.
    kw = (
        {}
        if interpret
        else tpu_compiler_params(("parallel", "parallel", "arbitrary"))
    )
    out2 = pl.pallas_call(
        functools.partial(_qmm_kernel, nk=k // bk, mixed=not interpret),
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=_scratch([(bm, bn)]),
        interpret=interpret,
        name="tk_qmatmul",
        **kw,
    )(x2, q, scale.astype(jnp.float32))
    return out2.reshape(*lead, n)
