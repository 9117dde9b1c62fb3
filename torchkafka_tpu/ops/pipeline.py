"""GPipe-style pipeline parallelism over a ``pp`` mesh axis.

Net-new vs the reference (no model code, SURVEY.md §2 parallelism table).
The stacked-layer representation ([L, ...] params + one scanned body, see
models/transformer.py) pipelines naturally: shard the layer axis over
``pp`` so each stage owns L/P consecutive layers, split the batch into
microbatches, and run the classic GPipe schedule — M + P - 1 ticks, each
stage applying its local layer stack and handing its activation to the next
stage over ``lax.ppermute`` (one ICI hop on a TPU torus).

Manual collectives are confined to the ``pp`` axis via partial-manual
``shard_map`` (``axis_names={'pp'}``): tensor/data/fsdp sharding inside the
stage body stays automatic, so the same layer code composes with tp/sp/ep
exactly as in the non-pipelined path. The whole schedule is built from
``lax.scan`` + ``ppermute`` + ``where``, all with transpose rules, so
``jax.grad`` through the pipeline just works (backward replays the schedule
in reverse).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(
    layer_fn: Callable[[jax.Array, Any], Any],
    layer_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "pp",
    microbatches: int | None = None,
    extra_manual: frozenset[str] | set[str] = frozenset(),
    act_spec: P | None = None,
    collect_stats: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Pipelined equivalent of ``lax.scan(layer_fn)`` over stacked layers.

    layer_fn(act, one_layer) -> act; layer_params: pytree with leading layer
    dim L (sharded over ``axis``: stage p owns layers [p·L/P, (p+1)·L/P));
    x: [B, ...] activations. Returns the same value as the sequential scan,
    bitwise up to reduction order.

    ``microbatches`` (default = pipeline depth P) must divide B; deeper
    M reduces the bubble fraction (P-1)/(M+P-1) at the cost of smaller
    per-tick matmuls.

    ``extra_manual``/``act_spec``: axes the layer body handles manually
    itself (e.g. 'sp' when the body runs ring attention — a nested
    shard_map over the same axis is illegal, so the stage binds it and the
    body's collectives run directly). ``act_spec`` is the PartitionSpec of
    one activation [B, ...] over those axes; its batch entry is ignored.

    ``collect_stats``: layer_fn instead returns (act, stats) with stats a
    fixed-shape f32 array of per-layer TOKEN-SUMMED statistics (e.g. MoE
    router load sums — sums, not means, so they add across microbatches).
    gpipe then also returns a stacked [L, *stats] array holding, per layer,
    the statistic summed over the full batch: each stage accumulates its
    local layers' stats across its valid schedule ticks (warmup/drain ticks
    process garbage and are masked out), and a psum over ``axis`` (and any
    ``extra_manual`` axes that shard tokens, e.g. 'sp') assembles the
    global view, replicated on every stage.
    """
    n_stages = mesh.shape[axis]
    if n_stages == 1:
        def seq_body(a, layer):
            out = layer_fn(a, layer)
            return out if collect_stats else (out, None)

        x_out, ys = lax.scan(seq_body, x, layer_params)
        return (x_out, ys) if collect_stats else x_out
    m = microbatches if microbatches is not None else n_stages
    batch = x.shape[0]
    if batch % m != 0:
        raise ValueError(f"batch {batch} not divisible by microbatches {m}")

    orig_dtype = x.dtype

    def stage_body(params_local: Any, x_mb_f32: jax.Array):
        # The shard_map boundary is f32 (cast back immediately): x is
        # replicated over pp, so its cotangent is an all-reduce across the
        # stages — and XLA's CPU AllReducePromotion pass miscompiles bf16
        # all-reduces. Stage-internal compute still runs in the caller's
        # dtype; ppermute (the only steady-state collective) is unaffected.
        x_mb = x_mb_f32.astype(orig_dtype)
        stage = lax.axis_index(axis)
        n_local = jax.tree_util.tree_leaves(params_local)[0].shape[0]

        def apply_stage(act):
            def body(a, layer):
                if collect_stats:
                    return layer_fn(a, layer)
                return layer_fn(a, layer), None

            return lax.scan(body, act, params_local)

        out_buf = jnp.zeros_like(x_mb)  # [M, mb, ...]
        act = jnp.zeros_like(x_mb[0])
        if collect_stats:
            st_shape = jax.eval_shape(
                lambda a: apply_stage(a)[1], act
            )
            stats_acc = jnp.zeros(st_shape.shape, jnp.float32)
        else:
            stats_acc = jnp.float32(0.0)  # placeholder carry leaf

        def tick(carry, t):
            act, out_buf, stats_acc = carry
            # Stage 0 ingests microbatch t (harmless clipped re-read after M).
            incoming = lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, m - 1), keepdims=False
            )
            act = jnp.where(stage == 0, incoming, act)
            act, stats = apply_stage(act)
            if collect_stats:
                # Stage p holds microbatch t-p at tick t; outside [0, M)
                # it is processing warmup zeros or drain re-reads whose
                # statistics must not count.
                valid = (t >= stage) & (t - stage < m)
                stats_acc = stats_acc + jnp.where(valid, 1.0, 0.0) * stats
            # Last stage retires microbatch t-(P-1).
            idx = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (idx >= 0)
            safe = jnp.clip(idx, 0, m - 1)
            current = lax.dynamic_index_in_dim(out_buf, safe, keepdims=False)
            out_buf = lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(write, act, current), safe, 0
            )
            # Hand activations downstream: stage p -> p+1.
            act = lax.ppermute(
                act, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (act, out_buf, stats_acc), None

        (act, out_buf, stats_acc), _ = lax.scan(
            tick, (act, out_buf, stats_acc), jnp.arange(m + n_stages - 1)
        )
        # Replicate the last stage's result across the pp axis (f32 — see
        # the boundary note above).
        masked = jnp.where(
            stage == n_stages - 1, out_buf, jnp.zeros_like(out_buf)
        ).astype(jnp.float32)
        out = lax.psum(masked, axis)
        if not collect_stats:
            return out
        # Place each stage's [L/P, ...] stats at its layer offset in the
        # full [L, ...] array; psum over pp assembles + replicates, psum
        # over manual token-sharding axes (sp) globalises the token sums.
        full = jnp.zeros((n_local * n_stages,) + stats_acc.shape[1:],
                         jnp.float32)
        full = lax.dynamic_update_slice(
            full, stats_acc,
            (stage * n_local,) + (0,) * (stats_acc.ndim - 1),
        )
        reduce_axes = (axis,) + tuple(a for a in extra_manual)
        return out, lax.psum(full, reduce_axes)

    # [B, ...] -> [M, B/M, ...]; the microbatch loop runs inside the stages.
    x_mb = x.reshape(m, batch // m, *x.shape[1:]).astype(jnp.float32)
    layer_specs = jax.tree_util.tree_map(lambda _: P(axis), layer_params)
    if act_spec is not None:
        # [B, d1, d2, ...] spec -> [M, mb, d1, d2, ...] spec.
        x_spec = P(None, None, *tuple(act_spec)[1:])
    else:
        x_spec = P()
    result = jax.shard_map(
        stage_body,
        mesh=mesh,
        in_specs=(layer_specs, x_spec),
        out_specs=(x_spec, P()) if collect_stats else x_spec,
        axis_names=frozenset({axis}) | frozenset(extra_manual),
        check_vma=False,
    )(layer_params, x_mb)
    out, stats = result if collect_stats else (result, None)
    out = out.reshape(batch, *x.shape[1:]).astype(orig_dtype)
    return (out, stats) if collect_stats else out
