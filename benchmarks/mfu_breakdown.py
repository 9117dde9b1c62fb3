"""Where do the flagship train step's FLOPs go, and what's the MFU?

Times the 45.4M-parameter flagship transformer's jitted train step on the
TPU (it fails without one; strict completion: chained steps, scalar loss
fetch), comparing the fused blocked CE (default) against the round-2 dense
CE (`ce_block_size=0`), and decomposing a step into trunk / head+CE /
backward / optimizer by timing nested jits. Writes a markdown table to
stdout for PERF.md.

Usage:  python benchmarks/mfu_breakdown.py [--batches 8,32,64] [--steps 20]
        python benchmarks/mfu_breakdown.py --long-ctx   # B=4/S=2048, B=1/S=16384
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchkafka_tpu.models import Transformer, TransformerConfig, make_train_step
from torchkafka_tpu.models.transformer import count_params
from torchkafka_tpu.utils.devices import (
    device_peaks,
    enable_compile_cache,
    require_tpu,
)


def train_flops_per_step(cfg: TransformerConfig, batch: int, seq: int) -> float:
    """6·N·tokens (N = matmul params incl. head, excl. embedding gather)
    + attention 6·L·d·B·S² — the CAUSAL-halved count (non-causal would be
    12·L·d·B·S²: QK^T + PV at 2 FLOPs/MAC × 3 fwd+bwd passes); the flash
    kernels skip the masked half, so this matches executed FLOPs. Same
    convention as PERF.md round 2."""
    n = (
        cfg.n_layers
        * (
            cfg.d_model * (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim
            + cfg.n_heads * cfg.head_dim * cfg.d_model
            + 3 * cfg.d_model * cfg.d_ff
        )
        + cfg.d_model * cfg.vocab_size
    )
    tokens = batch * seq
    return 6.0 * n * tokens + 6.0 * cfg.n_layers * cfg.d_model * batch * seq * seq


def timed(fn, *args, steps: int, fetch) -> float:
    """Two-point-slope over PYTHON-LOOP chains of jitted calls.

    The slope cancels the constant fetch round trip but NOT the per-call
    host dispatch cost, which scales with the chain length: any piece
    whose device time is below the dispatch cost reads as ~dispatch-rate
    here. Used only by ``decompose``, whose output is presented as
    RELATIVE shares — for honest device absolutes use
    ``utils.timing.device_step_seconds`` (fori-chained inside one jit),
    as ``run_config`` does."""
    from torchkafka_tpu.utils.timing import two_point_slope

    outs = fn(*args)
    fetch(outs)  # compile + warmup

    def window(k: int) -> float:
        t0 = time.perf_counter()
        o = None
        for _ in range(k):
            o = fn(*args)
        fetch(o)
        return time.perf_counter() - t0

    shorts, longs = [], []
    for _ in range(3):  # interleaved so drift can't flip the slope
        shorts.append(window(steps))
        longs.append(window(3 * steps))
    per_iter, _ov, ok = two_point_slope(
        float(np.median(shorts)), float(np.median(longs)), steps, 3 * steps
    )
    if not ok:
        raise RuntimeError("drift between windows swamped the timing slope")
    return per_iter


def run_config(cfg: TransformerConfig, batch: int, seq: int, steps: int) -> dict:
    """Pure device step via the fori-chained slope (utils.timing): one
    dispatch per window, so the host's per-call dispatch cost is not in
    the number. ``--steps`` sets the LONG window's loop length (short = a
    quarter)."""
    from torchkafka_tpu.utils.timing import device_step_seconds

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    init_fn, step_fn = make_train_step(cfg, mesh, optax.adamw(3e-4))
    params, opt_state = init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    mask = jnp.ones((batch, seq), jnp.float32)
    k_long = max(8, steps)
    dt, ok = device_step_seconds(
        step_fn, params, opt_state, tokens, mask,
        k_short=max(2, k_long // 4), k_long=k_long,
    )
    if not ok:
        raise RuntimeError("drift between windows swamped the timing slope")
    fl = train_flops_per_step(cfg, batch, seq)
    return {
        "ms": dt * 1e3, "tflop": fl / 1e12,
        "mfu": fl / dt / device_peaks().bf16_flops,
    }


def decompose(cfg: TransformerConfig, batch: int, seq: int, steps: int) -> dict:
    """Forward-only pieces + full fwd+bwd, each as its own jit."""
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    params = jax.device_put(params)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    mask = jnp.ones((batch, seq), jnp.float32)

    trunk = jax.jit(lambda p, t: model.trunk(p, t)[0].sum())
    lossf = jax.jit(lambda p, t, m: model.loss(p, t, m))
    gradf = jax.jit(lambda p, t, m: jax.grad(model.loss)(p, t, m))

    t_trunk = timed(trunk, params, tokens, steps=steps, fetch=lambda o: float(o))
    t_loss = timed(lossf, params, tokens, mask, steps=steps, fetch=lambda o: float(o))
    t_grad = timed(
        gradf, params, tokens, mask, steps=steps,
        fetch=lambda o: float(jax.tree_util.tree_leaves(o)[0].ravel()[0]),
    )
    return {
        "trunk_fwd_ms": t_trunk * 1e3,
        "loss_fwd_ms": t_loss * 1e3,
        "headce_fwd_ms": (t_loss - t_trunk) * 1e3,
        "fwd_bwd_ms": t_grad * 1e3,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="8,32,64")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--long-ctx", action="store_true")
    ap.add_argument("--decompose", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    platform, device_kind, device_count = require_tpu()
    print(f"platform={platform} device_kind={device_kind} devices={device_count}")
    if args.long_ctx:
        combos = [
            (TransformerConfig(max_seq_len=2048, attn_impl="flash"), 4, 2048),
            (
                TransformerConfig(max_seq_len=16384, attn_impl="flash", remat=True),
                1, 16384,
            ),
        ]
        for cfg, b, s in combos:
            for blk in (None, 0):
                import dataclasses

                c = dataclasses.replace(cfg, ce_block_size=blk)
                label = "fused" if blk is None else "dense"
                try:
                    r = run_config(c, b, s, max(4, args.steps // 4))
                    print(
                        f"B={b} S={s} ce={label}: {r['ms']:.1f} ms/step, "
                        f"{r['tflop']:.2f} TFLOP, MFU {r['mfu'] * 100:.1f}%"
                    )
                except Exception as e:  # noqa: BLE001 — report OOMs inline
                    print(f"B={b} S={s} ce={label}: FAILED {type(e).__name__}: {e}")
        return

    import dataclasses

    cfg = TransformerConfig()
    n_params = count_params(Transformer(cfg).init(jax.random.key(0)))
    print(f"flagship params: {n_params / 1e6:.1f}M, seq {cfg.max_seq_len}")
    for b in [int(x) for x in args.batches.split(",")]:
        for blk in (None, 0):
            c = dataclasses.replace(cfg, ce_block_size=blk)
            label = "fused" if blk is None else "dense"
            r = run_config(c, b, cfg.max_seq_len, args.steps)
            print(
                f"B={b} ce={label}: {r['ms']:.1f} ms/step, {r['tflop']:.2f} "
                f"TFLOP/step, MFU {r['mfu'] * 100:.1f}%"
            )
        if args.decompose:
            d = decompose(cfg, b, cfg.max_seq_len, args.steps)
            print(
                f"  decompose B={b}: trunk fwd {d['trunk_fwd_ms']:.1f} ms, "
                f"+head+CE {d['headce_fwd_ms']:.1f} ms, full fwd "
                f"{d['loss_fwd_ms']:.1f} ms, fwd+bwd {d['fwd_bwd_ms']:.1f} ms"
            )


if __name__ == "__main__":
    main()
