"""The training loop: a token topic, ``KafkaStream``, ``make_train_step``,
the barrier and a commit after every step (the loop of
``chip_smoke.py::run_train``, without its asserts in the timed path).

Set-up builds one object, the compiled step with its state, drives it
from the seed through its first three steps by the window's own call and
feed, and hands that same object to the window. Those three steps are
what the plain reference follows: each loss, the first gradient as the
optimizer got it (read back from AdamW's first moment after one step)
and how far the parameters moved.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import common
from chipbench import weights as W

GROUP, TOPIC = "chipbench-train", "tokens"
CHECKED_STEPS = 3


def _layer_norms(tree) -> dict:
    """Norm of each tensor of each layer: a stacked leaf gives one a
    layer, a table gives one."""
    import jax.numpy as jnp

    out = {}
    for name in ("embed", "lm_head", "ln_f"):
        x = tree[name].astype(jnp.float32)
        out[name] = jnp.sqrt(jnp.sum(x * x))
    for name, leaf in tree["layers"].items():
        x = leaf.astype(jnp.float32)
        out[f"layers.{name}"] = jnp.sqrt(
            jnp.sum(x * x, axis=tuple(range(1, x.ndim)))
        )
    return out


def _flatten(norms: dict) -> dict:
    flat = {}
    for name, v in norms.items():
        v = np.asarray(v)
        if v.ndim == 0:
            flat[name] = float(v)
        else:
            for l, x in enumerate(v):
                flat[f"{name}.{l}"] = float(x)
    return flat


def first_moment(opt_state):
    """AdamW's first moment, wherever optax keeps it in the state."""
    import jax

    for part in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")
    ):
        if hasattr(part, "mu"):
            return part.mu
    raise common.Refused("the optimizer state has no first moment")


def worst_leaf_gap(got: dict, want: dict) -> tuple[float, str]:
    """The gap between two norms by the worst leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    floor = float(np.median(list(want.values())))
    worst, where = 0.0, ""
    for name, w in want.items():
        gap = abs(got[name] - w) / max(w, floor, 1e-30)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import torchkafka_tpu as tk
    from torchkafka_tpu.models import Transformer, make_train_step

    conf, mix, dep = ctx.conf, ctx.mix, ctx.conf["deployment"]
    dims = W.Dims.from_conf(conf)
    shape = mix["traffic"]
    seq, batch = int(shape["seq"]), int(shape["rows_per_step"])
    parts = int(dep["token_partitions"])
    n_dev = len(ctx.devices)
    want_mesh = dict(dep["mesh"])
    if int(np.prod(list(want_mesh.values()))) != n_dev:
        raise common.Refused(f"mesh {want_mesh} on {n_dev} device(s)")
    mesh = tk.make_mesh(want_mesh, devices=ctx.devices)
    cfg = ctx.model.program_config(conf, seq, remat=bool(dep["remat"]))
    use_flash = bool(Transformer(cfg, mesh)._use_flash)
    ctx.say("attention", {"use_flash": use_flash})
    if dep.get("require_flash") and not use_flash:
        raise common.Refused("flash attention does not engage")

    rows = ctx.traffic.generate(
        shape, ctx.seed, {"seq": seq, "batch": batch, "vocab": dims.vocab}
    )["rows"]
    broker = tk.InMemoryBroker()
    broker.create_topic(TOPIC, partitions=parts)
    for i, row in enumerate(rows):
        broker.produce(TOPIC, row.tobytes(), partition=i % parts)
    consumer = tk.MemoryConsumer(
        broker, TOPIC, group_id=GROUP,
        assignment=tk.partitions_for_process(TOPIC, parts, 0, 1),
    )
    row_of = {row.tobytes(): i for i, row in enumerate(rows)}

    with ctx.phase("init"):
        init_fn, step_fn = make_train_step(
            cfg, mesh, ctx.model.optimizer(conf)
        )
        params, opt_state = init_fn(jax.random.key(0))
        # The layout and the optimizer's state are the program's; the
        # weights are the benchmark's, from the seed.
        layout = jax.tree.map(lambda a: a.sharding, params)
        del params
        params = ctx.model.training_params(conf, ctx.seed, layout)
        jax.block_until_ready(params)
    opt = dep["optimizer"]
    norms_of = jax.jit(_layer_norms)
    dtype = ctx.model.dtype_of(dep["param_dtype"])

    @jax.jit
    def change_norms(p, key):
        init = W.training_tree(key, dims, dtype)
        return _layer_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, init
        ))

    steps: list[dict] = []
    seen_rows: list[list[int]] = []
    mask = jnp.ones((batch, seq), jnp.int32)
    stream = tk.KafkaStream(
        consumer, tk.fixed_width(seq, np.int32), batch_size=batch, mesh=mesh,
        idle_timeout_ms=2000, owns_consumer=True,
    )
    program = {}
    with stream:
        it = iter(stream)

        def one_step(check: bool = False) -> dict:
            nonlocal params, opt_state
            t_a = time.perf_counter()
            with ctx.span("bench:next_batch"):
                try:
                    batch_, token = next(it)
                except StopIteration:
                    raise common.Refused(
                        "the token topic ran dry: raise steps_cap"
                    ) from None
            t_b = time.perf_counter()
            if check:
                host = np.asarray(batch_.data)
                seen_rows.append([row_of.get(r.tobytes(), -1) for r in host])
            with ctx.span("bench:step_dispatch"):
                params, opt_state, loss = step_fn(
                    params, opt_state, batch_.data, mask
                )
            t_c = time.perf_counter()
            with ctx.span("bench:commit"):
                ok = token.commit(wait_for=loss)
            t_d = time.perf_counter()
            return {
                "t_start": t_a, "batch_wait_s": t_b - t_a,
                "dispatch_s": t_c - t_b, "commit_s": t_d - t_c, "t_done": t_d,
                "loss": float(loss), "committed": bool(ok),
                "rows": batch, "tokens": batch * seq,
            }

        with ctx.phase("first_steps"):
            first = []
            for i in range(CHECKED_STEPS):
                first.append(one_step(check=True))
                if i == 0:
                    mu = norms_of(first_moment(opt_state))
                    program["grad_norm"] = {
                        k: v / (1.0 - opt["b1"])
                        for k, v in _flatten(jax.device_get(mu)).items()
                    }
                    program["change_norm_1"] = _flatten(jax.device_get(
                        change_norms(params, W.seed_key(ctx.seed))
                    ))
            program["change_norm_3"] = _flatten(jax.device_get(
                change_norms(params, W.seed_key(ctx.seed))
            ))
            program["losses"] = [s["loss"] for s in first]

        ctx.open_window()
        deadline = ctx.t0 + ctx.seconds
        with ctx.span("bench:train_loop"):
            while True:
                with jax.profiler.StepTraceAnnotation(
                    "bench_step", step_num=len(steps)
                ):
                    steps.append(one_step())
                now = steps[-1]["t_done"]
                if now >= deadline:
                    break  # the trace, if any, stops with the window
                ctx.trace_tick(now)
        ctx.close_window()
    peak = common.memory_peak_bytes(ctx.devices)
    committed = {
        p: broker.committed(GROUP, tk.TopicPartition(TOPIC, p)) or 0
        for p in range(parts)
    }
    del params, opt_state, stream, it
    gc.collect()

    run = {
        "kind": "train", "steps": steps, "first_steps": first,
        "memory_peak_bytes": peak, "dims": dims, "seq": seq, "batch": batch,
        "use_flash": use_flash, "mesh": want_mesh,
    }
    ctx.finish_trace(run)

    checks = ctx.checks
    done_rows = (len(steps) + CHECKED_STEPS) * batch
    # Watermarks are next-read offsets and reading is contiguous from 0,
    # so their sum is exactly the rows of the steps counted.
    checks.exact("committed_rows_gap", sum(committed.values()) - done_rows)
    checks.exact(
        "steps_uncommitted",
        sum(1 for s in steps + first if not s["committed"]),
    )
    checks.exact(
        "losses_not_finite",
        sum(1 for s in steps + first if not np.isfinite(s["loss"])),
    )
    flat_seen = [r for rs in seen_rows for r in rs]
    checks.exact(
        "rows_not_as_produced",
        sum(1 for r in flat_seen if r < 0) + len(flat_seen) - len(set(flat_seen)),
    )
    with ctx.phase("reference"):
        run["compared"] = compare_with_reference(
            ctx, program, rows, seen_rows, dims, dtype
        )
    run["first_rows"] = [rows[np.asarray(r)] for r in seen_rows[:2]]
    run["dtype"] = dtype
    run["attempted"] = len(steps)
    run["failed"] = sum(
        1 for s in steps if not s["committed"] or not np.isfinite(s["loss"])
    )
    return run


def compare_with_reference(ctx, program, rows, seen_rows, dims, dtype) -> dict:
    limits = ctx.mix["check"]
    opt = ctx.conf["deployment"]["optimizer"]
    ref = ctx.reference.TrainingReference(
        ctx.seed, dims, dtype, opt,
        head_block=int(limits.get("head_block", 2048)), devices=ctx.devices,
    ).run(rows[np.asarray(seen_rows[0])], rows[np.asarray(seen_rows[1])])
    numbers = compared_numbers(program, ref)
    ctx.say("reference", {
        "losses_program": program["losses"],
        "losses_reference": [ref["loss1"], ref["loss2"]], **numbers,
    })
    judge(ctx.checks, numbers, limits)
    return {"reference": ref, "numbers": numbers}


def compared_numbers(got: dict, ref: dict) -> dict:
    """The numbers compared, of a program (or of the control put in its
    place) against the reference."""
    g_gap, g_where = worst_leaf_gap(got["grad_norm"], ref["grad_norm"])
    c_gap, c_where = worst_leaf_gap(got["change_norm_1"], ref["change_norm"])
    def total(norms: dict) -> float:
        return float(np.sqrt(sum(v * v for v in norms.values())))

    return {
        "loss1_rel_gap": abs(got["losses"][0] - ref["loss1"]) / abs(ref["loss1"]),
        "loss2_rel_gap": abs(got["losses"][1] - ref["loss2"]) / abs(ref["loss2"]),
        "grad_norm_worst_leaf_gap": g_gap, "grad_norm_worst_leaf": g_where,
        "change_norm_worst_leaf_gap": c_gap, "change_norm_worst_leaf": c_where,
        "change_after_3_steps": total(got["change_norm_3"])
        / max(total(ref["change_norm"]), 1e-30),
    }


def judge(c, n: dict, limits: dict) -> None:
    c.at_most("loss1_rel_gap", n["loss1_rel_gap"],
              float(limits["max_loss1_rel_gap"]))
    c.at_most("loss2_rel_gap", n["loss2_rel_gap"],
              float(limits["max_loss2_rel_gap"]))
    c.at_most("grad_norm_worst_leaf_gap", n["grad_norm_worst_leaf_gap"],
              float(limits["max_grad_norm_gap"]))
    c.at_most("change_norm_worst_leaf_gap", n["change_norm_worst_leaf_gap"],
              float(limits["max_change_norm_gap"]))
    # Three steps move the parameters further than one and, each bounded
    # by the learning rate an element, no further than three times one.
    c.at_least("change_after_3_steps", n["change_after_3_steps"],
               float(limits["min_moved"]))
    c.at_most("change_after_3_steps_ceiling", n["change_after_3_steps"],
              float(limits["max_moved"]))


def control(ctx, run) -> dict:
    """The control of the comparison: the reference in the precision
    below the configuration's (8-bit floating point operands), put in the
    program's place and held to the same numbers. It takes one step, so
    its change after three is the program's own."""
    limits = ctx.mix["check"]
    low = ctx.reference.TrainingReference(
        ctx.seed, run["dims"], run["dtype"],
        ctx.conf["deployment"]["optimizer"], lowp=True,
        head_block=int(limits.get("head_block", 2048)), devices=ctx.devices,
    ).run(*run["first_rows"])
    ref = run["compared"]["reference"]
    as_program = {
        "losses": [low["loss1"], low["loss2"]],
        "grad_norm": low["grad_norm"], "change_norm_1": low["change_norm"],
        "change_norm_3": ref["change_norm"],
    }
    ctl, prog = compared_numbers(as_program, ref), run["compared"]["numbers"]
    return {
        k: {"program": prog[k], "control": ctl[k]}
        for k in ("loss1_rel_gap", "loss2_rel_gap", "grad_norm_worst_leaf_gap",
                  "change_norm_worst_leaf_gap")
    }
