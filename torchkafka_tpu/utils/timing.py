"""Two-point slope timing: device time per iteration, host cost excluded.

Any timing of the form "run K device iterations, fetch, divide by K"
carries the constant dispatch+fetch cost in every estimate — overhead/K per
iteration, which buries a quantity of a few milliseconds at small K. Timing
TWO chain lengths and taking the slope cancels the constant term exactly,
whatever its size. One implementation, shared by the harness scenarios.
"""

from __future__ import annotations


def device_step_seconds(
    step_fn, params, opt_state, *batch_args,
    k_short: int = 2, k_long: int = 8, repeats: int = 3,
) -> tuple[float, bool]:
    """Pure DEVICE seconds per train step: (step_s, ok).

    Chains the step INSIDE one jitted ``lax.fori_loop`` (so the host
    dispatches once per window, not once per step) and slopes two loop
    lengths. Where a step's device time is below the host's per-dispatch
    cost, a Python-loop chain of jitted calls measures the host's dispatch
    rate, not the device — wall/step keeps FALLING as the window grows and
    never converges to the device time.

    ``step_fn(params, opt, *batch_args) -> (params, opt, loss)`` (the
    make_train_step / make_dlrm_train_step shape; donation inside the
    outer jit is inert, which is fine — buffer reuse across loop
    iterations is XLA's job here).
    """
    import time

    import jax
    import numpy as np
    from jax import lax

    # k is a TRACED loop bound (one compile serves both window lengths —
    # a static bound would compile the full step loop twice).
    @jax.jit
    def run(k, p, o, *args):
        def body(_, carry):
            p, o = carry
            p, o, _loss = step_fn(p, o, *args)
            return (p, o)

        p, o = lax.fori_loop(0, k, body, (p, o))
        # Scalar fence transitively dependent on every iteration.
        return jax.tree_util.tree_leaves(p)[0].ravel()[0]

    float(run(k_short, params, opt_state, *batch_args))  # compile + warm
    shorts, longs = [], []
    for _ in range(repeats):  # interleaved: drift can't flip the slope
        t0 = time.perf_counter()
        float(run(k_short, params, opt_state, *batch_args))
        shorts.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(run(k_long, params, opt_state, *batch_args))
        longs.append(time.perf_counter() - t0)
    step_s, _overhead, ok = two_point_slope(
        float(np.median(shorts)), float(np.median(longs)), k_short, k_long
    )
    return step_s, ok


def two_point_slope(
    t_short: float, t_long: float, k_short: int, k_long: int
) -> tuple[float, float, bool]:
    """(per_iteration_s, overhead_s, ok).

    ``ok`` is False when the slope degenerates (t_long <= t_short): the
    windows' fixed costs drifted by more than the device work separating
    them, and nothing numeric can honestly be derived — callers
    must FLAG the measurement, not publish the floored values (a 1e-9
    floor silently becomes "1.6e10 tok/s" downstream). The floored
    per-iteration value is still returned so callers can avoid division
    by zero while reporting the failure.
    """
    if k_long <= k_short:
        raise ValueError("k_long must exceed k_short")
    slope = (t_long - t_short) / (k_long - k_short)
    ok = slope > 0
    per_iter = max(slope, 1e-9)
    overhead = max(t_short - k_short * per_iter, 0.0)
    return per_iter, overhead, ok
