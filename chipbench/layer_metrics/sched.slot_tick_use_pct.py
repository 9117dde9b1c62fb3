"""Share of the slot-ticks the device ran that surfaced a served token,
over the window: the program's own counts (``slot_ticks_served`` over
``slot_ticks_run``), where ``sched.occupancy_pct`` rebuilds the same
share from the tracer's per-request events."""

from chipbench.layer_metrics import _named


def read(run):
    served = _named.counter_delta(run, "slot_ticks_served")
    ran = _named.counter_delta(run, "slot_ticks_run")
    return 100.0 * served / ran if ran else None
