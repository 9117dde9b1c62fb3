"""Device time of one call of the linear-attention decode step (one layer
of one tick: decay, rank-one correction and read-out over every slot's
state, in place), found by the kernel's own name."""

from chipbench.layer_metrics import _kda


def read(run):
    seconds, calls = _kda.step_total(run)
    return 1e6 * seconds / calls if calls else None
