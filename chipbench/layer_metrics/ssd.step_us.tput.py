"""Device time of one call of the Mamba-2 decode step (one layer of one
tick: decay, outer product, read-out and skip over every slot's state, in
place), found by the kernel's own name."""

from chipbench.layer_metrics import _ssd


def read(run):
    seconds, calls = _ssd.step_total(run)
    return 1e6 * seconds / calls if calls else None
