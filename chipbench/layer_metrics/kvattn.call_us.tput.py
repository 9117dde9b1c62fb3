"""Device time of one call of the dyn-len int8 decode read (one layer of
one tick), found by the kernel's own name."""

from chipbench.layer_metrics import _named


def read(run):
    seconds, calls = _named.kernel_total(run, "tk_kvattn_dynlen")
    return 1e6 * seconds / calls if calls else None
