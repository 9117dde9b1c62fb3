"""Device time of one call of the selected read (one layer of one tick:
every live slot's selected rows fetched by index and attended), found by
the kernel's own name, ``tk_dsa_attend``."""

from chipbench.layer_metrics import _dsa


def read(run):
    seconds, calls = _dsa.total(run, _dsa.kernels(run).ATTEND)
    return 1e6 * seconds / calls if calls else None
