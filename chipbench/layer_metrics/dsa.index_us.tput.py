"""Device time of one call of the index scoring (one layer of one tick:
every live slot's valid index keys streamed and scored against the token's
index queries), found by the kernel's own name, ``tk_dsa_index``."""

from chipbench.layer_metrics import _dsa


def read(run):
    seconds, calls = _dsa.total(run, _dsa.kernels(run).INDEX)
    return 1e6 * seconds / calls if calls else None
