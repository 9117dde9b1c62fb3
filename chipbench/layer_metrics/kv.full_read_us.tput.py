"""Device time of one full layer's read of its whole-context slab in one
decode tick (the tick's operations that read the full layers' stacked K
or V)."""

from chipbench.layer_metrics import _kv_kinds


def read(run):
    return _kv_kinds.call_us(run, window=False)
