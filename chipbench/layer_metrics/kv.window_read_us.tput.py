"""Device time of one sliding-window layer's read of its ring in one
decode tick (scores, softmax and the weighted values: the tick's
operations that read the window layers' stacked K or V)."""

from chipbench.layer_metrics import _kv_kinds


def read(run):
    return _kv_kinds.call_us(run, window=True)
