"""The full layers' read's share of the memory roofline: K and V of the
positions the served tokens attend to (``p + 1`` a layer, the program's
count), over the read's time in the trace and the chip's peak bytes a
second. Never the slab's bytes: XLA fetches every slot's whole slab."""

from chipbench.layer_metrics import _kv_kinds


def read(run):
    return _kv_kinds.roofline_pct(run, window=False)
