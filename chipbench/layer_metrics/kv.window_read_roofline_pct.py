"""The window layers' ring read's share of the memory roofline: K and V of
the positions the served tokens may attend to (``min(p + 1, window)`` a
layer, the program's count), over the read's time in the trace and the
chip's peak bytes a second. Never the ring's bytes: the read runs for
idle slots too and fetches every ring whole."""

from chipbench.layer_metrics import _kv_kinds


def read(run):
    return _kv_kinds.roofline_pct(run, window=True)
