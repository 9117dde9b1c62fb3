"""``tk_commit:fetch`` (the barrier's one-scalar fetch that proves the
step retired, after ``block_until_ready`` has returned) in the traced
part of the window, median."""

from chipbench.layer_metrics import _named


def read(run):
    return _named.median_ms(run, "tk_commit:fetch")
