"""``tk_serve:output_flush`` (the output producer's flush and the waits
on the send handles, before each offset commit) in the traced part of
the window, median."""

from chipbench.layer_metrics import _named


def read(run):
    return _named.median_ms(run, "tk_serve:output_flush")
