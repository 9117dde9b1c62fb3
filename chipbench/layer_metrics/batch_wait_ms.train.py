"""Host clock round ``next(stream)`` in the benchmark's loop, median."""

from chipbench import stats


def read(run):
    return 1e3 * stats.median(s["batch_wait_s"] for s in run["steps"])
