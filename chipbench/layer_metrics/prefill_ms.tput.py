"""Device time of one admission prefill: the admit program's time in the
trace over its runs."""

from chipbench.layer_metrics import _programs


def read(run):
    total, count = _programs.total(run, r"admit")
    return 1e3 * total / count if count else None
