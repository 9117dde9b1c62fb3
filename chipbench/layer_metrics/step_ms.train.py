"""Device time of one training step: the step program's time in the
trace over its runs."""

from chipbench.layer_metrics import _programs


def read(run):
    total, count = _programs.total(run, r"_step")
    return 1e3 * total / count if count else None
