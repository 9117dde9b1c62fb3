"""Host clock from the step's dispatch to the return of
``token.commit(wait_for=loss)``, median, less the step's device time:
what the barrier and the commit add to a step."""

from chipbench import stats
from chipbench.layer_metrics import _programs


def read(run):
    total, count = _programs.total(run, r"_step")
    if not count:
        return None
    host = stats.median(s["dispatch_s"] + s["commit_s"] for s in run["steps"])
    return 1e3 * (host - total / count)
