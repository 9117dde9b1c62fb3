"""``tk_serve:commit`` in the traced part of the window, median."""

from chipbench import stats


def read(run):
    tr = run["trace"]
    spans = tr["host_spans"].get("tk_serve:commit") if tr else None
    return 1e3 * stats.median(d for _s, d in spans) if spans else None
