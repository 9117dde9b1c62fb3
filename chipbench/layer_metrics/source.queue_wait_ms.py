"""Due to polled (``RecordTracer.polled``), median: how long a record
waited in the topic before the server took it."""

from chipbench import stats


def read(run):
    xs = [
        1e3 * (r["polled"] - r["due"]) for r in run["requests"]
        if r["polled"] is not None and r["due"] <= run["deadline"]
    ]
    return stats.median(xs) if xs else None
