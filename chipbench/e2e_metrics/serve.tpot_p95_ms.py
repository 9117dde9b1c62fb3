"""Per request, (last token - first sync) / (tokens after the first
sync), 95th percentile over the requests due in the window that got
further tokens."""

from chipbench import stats


def times(run):
    out = []
    for r in run["requests"]:
        if r["due"] > run["deadline"] or r["finished"] is None:
            continue
        t = stats.tpot_s(r["first"], r["finished"], r["n_first"], r["n_tokens"])
        if t is not None:
            out.append(1e3 * t)
    return out


def read(run):
    xs = times(run)
    return stats.percentile(xs, 95) if xs else None
