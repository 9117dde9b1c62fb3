"""From the instant a request was due to the first host sync that
surfaced a token of it, 95th percentile (nearest rank) over the requests
due in the window. The program stamps ``slot_active`` when the admission
is dispatched, before the device has run it, so the first token is read
where the host first sees one. A request that never got a token counts
from its due time to the end of the run: slower than every finished one."""

from chipbench import stats


def times(run):
    out = []
    for r in run["requests"]:
        if r["due"] > run["deadline"]:
            continue
        end = r["first"] if r["first"] is not None else run["t_close"]
        out.append(1e3 * (end - r["due"]))
    return out


def read(run):
    xs = times(run)
    return stats.percentile(xs, 95) if xs else None
