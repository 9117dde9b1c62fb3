"""Time of the collective operations in one step on a chip (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute), from the
trace. The part with no compute beside it goes on an earlier line."""

from chipbench import common
from chipbench.layer_metrics import _programs


def read(run):
    tr = run["trace"]
    _, steps = _programs.total(run, r"_step")
    if not tr or not steps or not tr["collective_s"]:
        return None
    common.say("collectives", {
        "ms_a_step": 1e3 * tr["collective_s"] / steps,
        "exposed_ms_a_step": 1e3 * tr["collective_exposed_s"] / steps,
    })
    return 1e3 * tr["collective_s"] / steps
