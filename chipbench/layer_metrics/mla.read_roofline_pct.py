"""The latent read's share of the memory roofline: the cached rows the
served tokens' ticks need at their valid lengths (1,152 bytes a row a
layer, read once) with their queries and outputs, over the read's time in
the trace and the chip's peak bytes a second. The read also runs for
slots that are idle or past their budget, and XLA's reads the whole pool
twice; those bytes are not needed and not counted."""

from chipbench import common
from chipbench.layer_metrics import _latent_ops as L


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    s = common.load_named("layer_metrics", "mla.read_us.tput", run["root"]).seconds(run)
    if not s:
        return None
    k = L.kernels(run, "mla")
    positions = slot_ticks = 0
    for r in run["requests"]:
        before = 0
        for t, n in r["syncs"]:
            # A request's first token is the admission's, not a tick's.
            first, ticks = (1, n - 1) if before == 0 else (before, n)
            if tr["host_t0"] < t <= tr["host_t1"]:
                positions += k.positions_of_block(
                    run["prompt_window"], first, ticks
                )
                slot_ticks += ticks
            before += n
    need = k.read_bytes(run["conf"], positions, slot_ticks)
    return 100.0 * need / (s * run["peaks"]["hbm_bytes_s"])
