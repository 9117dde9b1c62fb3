"""The int8 decode read's share of the memory roofline: the bytes the
served tokens' ticks need at their valid lengths, over the kernel's time
in the trace and the chip's peak bytes a second. The kernel also runs for
slots that are idle or past their budget; their bytes are not needed and
not counted, so waste lowers the share."""

from chipbench import common
from chipbench.layer_metrics import _programs


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    k = common.load_named("kernels", "kvattn", run["root"])
    seconds, _ = _programs.kernel_total(run, k.TRACE_PROGRAM, k.TRACE_OPERANDS)
    if not seconds:
        return None
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
    need = k.read_bytes(run["dims"], positions, slot_ticks)
    return 100.0 * need / (seconds * run["peaks"]["hbm_bytes_s"])
