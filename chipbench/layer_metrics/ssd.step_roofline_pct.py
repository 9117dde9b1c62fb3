"""The Mamba-2 decode step's share of the memory roofline: the state read
and written once and the slot's rows, for the slot-ticks that SERVED a
token (``chipbench/kernels/ssd.py``), over the kernel's time in the trace
and the chip's peak bytes a second. The kernel also runs for slots that
are idle or past their budget; their bytes are not needed and not counted,
so waste lowers the share."""

from chipbench import common
from chipbench.layer_metrics import _ssd


def read(run):
    seconds, _calls = _ssd.step_total(run)
    if not seconds:
        return None
    k = common.load_named("kernels", "ssd", run["root"])
    need = k.step_bytes(run["conf"], _ssd.slot_ticks_served(run))
    return 100.0 * need / (seconds * run["peaks"]["hbm_bytes_s"])
