"""The index scoring's share of the memory roofline: the index keys of the
VALID positions of the slot-ticks that SERVED a token
(``chipbench/kernels/dsa.py``), over ``tk_dsa_index``'s time in the trace
and the chip's peak bytes a second. What the kernel fetches beyond them (a
block's tail, a slot past its budget) is not needed and not counted, so
waste lowers the share."""

from chipbench.layer_metrics import _dsa


def read(run):
    k = _dsa.kernels(run)
    seconds, _calls = _dsa.total(run, k.INDEX)
    if not seconds:
        return None
    held, _selected = _dsa.positions_served(run)
    need = k.index_bytes(run["conf"], held)
    return 100.0 * need / (seconds * run["peaks"]["hbm_bytes_s"])
