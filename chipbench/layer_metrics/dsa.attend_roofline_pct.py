"""The selected read's share of the memory roofline: the K and V bytes of
the SELECTED rows, ``min(held, topk)`` a served slot-tick a layer
(``chipbench/kernels/dsa.py``), over ``tk_dsa_attend``'s time in the trace
and the chip's peak bytes a second. A row a DMA: the share says what a
read by index list costs on this chip against a stream."""

from chipbench.layer_metrics import _dsa


def read(run):
    k = _dsa.kernels(run)
    seconds, _calls = _dsa.total(run, k.ATTEND)
    if not seconds:
        return None
    _held, selected = _dsa.positions_served(run)
    need = k.attend_bytes(run["conf"], selected)
    return 100.0 * need / (seconds * run["peaks"]["hbm_bytes_s"])
