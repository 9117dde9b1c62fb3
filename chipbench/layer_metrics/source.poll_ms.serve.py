"""Host time a sync costs in ``tk_serve:poll`` (the broker poll and the
registration of what it returned with the ledger and the tracer), in the
traced part of the window: the poll spans summed, over the syncs. One
poll brings up to 512 records and feeds many admissions, so most syncs
need none and a median of the polls alone would often have nothing to
read."""

from chipbench.layer_metrics import _named


def read(run):
    return _named.ms_per_sync(run, ("poll",))
