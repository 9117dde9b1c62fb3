"""Share of the rows the admission programs prefilled that admitted a
record, over the window: the program's own counts (``admit_rows`` over
``admit_rows_prefilled``). The dense admission prefills every slot of its
batch to admit the few that are free."""

from chipbench.layer_metrics import _named


def read(run):
    rows = _named.counter_delta(run, "admit_rows")
    prefilled = _named.counter_delta(run, "admit_rows_prefilled")
    return 100.0 * rows / prefilled if prefilled else None
