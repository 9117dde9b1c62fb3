"""Share of the tick program's leaf-operation time that stands under no
scope of the program's (loop counters, a scan's slices, copies the
compiler adds)."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.unscoped_pct(run, r"tick")
