"""Share of the step program's leaf-operation time that stands under no
scope of the program's."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.unscoped_pct(run, r"_step")
