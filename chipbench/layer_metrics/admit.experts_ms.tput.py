"""Device time one admission call spends in the expert products themselves
(``tk_moe_experts``: the grouped matmul's kernels, or a tile's three
products)."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.admit_ms(run, r"tk_moe_experts")
