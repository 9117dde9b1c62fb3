"""Device time one admission call spends round the expert products: router,
top-k, sort, group sizes, the gathers into and out of expert order, the
weighted sum (``tk_moe_route``, ``tk_moe_dispatch``)."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.admit_ms(run, r"tk_moe_(route|dispatch)")
