"""Device time a decode tick spends in the routed expert layer: routing,
dispatch and the expert products (``tk_moe_route``, ``tk_moe_dispatch``,
``tk_moe_experts``), over the ticks traced."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.tick_ms(run, r"tk_moe_(route|dispatch|experts)")
