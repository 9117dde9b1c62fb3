"""Device time one training step spends in the optimizer's update
(``tk_optimizer``)."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.step_ms(run, r"tk_optimizer")
