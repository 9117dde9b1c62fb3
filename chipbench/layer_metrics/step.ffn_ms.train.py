"""Device time one training step spends in the dense FFN, forward and
backward (``tk_ffn``)."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.step_ms(run, r"tk_ffn")
