"""Device time a decode tick spends reading the KV pool: the tick program's
leaf operations under ``tk_kv_read``, ``_window``, ``_full`` or ``_latent``
(the Pallas read's row write with it), over the ticks traced."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.tick_ms(run, r"tk_kv_read.*")
