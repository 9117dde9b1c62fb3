"""Device time one admission call spends in the embedding, the dense FFN and
shared experts, and the head (``tk_embed``, ``tk_ffn``, ``tk_head``)."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.admit_ms(run, r"tk_(embed|ffn|head)")
