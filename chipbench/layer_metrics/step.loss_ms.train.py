"""Device time one training step spends at the vocabulary's two ends, forward
and backward: the embedding's rows and their gradient's scatter-add, the
final norm, the blocked cross-entropy over the head (``tk_embed``,
``tk_head``, ``tk_loss``)."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.step_ms(run, r"tk_(embed|head|loss)")
