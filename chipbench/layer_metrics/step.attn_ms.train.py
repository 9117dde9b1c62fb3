"""Device time one training step spends in attention, forward and backward:
projections, rope, the flash kernels (``tk_attn_proj``, ``tk_attn_flash``;
the backward's operations carry the forward's scope)."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.step_ms(run, r"tk_(attn_proj|attn_flash)")
