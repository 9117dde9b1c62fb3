"""Device time one admission call spends in attention: projections, rope,
the flash kernels, the pool's write (``tk_attn_proj``, ``tk_attn_flash``,
``tk_kv_write``; a read of cached positions, ``tk_kv_read*``, where an
admission makes one)."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.admit_ms(run, r"tk_(attn_proj|attn_flash|kv_write|kv_read.*)")
