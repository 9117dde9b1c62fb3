"""Device time a decode tick spends streaming the dense weights: embedding,
attention projections, the KV row write, dense FFN and shared experts, the
head and sampling (``tk_embed``, ``tk_attn_proj``, ``tk_kv_write``,
``tk_ffn``, ``tk_head``), over the ticks traced."""

from chipbench.layer_metrics import _scopes


def read(run):
    return _scopes.tick_ms(run, r"tk_(embed|attn_proj|kv_write|ffn|head)")
