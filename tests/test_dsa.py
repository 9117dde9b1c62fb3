"""Learned sparse attention (``ops/dsa.py``, ``TransformerConfig.index_*``,
the indexed slot pool): the decode kernels ``tk_dsa_index`` and
``tk_dsa_attend`` under the Pallas interpreter against ``jax.numpy`` forms
at lengths on both sides of the top-k, the admission's selection against
``lax.top_k`` (ties too) and its flash forward against a dense masked
softmax, the configuration's refusals by name, the held share beside
grouped-query attention summed to the uncut layer, and the server: tokens,
rows, index keys and meters through ``StreamingGenerator``."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import torchkafka_tpu as tk
from torchkafka_tpu.kvcache import resolve_kv_backend
from torchkafka_tpu.models.transformer import (
    Transformer, TransformerConfig, init_params,
)
from torchkafka_tpu.ops import dsa
from torchkafka_tpu.ops.moe import routed_moe_mlp
from torchkafka_tpu.serve import StreamingGenerator

TOPK = 8


def sparse_cfg(**kw) -> TransformerConfig:
    base = dict(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32, stated_head_dim=16, window_pattern=(False,),
        n_experts=8, expert_top_k=2, expert_d_ff=32, experts_held=(0, 2),
        router_score="softmax", norm_topk=True, index_heads=4,
        index_head_dim=16, index_topk=TOPK,
    )
    base.update(kw)
    return TransformerConfig(**base)


# ----------------------------------------------------------- the kernels


def pool_of(key, dtype, L=2, B=4, M=32, K=2, Dh=16, Di=16):
    ks = jax.random.split(key, 3)
    k = jax.random.normal(ks[0], (L, B, M, K, Dh), dtype)
    v = jax.random.normal(ks[1], (L, B, M, K, Dh), dtype)
    rows = dsa.pack_rows(k, v)
    rows = rows.reshape(*rows.shape[:3], *dsa.row_tile(rows.shape[-1]))
    keys = jax.random.normal(ks[2], (L, B, Di, M), dtype)
    return k, v, rows, keys


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_row_packs_and_unpacks_to_itself(dtype):
    k, v, rows, _ = pool_of(jax.random.key(0), dtype)
    k2, v2 = dsa.unpack_rows(rows.reshape(*rows.shape[:3], -1), 2, 16, dtype)
    assert (k2 == k).all() and (v2 == v).all()
    assert rows.dtype == (jnp.int32 if dtype == jnp.bfloat16 else dtype)


# A slot that is not live, lengths 1, top-k - 1, top-k, top-k + 1, a full slot.
LENGTHS = (0, 1, TOPK - 1, TOPK, TOPK + 1, 32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_decode_kernels_against_their_plain_forms(dtype):
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5
    )
    _k, _v, rows, keys = pool_of(jax.random.key(1), dtype, B=len(LENGTHS))
    ks = jax.random.split(jax.random.key(2), 3)
    b = len(LENGTHS)
    qi = jax.random.normal(ks[0], (b, 4, 16), dtype)
    w = jax.random.normal(ks[1], (b, 4), jnp.float32)
    q = jax.random.normal(ks[2], (b, 4, 16), dtype)
    n = jnp.asarray(LENGTHS, jnp.int32)
    for layer in (0, 1):
        got = dsa.index_scores(qi, w, keys, layer, n)
        want = dsa.index_scores_dense(
            qi[:, None], keys[layer].swapaxes(1, 2), w[:, None]
        )[:, 0]
        held = jnp.arange(32)[None, :] < n[:, None]
        assert (jnp.isneginf(got) == ~held).all()
        np.testing.assert_allclose(
            np.where(held, got, 0), np.where(held, want, 0), **tol
        )
        _best, idx = lax.top_k(got, TOPK)
        chosen = jnp.minimum(n, TOPK)
        out = dsa.attend_selected(
            q, rows, layer, idx, chosen, n_kv=2, scale=0.25
        )
        ref = dsa.attend_selected_reference(
            q, rows, layer, idx, chosen, n_kv=2, scale=0.25
        )
        assert (out[0] == 0).all()  # the slot that is not live
        np.testing.assert_allclose(
            np.asarray(out[1:], np.float32), np.asarray(ref[1:], np.float32),
            **tol,
        )


def test_a_slot_that_is_not_live_names_the_block_before_it():
    """No fetch for it: the order of slots changes no score."""
    _k, _v, _rows, keys = pool_of(jax.random.key(3), jnp.float32, M=256)
    qi = jax.random.normal(jax.random.key(4), (4, 4, 16))
    w = jnp.ones((4, 4))
    for n in ([0, 0, 200, 0], [130, 0, 0, 256], [0, 0, 0, 0]):
        n = jnp.asarray(n, jnp.int32)
        got = dsa.index_scores(qi, w, keys, 1, n)
        assert (jnp.isneginf(got) == (jnp.arange(256)[None] >= n[:, None])).all()


# --------------------------------------------------- the admission's forms


def indexer_inputs(key, b=2, s=256, hi=4, di=16):
    ks = jax.random.split(key, 3)
    return (
        jax.random.normal(ks[0], (b, s, hi, di)),
        jax.random.normal(ks[1], (b, s, di)),
        jax.random.normal(ks[2], (b, s, hi)),
    )


def top_k_mask(scores, k):
    b, s, _ = scores.shape
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(scores == 0, 0.0, scores)
    _best, idx = lax.top_k(jnp.where(causal[None], scores, -jnp.inf), k)
    return jnp.zeros((b, s, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None], idx
    ].set(True) & causal[None]


def test_the_selection_by_bisection_is_top_k_s():
    qi, ki, w = indexer_inputs(jax.random.key(5))
    got = dsa.select_mask(qi, ki, w, 40, block=64)
    assert got.dtype == jnp.int8
    want = top_k_mask(dsa.index_scores_dense(qi, ki, w), 40)
    assert ((got != 0) == want).all()
    # The rows under the top-k hold their whole past; the others 40.
    assert (got.sum(-1)[:, :40] == jnp.arange(1, 41)[None]).all()
    assert (got.sum(-1)[:, 40:] == 40).all()


def test_ties_go_to_the_lower_position():
    qi, ki, w = indexer_inputs(jax.random.key(6))
    coarse = jnp.round(dsa.index_scores_dense(qi, ki, w) * 2) / 2  # many equal
    got = dsa._select_block(coarse, jnp.arange(256), 40)
    assert ((got != 0) == top_k_mask(coarse, 40)).all()


def test_the_selected_flash_forward_is_the_dense_masked_softmax():
    qi, ki, w = indexer_inputs(jax.random.key(7))
    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 128))
    k = jax.random.normal(ks[1], (2, 256, 2, 128))
    v = jax.random.normal(ks[2], (2, 256, 2, 128))
    kw = dict(topk=40, scale=128 ** -0.5)
    kernel = dsa.sparse_prefill_attention(
        q, k, v, qi, ki, w, use_kernel=True, interpret=True, **kw
    )
    dense = dsa.sparse_prefill_attention(q, k, v, qi, ki, w, use_kernel=False, **kw)
    np.testing.assert_allclose(kernel, dense, rtol=1e-4, atol=1e-4)
    # Under the top-k everywhere the mask is the causal triangle.
    whole = dsa.sparse_prefill_attention(
        q, k, v, qi, ki, w, topk=256, scale=128 ** -0.5, use_kernel=False
    )
    from torchkafka_tpu.ops.attention import mha

    plain = mha(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), causal=True)
    np.testing.assert_allclose(whole, plain, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------ the configuration


def test_the_configuration_is_taken_and_refused_by_name():
    cfg = sparse_cfg()
    assert cfg.is_sparse and cfg.routed_moe and cfg.moe_partial
    params = init_params(jax.random.key(0), cfg)
    layers = params["layers"]
    assert layers["wiq"].shape == (2, 64, 4, 16)
    assert layers["wik"].shape == (2, 64, 16) and layers["wiw"].shape == (2, 64, 4)
    assert layers["router"].shape == (2, 64, 8)  # every output of the router
    assert layers["w_gate"].shape == (2, 2, 64, 32)  # the held share
    for kw in (
        dict(index_topk=0), dict(index_head_dim=15), dict(window_pattern=()),
        dict(window_pattern=(True, False), sliding_window=8),
    ):
        with pytest.raises(ValueError, match="learned sparse attention"):
            sparse_cfg(**kw)
    with pytest.raises(ValueError, match="linear_pattern model alone"):
        sparse_cfg(n_shared_experts=1)
    backend = resolve_kv_backend(cfg, max_len=48, slots=4, backend="cpu")
    assert backend.layout == "indexed" and not backend.resumable
    for kw, why in (
        (dict(kv_dtype="int8"), "compute-dtype only"),
        (dict(kv_kernel=True), "tk_dsa_index"),
        (dict(kv_pages=object()), "dense per-slot pool"),
    ):
        with pytest.raises(ValueError, match=why):
            resolve_kv_backend(cfg, max_len=48, slots=4, backend="cpu", **kw)


def test_what_else_takes_a_cache_refuses_it_by_name():
    from torchkafka_tpu.models.generate import generate
    from torchkafka_tpu.models.quant import quantize_params
    from torchkafka_tpu.models.transformer import make_train_step, param_specs
    from torchkafka_tpu.serve_spec import SpecStreamingGenerator

    cfg = sparse_cfg()
    params = init_params(jax.random.key(0), cfg)
    prompts = jnp.ones((1, 4), jnp.int32)
    for call in (
        lambda: generate(params, cfg, prompts, 2),
        lambda: quantize_params(params, cfg),
        lambda: make_train_step(cfg, None, None),
        lambda: param_specs(cfg),
        lambda: SpecStreamingGenerator(None, params, cfg, draft_layers=1),
    ):
        with pytest.raises(ValueError, match="learned sparse attention"):
            call()


def test_the_shares_of_a_layer_sum_to_the_uncut_layer():
    """One layer's routed part: the 4 shares of 2 of 8 experts, each given
    its own experts' weights, summed, are the layer that holds all 8."""
    whole = sparse_cfg(experts_held=None)
    layer = jax.tree.map(
        lambda t: t[0], init_params(jax.random.key(1), whole)["layers"]
    )
    h = jax.random.normal(jax.random.key(2), (3, 24, 64))
    want, routing = routed_moe_mlp(h, layer, whole)
    total = jnp.zeros_like(want)
    for first in range(0, 8, 2):
        share = dataclasses.replace(whole, experts_held=(first, 2))
        part = {
            n: (w[first: first + 2] if n in ("w_gate", "w_up", "w_down") else w)
            for n, w in layer.items()
        }
        got, chosen = routed_moe_mlp(h, part, share)
        assert (chosen == routing).all()  # the router keeps all its outputs
        total = total + got
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------- the server


def serve(cfg, params, prompts, new, **kw):
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    for row in prompts:
        broker.produce("p", np.asarray(row, np.int32).tobytes())
    server = StreamingGenerator(
        tk.MemoryConsumer(broker, "p", group_id="g"), params, cfg,
        slots=4, prompt_len=prompts.shape[1], max_new=new, ticks_per_sync=4,
        **kw,
    )
    out = {}
    for rec, toks in server.run(max_records=len(prompts), idle_timeout_ms=200):
        out[rec.offset] = np.asarray(toks)
    return server, out


@pytest.mark.parametrize("window", [6, 24])  # under the top-k, and past it
def test_the_served_tokens_are_the_full_forward_s(window):
    cfg = sparse_cfg()
    params = init_params(jax.random.key(3), cfg)
    prompts = np.asarray(
        jax.random.randint(jax.random.key(4), (3, window), 1, 128)
    )
    new = 12
    server, out = serve(cfg, params, prompts, new)
    # Teacher-forced through the full forward: every served token is the
    # first choice at the position before it.
    served = np.stack([out[i] for i in range(len(prompts))])
    logits = Transformer(cfg)(
        params, jnp.asarray(np.concatenate([prompts, served], axis=1))
    )
    first = np.asarray(jnp.argmax(logits[:, window - 1: window + new - 1], -1))
    assert (first == served).all()
    summary = server.metrics.summary()
    assert summary["kv_backend"]["layout"] == "indexed"
    pool = summary["kv_pool"]
    assert pool["topk"] == TOPK and pool["index_layers"] == 2
    assert pool["read"] == "kernel" and pool["bytes_index"] > 0
    # Token j >= 1 of a request holds window + j rows, a layer.
    held = 2 * 3 * sum(window + j for j in range(1, new))
    chosen = 2 * 3 * sum(min(window + j, TOPK) for j in range(1, new))
    assert pool["index_positions_valid"] == held == pool["sparse_positions_valid"]
    assert pool["sparse_positions_selected"] == chosen
    # (the read fetches whole chunks, and the toy's chunk is the top-k)
    assert pool["sparse_positions_read"] == 2 * 3 * TOPK * (new - 1)
    assert pool["index_positions_read"] >= held
    assert summary["expert_layer"]["experts_held"] == [0, 2]
    assert server.cache_tensors[0].shape[:3] == (2, 4, window + new)
    assert server.cache_tensors[1].shape == (2, 4, 16, window + new)
