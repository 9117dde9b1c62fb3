"""Latent attention (models/mla.py) and its slot pool, at a
tiny size on the CPU in float32, against the model's own full forward.

Logits are compared, not tokens. Tolerances: the absorbed read reorders
float32 sums (q·W_uk first, then the cached row) and the un-absorbed
forward does not, so equal mathematics differs by rounding, a few 1e-6
relative at these sizes; 2e-5 absolute on logits of order one leaves
room and is 1,000 times below what a wrong pairing, scale or mask gives.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchkafka_tpu as tk
from torchkafka_tpu.models import mla
from torchkafka_tpu.models.generate import generate, latent_forward, prefill
from torchkafka_tpu.models.transformer import (
    Transformer, TransformerConfig, _rope, init_params, make_train_step,
)
from torchkafka_tpu.kvcache.slot_pool import _slot_layer_step_latent
from torchkafka_tpu.serve import StreamingGenerator

P, NEW, VOCAB = 8, 8, 64
TOL = 2e-5


def latent_cfg(**over) -> TransformerConfig:
    base = dict(
        vocab_size=VOCAB, d_model=32, n_layers=3, n_heads=2, n_kv_heads=2,
        d_ff=48, max_seq_len=P + NEW, dtype=jnp.float32, kv_lora_rank=16,
        qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, rope_interleave=True,
        first_dense_layers=1, n_experts=8, expert_top_k=2, expert_d_ff=12,
        n_shared_experts=2, router_score="sigmoid", routed_scaling=2.448,
    )
    base.update(over)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = latent_cfg()
    params = init_params(jax.random.key(3), cfg)
    # A selection bias that matters (init leaves it at zero).
    params["layers"]["router_bias"] = 0.3 * jax.random.normal(
        jax.random.key(4), params["layers"]["router_bias"].shape
    )
    return cfg, params


def full_logits(cfg, params, tokens):
    return np.asarray(Transformer(cfg)(params, jnp.asarray(tokens)))


def decode_through_pool(cfg, params, tokens, prompt_lens):
    """Rows prefilled to their own prompt length, then decoded token by
    token through one stacked pool with a DIFFERENT position a row;
    returns the logits at every decoded position, [B, T, V] (NaN where a
    row was still in its prompt)."""
    from torchkafka_tpu.models.transformer import _layer_groups, _rms_norm

    b, t = tokens.shape
    n_layers = cfg.n_layers
    pool = jnp.zeros((n_layers, b, t, cfg.latent_dim), cfg.dtype)
    model = Transformer(cfg)
    for row, n in enumerate(prompt_lens):
        _logits, rows, _rt = latent_forward(
            params, model, jnp.asarray(tokens[row: row + 1, :n])
        )
        pool = pool.at[:, row, :n].set(rows[:, 0])
    out = np.full((b, t, cfg.vocab_size), np.nan, np.float32)
    pos = np.asarray(prompt_lens)
    while (pos < t).any():
        live = pos < t
        at = np.minimum(pos, t - 1)
        x = params["embed"][jnp.asarray(tokens[np.arange(b), at])][:, None, :]
        first = 0
        for key, nl, _e in _layer_groups(cfg):
            for i in range(nl):
                layer = jax.tree.map(lambda a: a[i], params[key])
                x, pool, _rt = _slot_layer_step_latent(
                    x, layer, pool, first + i, jnp.asarray(at), cfg,
                )
            first += nl
        logits = _rms_norm(x, params["ln_f"])[:, 0] @ params["lm_head"]
        for row in np.nonzero(live)[0]:
            out[row, at[row]] = np.asarray(logits[row])
        pos = pos + live
    return out


@pytest.mark.parametrize("prompt_lens", [[3, 8, 5], [8, 1, 8]], ids=str)
def test_absorbed_decode_through_the_pool_equals_the_full_forward(model, prompt_lens):
    """Ragged slot positions: every row starts from another prompt length,
    so every tick reads another valid length a row."""
    cfg, params = model
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, VOCAB, (3, P + NEW), dtype=np.int32)
    got = decode_through_pool(cfg, params, tokens, prompt_lens)
    want = full_logits(cfg, params, tokens)
    for row, n in enumerate(prompt_lens):
        np.testing.assert_allclose(got[row, n:], want[row, n:], atol=TOL, rtol=0)
        assert np.isnan(got[row, :n]).all()


def test_the_absorbed_read_masks_what_lies_past_a_slot_s_position():
    """Rows past a slot's watermark hold another request's leftovers: the
    read's answer does not depend on them."""
    cfg = latent_cfg(max_seq_len=64)
    layer = jax.tree.map(
        lambda a: a[0], init_params(jax.random.key(1), cfg)["layers"]
    )
    rng = jax.random.split(jax.random.key(2), 4)
    b, m, h = 3, 24, cfg.n_heads
    pool = jax.random.normal(rng[0], (2, b, m, cfg.latent_dim))
    q_nope = jax.random.normal(rng[1], (b, 1, h, cfg.qk_nope_dim))
    q_rope = jax.random.normal(rng[2], (b, 1, h, cfg.qk_rope_dim))
    pos = jnp.asarray([2, 9, 23], jnp.int32)
    past = jnp.arange(m)[None, :, None] > pos[:, None, None]
    other = jnp.where(past, 7.0 * jax.random.normal(rng[3], pool.shape), pool)
    outs = [
        mla.attend_absorbed(q_nope, q_rope, c, jnp.int32(1), pos, layer, cfg)
        for c in (pool, other)
    ]
    assert outs[0].shape == (b, 1, h, cfg.v_head_dim)
    np.testing.assert_array_equal(outs[0], outs[1])


def test_rope_over_interleaved_pairs():
    """Pair i is columns (2i, 2i+1), rotated as one complex number by
    ``position * theta**(-2i/D)``; the split-half form pairs (i, i+D/2)
    and is another function of the same columns."""
    x = np.random.default_rng(0).normal(size=(2, 5, 3, 8)).astype(np.float32)
    pos = np.arange(5)
    theta = 1e4
    got = np.asarray(_rope(jnp.asarray(x), jnp.asarray(pos), theta, True))
    z = x[..., 0::2] + 1j * x[..., 1::2]
    ang = pos[:, None] * theta ** (-np.arange(0, 8, 2) / 8)
    want = z * np.exp(1j * ang)[None, :, None, :]
    np.testing.assert_allclose(got[..., 0::2], want.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 1::2], want.imag, atol=1e-5)
    halves = np.asarray(_rope(jnp.asarray(x), jnp.asarray(pos), theta))
    assert np.abs(halves - got).max() > 0.1
    # The split halves are the pairs de-interleaved: the fixed permutation.
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    np.testing.assert_allclose(
        np.asarray(_rope(jnp.asarray(x[..., perm]), jnp.asarray(pos), theta)),
        got[..., perm], atol=1e-5,
    )


def test_flash_forward_at_unequal_head_widths():
    """The prefill's flash path (q/k wider than v, q/k padded to the
    lanes) against the dense un-absorbed attention, interpreted."""
    from torchkafka_tpu.ops.flash import flash_forward

    rng = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(rng[0], (2, 128, 2, 12))
    k = jax.random.normal(rng[1], (2, 128, 2, 12))
    v = jax.random.normal(rng[2], (2, 128, 2, 8))
    got = flash_forward(q, k, v, scale=12 ** -0.5, interpret=True)
    s = jnp.einsum("bqhe,bkhe->bhqk", q, k) * 12 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((128, 128), bool)), s, -1e30)
    want = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(s, -1), v)
    assert got.shape == (2, 128, 2, 8)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    assert flash_forward(q[:, :100], k[:, :100], v[:, :100], scale=1.0) is None


def _server(cfg, params, **kw):
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=2)
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    return StreamingGenerator(
        consumer, params, cfg, slots=3, prompt_len=P, max_new=NEW, **kw
    ), consumer


def test_the_pool_is_one_tensor_of_latent_rows(model):
    cfg, params = model
    srv, consumer = _server(cfg, params)
    (pool,) = srv._caches
    assert pool.shape == (cfg.n_layers, 3, P + NEW, 16 + 4)
    assert pool.dtype == cfg.dtype
    backend = srv.metrics.summary()["kv_backend"]
    assert backend["layout"] == "latent" and backend["kernel_engaged"] == 0
    # A GQA pool of the same heads would hold q/k and v widths a head.
    gqa_row = cfg.n_heads * (cfg.qk_head_dim + cfg.v_head_dim)
    assert gqa_row / pool.shape[-1] == 2 * (12 + 8) / 20
    # What prefill hands over are the window's rows, not a pool.
    _logits, rows = prefill(params, cfg, jnp.zeros((3, P), jnp.int32), P + NEW)
    assert rows.shape == (cfg.n_layers, 3, P, 20)
    srv.close()
    consumer.close()


def _mesh2():
    from torchkafka_tpu.parallel import make_mesh

    return make_mesh({"data": 2}, devices=jax.devices()[:2])


REFUSALS = {
    "kv_dtype=int8": lambda c, p: _server(c, p, kv_dtype="int8"),
    "kv_pages": lambda c, p: _server(
        c, p, kv_pages={"block_size": 4, "num_blocks": 16}
    ),
    "kv_tier": lambda c, p: _server(
        c, p, kv_pages={"block_size": 4, "num_blocks": 16},
        kv_tier={"capacity_bytes": 1 << 20},
    ),
    "prefill_role": lambda c, p: _server(
        c, p, kv_pages={"block_size": 4, "num_blocks": 16}, prefill_role=True
    ),
    "mesh": lambda c, p: _server(c, p, mesh=_mesh2()),
    "speculative": lambda c, p: __import__(
        "torchkafka_tpu.serve_spec", fromlist=["x"]
    ).SpecStreamingGenerator(None, p, c, slots=2, prompt_len=P, max_new=NEW),
    "generate": lambda c, p: generate(p, c, jnp.zeros((1, P), jnp.int32), 4),
    "make_train_step": lambda c, p: make_train_step(c, _mesh2(), None),
    "kv_kernel=True": lambda c, p: _server(c, p, kv_kernel=True),
    "no roped key": lambda c, p: latent_cfg(qk_rope_dim=0),
    "interleave without latent attention": lambda c, p: TransformerConfig(
        rope_interleave=True
    ),
    "shared experts without the routed layer": lambda c, p: TransformerConfig(
        n_experts=4, n_shared_experts=1
    ),
    "routed experts without latent attention": lambda c, p: TransformerConfig(
        n_experts=4, router_score="sigmoid"
    ),
    "softmax experts under latent attention": lambda c, p: latent_cfg(
        router_score="softmax", first_dense_layers=0, n_shared_experts=0,
        expert_d_ff=0, routed_scaling=1.0,
    ),
}
REASONS = {
    "kv_dtype=int8": "compute-dtype only",
    "kv_pages": "dense per-slot pool",
    "kv_tier": "kv_tier",
    "prefill_role": "prefill_role",
    "mesh": "one device",
    "speculative": "speculative serving is not built",
    "generate": "lockstep decode is not built",
    "make_train_step": "make_train_step is not built",
    "kv_kernel=True": "no Pallas read is built",
    "no roped key": "even qk_rope_dim",
    "interleave without latent attention": "latent attention alone",
    "shared experts without the routed layer": "router_score='sigmoid'",
    "routed experts without latent attention": "built together only",
    # (unnormalised softmax scores ARE built since PR 31: tests/test_longcat_layer.py)
    "softmax experts under latent attention": "norm_topk=False alone",
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_what_is_not_built_refuses_with_its_reason(model, what):
    cfg, params = model
    with pytest.raises(ValueError, match=REASONS[what]):
        REFUSALS[what](cfg, params)
