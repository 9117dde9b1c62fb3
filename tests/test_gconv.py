"""The gated short convolution through the program: the operator token by
token through its tail against its sequence form; the third kind of
linear layer of a ``linear_pattern`` model (what it builds, what it
refuses); a slot memory WITHOUT a state tensor; the norm a head on q and
k; the sigmoid router with a selection bias beside grouped-query
attention; serving through tails and K/V rows against the full forward;
and every path that cannot hold a tail refusing it by its own reason."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_linear_attn import REFUSALS, _server

from torchkafka_tpu.models import Transformer, TransformerConfig, linear_attn
from torchkafka_tpu.models.transformer import (
    hybrid_tensors,
    init_params,
    qk_head_norm,
)
from torchkafka_tpu.ops import gconv, moe

P, NEW, VOCAB = 16, 12, 512


def conv_cfg(**kw) -> TransformerConfig:
    """Both leading dense layers and one period (c c | a c c c)."""
    base = dict(
        vocab_size=VOCAB, d_model=64, n_layers=6, n_heads=4, n_kv_heads=2,
        d_ff=96, max_seq_len=P + NEW, dtype=jnp.float32,
        param_dtype=jnp.float32, rope_theta=1e6, n_experts=8, expert_top_k=2,
        expert_d_ff=32, first_dense_layers=2, router_score="sigmoid",
        linear_pattern=(False, True, True, True), linear_kind="conv",
        linear_conv=3, qk_norm=True, tie_embeddings=True, norm_eps=1e-5,
    )
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = conv_cfg()
    params = init_params(jax.random.key(0), cfg)
    layers = params["layers"]
    # Norms a head that are not one, a bias that decides near-ties, and an
    # embedding small enough that the tied head does not repeat its input.
    for i, name in enumerate(("q_head_norm", "k_head_norm")):
        layers[name] = 1.0 + 0.5 * jax.random.normal(
            jax.random.key(3 + i), layers[name].shape
        )
    layers["router_bias"] = 0.01 * jax.random.normal(
        jax.random.key(5), layers["router_bias"].shape
    )
    params["embed"] = params["embed"] / 8.0
    return cfg, params


# ----------------------------------------------------------- the operator


def _operands(s=7, c=16, slots=3):
    keys = jax.random.split(jax.random.key(1), 4)
    b, g, x = (jax.random.normal(k, (slots, s, c)) for k in keys[:3])
    return b, g, x, jax.random.normal(keys[3], (3, c))


def test_token_by_token_through_the_tail_is_the_sequence_form():
    b, g, x, taps = _operands()
    want, tail = gconv.gconv_seq(b, g, x, taps)
    u = np.asarray(b * x)
    # By hand: zeros before the first token, the LAST tap the current one's.
    rows = np.pad(u, ((0, 0), (2, 0), (0, 0)))
    by_hand = np.asarray(g) * sum(
        rows[:, i:i + 7] * np.asarray(taps[i]) for i in range(3)
    )
    np.testing.assert_allclose(want, by_hand, atol=1e-6)
    # The admission's tail is the last two rows of u, the older first.
    np.testing.assert_array_equal(tail, u[:, -2:].reshape(3, -1))
    held = jnp.zeros((3, 2 * 16))
    for t in range(7):
        y, held = gconv.gconv_step(held, b[:, t], g[:, t], x[:, t], taps)
        np.testing.assert_allclose(y, want[:, t], atol=1e-6)
    np.testing.assert_array_equal(held, tail)


def test_an_idle_slot_keeps_its_tail_bit_for_bit():
    b, g, x, taps = _operands()
    tail = jax.random.normal(jax.random.key(2), (3, 32)).astype(jnp.bfloat16)
    act = jnp.asarray([True, False, True])
    cast = [a[:, 0].astype(jnp.bfloat16) for a in (b, g, x)]
    _y, fresh = gconv.gconv_step(tail, *cast, taps, act)
    assert fresh.dtype == tail.dtype
    np.testing.assert_array_equal(fresh[1], tail[1])
    every = gconv.gconv_step(tail, *cast, taps)[1]
    np.testing.assert_array_equal(fresh[::2], every[::2])
    assert not np.array_equal(every[1], tail[1])


# ------------------------------------------------- the kind and its config


def test_the_third_kind_and_what_it_holds(model):
    cfg, params = model
    assert (cfg.linear_kind, cfg.is_mla, cfg.routed_moe) == ("conv", False, True)
    assert hybrid_tensors(cfg)[True] == ("g_in", "g_conv", "g_out")
    assert hybrid_tensors(cfg)[False] == (
        "wq", "wk", "wv", "wo", "q_head_norm", "k_head_norm",
    )
    assert hybrid_tensors(conv_cfg(qk_norm=False))[False] == (
        "wq", "wk", "wv", "wo",
    )
    assert cfg.hybrid_layers(True) == 5 and cfg.cache_layers == 1
    assert linear_attn.slot_shapes(cfg) == (None, (2 * 64,))
    assert linear_attn.kept_tensors(cfg) == 1
    assert linear_attn.prefill_chunk(cfg) is None
    lead, layers = params["dense_layers"], params["layers"]
    assert "lm_head" not in params and "router" not in lead
    assert lead["g_in"].shape == (2, 64, 192) and lead["w_gate"].shape == (2, 64, 96)
    assert layers["g_in"].shape == (3, 64, 192)
    assert layers["g_conv"].shape == (3, 3, 64)
    assert layers["g_out"].shape == (3, 1, 64, 64)
    assert layers["wk"].shape == (1, 64, 2, 16)
    assert layers["q_head_norm"].shape == layers["k_head_norm"].shape == (1, 16)
    assert layers["router"].shape == (4, 64, 8)
    assert layers["router_bias"].shape == (4, 8)
    assert layers["w_gate"].shape == (4, 8, 64, 32)


@pytest.mark.parametrize("kw,why", [
    (dict(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
          qk_norm=False), "linear_kind='conv'"),
    (dict(attn_gate=True), "no attn_gate"),
    (dict(linear_pattern=(True, True, True, True)), "linear AND attention"),
    (dict(linear_pattern=(), tie_embeddings=False, norm_eps=1e-6,
          qk_norm=False, router_score="softmax", first_dense_layers=0),
     "describe the linear layers"),
    (dict(linear_pattern=(), linear_kind="kda", tie_embeddings=False,
          norm_eps=1e-6, router_score="softmax", first_dense_layers=0),
     "qk_norm"),
    (dict(linear_kind="ssd", ssd_heads=4, ssd_head_dim=16, ssd_state_dim=16),
     "linear_kind='conv' alone"),
    (dict(zero_experts=1), "built together only"),
    (dict(ssd_heads=2), "ssd_heads"),
])
def test_a_config_that_is_not_built_says_why(kw, why):
    with pytest.raises(ValueError, match=why):
        conv_cfg(**kw)


def test_a_config_without_the_new_fields_is_what_it_was():
    fields = {f.name: f.default for f in dataclasses.fields(TransformerConfig)}
    assert fields["linear_kind"] == "kda" and fields["qk_norm"] is False
    # The norm a head left off traces nothing: q and k come back as they
    # went in, so every other model's program is what it was (the compiled
    # programs' own tests hold their structure: PERF.md names them).
    cfg = conv_cfg(qk_norm=False)
    q, k = jnp.ones((1, 1, 4, 16)), jnp.ones((1, 1, 2, 16))
    got = qk_head_norm(q, k, {}, cfg)
    assert got[0] is q and got[1] is k


# ------------------------------------------------------------- the router


def test_route_with_a_bias_picks_by_s_plus_b_and_weighs_by_s():
    h = jax.random.normal(jax.random.key(6), (64, 32))
    router = jax.random.normal(jax.random.key(7), (32, 8)) / np.sqrt(32)
    bias = jnp.zeros((8,)).at[3].set(10.0).at[5].set(-10.0)
    idx, w = moe.route(h, router, bias, top_k=2, scaling=1.0)
    scores = np.asarray(jax.nn.sigmoid(h @ router))
    assert (np.asarray(idx) == 3).any(-1).all()  # the bias decides
    assert not (np.asarray(idx) == 5).any()
    picked = np.take_along_axis(scores, np.asarray(idx), -1)
    # ... and is no part of a gate: the chosen scores over their sum.
    np.testing.assert_allclose(
        w, picked / picked.sum(-1, keepdims=True), atol=1e-6
    )
    plain, _ = moe.route(h, router, None, top_k=2, scaling=1.0)
    assert (np.sort(plain, -1) != np.sort(idx, -1)).any()


# ---------------------------------------------------------------- serving


def test_the_slot_memory_has_no_state_tensor(model):
    cfg, params = model
    srv, consumer, _ = _server(cfg, params)
    tails, pool_k, pool_v = srv.cache_tensors
    assert tails.shape == (5, 3, 2 * 64) and tails.dtype == cfg.dtype
    assert pool_k.shape == pool_v.shape == (1, 3, P + NEW, 2 * 16)
    s = srv.metrics.summary()
    assert s["kv_backend"]["layout"] == "state"
    assert s["linear_state"] == {
        "kind": "conv", "layers": 5, "bytes_state": 0,
        "bytes_conv": tails.nbytes, "state_dtype": None, "step": "xla",
        "prefill": "shifted_sum",
    }
    assert s["kv_pool"]["full_layers"] == 1 and s["kv_pool"]["read"] == "xla"
    assert s["kv_pool"]["bytes_full"] == pool_k.nbytes + pool_v.nbytes
    assert s["expert_layer"]["experts_held"] == [0, 8]
    assert srv._resume_supported() is False
    srv.close()
    consumer.close()


def _greedy(forward, params, prompt, new):
    seq = np.zeros((1, P + NEW), np.int32)
    seq[0, : len(prompt)] = prompt
    for at in range(P, P + new):
        logits = forward(params, jnp.asarray(seq))
        seq[0, at] = int(jnp.argmax(logits[0, at - 1]))
    return seq[0, P: P + new].tolist()


def test_serving_through_tails_and_rows_is_the_full_forward(model):
    """Seven prompts through three slots, so that slots are admitted again
    over used tails and rows: every completion is the full forward's
    greedy continuation of its padded prompt, and the tokens vary."""
    cfg, params = model
    srv, consumer, rows = _server(cfg, params, n=7, ticks_per_sync=3)
    forward = jax.jit(Transformer(cfg).__call__)
    served = {}
    for rec, toks in srv.run(max_records=7, idle_timeout_ms=100):
        served[rec.offset] = toks.tolist()
    assert len(served) == 7
    for i, toks in served.items():
        assert toks == _greedy(forward, params, rows[i, : 6 + i % 9], NEW), i
    assert len({t for toks in served.values() for t in toks}) > 7
    s = srv.metrics.summary()
    assert 0 < s["kv_pool"]["full_positions_valid"] <= (
        s["kv_pool"]["full_positions_read"]
    )
    assert s["expert_layer"]["moe_assignments"] == sum(
        s["expert_layer"]["moe_expert_load"]
    ) > 0
    srv.close()
    consumer.close()


def test_the_norm_a_head_is_in_the_cached_k_rows(model):
    """The rows an admission caches are normed before they are rotated:
    with the norm left out they are other rows, and V is as it was."""
    from torchkafka_tpu.models.generate import prefill

    cfg, params = model
    tokens = jax.random.randint(jax.random.key(8), (1, P), 1, VOCAB)

    def cached(c):
        _logits, (_tails, k_rows, v_rows) = prefill(params, c, tokens, P)
        return np.asarray(k_rows), np.asarray(v_rows)

    (k, v), (k_bare, v_bare) = cached(cfg), cached(
        dataclasses.replace(cfg, qk_norm=False)
    )
    assert k.shape == k_bare.shape == (1, 1, P, 32)
    assert np.abs(k - k_bare).max() > 0.1
    np.testing.assert_array_equal(v, v_bare)


REASONS = {
    "kv_dtype=int8": "int8 rows .* are not built for it",
    "kv_kernel=True": "tk_gconv_step is a fusion, not a kernel",
    "kv_pages": "not built to keep a tail a block",
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_what_cannot_hold_a_tail_refuses_by_its_own_reason(model, what):
    cfg, params = model
    why = REASONS.get(what, "gated short convolutions")
    with pytest.raises(ValueError, match=why) as e:
        REFUSALS[what](cfg, params)
    assert "linear_kind" in str(e.value) and "conv" in str(e.value)
    assert "float32 recurrent state" not in str(e.value)
