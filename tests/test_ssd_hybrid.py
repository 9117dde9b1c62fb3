"""State-space (Mamba-2) layers beside a grouped-query layer through the
program: the two properties of a ``linear_pattern`` model (the recurrence
of its linear layers, the attention of the others) and what each pairing
builds; the slot memory by kind; serving through state, conv tail and K/V
rows against the full forward; a re-admitted slot; and every path that
cannot hold a state, a multiplier, attention without positions or a tied
head refusing it by the mechanism's name."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_linear_attn import REFUSALS, _server, hybrid_cfg

from torchkafka_tpu.models import Transformer, TransformerConfig
from torchkafka_tpu.models.transformer import hybrid_tensors, init_params

P, NEW, VOCAB = 16, 12, 512
STATED = dict(
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=1 / 16, logits_scaling=4.0, use_rope=False,
    tie_embeddings=True, norm_eps=1e-5,
)


def ssd_cfg(**kw) -> TransformerConfig:
    base = dict(
        vocab_size=VOCAB, d_model=128, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=96, stated_head_dim=16, max_seq_len=P + NEW, dtype=jnp.float32,
        param_dtype=jnp.float32, n_experts=8, expert_top_k=2, expert_d_ff=48,
        n_shared_experts=2, router_score="softmax", experts_held=(2, 4),
        linear_pattern=(True, False, True, True), linear_kind="ssd",
        ssd_heads=4, ssd_head_dim=16, ssd_state_dim=128, ssd_chunk=8,
        **STATED,
    )
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = ssd_cfg()
    params = init_params(jax.random.key(0), cfg)
    # Steps and rates of every speed, so that the state matters over the
    # window; an embedding small enough that the tied head does not just
    # repeat the token it read.
    layers = params["layers"]
    layers["s_dt"] = jax.random.uniform(
        jax.random.key(1), layers["s_dt"].shape, minval=-6.0, maxval=-1.0
    )
    layers["s_alog"] = jax.random.uniform(
        jax.random.key(2), layers["s_alog"].shape, minval=0.0, maxval=2.7
    )
    params["embed"] = params["embed"] / 64.0
    return cfg, params


def test_the_two_properties_of_a_hybrid_and_what_each_pairing_holds():
    ling, granite = hybrid_cfg(), ssd_cfg()
    assert (ling.linear_kind, ling.is_mla) == ("kda", True)
    assert (granite.linear_kind, granite.is_mla) == ("ssd", False)
    assert hybrid_tensors(ling)[True][0] == "lqkv"
    assert hybrid_tensors(ling)[False] == (
        "wq", "wkva", "kv_norm", "wkvb", "wo", "wg",
    )
    assert hybrid_tensors(granite)[True][:2] == ("s_in", "s_in_dt")
    assert hybrid_tensors(granite)[False] == ("wq", "wk", "wv", "wo")
    assert granite.hybrid_layers(True) == 3 and granite.cache_layers == 1
    assert (granite.ssd_inner, granite.ssd_conv_dim) == (64, 64 + 256)
    assert granite.attn_scale == 1 / 16 and ling.attn_scale == 1 / 8


def test_the_tree_is_stacked_by_kind_and_has_no_head_of_its_own(model):
    cfg, params = model
    layers = params["layers"]
    assert "lm_head" not in params and "router_bias" not in layers
    assert layers["s_in"].shape == (3, 128, 64 + 320)
    assert layers["s_in_dt"].shape == (3, 128, 4)
    assert layers["s_conv"].shape == (3, 4, 320)
    assert layers["wk"].shape == (1, 128, 2, 16)
    assert layers["router"].shape == (4, 128, 8)  # EVERY layer routes
    assert layers["w_gate"].shape == (4, 4, 128, 48)  # the held share
    assert layers["ws_gate"].shape == (4, 128, 96)  # one SwiGLU of 2 x 48


def test_the_slot_memory_is_allocated_by_kind(model):
    cfg, params = model
    srv, consumer, _ = _server(cfg, params)
    states, tails, pool_k, pool_v = srv.cache_tensors
    assert states.shape == (3, 3, 4, 16, 128) and states.dtype == jnp.float32
    assert tails.shape == (3, 3, 3 * 320) and tails.dtype == cfg.dtype
    assert pool_k.shape == pool_v.shape == (1, 3, P + NEW, 2 * 16)
    s = srv.metrics.summary()
    assert s["kv_backend"]["layout"] == "state"
    assert s["linear_state"] == {
        "kind": "ssd", "layers": 3, "bytes_state": states.nbytes,
        "bytes_conv": tails.nbytes, "state_dtype": "float32", "step": "xla",
        "prefill": "chunked", "chunk": 8,
    }
    assert s["kv_pool"]["full_layers"] == 1 and s["kv_pool"]["read"] == "xla"
    assert s["kv_pool"]["bytes_full"] == pool_k.nbytes + pool_v.nbytes
    assert s["expert_layer"]["experts_held"] == [2, 4]
    srv.close()
    consumer.close()


def _greedy(forward, params, prompt, new):
    """Greedy continuation by the full forward, no cache and no state."""
    seq = np.zeros((1, P + NEW), np.int32)
    seq[0, : len(prompt)] = prompt
    for at in range(P, P + new):
        logits = forward(params, jnp.asarray(seq))
        seq[0, at] = int(jnp.argmax(logits[0, at - 1]))
    return seq[0, P: P + new].tolist()


def test_serving_through_the_state_is_the_full_forward(model):
    """Seven prompts through three slots, so that slots are admitted
    again over a used state, tail and rows: every completion is the full
    forward's greedy continuation of its padded prompt, and the tokens
    vary (the tied head does not repeat its input)."""
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
    assert s["kv_pool"]["full_positions_valid"] > 0
    assert s["kv_pool"]["full_positions_read"] >= (
        s["kv_pool"]["full_positions_valid"]
    )
    assert s["expert_layer"]["moe_local_assignments"] > 0
    assert s["expert_layer"]["moe_absent_assignments"] > 0
    srv.close()
    consumer.close()


def test_each_stated_mechanism_moves_the_logits(model):
    """A multiplier, the rotation, the head or the norm's eps left at its
    default is another function: none is silently dropped."""
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(4), (1, P), 1, VOCAB)
    base = np.asarray(Transformer(cfg)(params, tokens))
    untied = {**params, "lm_head": params["embed"].T}
    defaults = {
        f.name: f.default for f in dataclasses.fields(TransformerConfig)
    }
    for name in STATED:
        other = dataclasses.replace(cfg, **{name: defaults[name]})
        got = np.asarray(Transformer(other)(untied, tokens))
        moved = np.abs(got - base).max() / np.abs(base).max()
        if name == "tie_embeddings":  # the same head, held twice
            assert moved < 1e-6
        else:
            assert moved > (1e-5 if name == "norm_eps" else 1e-2), name


@pytest.mark.parametrize("kw,why", [
    (dict(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
          stated_head_dim=0, router_score="sigmoid"), "linear_kind='ssd'"),
    (dict(ssd_state_dim=0), "ssd_state_dim"),
    (dict(attn_gate=True), "no attn_gate"),
    (dict(linear_kind="mamba"), "linear_kind"),
    (dict(linear_kind="kda"), "beside latent attention"),
    (dict(linear_pattern=(True, True)), "linear AND attention"),
    (dict(linear_pattern=(), linear_kind="kda", ssd_heads=0, ssd_head_dim=0,
          ssd_state_dim=0, n_shared_experts=0, experts_held=None),
     "embedding_multiplier.*residual_multiplier.*tie_embeddings"),
    (dict(linear_pattern=(), n_shared_experts=0, experts_held=None, **{
        k: v for k, v in dataclasses.asdict(TransformerConfig(
            vocab_size=8, d_model=8, n_layers=1, n_heads=1, n_kv_heads=1,
            d_ff=8,
        )).items() if k in STATED
    }), "describe the linear layers"),
    (dict(first_dense_layers=1), "no leading dense layer"),
    (dict(zero_experts=1, norm_topk=False), "no zero"),
    (dict(logits_scaling=0.0), "logits_scaling"),
])
def test_a_config_that_is_not_built_says_why(kw, why):
    with pytest.raises(ValueError, match=why):
        ssd_cfg(**kw)


@pytest.mark.parametrize("kw", [
    dict(use_rope=False), dict(attention_multiplier=0.125),
])
def test_latent_attention_keeps_its_positions_and_its_scale(kw):
    with pytest.raises(ValueError, match="built for grouped-query layers"):
        hybrid_cfg(**kw)
    assert hybrid_cfg(residual_multiplier=0.5).is_mla  # (the others are built)


def test_a_shared_expert_beside_gqa_needs_the_hybrid_and_a_share_does_not():
    """Beside grouped-query attention outside a hybrid a shared expert is
    still the hybrid's alone; a held share is taken (PR 49: one chip's
    share of a grouped-query expert model)."""
    plain = {k: v for k, v in dataclasses.asdict(ssd_cfg()).items()}
    plain.update(
        linear_pattern=(), linear_kind="kda", ssd_heads=0, ssd_head_dim=0,
        ssd_state_dim=0, embedding_multiplier=1.0, residual_multiplier=1.0,
        attention_multiplier=0.0, logits_scaling=1.0, use_rope=True,
        tie_embeddings=False, norm_eps=1e-6, window_pattern=(False,),
    )
    with pytest.raises(ValueError, match="linear_pattern model alone"):
        TransformerConfig(**plain)
    plain.update(n_shared_experts=0)
    share = TransformerConfig(**plain)
    assert share.routed_moe and share.moe_partial
    assert share.held_experts == tuple(plain["experts_held"])
    with pytest.raises(ValueError, match="linear_pattern model alone"):
        TransformerConfig(**{**plain, "n_shared_experts": 1})
    plain.update(experts_held=None)
    assert TransformerConfig(**plain).routed_moe


def test_a_config_without_the_new_fields_is_what_it_was():
    fields = {f.name: f.default for f in dataclasses.fields(TransformerConfig)}
    assert fields["linear_kind"] == "kda" and fields["ssd_heads"] == 0
    assert (fields["embedding_multiplier"], fields["residual_multiplier"],
            fields["attention_multiplier"], fields["logits_scaling"]) == (
        1.0, 1.0, 0.0, 1.0,
    )
    assert fields["use_rope"] and not fields["tie_embeddings"]
    assert fields["norm_eps"] == 1e-6


REASONS = {
    "kv_dtype=int8": "float32 recurrent state",
    "kv_kernel=True": "tk_ssd_step",
    "kv_pages": "a state a slot, not rows a position",
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_what_cannot_hold_a_state_refuses_by_the_mechanism(model, what):
    cfg, params = model
    why = REASONS.get(what, "state-space layers")
    with pytest.raises(ValueError, match=why) as e:
        REFUSALS[what](cfg, params)
    assert "linear_kind" in str(e.value) and "ssd" in str(e.value)


def test_a_journal_hint_is_not_warm_resumed(model):
    cfg, params = model
    srv, consumer, _ = _server(cfg, params)
    assert srv._resume_supported() is False and srv._resume_exec is None
    srv.close()
    consumer.close()
