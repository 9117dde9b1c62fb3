"""Linear-attention (KDA) layers beside latent ones through the program:
the slot memory by kind, serving through the recurrent state against the
full forward, a re-admitted slot, group-limited selection, and every path
that cannot hold a state refusing it by the mechanism's name."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchkafka_tpu as tk
from torchkafka_tpu.models import Transformer, TransformerConfig
from torchkafka_tpu.models.generate import generate, prefill
from torchkafka_tpu.models.transformer import init_params, make_train_step
from torchkafka_tpu.ops import moe
from torchkafka_tpu.serve import StreamingGenerator

P, NEW, VOCAB = 16, 12, 512


def hybrid_cfg(**kw) -> TransformerConfig:
    base = dict(
        vocab_size=VOCAB, d_model=128, n_layers=4, n_heads=2, n_kv_heads=2,
        d_ff=96, max_seq_len=P + NEW, dtype=jnp.float32,
        param_dtype=jnp.float32, kv_lora_rank=32, qk_nope_dim=16,
        qk_rope_dim=8, v_head_dim=16, rope_interleave=True,
        first_dense_layers=1, n_experts=8, expert_top_k=2, expert_d_ff=48,
        n_shared_experts=1, router_score="sigmoid", routed_scaling=2.5,
        experts_held=(0, 4), linear_pattern=(True, True, False),
        linear_head_dim=128, attn_gate=True, n_group=2, topk_group=1,
    )
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = hybrid_cfg()
    params = init_params(jax.random.key(0), cfg)
    # Decays of every speed, so that the state matters over the window.
    for group in ("dense_layers", "layers"):
        dt = params[group]["l_dt"]
        params[group]["l_dt"] = jax.random.uniform(
            jax.random.key(len(group)), dt.shape, minval=-6.0, maxval=2.0
        )
    return cfg, params


def _server(cfg, params, slots=3, n=0, **kw):
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    rows = np.asarray(
        jax.random.randint(jax.random.key(9), (max(n, 1), P), 1, VOCAB)
    ).astype(np.int32)
    for i in range(n):
        broker.produce("p", rows[i, : 6 + i % 9].tobytes())
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    kw.setdefault("max_new", NEW)
    return StreamingGenerator(
        consumer, params, cfg, slots=slots, prompt_len=P, **kw
    ), consumer, rows


_FORWARD: dict = {}


def _greedy(cfg, params, prompt, new):
    """Greedy continuation by the full forward, no cache and no state:
    every layer is causal, so the row is run at one length and read at
    the last real position."""
    if cfg not in _FORWARD:
        _FORWARD[cfg] = jax.jit(Transformer(cfg).__call__)
    seq = np.zeros((1, P + NEW), np.int32)
    seq[0, : len(prompt)] = prompt
    for at in range(P, P + new):
        logits = _FORWARD[cfg](params, jnp.asarray(seq))
        seq[0, at] = int(jnp.argmax(logits[0, at - 1]))
    return seq[0, P: P + new].tolist()


def test_the_slot_memory_is_allocated_by_kind(model):
    cfg, params = model
    srv, consumer, _ = _server(cfg, params)
    states, tails, pool = srv.cache_tensors
    assert states.shape == (3, 3, 2, 128, 128) and states.dtype == jnp.float32
    assert tails.shape == (3, 3, 3, 3 * 2 * 128) and tails.dtype == cfg.dtype
    assert pool.shape == (1, 3, P + NEW, 32 + 8)
    s = srv.metrics.summary()
    assert s["kv_backend"]["layout"] == "state"
    assert s["linear_state"] == {
        "kind": "kda", "layers": 3, "bytes_state": states.nbytes,
        "bytes_conv": tails.nbytes, "state_dtype": "float32", "step": "xla",
        "prefill": "chunked", "chunk": 64,
    }
    assert s["expert_layer"]["groups"] == {"n_group": 2, "topk_group": 1}
    assert s["expert_layer"]["experts_held"] == [0, 4]
    assert cfg.cache_layers == 1 and cfg.hybrid_layers(True) == 3
    # What prefill hands over: the states and tails after the window and
    # the latent layer's rows over it, not pools.
    _logits, kept = prefill(params, cfg, jnp.zeros((2, P), jnp.int32), P + NEW)
    assert [k.shape for k in kept] == [
        (3, 2, 2, 128, 128), (3, 2, 3, 768), (1, 2, P, 40),
    ]
    srv.close()
    consumer.close()


def test_serving_through_the_state_is_the_full_forward(model):
    """Prefill, then decode through the slots' states, conv tails and the
    latent pool: in float32 every served token is the greedy choice of
    the full forward over the padded row, slots re-admitted along the way
    (seven records through three slots)."""
    cfg, params = model
    srv, consumer, rows = _server(cfg, params, n=7, ticks_per_sync=4)
    got = {
        rec.offset: toks for rec, toks in srv.run(max_records=7, idle_timeout_ms=200)
    }
    assert len(got) == 7
    for off, toks in got.items():
        want = _greedy(cfg, params, rows[off, : 6 + off % 9], NEW)
        np.testing.assert_array_equal(toks, want)
    s = srv.metrics.summary()
    assert s["latent_pool"]["latent_positions_valid"] > 0
    local = s["expert_layer"]["moe_local_assignments"]
    assert 0 < local < s["expert_layer"]["moe_assignments"]
    srv.close()
    consumer.close()


def test_a_readmitted_slot_carries_nothing_of_its_predecessor(model):
    """One slot serves two records in a row. The second's state after its
    admission is the admission's own (what ``prefill`` gives its padded
    prompt alone), whatever the first left behind; its tokens are the
    full forward's."""
    cfg, params = model
    srv, consumer, rows = _server(cfg, params, slots=1, n=2, ticks_per_sync=4)
    recs = consumer.poll(max_records=2, timeout_ms=200)
    assert srv.admit_records(recs[:1]) == 1
    done = []
    while not done:
        done = srv.step()
    left_behind = np.asarray(srv.cache_tensors[0])
    assert np.abs(left_behind).max() > 0
    assert srv.admit_records(recs[1:]) == 1
    states, tails, _pool = (np.asarray(c) for c in srv.cache_tensors)
    second = np.pad(rows[1, :7], (0, P - 7))
    _logits, kept = prefill(params, cfg, jnp.asarray([second]), P + NEW)
    np.testing.assert_allclose(states[:, 0], np.asarray(kept[0])[:, 0], atol=1e-5)
    np.testing.assert_allclose(tails[:, 0], np.asarray(kept[1])[:, 0], atol=1e-5)
    assert np.abs(states - left_behind).max() > 1e-3
    done = []
    while not done:
        done = srv.step()
    np.testing.assert_array_equal(
        done[0][1], _greedy(cfg, params, rows[1, :7], NEW)
    )
    srv.close()
    consumer.close()


def test_the_step_kernel_serves_the_same_tokens(model, monkeypatch):
    """The tick with ``tk_kda_step`` (under the Pallas interpreter) in
    place of the ``jax.numpy`` step: the same served tokens."""
    from torchkafka_tpu.models import linear_attn
    from torchkafka_tpu.ops import kda

    cfg, params = model
    monkeypatch.setattr(linear_attn, "step_form", lambda: "kernel")
    monkeypatch.setattr(
        kda, "kda_step",
        lambda *a, _k=kda.kda_step: _k(*a, interpret=True),
    )
    srv, consumer, rows = _server(cfg, params, n=3, ticks_per_sync=4, max_new=6)
    assert srv.metrics.summary()["linear_state"]["step"] == "kernel"
    got = {
        rec.offset: toks for rec, toks in srv.run(max_records=3, idle_timeout_ms=200)
    }
    for off, toks in got.items():
        np.testing.assert_array_equal(
            toks, _greedy(cfg, params, rows[off, : 6 + off % 9], 6)
        )
    srv.close()
    consumer.close()


def test_group_selection_against_a_case_by_hand():
    """8 experts in 2 groups of 4, one group kept, top 2. The scores put
    the two best experts in group 1 (0.9, 0.6: group score 1.5) but the
    best PAIR in group 0 (0.8 + 0.75 = 1.55): group 0 stays and the pick
    is its two best, weights their scores over their sum times the
    scaling; without groups the pick is the global top 2."""
    scores = jnp.array([[0.8, 0.75, 0.1, 0.1, 0.9, 0.6, 0.1, 0.1]])
    logits = jnp.log(scores / (1 - scores))
    # A row of ones through a diagonal router: the logits themselves.
    h, router = jnp.ones((1, 8)), jnp.diag(logits[0])
    idx, w = moe.route(
        h, router, None, top_k=2, scaling=2.5, n_group=2, topk_group=1
    )
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1]
    order = np.argsort(np.asarray(idx[0]))
    np.testing.assert_allclose(
        np.asarray(w[0])[order], 2.5 * np.array([0.8, 0.75]) / 1.55, rtol=1e-5
    )
    idx, _w = moe.route(h, router, None, top_k=2, scaling=2.5)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 4]
    # The bias moves the selection, groups and all, and no weight: +0.2 on
    # expert 5 lifts group 1 to 1.7.
    bias = jnp.zeros((8,)).at[5].set(0.2)
    idx, w = moe.route(
        h, router, bias, top_k=2, scaling=1.0, n_group=2, topk_group=1
    )
    assert sorted(np.asarray(idx[0]).tolist()) == [4, 5]
    np.testing.assert_allclose(float(w.sum()), 1.0, rtol=1e-5)


def test_no_groups_is_the_program_it_was():
    """``n_group`` 1: the jaxpr of ``route`` holds nothing of the groups."""
    h, router = jnp.ones((4, 8)), jnp.ones((8, 16))

    def text(**kw):
        return str(jax.make_jaxpr(
            lambda h, r: moe.route(h, r, None, top_k=2, scaling=1.0, **kw)
        )(h, router))

    assert text() == text(n_group=1, topk_group=1)
    assert text() != text(n_group=4, topk_group=2)


def _mesh2():
    from torchkafka_tpu.parallel import make_mesh

    return make_mesh({"data": 2}, devices=jax.devices()[:2])


def _build(c, p, **kw):
    return _server(c, p, **kw)[0]


REFUSALS = {
    "kv_dtype=int8": lambda c, p: _build(c, p, kv_dtype="int8"),
    "kv_kernel=True": lambda c, p: _build(c, p, kv_kernel=True),
    "kv_pages": lambda c, p: _build(
        c, p, kv_pages={"block_size": 4, "num_blocks": 16}
    ),
    "kv_tier": lambda c, p: _build(
        c, p, kv_pages={"block_size": 4, "num_blocks": 16},
        kv_tier={"capacity_bytes": 1 << 20},
    ),
    "prefill_role": lambda c, p: _build(
        c, p, kv_pages={"block_size": 4, "num_blocks": 16}, prefill_role=True
    ),
    "mesh": lambda c, p: _build(c, p, mesh=_mesh2()),
    "model on a mesh": lambda c, p: Transformer(c, _mesh2()),
    "speculative": lambda c, p: __import__(
        "torchkafka_tpu.serve_spec", fromlist=["x"]
    ).SpecStreamingGenerator(None, p, c, slots=2, prompt_len=P, max_new=NEW),
    "generate": lambda c, p: generate(p, c, jnp.zeros((1, P), jnp.int32), 4),
    "make_train_step": lambda c, p: make_train_step(c, _mesh2(), None),
    "param_specs": lambda c, p: __import__(
        "torchkafka_tpu.models.transformer", fromlist=["x"]
    ).param_specs(c),
    "quantize_params": lambda c, p: __import__(
        "torchkafka_tpu.models.quant", fromlist=["x"]
    ).quantize_params(p, c),
}
STATE = "linear-attention layers"
REASONS = {
    "kv_dtype=int8": "float32 recurrent state",
    "kv_kernel=True": "tk_kda_step",
    "kv_pages": "a state a slot, not rows a position",
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_what_cannot_hold_a_state_refuses_by_the_mechanism(model, what):
    cfg, params = model
    with pytest.raises(ValueError, match=REASONS.get(what, STATE)) as e:
        REFUSALS[what](cfg, params)
    assert "linear" in str(e.value)


def test_a_journal_hint_is_not_warm_resumed(model):
    """Warm resume rebuilds a slot's K and V from tokens; no resume
    program is built for a state, and a hint falls back to cold replay."""
    cfg, params = model
    srv, consumer, _ = _server(cfg, params)
    assert srv._resume_supported() is False and srv._resume_exec is None
    srv.close()
    consumer.close()


@pytest.mark.parametrize("kw,why", [
    (dict(kv_lora_rank=0, qk_nope_dim=0, qk_rope_dim=0, v_head_dim=0,
          rope_interleave=False, experts_held=None, n_experts=0,
          n_shared_experts=0, expert_d_ff=0, routed_scaling=1.0,
          first_dense_layers=0, n_group=1, attn_gate=False,
          router_score="softmax"), "beside latent attention"),
    (dict(linear_pattern=(True, True)), "linear AND attention"),
    (dict(linear_pattern=(True, False)), "divides the layers"),
    (dict(linear_pattern=()), "attn_gate"),
    (dict(n_group=3), "n_group"),
    (dict(n_group=2, topk_group=3), "topk_group"),
    (dict(n_group=4, topk_group=1, expert_top_k=3), "expert_top_k"),
])
def test_a_config_that_is_not_built_says_why(kw, why):
    with pytest.raises(ValueError, match=why):
        hybrid_cfg(**kw)


def test_a_config_without_the_new_fields_is_what_it_was():
    fields = {f.name: f.default for f in dataclasses.fields(TransformerConfig)}
    assert fields["linear_pattern"] == () and fields["attn_gate"] is False
    assert (fields["n_group"], fields["topk_group"]) == (1, 1)


def test_an_admission_derives_its_records_keys_in_one_dispatch(model):
    """``_records_key_data``: every record's sampling key as three eager
    folds of its identity give it, bit for bit, for a typed and a raw
    base key, from ONE program whose shape is the slots' (a window
    compiles nothing however many records an admission takes)."""
    import zlib

    from torchkafka_tpu import serve

    def a_record_at_a_time(rng, rec):
        k = jax.random.fold_in(rng, zlib.crc32(rec.topic.encode()) & 0x7FFFFFFF)
        k = jax.random.fold_in(k, rec.partition & 0x7FFFFFFF)
        k = jax.random.fold_in(k, rec.offset & 0x7FFFFFFF)
        return np.asarray(jax.random.key_data(k), np.uint32)

    cfg, params = model
    for rng in (jax.random.key(7), jax.random.PRNGKey(7)):
        srv, consumer, _ = _server(
            cfg, params, slots=5, n=9, rng=rng, temperature=1.0
        )
        recs = consumer.poll(max_records=5, timeout_ms=200)
        before = serve._fold_record_ids._cache_size()
        some = srv._records_key_data(recs[:2])
        every = srv._records_key_data(recs)
        assert serve._fold_record_ids._cache_size() <= before + 1
        assert srv._records_key_data([]) == {}
        for rec in recs:
            at = (rec.topic, rec.partition, rec.offset)
            np.testing.assert_array_equal(every[at], a_record_at_a_time(rng, rec))
            assert every[at].dtype == np.uint32
        np.testing.assert_array_equal(
            some[(recs[1].topic, recs[1].partition, recs[1].offset)],
            every[(recs[1].topic, recs[1].partition, recs[1].offset)],
        )
        srv.close()
        consumer.close()
