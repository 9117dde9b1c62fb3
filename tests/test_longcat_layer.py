"""The shortcut-connected double layer (``attn_blocks`` 2), compressed
queries with the two scale factors, zero-compute experts behind a softmax
router, and an expert layer that holds a share of the experts: at a tiny
size on the CPU in float32, against the model's own full forward and
against the layer's mathematics written out by hand. The plain reference
of the benchmark holds the same in ``tests/chipbench/
test_chipbench_longcat.py``.

Tolerances as ``tests/test_mla.py``: the absorbed read and the compacted
expert sum reorder float32 additions; 2e-5 absolute on values of order one
is 1,000 times below what a wrong pairing, scale, row or range gives.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchkafka_tpu as tk
from torchkafka_tpu.models import mla
from torchkafka_tpu.models.generate import generate, latent_forward, prefill
from torchkafka_tpu.models.quant import quantize_params
from torchkafka_tpu.models.transformer import (
    Transformer, TransformerConfig, _rms_norm, _rope, init_params,
    make_train_step,
)
from torchkafka_tpu.ops import moe
from torchkafka_tpu.kvcache.slot_pool import _slot_layer_step_latent
from torchkafka_tpu.serve import StreamingGenerator

P, NEW, VOCAB = 8, 8, 64
TOL = 2e-5


def double_cfg(**over) -> TransformerConfig:
    base = dict(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=48, max_seq_len=P + NEW, dtype=jnp.float32, kv_lora_rank=16,
        qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, rope_interleave=True,
        q_lora_rank=12, mla_scale_q_lora=True, mla_scale_kv_lora=True,
        attn_blocks=2, n_experts=8, zero_experts=4, expert_top_k=3,
        expert_d_ff=12, router_score="softmax", norm_topk=False,
        routed_scaling=6.0, experts_held=(2, 4),
    )
    base.update(over)
    return TransformerConfig(**base)


def with_bias(params, seed=4, sigma=0.02):
    """A selection bias that matters (init leaves it at zero)."""
    bias = params["layers"]["router_bias"]
    params = dict(params, layers=dict(params["layers"]))
    params["layers"]["router_bias"] = sigma * jax.random.normal(
        jax.random.key(seed), bias.shape
    )
    return params


@pytest.fixture(scope="module")
def model():
    cfg = double_cfg()
    return cfg, with_bias(init_params(jax.random.key(3), cfg))


def _server(cfg, params, **kw):
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    kw.setdefault("slots", 3)
    return StreamingGenerator(
        consumer, params, cfg, prompt_len=P, max_new=NEW, **kw
    ), consumer, broker


# ------------------------------------------------------------ the layout


def test_the_parameters_are_stacked_by_block_and_by_held_expert(model):
    cfg, params = model
    shapes = jax.tree.map(lambda a: a.shape, params["layers"])
    assert shapes == {
        "ln1": (2, 2, 32), "ln2": (2, 2, 32), "wqa": (2, 2, 32, 12),
        "q_norm": (2, 2, 12), "wqb": (2, 2, 12, 2, 12),
        "wkva": (2, 2, 32, 20), "kv_norm": (2, 2, 16),
        "wkvb": (2, 2, 16, 2, 16), "wo": (2, 2, 2, 8, 32),
        "w_gate": (2, 2, 32, 48), "w_up": (2, 2, 32, 48),
        "w_down": (2, 2, 48, 32),
        # The router keeps every output; the experts are the 4 held.
        "router": (2, 32, 12), "router_bias": (2, 12),
        "we_gate": (2, 4, 32, 12), "we_up": (2, 4, 32, 12),
        "we_down": (2, 4, 12, 32),
    }
    assert cfg.cache_layers == 4 and cfg.router_width == 12
    assert cfg.held_experts == (2, 4) and cfg.moe_partial
    # Kanana's kind is laid out as it was.
    plain = TransformerConfig(
        d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, kv_lora_rank=16,
        qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, n_experts=8,
        router_score="sigmoid", expert_d_ff=12, vocab_size=VOCAB,
    )
    layers = init_params(jax.random.key(0), plain)["layers"]
    assert layers["w_gate"].shape == (2, 8, 32, 12)
    assert layers["wq"].shape == (2, 32, 2, 12) and "wqa" not in layers
    assert plain.cache_layers == 2 and not plain.moe_partial


# ------------------------------------- (a) prefill, then decode, the pool


def decode_through_pool(cfg, params, tokens, prompt_lens):
    """``tests/test_mla.py::decode_through_pool`` for a pool of a row a
    block: rows prefilled to their own prompt length, then decoded token
    by token, a different position a row."""
    b, t = tokens.shape
    pool = jnp.zeros((cfg.cache_layers, b, t, cfg.latent_dim), cfg.dtype)
    model = Transformer(cfg)
    for row, n in enumerate(prompt_lens):
        _logits, rows, _rt = latent_forward(
            params, model, jnp.asarray(tokens[row: row + 1, :n])
        )
        assert rows.shape == (cfg.cache_layers, 1, n, cfg.latent_dim)
        pool = pool.at[:, row, :n].set(rows[:, 0])
    out = np.full((b, t, cfg.vocab_size), np.nan, np.float32)
    pos = np.asarray(prompt_lens)
    while (pos < t).any():
        live = pos < t
        at = np.minimum(pos, t - 1)
        x = params["embed"][jnp.asarray(tokens[np.arange(b), at])][:, None, :]
        for i in range(cfg.n_layers):
            layer = jax.tree.map(lambda a: a[i], params["layers"])
            x, pool, routing = _slot_layer_step_latent(
                x, layer, pool, i, jnp.asarray(at), cfg
            )
            assert routing.shape == (b, 1, cfg.expert_top_k)
        logits = _rms_norm(x, params["ln_f"])[:, 0] @ params["lm_head"]
        for row in np.nonzero(live)[0]:
            out[row, at[row]] = np.asarray(logits[row])
        pos = pos + live
    return out


@pytest.mark.parametrize("prompt_lens", [[3, 8, 5], [8, 1, 8]], ids=str)
def test_decode_through_the_pool_equals_the_full_forward(model, prompt_lens):
    cfg, params = model
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, VOCAB, (3, P + NEW), dtype=np.int32)
    got = decode_through_pool(cfg, params, tokens, prompt_lens)
    want = np.asarray(Transformer(cfg)(params, jnp.asarray(tokens)))
    for row, n in enumerate(prompt_lens):
        np.testing.assert_allclose(got[row, n:], want[row, n:], atol=TOL, rtol=0)


def branch_by_hand(cfg, layer, m):
    """The expert branch on ``m``: a loop over the choices and the held
    experts; a zero expert is the identity, an absent one adds nothing."""
    p = jax.nn.softmax(m @ layer["router"], -1)
    _, sel = jax.lax.top_k(p + layer["router_bias"], cfg.expert_top_k)
    w = jnp.take_along_axis(p, sel, -1) * cfg.routed_scaling
    first, count = cfg.held_experts
    out = jnp.zeros_like(m)
    for k in range(cfg.expert_top_k):
        e, wk = sel[..., k], w[..., k: k + 1]
        out = out + jnp.where((e >= cfg.n_experts)[..., None], wk * m, 0.0)
        for j in range(count):
            g = jax.nn.silu(m @ layer["we_gate"][j]) * (m @ layer["we_up"][j])
            out = out + jnp.where(
                (e == first + j)[..., None], wk * (g @ layer["we_down"][j]), 0.0
            )
    return out


def layer_by_hand(x, layer, cfg, positions):
    """ISSUE 31's equations for one double layer on [B, S, D], with the
    expert branch by a loop over the held experts. Returns (y, the
    branch's input m, the branch s)."""
    s_len = x.shape[1]
    causal = jnp.tril(jnp.ones((s_len, s_len), bool))

    def mla_block(u, i):
        blk = jax.tree.map(lambda a: a[i], {
            n: layer[n] for n in ("wqa", "q_norm", "wqb", "wkva", "kv_norm",
                                  "wkvb", "wo")
        })
        cq = _rms_norm(u @ blk["wqa"], blk["q_norm"]) * math.sqrt(32 / 12)
        q = jnp.einsum("bsq,qhe->bshe", cq, blk["wqb"])
        q_nope, q_rope = q[..., :8], _rope(q[..., 8:], positions, cfg.rope_theta, True)
        kva = u @ blk["wkva"]
        c = _rms_norm(kva[..., :16], blk["kv_norm"]) * math.sqrt(32 / 16)
        k_r = _rope(kva[..., None, 16:], positions, cfg.rope_theta, True)[:, :, 0]
        kv = jnp.einsum("bsr,rhe->bshe", c, blk["wkvb"])
        k_nope, v = kv[..., :8], kv[..., 8:]
        sc = jnp.einsum("bqhe,bkhe->bhqk", q_nope, k_nope) + jnp.einsum(
            "bqhe,bke->bhqk", q_rope, k_r
        )
        p = jax.nn.softmax(jnp.where(causal, sc / math.sqrt(12), -1e30), -1)
        out = jnp.einsum("bhqk,bkhe->bqhe", p, v)
        return jnp.einsum("bshe,hed->bsd", out, blk["wo"])

    def ffn(h, i):
        g = jax.nn.silu(h @ layer["w_gate"][i]) * (h @ layer["w_up"][i])
        return g @ layer["w_down"][i]

    a0 = x + mla_block(_rms_norm(x, layer["ln1"][0]), 0)
    m = _rms_norm(a0, layer["ln2"][0])
    s = branch_by_hand(cfg, layer, m)
    b0 = a0 + ffn(m, 0)
    a1 = b0 + mla_block(_rms_norm(b0, layer["ln1"][1]), 1)
    return a1 + ffn(_rms_norm(a1, layer["ln2"][1]), 1) + s, m, s


def test_the_double_layer_is_the_issue_s_equations(model):
    cfg, params = model
    x = jax.random.normal(jax.random.key(7), (2, P, 32))
    layer = jax.tree.map(lambda a: a[1], params["layers"])
    got, _stats, (latents, routing) = Transformer(cfg)._layer_capture(x, layer)
    want, _m, _s = layer_by_hand(x, layer, cfg, jnp.arange(P))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert latents.shape == (2, 2, P, 20) and routing.shape == (2, P, 3)


# ------------------------------------------- (c) zero experts, the share


def branch(cfg, layer, m):
    out, idx = moe.routed_moe_mlp(m, layer, cfg, experts=(
        *(layer[n] for n in ("we_gate", "we_up", "we_down")), 0
    ))
    return np.asarray(out), np.asarray(idx)


def test_a_token_whose_choices_are_all_zero_experts_gets_w_times_m(model):
    cfg, params = model
    layer = jax.tree.map(lambda a: a[0], params["layers"])
    # A bias that puts the four zero experts (outputs 8..11) first.
    layer = dict(layer, router_bias=jnp.where(jnp.arange(12) >= 8, 5.0, 0.0))
    m = jax.random.normal(jax.random.key(1), (2, 5, 32))
    out, idx = branch(cfg, layer, m)
    assert (idx >= cfg.n_experts).all()
    p = jax.nn.softmax(m @ layer["router"], -1)
    w = np.take_along_axis(np.asarray(p), idx, -1).sum(-1) * cfg.routed_scaling
    np.testing.assert_allclose(out, w[..., None] * np.asarray(m), rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("rows", [2, 64], ids=["a-tick-s-rows", "a-trip-s-rows"])
def test_the_shares_add_up_to_the_uncut_layer(model, rows):
    """Experts [0, 4) and [4, 8) held in turn: their parts summed, the
    zero experts' term counted once, are the branch of a layer that holds
    all eight; and each part is the by-hand sum over its own range."""
    cfg, params = model
    full_cfg = double_cfg(experts_held=None)
    full = init_params(jax.random.key(9), full_cfg)["layers"]
    full = jax.tree.map(lambda a: a[0], with_bias({"layers": full})["layers"])
    m = jax.random.normal(jax.random.key(2), (1, rows, 32))
    whole, idx = branch(full_cfg, full, m)
    zero_cfg = double_cfg(experts_held=(0, 1))
    none = dict(full, **{n: jnp.zeros_like(full[n][:1]) for n in
                         ("we_gate", "we_up", "we_down")})
    zero_term, _ = branch(zero_cfg, none, m)  # a held expert that adds 0
    parts = []
    for first in (0, 4):
        c = double_cfg(experts_held=(first, 4))
        held = dict(full, **{n: full[n][first: first + 4] for n in
                             ("we_gate", "we_up", "we_down")})
        part, part_idx = branch(c, held, m)
        assert (part_idx == idx).all()  # the router is not cut
        parts.append(part - zero_term)
    np.testing.assert_allclose(
        zero_term + parts[0] + parts[1], whole, atol=TOL, rtol=0
    )
    assert np.abs(parts[0]).max() > 1e-3 and np.abs(parts[1]).max() > 1e-3


def test_a_layer_holding_every_expert_takes_its_own_out_of_the_stack():
    """No zero experts, every expert held: the parent's forms (grouped,
    all-experts) on the layer's experts, which inside the layer scan are
    rows ``[l * E, (l + 1) * E)`` of every layer's stack."""
    cfg = double_cfg(zero_experts=0, experts_held=None)
    assert not cfg.moe_partial
    params = with_bias(init_params(jax.random.key(2), cfg))
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, VOCAB, (2, P)))
    model = Transformer(cfg)
    want = model(params, tokens)  # the scan over the stacked layers
    x = params["embed"][tokens]
    for i in range(cfg.n_layers):  # a layer's own slice at a time
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x, _stats, _capture = model._layer_capture(x, layer)
    got = _rms_norm(x, params["ln_f"]) @ params["lm_head"]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_the_compaction_drops_no_pair_at_any_routing(model):
    """Every row to ONE held expert (what a bound on the local pairs
    would have to hold): the tiles are as many as that expert needs."""
    cfg, params = model
    layer = jax.tree.map(lambda a: a[0], params["layers"])
    bias = jnp.zeros((12,)).at[3].set(9.0)  # expert 3: held (2, 4) -> local 1
    layer = dict(layer, router_bias=bias)
    m = jax.random.normal(jax.random.key(5), (1, 200, 32))
    out, idx = branch(cfg, layer, m)
    assert (idx == 3).any(-1).all()
    np.testing.assert_allclose(
        out, branch_by_hand(cfg, layer, m), atol=TOL, rtol=0
    )
    # cap is 16 x ceil(2 * 600 / 12 / 16) = 112 rows a tile: 200 rows to one
    # expert are two tiles.
    sizes = np.bincount(idx.reshape(-1), minlength=12)[2:6]
    assert sizes.max() == 200 and -(-2 * 600 // 12 // 16) * 16 == 112


@pytest.mark.parametrize("score,norm", [
    ("softmax", False), ("sigmoid", True), ("sigmoid", False),
])
def test_route_by_score_kind(score, norm):
    h = jax.random.normal(jax.random.key(0), (6, 16))
    router = jax.random.normal(jax.random.key(1), (16, 10)) / 4
    bias = 0.05 * jax.random.normal(jax.random.key(2), (10,))
    idx, w = moe.route(h, router, bias, top_k=3, scaling=2.5, score=score,
                       norm_topk=norm)
    logits = np.asarray(h, np.float64) @ np.asarray(router, np.float64)
    if score == "softmax":
        s = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    else:
        s = 1 / (1 + np.exp(-logits))
    want = np.argsort(-(s + np.asarray(bias)), -1)[:, :3]
    assert (np.asarray(idx) == want).all()
    picked = np.take_along_axis(s, want, -1)
    if norm:
        picked = picked / picked.sum(-1, keepdims=True)
    np.testing.assert_allclose(w, picked * 2.5, rtol=1e-5)


# -------------------------------------------- (d) compressed queries, scales


@pytest.mark.parametrize("scale_q,scale_kv", [
    (True, True), (True, False), (False, True), (False, False),
])
def test_project_with_compressed_queries_and_each_scale(scale_q, scale_kv):
    cfg = double_cfg(mla_scale_q_lora=scale_q, mla_scale_kv_lora=scale_kv)
    layer = jax.tree.map(
        lambda a: a[0, 1], {
            n: v for n, v in init_params(jax.random.key(1), cfg)["layers"].items()
            if n in ("wqa", "q_norm", "wqb", "wkva", "kv_norm", "wkvb")
        },
    )
    h = jax.random.normal(jax.random.key(2), (2, 5, 32))
    pos = jnp.arange(5)
    q_nope, q_rope, latent = mla.project(h, layer, cfg, pos)
    cq = _rms_norm(h @ layer["wqa"], layer["q_norm"])
    cq = cq * (math.sqrt(32 / 12) if scale_q else 1.0)
    q = jnp.einsum("bsq,qhe->bshe", cq, layer["wqb"])
    kva = h @ layer["wkva"]
    c = _rms_norm(kva[..., :16], layer["kv_norm"])
    c = c * (math.sqrt(32 / 16) if scale_kv else 1.0)
    np.testing.assert_allclose(q_nope, q[..., :8], atol=1e-6)
    np.testing.assert_allclose(
        q_rope, _rope(q[..., 8:], pos, cfg.rope_theta, True), atol=1e-6
    )
    np.testing.assert_allclose(latent[..., :16], c, atol=1e-6)
    np.testing.assert_allclose(
        latent[..., 16:],
        _rope(kva[..., None, 16:], pos, cfg.rope_theta, True)[:, :, 0], atol=1e-6,
    )
    if scale_kv:  # the SCALED latent is what is cached
        assert float(jnp.abs(latent[..., :16]).mean()) > 1.2 * float(
            jnp.abs(_rms_norm(kva[..., :16], layer["kv_norm"])).mean()
        )


def test_project_without_compression_is_what_it_was_bit_for_bit():
    """A config with no ``q_lora_rank``: ``project`` is PR 27's five
    lines, to the bit."""
    cfg = TransformerConfig(
        d_model=32, n_layers=1, n_heads=2, n_kv_heads=2, kv_lora_rank=16,
        qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, rope_interleave=True,
        vocab_size=VOCAB, dtype=jnp.bfloat16,
    )
    layer = jax.tree.map(
        lambda a: a[0].astype(jnp.bfloat16),
        init_params(jax.random.key(1), cfg)["layers"],
    )
    h = jax.random.normal(jax.random.key(2), (2, 5, 32), jnp.bfloat16)
    pos = jnp.asarray([[0, 1, 2, 3, 4], [3, 4, 5, 6, 7]])
    got = mla.project(h, layer, cfg, pos)
    q = jnp.einsum("bsd,dhe->bshe", h, layer["wq"])
    q_nope, q_rope = jnp.split(q, [8], axis=-1)
    kva = jnp.einsum("bsd,dc->bsc", h, layer["wkva"])
    c, k_r = jnp.split(kva, [16], axis=-1)
    c = _rms_norm(c, layer["kv_norm"])
    q_rope = _rope(q_rope, pos, cfg.rope_theta, True)
    k_r = _rope(k_r[:, :, None, :], pos, cfg.rope_theta, True)[:, :, 0, :]
    want = (q_nope, q_rope, jnp.concatenate([c, k_r], axis=-1))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and (np.asarray(g) == np.asarray(w)).all()


# --------------------------------------------------- (e) the pool, served


def test_the_pool_holds_a_row_a_block_and_a_block_reads_its_own(model, monkeypatch):
    cfg, params = model
    srv, consumer, _broker = _server(cfg, params)
    (pool,) = srv._caches
    assert pool.shape == (2 * cfg.n_layers, 3, P + NEW, 20)
    _logits, rows = prefill(params, cfg, jnp.zeros((3, P), jnp.int32), P + NEW)
    assert rows.shape == (2 * cfg.n_layers, 3, P, 20)
    srv.close()
    consumer.close()
    # Layer 1's step: block i writes and reads row 2 + i. With block 0's
    # row POISONED in the pool handed to block 1's read, the layer's
    # output does not move: block 1 never reads it.
    pool = jax.random.normal(jax.random.key(8), (4, 3, P + NEW, 20))
    x = jax.random.normal(jax.random.key(9), (3, 1, 32))
    layer = jax.tree.map(lambda a: a[1], params["layers"])
    pos = jnp.asarray([3, 9, 6])
    clean, pool_out, _rt = _slot_layer_step_latent(x, layer, pool, 1, pos, cfg)
    honest, seen = mla.attend_absorbed, []

    def poisoned(q_nope, q_rope, pool, l, pos_b, blk, cfg):
        seen.append(int(l))
        if len(seen) == 2:  # block 1's read
            pool = pool.at[2].set(jnp.nan).at[:2].set(jnp.nan)
        return honest(q_nope, q_rope, pool, l, pos_b, blk, cfg)

    monkeypatch.setattr(mla, "attend_absorbed", poisoned)
    got, _pool, _rt = _slot_layer_step_latent(x, layer, pool, 1, pos, cfg)
    assert seen == [2, 3]
    assert np.isfinite(np.asarray(got)).all()
    assert (np.asarray(got) == np.asarray(clean)).all()
    # The rows written are the two blocks' own, at each slot's position.
    changed = np.asarray((pool_out != pool).any(-1))  # [4, 3, M]
    assert not changed[:2].any()
    for slot, p in enumerate([3, 9, 6]):
        assert changed[2:, slot].sum(-1).tolist() == [1, 1]
        assert changed[2, slot, p] and changed[3, slot, p]


def test_served_tokens_counters_and_fates(model):
    """The double layer through ``StreamingGenerator.run()``: in float32
    the served tokens are the full forward's greedy choices, the commit
    watermark is exact, and the counters tell every pair's fate."""
    cfg, params = model
    srv, consumer, broker = _server(cfg, params, ticks_per_sync=3)
    rng = np.random.default_rng(11)
    prompts = rng.integers(1, VOCAB, (5, P), dtype=np.int32)
    for row in prompts:
        broker.produce("p", row.tobytes())
    served = {}
    for rec, toks in srv.run(max_records=5, idle_timeout_ms=200):
        served[rec.offset] = np.asarray(toks)
    fwd = jax.jit(Transformer(cfg).__call__)
    for off, row in enumerate(prompts):
        toks = list(row)
        for _ in range(NEW):
            toks.append(int(np.asarray(fwd(params, jnp.asarray([toks])))[0, -1].argmax()))
        assert served[off].tolist() == toks[P:]
    assert broker.committed("g", tk.TopicPartition("p", 0)) == 5
    s = srv.metrics.summary()
    e, sched = s["expert_layer"], s["scheduler"]
    assert e["experts_held"] == [2, 4] and s["latent_pool"]["attn_blocks"] == 2
    fates = (e["moe_zero_assignments"], e["moe_local_assignments"],
             e["moe_absent_assignments"])
    assert sum(fates) == e["moe_assignments"] > 0
    # Served slot-ticks x top-k x layers: no slot outruns its budget here.
    assert e["moe_assignments"] == sched["slot_ticks_served"] * 3 * cfg.n_layers
    assert all(f > 0 for f in fates)
    assert len(e["moe_expert_load"]) == 4
    assert sum(e["moe_expert_load"]) == e["moe_local_assignments"]
    # Rows needed and read count 2L blocks.
    need = sum(P + j for j in range(1, NEW)) * 5 * cfg.cache_layers
    assert s["latent_pool"]["latent_positions_valid"] == need
    assert s["latent_pool"]["latent_positions_read"] % (
        cfg.cache_layers * 3 * 3 * (P + NEW)
    ) == 0
    text = srv.metrics.render_prometheus()
    assert "moe_zero_assignments_total" in text and "experts_held" not in text
    srv.close()
    consumer.close()


# ------------------------------------------------------- (f) the refusals


def _mesh2():
    from torchkafka_tpu.parallel import make_mesh

    return make_mesh({"data": 2}, devices=jax.devices()[:2])


REFUSALS = {
    "q rank without latent attention": (
        lambda c, p: TransformerConfig(q_lora_rank=8), "describe latent attention"),
    "blocks without latent attention": (
        lambda c, p: TransformerConfig(attn_blocks=2), "describe latent attention"),
    "kv scale without latent attention": (
        lambda c, p: TransformerConfig(mla_scale_kv_lora=True),
        "describe latent attention"),
    "q scale without compression": (
        lambda c, p: double_cfg(q_lora_rank=0), "needs q_lora_rank > 0"),
    "three blocks": (lambda c, p: double_cfg(attn_blocks=3), "must be 1 or 2"),
    "double layer with a leading dense layer": (
        lambda c, p: double_cfg(n_layers=3, first_dense_layers=1),
        "shortcut-connected double layer as built"),
    "double layer with shared experts": (
        lambda c, p: double_cfg(n_shared_experts=1),
        "shortcut-connected double layer as built"),
    "double layer without experts": (
        lambda c, p: double_cfg(
            n_experts=0, zero_experts=0, experts_held=None, expert_d_ff=0,
            routed_scaling=1.0, norm_topk=True, expert_top_k=2,
        ), "shortcut-connected double layer as built"),
    "softmax scores renormalised": (
        lambda c, p: double_cfg(norm_topk=True), "norm_topk=False alone"),
    "zero experts without the routed layer": (
        lambda c, p: TransformerConfig(n_experts=4, zero_experts=2),
        "describe the routed expert layer"),
    "a share without the routed layer": (
        lambda c, p: TransformerConfig(n_experts=4, experts_held=(0, 2)),
        "describe the routed expert layer"),
    "a share outside the experts": (
        lambda c, p: double_cfg(experts_held=(6, 4)), "non-empty range"),
    "an empty share": (
        lambda c, p: double_cfg(experts_held=(2, 0)), "non-empty range"),
    "top-k wider than the router": (
        lambda c, p: double_cfg(expert_top_k=13), "cannot exceed"),
    "kv_dtype=int8": (
        lambda c, p: _server(c, p, kv_dtype="int8"), "compute-dtype only"),
    "int8 experts": (
        lambda c, p: quantize_params(p, c), "quantize_params .* is not built"),
    "kv_pages": (
        lambda c, p: _server(c, p, kv_pages={"block_size": 4, "num_blocks": 16}),
        "dense per-slot pool"),
    "mesh": (lambda c, p: _server(c, p, mesh=_mesh2()), "one device"),
    "param_specs": (
        lambda c, p: __import__(
            "torchkafka_tpu.models.transformer", fromlist=["x"]
        ).param_specs(c), "no exchange across chips"),
    "speculative": (
        lambda c, p: __import__(
            "torchkafka_tpu.serve_spec", fromlist=["x"]
        ).SpecStreamingGenerator(None, p, c, slots=2, prompt_len=P, max_new=NEW),
        "speculative serving is not built"),
    "generate": (
        lambda c, p: generate(p, c, jnp.zeros((1, P), jnp.int32), 4),
        "lockstep decode is not built"),
    "make_train_step": (
        lambda c, p: make_train_step(c, _mesh2(), None),
        "make_train_step is not built"),
    "kv_kernel=True": (
        lambda c, p: _server(c, p, kv_kernel=True), "no Pallas read is built"),
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_what_is_not_built_refuses_with_its_reason(model, what):
    cfg, params = model
    make, reason = REFUSALS[what]
    with pytest.raises(ValueError, match=reason):
        make(cfg, params)


def test_a_warm_resume_hint_has_no_program_to_run(model):
    """Resume is not built for the latent pool: no resume program exists
    (hints fall back to cold replay, ``serve.py::_build``)."""
    cfg, params = model
    srv, consumer, _broker = _server(cfg, params)
    assert srv._resume_exec is None
    srv.close()
    consumer.close()


def test_a_layer_that_holds_every_expert_counts_local_pairs_alone():
    """A layer that holds every expert and has no zero experts: the same
    device counters, and every pair of its load is a local one."""
    cfg = TransformerConfig(  # tests/test_mla.py::latent_cfg, the PR 27 toy
        vocab_size=VOCAB, d_model=32, n_layers=3, n_heads=2, n_kv_heads=2,
        d_ff=48, max_seq_len=P + NEW, dtype=jnp.float32, kv_lora_rank=16,
        qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, rope_interleave=True,
        first_dense_layers=1, n_experts=8, expert_top_k=2, expert_d_ff=12,
        n_shared_experts=2, router_score="sigmoid", routed_scaling=2.448,
    )
    assert not cfg.moe_partial and cfg.held_experts == (0, 8)
    params = init_params(jax.random.key(3), cfg)
    srv, consumer, broker = _server(cfg, params)
    broker.produce("p", np.arange(1, P + 1, dtype=np.int32).tobytes())
    list(srv.run(max_records=1, idle_timeout_ms=200))
    e = srv.metrics.summary()["expert_layer"]
    assert e["moe_assignments"] == (NEW - 1) * 2 * 2
    assert e["moe_zero_assignments"] == e["moe_absent_assignments"] == 0
    assert e["moe_local_assignments"] == sum(e["moe_expert_load"])
    assert e["moe_local_assignments"] == e["moe_assignments"]
    assert e["experts_held"] == [0, 8] and len(e["moe_expert_load"]) == 8
    srv.close()
    consumer.close()
