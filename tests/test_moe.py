"""Mixture-of-experts MLP: routing exactness, ep-sharded training, decode.

The expert dimension shards over the mesh's ``ep`` axis (dense one-hot
dispatch — every routing decision exact, no capacity drops); these tests pin
the math against a per-token loop and prove training/decoding work under
expert parallelism.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchkafka_tpu.models import Transformer, TransformerConfig, make_train_step
from torchkafka_tpu.models.transformer import _moe_mlp, router_aux
from torchkafka_tpu.parallel import make_mesh

MOE_CFG = TransformerConfig(
    vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, max_seq_len=16, dtype=jnp.float32, n_experts=4, expert_top_k=2,
)


class TestRouting:
    def test_matches_per_token_loop(self, rng):
        """Dense-dispatch einsum == naive loop over (token, top-k expert)."""
        h = jnp.asarray(rng.normal(size=(2, 8, 32)), jnp.float32)
        layer = {
            "router": jnp.asarray(rng.normal(size=(32, 4)), jnp.float32),
            "w_gate": jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32) * 0.1,
            "w_up": jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32) * 0.1,
            "w_down": jnp.asarray(rng.normal(size=(4, 64, 32)), jnp.float32) * 0.1,
        }
        out, stats = _moe_mlp(h, layer, MOE_CFG)
        aux = router_aux(stats, 2 * 8)
        href = np.asarray(h)
        logits = href @ np.asarray(layer["router"])
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        ref = np.zeros_like(href)
        for b in range(2):
            for s in range(8):
                idx = np.argsort(-probs[b, s])[:2]
                g = probs[b, s, idx] / probs[b, s, idx].sum()
                for gi, e in zip(g, idx):
                    x = href[b, s]
                    sil = x @ np.asarray(layer["w_gate"][e])
                    sil = sil / (1 + np.exp(-sil))
                    up = x @ np.asarray(layer["w_up"][e])
                    ref[b, s] += gi * ((sil * up) @ np.asarray(layer["w_down"][e]))
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)
        assert float(aux) >= 1.0 - 1e-5  # Switch aux loss is minimized at 1

    def test_top1_routes_single_expert(self, rng):
        cfg = dataclasses.replace(MOE_CFG, expert_top_k=1)
        h = jnp.asarray(rng.normal(size=(1, 4, 32)), jnp.float32)
        layer = {
            "router": jnp.asarray(rng.normal(size=(32, 4)), jnp.float32),
            "w_gate": jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32) * 0.1,
            "w_up": jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32) * 0.1,
            "w_down": jnp.asarray(rng.normal(size=(4, 64, 32)), jnp.float32) * 0.1,
        }
        out, _ = _moe_mlp(h, layer, cfg)
        assert bool(jnp.isfinite(out).all())

    def test_topk_exceeding_experts_rejected(self):
        with pytest.raises(ValueError):
            dataclasses.replace(MOE_CFG, n_experts=2, expert_top_k=3)


class TestTrainingAndDecode:
    @pytest.mark.parametrize(
        "axes", [{"data": 8}, {"data": 2, "ep": 2, "tp": 2}, {"data": 2, "ep": 2, "sp": 2}]
    )
    def test_loss_decreases_on_ep_meshes(self, rng, axes):
        mesh = make_mesh(axes)
        init_fn, step_fn = make_train_step(MOE_CFG, mesh, optax.adamw(3e-3))
        params, opt = init_fn(jax.random.key(0))
        toks = jnp.asarray(rng.integers(0, 128, (8, 16)), jnp.int32)
        mask = jnp.ones_like(toks)
        first = None
        for _ in range(6):
            params, opt, loss = step_fn(params, opt, toks, mask)
            first = float(loss) if first is None else first
        assert float(loss) < first

    def test_moe_generate_matches_full_forward(self, rng):
        from torchkafka_tpu.models.generate import generate

        model = Transformer(MOE_CFG)
        params = model.init(jax.random.key(1))
        prompt = jnp.asarray(rng.integers(0, 128, (2, 4)), jnp.int32)
        out = generate(params, MOE_CFG, prompt, 4)
        seq = prompt
        for _ in range(4):
            nxt = jnp.argmax(model(params, seq)[:, -1], -1).astype(jnp.int32)
            seq = jnp.concatenate([seq, nxt[:, None]], 1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(seq[:, 4:]))

    def test_ep_sharded_loss_matches_unsharded(self, rng):
        params = Transformer(MOE_CFG).init(jax.random.key(2))
        toks = jnp.asarray(rng.integers(0, 128, (8, 16)), jnp.int32)
        dense = Transformer(MOE_CFG).loss(params, toks)
        mesh = make_mesh({"data": 2, "ep": 2, "tp": 2})
        sharded = jax.jit(lambda p, t: Transformer(MOE_CFG, mesh).loss(p, t))(params, toks)
        assert abs(float(dense) - float(sharded)) < 1e-4


class TestCapacityDispatch:
    """Switch-style capacity dispatch (the pod-scale path) vs the exact
    dense combine."""

    def _layer(self, rng):
        return {
            "router": jnp.asarray(rng.normal(size=(32, 4)), jnp.float32),
            "w_gate": jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32) * 0.1,
            "w_up": jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32) * 0.1,
            "w_down": jnp.asarray(rng.normal(size=(4, 64, 32)), jnp.float32) * 0.1,
        }

    def test_ample_capacity_matches_dense(self, rng):
        """With capacity >= every expert's actual load there are zero drops
        and the capacity path must equal the dense path exactly."""
        from torchkafka_tpu.models.transformer import _moe_mlp_capacity

        h = jnp.asarray(rng.normal(size=(2, 8, 32)), jnp.float32)
        layer = self._layer(rng)
        # capacity_factor = E covers even an all-tokens-to-one-expert router.
        cfg = dataclasses.replace(MOE_CFG, moe_dispatch="capacity",
                                  capacity_factor=float(MOE_CFG.n_experts))
        out_c, stats_c = _moe_mlp_capacity(h, layer, cfg)
        out_d, stats_d = _moe_mlp(h, layer, MOE_CFG)
        np.testing.assert_allclose(
            np.asarray(out_c), np.asarray(out_d), atol=1e-5
        )
        np.testing.assert_allclose(
            float(router_aux(stats_c, 16)), float(router_aux(stats_d, 16)),
            rtol=1e-6,
        )

    def test_tight_capacity_drops_but_stays_finite(self, rng):
        """Starved capacity: outputs stay finite, dropped (token, choice)
        pairs contribute zero (norm of output <= ample-capacity norm)."""
        from torchkafka_tpu.models.transformer import _moe_mlp_capacity, moe_capacity

        h = jnp.asarray(rng.normal(size=(2, 8, 32)), jnp.float32)
        layer = self._layer(rng)
        starve = dataclasses.replace(MOE_CFG, moe_dispatch="capacity",
                                     capacity_factor=0.01)
        assert moe_capacity(starve, 16) == 8  # the floor engages
        out_s, _ = _moe_mlp_capacity(h, layer, starve)
        assert np.all(np.isfinite(np.asarray(out_s)))
        ample = dataclasses.replace(starve, capacity_factor=float(MOE_CFG.n_experts))
        out_a, _ = _moe_mlp_capacity(h, layer, ample)
        assert np.linalg.norm(out_s) <= np.linalg.norm(out_a) + 1e-5

    def test_primary_choice_has_priority(self, rng):
        """When capacity runs out, k=0 (primary) assignments survive over
        k=1 (secondary) ones: force every token's primary to expert 0 and
        check the survivors are the FIRST tokens' primaries."""
        from torchkafka_tpu.models.transformer import _moe_mlp_capacity

        layer = self._layer(rng)
        # Zero router → uniform logits → top_k deterministic by index
        # order: every token routes primarily to expert 0, secondarily to 1.
        layer["router"] = jnp.zeros((32, 4), jnp.float32)
        h = jnp.asarray(rng.normal(size=(1, 16, 32)), jnp.float32)
        cfg = dataclasses.replace(MOE_CFG, moe_dispatch="capacity",
                                  capacity_factor=0.5, moe_group_size=16)
        out, _ = _moe_mlp_capacity(h, layer, cfg)
        # cap = max(8, ceil(16*2/4*0.5)=4→8) = 8 per expert. K-major
        # priority: ALL primary choices outrank ALL secondary ones, so
        # expert 0's 8 slots go to tokens 0-7's primaries AND expert 1's
        # 8 slots go to tokens 0-7's secondaries — tokens 8-15 lose BOTH
        # choices and must produce exactly zero (residual passthrough).
        o = np.asarray(out)
        assert np.all(np.isfinite(o))
        np.testing.assert_allclose(o[0, 8:], 0.0, atol=1e-6)
        assert np.linalg.norm(o[0, :8]) > 1e-3

    def test_capacity_trains_on_ep_mesh(self, rng):
        cfg = dataclasses.replace(MOE_CFG, moe_dispatch="capacity",
                                  capacity_factor=2.0)
        mesh = make_mesh({"data": 2, "ep": 2, "tp": 2})
        init_fn, step_fn = make_train_step(cfg, mesh, optax.adamw(3e-3))
        params, opt = init_fn(jax.random.key(0))
        toks = jnp.asarray(rng.integers(0, 128, (8, 16)), jnp.int32)
        mask = jnp.ones_like(toks)
        first = None
        for _ in range(8):
            params, opt, loss = step_fn(params, opt, toks, mask)
            first = float(loss) if first is None else first
        assert float(loss) < first

    def test_ep_sharded_capacity_matches_unsharded(self, rng):
        cfg = dataclasses.replace(MOE_CFG, moe_dispatch="capacity",
                                  capacity_factor=float(MOE_CFG.n_experts))
        params = Transformer(cfg).init(jax.random.key(2))
        toks = jnp.asarray(rng.integers(0, 128, (8, 16)), jnp.int32)
        unsharded = Transformer(cfg).loss(params, toks)
        mesh = make_mesh({"data": 2, "ep": 2, "tp": 2})
        sharded = jax.jit(lambda p, t: Transformer(cfg, mesh).loss(p, t))(
            params, toks
        )
        assert abs(float(unsharded) - float(sharded)) < 1e-4

    def test_bad_dispatch_config_rejected(self):
        with pytest.raises(ValueError, match="moe_dispatch"):
            dataclasses.replace(MOE_CFG, moe_dispatch="nope")
        with pytest.raises(ValueError, match="capacity_factor"):
            dataclasses.replace(MOE_CFG, capacity_factor=0.0)
        with pytest.raises(ValueError, match="moe_group_size"):
            dataclasses.replace(MOE_CFG, moe_group_size=0)

    def test_nondividing_group_size_stays_grouped(self, rng):
        """A token count that doesn't divide moe_group_size pads the tail
        group with masked rows — groups stay full-size, padding contributes
        nothing, and ample capacity still matches the dense path."""
        from torchkafka_tpu.models.transformer import _moe_mlp_capacity

        layer = self._layer(rng)
        # b=2, s=12 → n=24; group target 10 → 3 groups of 10, 6 pad rows.
        h = jnp.asarray(rng.normal(size=(2, 12, 32)), jnp.float32)
        cfg = dataclasses.replace(
            MOE_CFG, moe_dispatch="capacity",
            capacity_factor=float(MOE_CFG.n_experts), moe_group_size=10,
        )
        out_c, _ = _moe_mlp_capacity(h, layer, cfg)
        out_d, _ = _moe_mlp(h, layer, MOE_CFG)
        np.testing.assert_allclose(
            np.asarray(out_c), np.asarray(out_d), atol=1e-5
        )

    def test_prime_token_count_no_degenerate_groups(self, rng):
        """A PRIME token count larger than the group size (the ADVICE-r3
        degeneracy: the old largest-divisor search collapsed to 1-token
        groups) now pads into full groups: outputs match the dense path
        under ample capacity (no silent mass drop) and the aux stats
        exclude the padding."""
        from torchkafka_tpu.models.transformer import _moe_mlp_capacity

        layer = self._layer(rng)
        h = jnp.asarray(rng.normal(size=(1, 13, 32)), jnp.float32)  # n=13
        cfg = dataclasses.replace(
            MOE_CFG, moe_dispatch="capacity",
            capacity_factor=float(MOE_CFG.n_experts), moe_group_size=8,
        )  # 13 prime → 2 groups of 8, 3 pad rows
        out_c, stats_c = _moe_mlp_capacity(h, layer, cfg)
        out_d, stats_d = _moe_mlp(h, layer, MOE_CFG)
        np.testing.assert_allclose(
            np.asarray(out_c), np.asarray(out_d), atol=1e-5
        )
        # Padding must not leak into the routing statistics: the routed
        # count sums to exactly n·k real assignments.
        np.testing.assert_allclose(
            np.asarray(stats_c), np.asarray(stats_d), rtol=1e-6
        )
        assert float(stats_c[0].sum()) == 13 * MOE_CFG.expert_top_k


# ------------------------------------------------- the routed expert layer
# (ops/moe.py: sigmoid scores, a bias that moves the selection alone,
# normalised weights times routed_scaling, shared experts, no drops.)
# float32 on the CPU against NumPy loops; 1e-5 absolute on outputs of
# order one: the grouped form sums a token's k products in another order
# than the loop, and nothing else differs.

ROUTED_CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=3, n_heads=2, n_kv_heads=2, d_ff=48,
    max_seq_len=16, dtype=jnp.float32, kv_lora_rank=16, qk_nope_dim=8,
    qk_rope_dim=4, v_head_dim=8, rope_interleave=True, first_dense_layers=1,
    n_experts=8, expert_top_k=3, expert_d_ff=12, n_shared_experts=2,
    router_score="sigmoid", routed_scaling=2.448,
)


def _routed_layer(rng, bias_scale=0.0):
    e, d, f = 8, 32, 12
    n = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return {
        "router": n(d, e), "router_bias": bias_scale * n(e),
        "w_gate": 0.2 * n(e, d, f), "w_up": 0.2 * n(e, d, f),
        "w_down": 0.2 * n(e, f, d), "ws_gate": 0.2 * n(d, 2 * f),
        "ws_up": 0.2 * n(d, 2 * f), "ws_down": 0.2 * n(2 * f, d),
    }


def _np_swiglu(x, gate, up, down):
    g = x @ np.asarray(gate)
    return ((g / (1 + np.exp(-g))) * (x @ np.asarray(up))) @ np.asarray(down)


def _np_routed(h, layer, cfg, shared=True):
    """A per-token loop over the chosen experts."""
    x = np.asarray(h, np.float64).reshape(-1, h.shape[-1])
    s = 1 / (1 + np.exp(-(x @ np.asarray(layer["router"], np.float64))))
    sel = s + np.asarray(layer["router_bias"], np.float64)
    out, chosen = np.zeros_like(x), []
    for t in range(len(x)):
        idx = np.argsort(-sel[t], kind="stable")[: cfg.expert_top_k]
        w = s[t, idx] / s[t, idx].sum() * cfg.routed_scaling
        chosen.append(idx)
        for wi, e in zip(w, idx):
            out[t] += wi * _np_swiglu(
                x[t], layer["w_gate"][e], layer["w_up"][e], layer["w_down"][e]
            )
        if shared:
            out[t] += _np_swiglu(
                x[t], layer["ws_gate"], layer["ws_up"], layer["ws_down"]
            )
    return out.reshape(h.shape), np.asarray(chosen)


class TestRoutedExpertLayer:
    @pytest.mark.parametrize("form", ["grouped", "all_experts"])
    def test_each_form_equals_the_per_token_loop(self, rng, form, monkeypatch):
        """Sigmoid scores, the selection bias, the normalisation times
        2.448 and the shared experts, in both forms of the one sum."""
        from torchkafka_tpu.ops import moe

        monkeypatch.setattr(
            moe, "_GROUPED_MIN_PAIRS_PER_EXPERT", 0 if form == "grouped" else 10**9
        )
        h = jnp.asarray(rng.normal(size=(2, 9, 32)), jnp.float32)
        layer = _routed_layer(rng, bias_scale=0.3)
        out, idx = moe.routed_moe_mlp(h, layer, ROUTED_CFG)
        ref, chosen = _np_routed(h, layer, ROUTED_CFG)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)
        assert (np.sort(np.asarray(idx).reshape(-1, 3)) == np.sort(chosen)).all()

    def test_the_form_follows_the_static_row_count(self, rng, monkeypatch):
        from torchkafka_tpu.ops import moe

        called = []
        for name in ("grouped_experts", "all_experts"):
            honest = getattr(moe, name)
            monkeypatch.setattr(
                moe, name,
                lambda *a, _h=honest, _n=name: called.append(_n) or _h(*a),
            )
        layer = _routed_layer(rng)
        # 12 pairs over 8 experts; 384 pairs, 48 an expert (the grouped
        # form takes 32 an expert and more)
        for rows in (4, 128):
            moe.routed_moe_mlp(
                jnp.zeros((1, rows, 32), jnp.float32), layer, ROUTED_CFG
            )
        assert called == ["all_experts", "grouped_experts"]

    def test_the_bias_moves_the_selection_and_not_the_weights(self, rng):
        from torchkafka_tpu.ops.moe import route

        h = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
        layer = _routed_layer(rng)
        kw = dict(top_k=3, scaling=2.448)
        idx0, w0 = route(h, layer["router"], jnp.zeros((8,)), **kw)
        # A large bias on expert 5 puts it into every selection...
        bias = jnp.zeros((8,)).at[5].set(10.0)
        idx1, w1 = route(h, layer["router"], bias, **kw)
        assert (np.asarray(idx1) == 5).any(axis=1).all()
        assert not (np.asarray(idx0) == 5).any(axis=1).all()
        # ...and its weight is still its sigmoid score's share, not 10 more.
        s = 1 / (1 + np.exp(-(np.asarray(h) @ np.asarray(layer["router"]))))
        picked = np.take_along_axis(s, np.asarray(idx1), axis=1)
        np.testing.assert_allclose(
            np.asarray(w1), picked / picked.sum(1, keepdims=True) * 2.448,
            rtol=1e-5,
        )
        np.testing.assert_allclose(np.asarray(w1).sum(1), 2.448, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w0).sum(1), 2.448, rtol=1e-5)

    def test_every_token_to_one_expert_and_nothing_dropped(self, rng):
        """The worst load: all pairs of all tokens on the same experts.
        A capacity dispatch would drop most of them; here every token
        gets its full sum."""
        from torchkafka_tpu.ops import moe

        layer = _routed_layer(rng)
        layer["router_bias"] = jnp.zeros((8,)).at[jnp.asarray([1, 4, 6])].set(50.0)
        h = jnp.asarray(rng.normal(size=(1, 40, 32)), jnp.float32)
        out, idx = moe.routed_moe_mlp(h, layer, ROUTED_CFG)
        assert (np.sort(np.asarray(idx), axis=-1) == [1, 4, 6]).all()
        ref, _ = _np_routed(h, layer, ROUTED_CFG)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)
        for form in (moe.grouped_experts, moe.all_experts):
            x = h.reshape(-1, 32)
            i, w = moe.route(
                x, layer["router"], layer["router_bias"], top_k=3, scaling=2.448
            )
            got = form(x, i, w, layer["w_gate"], layer["w_up"], layer["w_down"])
            want, _ = _np_routed(h, layer, ROUTED_CFG, shared=False)
            np.testing.assert_allclose(np.asarray(got), want[0], atol=1e-5)

    def test_the_shared_experts_see_every_token(self, rng):
        from torchkafka_tpu.ops import moe

        layer = _routed_layer(rng)
        h = jnp.asarray(rng.normal(size=(1, 6, 32)), jnp.float32)
        with_shared, _ = moe.routed_moe_mlp(h, layer, ROUTED_CFG)
        without, _ = moe.routed_moe_mlp(
            h, layer, dataclasses.replace(ROUTED_CFG, n_shared_experts=0)
        )
        want = _np_swiglu(
            np.asarray(h[0]), layer["ws_gate"], layer["ws_up"], layer["ws_down"]
        )
        np.testing.assert_allclose(
            np.asarray(with_shared - without)[0], want, atol=1e-5
        )

    def test_the_leading_dense_layer(self):
        """``first_dense_layers`` 1: a stacked group of its own, run
        first, with the dense FFN's width and no router; the forward is
        the groups' layers applied in order."""
        from torchkafka_tpu.models.transformer import init_params

        cfg = ROUTED_CFG
        params = init_params(jax.random.key(0), cfg)
        assert params["dense_layers"]["w_gate"].shape == (1, 32, 48)
        assert "router" not in params["dense_layers"]
        assert params["layers"]["w_gate"].shape == (2, 8, 32, 12)
        assert params["layers"]["ws_down"].shape == (2, 24, 32)
        tokens = jnp.arange(10, dtype=jnp.int32).reshape(2, 5)
        model = Transformer(cfg)
        x = params["embed"][tokens]
        for key, n in (("dense_layers", 1), ("layers", 2)):
            for i in range(n):
                x, _ = model._layer(x, jax.tree.map(lambda a: a[i], params[key]))
        from torchkafka_tpu.models.transformer import _rms_norm

        want = _rms_norm(x, params["ln_f"]) @ params["lm_head"]
        np.testing.assert_allclose(
            np.asarray(model(params, tokens)), np.asarray(want), atol=1e-5
        )
        # Without it every layer is an expert layer, in one group.
        flat = dataclasses.replace(cfg, first_dense_layers=0)
        assert "dense_layers" not in init_params(jax.random.key(0), flat)

    def test_the_softmax_family_is_what_it_was(self, rng):
        """A config without the new fields builds the layer it built:
        ``_moe_mlp`` through ``Transformer._layer`` and ``_attn_tail``."""
        from torchkafka_tpu.models.generate import _attn_tail
        from torchkafka_tpu.models.transformer import init_params

        params = init_params(jax.random.key(0), MOE_CFG)
        layer = jax.tree.map(lambda a: a[0], params["layers"])
        assert set(layer) == {
            "ln1", "ln2", "wq", "wk", "wv", "wo", "router", "w_gate", "w_up",
            "w_down",
        }
        x = jnp.asarray(rng.normal(size=(1, 3, 32)), jnp.float32)
        attn = jnp.zeros((1, 3, 4, 8), jnp.float32)
        from torchkafka_tpu.models.transformer import _rms_norm

        want = x + _moe_mlp(_rms_norm(x, layer["ln2"]), layer, MOE_CFG)[0]
        np.testing.assert_allclose(
            np.asarray(_attn_tail(x, attn, layer, MOE_CFG)), np.asarray(want),
            atol=1e-6,
        )
