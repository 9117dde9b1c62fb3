"""Mixture-of-experts MLP: routing exactness, ep-sharded training, decode.

The expert dimension shards over the mesh's ``ep`` axis (dense one-hot
dispatch — every routing decision exact, no capacity drops); these tests pin
the math against a per-token loop and prove training/decoding work under
expert parallelism.
"""

import dataclasses
import functools
import sys

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
                lambda *a, _h=honest, _n=name, **kw: called.append(_n) or _h(*a, **kw),
            )
        layer = _routed_layer(rng)
        # 12 pairs over 8 experts, 1.5 an expert; 384 pairs, 48 an expert
        # (the grouped form takes 16 an expert and more)
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


# --------------------------------------- the grouped matmul's own kernel
# (ops/moe.py: ``tk_gmm_gate_up``, ``tk_gmm_down``; the Pallas interpreter
# on the CPU.) Sorted rows and group sizes in, a plain loop over the
# experts in float64 beside it: float32 operands, 1e-5 on outputs of order
# one as above.

# name: (group sizes of one layer's experts, rows a tile (the block a grid
# step holds), rows a piece of it (what one product multiplies), D, F)
_GMM_CASES = {
    "uniform": ([32] * 8, 32, 32, 32, 12),
    "every_pair_to_one_expert": ([0, 0, 0, 96, 0, 0], 32, 16, 32, 12),
    # half of the pairs to k = 2 experts, the rest spread (a row that is
    # half padding, which routes alike)
    "half_to_k_experts_the_rest_spread": (
        [70, 9, 11, 74, 8, 12, 10, 14, 7, 9], 64, 16, 32, 12
    ),
    "experts_with_no_pair": ([0, 40, 0, 0, 24, 0], 16, 16, 32, 12),
    # runs end at 40, 91, 98, 101, 128: the last tile [96, 128) holds the
    # ends of three experts' runs, and no size is a multiple of the tile
    "sizes_off_the_tile_and_a_tile_of_three": (
        [40, 51, 7, 3, 27], 32, 32, 32, 12
    ),
    # ... and in pieces of 16: the visit of the expert of 3 rows (98 to
    # 101) multiplies one piece of the tile's two and skips the other
    "a_tile_of_three_in_pieces": ([40, 51, 7, 3, 27], 32, 16, 32, 12),
    "rows_not_a_multiple_of_the_tile": ([30, 45, 25], 32, 16, 32, 12),
    "one_tile": ([3, 0, 5, 2], 16, 16, 32, 12),
    "widths_18_to_7": ([20, 33, 11], 32, 16, 72, 28),  # 2304 : 896
    "widths_8_to_3": ([20, 33, 11], 32, 16, 64, 24),  # 2048 : 768
}


def _gmm_swiglu(rows, mats, sizes, base, tm, ts):
    """The two kernel calls over sorted rows, padded to whole tiles."""
    from torchkafka_tpu.ops import moe

    m = rows.shape[0]
    tiles_m = -(-m // tm)
    rows = jnp.pad(rows, ((0, tiles_m * tm - m), (0, 0)), mode="edge")
    walk = moe._gmm_tiles(jnp.asarray(sizes, jnp.int32), tiles_m, tm)
    mid = moe._gmm(rows, mats[:2], base, walk, tm, ts, "tk_gmm_gate_up")
    out = moe._gmm(mid, mats[2:], base, walk, tm, ts, "tk_gmm_down")
    return out[:m], walk


class TestGroupedMatmulKernel:
    @pytest.mark.parametrize("case", list(_GMM_CASES))
    def test_the_kernel_equals_a_loop_over_the_experts(self, rng, case):
        sizes, tm, ts, d, f = _GMM_CASES[case]
        e, m = len(sizes), sum(sizes)
        rows = rng.normal(size=(m, d))
        mats = [
            0.2 * rng.normal(size=s)
            for s in ((e, d, f), (e, d, f), (e, f, d))
        ]
        got, walk = _gmm_swiglu(
            jnp.asarray(rows, jnp.float32),
            [jnp.asarray(w, jnp.float32) for w in mats], sizes, 0, tm, ts,
        )
        want, start = np.zeros((m, d)), 0
        for i, n in enumerate(sizes):
            want[start:start + n] = _np_swiglu(
                rows[start:start + n], *(w[i] for w in mats)
            )
            start += n
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
        # The visits are the tiles each run touches: a value of the
        # routing, with nothing for an expert no pair chose.
        ends = np.cumsum(sizes)
        visits = sum(
            -(-hi // tm) - lo // tm
            for lo, hi in zip(ends - sizes, ends) if hi > lo
        )
        assert int(walk[3]) == visits
        assert set(np.asarray(walk[1])[:visits]) == {
            i for i, n in enumerate(sizes) if n
        }

    @pytest.mark.parametrize("layer", [0, 1, 3])
    def test_stacks_are_reached_at_base(self, rng, layer):
        """Stacks of four layers' experts, taken whole: the layer's are
        rows [base, base + E), every other layer's rows NaN."""
        sizes, tm, ts, d, f, e = [20, 0, 33, 11], 32, 16, 32, 12, 4
        rows = rng.normal(size=(sum(sizes), d))
        own = [
            0.2 * rng.normal(size=s)
            for s in ((e, d, f), (e, d, f), (e, f, d))
        ]
        stacks = []
        for w in own:
            stack = np.full((4 * e, *w.shape[1:]), np.nan)
            stack[layer * e:(layer + 1) * e] = w
            stacks.append(jnp.asarray(stack, jnp.float32))
        got, _walk = _gmm_swiglu(
            jnp.asarray(rows, jnp.float32), stacks, sizes,
            jnp.int32(layer * e), tm, ts,
        )
        want, start = np.zeros_like(rows), 0
        for i, n in enumerate(sizes):
            want[start:start + n] = _np_swiglu(
                rows[start:start + n], *(w[i] for w in own)
            )
            start += n
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)

    @pytest.mark.parametrize("stacked", [False, True])
    def test_grouped_experts_equals_all_experts(self, rng, stacked):
        """End to end (sort, kernels, inverse permutation, weighted sum)
        at a row count that is no multiple of the tile, with an expert
        that no pair chose."""
        from torchkafka_tpu.ops import moe

        layer = _routed_layer(rng)
        layer["router_bias"] = jnp.zeros((8,)).at[2].set(-50.0)
        x = jnp.asarray(rng.normal(size=(117, 32)), jnp.float32)
        idx, w = moe.route(
            x, layer["router"], layer["router_bias"], top_k=3, scaling=2.448
        )
        assert not (np.asarray(idx) == 2).any()
        mats = [layer[n] for n in ("w_gate", "w_up", "w_down")]
        want = moe.all_experts(x, idx, w, *mats)
        at = None
        if stacked:  # the middle layer of three, the others' rows NaN
            mats = [
                jnp.concatenate([jnp.full_like(m, jnp.nan), m,
                                 jnp.full_like(m, jnp.nan)])
                for m in mats
            ]
            at = (jnp.int32(8), 8)
        got = moe.grouped_experts(x, idx, w, *mats, at)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_two_pallas_calls_take_the_stacks_as_they_came(self, rng):
        """Structure: ``grouped_experts`` holds the gated pair's kernel and
        the down projection's and no ``ragged_dot``; the stacks
        ``[L * E, ...]`` are the calls' own operands, with nothing (a
        slice, a dynamic slice, a copy, a gather) reading them first."""
        from torchkafka_tpu.ops import moe

        e, layers, d, f = 8, 3, 32, 12
        stacks = [
            jnp.zeros((layers * e, *s), jnp.float32)
            for s in ((d, f), (d, f), (f, d))
        ]
        x = jnp.zeros((64, d), jnp.float32)
        idx = jnp.zeros((64, 3), jnp.int32)
        w = jnp.ones((64, 3), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda x, idx, w, a, b, c, base: moe.grouped_experts(
                x, idx, w, a, b, c, (base, e)
            )
        )(x, idx, w, *stacks, jnp.int32(e)).jaxpr
        names = [eqn.primitive.name for eqn in jaxpr.eqns]
        assert "ragged_dot" not in names and "ragged_dot_general" not in names
        calls = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "pallas_call"]
        assert [c.params["name"] for c in calls] == [
            "tk_gmm_gate_up", "tk_gmm_down"
        ]
        gate, up, down = jaxpr.invars[3:6]
        assert [v for v in calls[0].invars if v in (gate, up, down)] == [gate, up]
        assert [v for v in calls[1].invars if v in (gate, up, down)] == [down]
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "pallas_call":
                assert not {gate, up, down} & {
                    v for v in eqn.invars if hasattr(v, "count")
                }, eqn.primitive.name

    def test_the_counts_are_the_pieces_multiplied(self, rng, monkeypatch):
        """``grouped_counts``: the pairs, and the rows of every piece an
        expert's run touches (pieces of 16 rows here, blocks of two)."""
        from torchkafka_tpu.ops import moe

        monkeypatch.setattr(moe, "_GMM_PIECE_ROWS", 16)
        monkeypatch.setattr(moe, "_GMM_BLOCK_PIECES", 2)
        assert moe._gmm_rows(120) == (32, 16) and moe._gmm_rows(9) == (16, 16)
        idx = jnp.asarray(rng.integers(0, 8, size=(40, 3)), jnp.int32)
        rows, tile_rows = np.asarray(moe.grouped_counts(idx[None], 8))
        sizes = np.bincount(np.asarray(idx).reshape(-1), minlength=8)
        ends = np.cumsum(sizes)
        pieces = sum(
            -(-hi // 16) - lo // 16
            for lo, hi in zip(ends - sizes, ends) if hi > lo
        )
        assert rows == 120 and tile_rows == pieces * 16 > rows
        # The kernels at those rows: the same sum as at the built ones.
        layer = _routed_layer(rng)
        x = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
        w = jnp.asarray(rng.uniform(size=(40, 3)), jnp.float32)
        mats = [layer[n] for n in ("w_gate", "w_up", "w_down")]
        np.testing.assert_allclose(
            np.asarray(moe.grouped_experts(x, idx, w, *mats)),
            np.asarray(moe.all_experts(x, idx, w, *mats)), atol=1e-5,
        )

    def test_the_built_rows(self):
        """Blocks of four pieces of 128 rows (PERF.md §6's sweep), a block
        no longer than the rows in whole pieces."""
        from torchkafka_tpu.ops import moe

        assert moe._gmm_rows(32768) == moe._gmm_rows(18432) == (512, 128)
        assert moe._gmm_rows(1024) == (512, 128)
        assert moe._gmm_rows(300) == (384, 128) and moe._gmm_rows(8) == (128, 128)


# ------------------------------------------------ a held share, grouped
# ``grouped_experts(share=True)`` against ``compacted_experts`` and the
# plain sum over every pair (float64, a pair at a time): 4 held experts
# of a router whose other outputs are absent (any idx outside [0, 4),
# negative too: ``idx - first``). Each case: (the routing [24, 3] from
# the seeded generator, the tokens that must read EXACTLY zero).


def _share_routing(rng, case):
    n, k, e = 24, 3, 4
    spread = rng.integers(-6, 10, size=(n, k))  # a quarter of the pairs local
    absent = np.where(rng.integers(0, 2, size=(n, k)) == 1, e + 3, -2)
    local = (spread >= 0) & (spread < e)
    if case == "no_local_pair":
        return absent, np.arange(n)
    if case == "every_pair_local":
        return rng.integers(0, e, size=(n, k)), []
    if case == "every_local_pair_to_one_expert":
        return np.where(local, 2, absent), np.flatnonzero(~local.any(1))
    if case == "a_token_with_every_choice_absent":
        spread[5], spread[17] = [e, e + 1, -1], [-3, 2 * e, e]
        return spread, [5, 17]
    return spread, np.flatnonzero(~local.any(1))  # balanced; stacks at base


_SHARE_CASES = [
    "balanced", "no_local_pair", "every_pair_local",
    "every_local_pair_to_one_expert", "a_token_with_every_choice_absent",
    "base_into_stacks_of_two_layers",
]


@pytest.fixture
def unvisited_rows_are_nan(monkeypatch):
    """What the chip does and the interpreter does not: a row of the
    kernels' output that no visit wrote holds anything. Here, NaN."""
    from torchkafka_tpu.ops import moe

    honest = moe._gmm

    def poisoned(rows, mats, base, walk, tm, ts, name):
        out = honest(rows, mats, base, walk, tm, ts, name)
        row = jnp.arange(out.shape[0])[:, None]
        return jnp.where(row < walk[0][-1], out, jnp.nan)

    monkeypatch.setattr(moe, "_gmm", poisoned)


class TestGroupedShare:
    @pytest.mark.parametrize("case", _SHARE_CASES)
    def test_the_share_s_grouped_form_equals_the_loop_and_the_plain_sum(
        self, rng, case, unvisited_rows_are_nan
    ):
        """An absent pair adds exactly nothing, an all-absent token reads
        exactly zero, no local pair is dropped at any load, and ``base``
        reaches the layer's experts in stacks of two layers'."""
        from torchkafka_tpu.ops import moe

        e, d = 4, 32
        routing, zero_tokens = _share_routing(rng, case)
        layer = _routed_layer(rng)
        mats = [layer[n][:e] for n in ("w_gate", "w_up", "w_down")]
        base = 0
        if case == "base_into_stacks_of_two_layers":
            other = [layer[n][e:] for n in ("w_gate", "w_up", "w_down")]
            stacks, base = [jnp.concatenate(p) for p in zip(other, mats)], e
        else:
            stacks = mats
        x = jnp.asarray(rng.normal(size=(24, d)), jnp.float32)
        w = jnp.asarray(rng.uniform(0.1, 1.0, size=(24, 3)), jnp.float32)
        idx = jnp.asarray(routing, jnp.int32)
        want = np.zeros((24, d))
        for t, ks in enumerate(routing):
            for j, ex in enumerate(ks):
                if 0 <= ex < e:
                    want[t] += float(w[t, j]) * _np_swiglu(
                        np.asarray(x[t], np.float64), *(m[ex] for m in mats)
                    )
        got = np.asarray(
            moe.grouped_experts(x, idx, w, *stacks, at=(base, e), share=True)
        )
        loop = np.asarray(
            moe.compacted_experts(x, idx, w, *stacks, e=e, cap=16, base=base)
        )
        np.testing.assert_allclose(got, want, atol=1e-5)
        np.testing.assert_allclose(loop, want, atol=1e-5)
        assert (got[zero_tokens] == 0).all() and np.isfinite(got).all()
        local = ((routing >= 0) & (routing < e)).sum()
        if case == "no_local_pair":
            assert local == 0 and (got == 0).all()
        if case == "every_pair_local":
            assert local == routing.size

    def test_the_walk_visits_the_local_pairs_blocks_alone(self, rng):
        """Sizes over the held experts alone: of 6 blocks of 16 sorted
        rows the walk visits those that the 20 local pairs fill, two, and
        an expert no pair chose makes no visit."""
        from torchkafka_tpu.ops import moe

        sizes = jnp.asarray([9, 0, 11, 0], jnp.int32)  # of 96 pairs
        offsets, expert, tile, visits = moe._gmm_tiles(sizes, 6, 16)
        assert int(visits) == 3 and int(offsets[-1]) == 20
        assert expert[:3].tolist() == [0, 2, 2] and tile[:3].tolist() == [0, 0, 1]

    def test_a_share_s_counts_are_the_local_pairs(self, rng):
        from torchkafka_tpu.ops import moe

        routing, _ = _share_routing(rng, "balanced")
        first = 8
        local = ((routing >= 0) & (routing < 4)).sum()
        pairs, rows = np.asarray(moe.grouped_counts(
            jnp.asarray(routing + first, jnp.int32)[None], 4, first
        ))
        assert pairs == local and rows >= pairs and rows % 128 == 0
        assert np.asarray(moe.grouped_counts(
            jnp.asarray(routing % 4, jnp.int32)[None], 4
        ))[0] == routing.size

    @pytest.mark.parametrize("floor,form", [(0, "grouped"), (10**9, "compacted")])
    def test_the_layer_hands_a_share_to_either_form(
        self, rng, monkeypatch, floor, form
    ):
        """``routed_moe_mlp`` with experts [2, 6) of 8 held: the same part
        of the sum by the kernels and by the tile loop, the form by the
        rule alone (its floors out of reach, or at zero)."""
        from torchkafka_tpu.ops import moe

        monkeypatch.setattr(moe, "_GROUPED_MIN_PAIRS_PER_EXPERT", floor)
        monkeypatch.setattr(moe, "_GROUPED_MIN_PAIRS_OUT_OF_STACKS", floor)
        cfg = dataclasses.replace(
            ROUTED_CFG, experts_held=(2, 4), n_shared_experts=0
        )
        assert moe.expert_form(cfg, 18) == form
        layer = _routed_layer(rng, bias_scale=0.3)
        held = {
            n: layer[n][2:6] if n in ("w_gate", "w_up", "w_down") else layer[n]
            for n in layer
        }
        h = jnp.asarray(rng.normal(size=(2, 9, 32)), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda h: moe.routed_moe_mlp(h, held, cfg))(h)
        names = {e.primitive.name for e in jaxpr.jaxpr.eqns}
        assert ("pallas_call" in names) == (form == "grouped")
        out, idx = moe.routed_moe_mlp(h, held, cfg)
        # The plain sum over all eight experts, the absent ones' outputs zero.
        absent = dict(layer, w_down=layer["w_down"].at[:2].set(0).at[6:].set(0))
        ref, chosen = _np_routed(h, absent, cfg, shared=False)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)
        assert (np.sort(np.asarray(idx).reshape(-1, 3)) == np.sort(chosen)).all()


# The benchmark's four serving configurations at their own sizes: (file
# under chipbench/configs, program, the form its routed layer takes there).
# Mellum2's tick averages 16 pairs an expert out of stacks, Kanana's 3 out
# of the layer's own tensors; their admissions 512 and 144; LongCat and
# Ling3 hold a share (2 and 6 local pairs a held expert a tick, 48 an
# admission's trip, whose sorted copy would be mostly absent pairs' rows);
# Mistral has no routed layer.
_CELL_FORMS = [
    ("mellum2-12b-a2.5b-8l", "tick", "grouped"),
    ("mellum2-12b-a2.5b-8l", "admit", "grouped"),
    ("kanana-2-30b-a3b-7l", "tick", "all_experts"),
    ("kanana-2-30b-a3b-7l", "admit", "grouped"),
    ("longcat-flash-omni-4l-ep32", "tick", "compacted"),
    ("longcat-flash-omni-4l-ep32", "admit", "compacted"),
    ("ling-3.0-flash-7l-ep8", "tick", "grouped"),
    ("ling-3.0-flash-7l-ep8", "admit", "compacted"),
    ("mistral-7b-v0.3-w8", "tick", None),
    ("mistral-7b-v0.3-w8", "admit", None),
    # A held share of 16 of 128 beside GQA, 48 slots: 3 local pairs a held
    # expert a tick, under the floor of 4; an admission's trip of 8,192
    # tokens would sort seven eighths of absent pairs' rows.
    ("keye-vl-2.0-30b-a3b-8l-ep8", "tick", "compacted"),
    ("keye-vl-2.0-30b-a3b-8l-ep8", "admit", "compacted"),
]


@functools.cache
def _cell_server(name, slots=None):
    """The configuration's server at its deployment's sizes, built under
    ``jax.eval_shape``: every static decision made, no byte allocated."""
    import importlib
    import json
    from pathlib import Path

    import torchkafka_tpu as tk
    from torchkafka_tpu.serve import StreamingGenerator

    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    conf = json.loads((root / "chipbench/configs" / f"{name}.json").read_text())
    model = importlib.import_module(conf["model"])
    dep = conf["deployment"]
    window, new = dep["prompt_window"], dep["max_new"]
    cfg = model.program_config(conf, window + new)
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    held = []

    def build():
        params = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda: model.serving_params(conf, 0)),
        )
        held.append(StreamingGenerator(
            consumer, params, cfg, slots=slots or dep["slots"],
            prompt_len=window, max_new=new,
            ticks_per_sync=dep["ticks_per_sync"], kv_dtype=dep["kv_dtype"],
            kv_kernel=dep["kv_kernel"],
        ))
        return 0

    jax.eval_shape(build)
    return held[0], cfg, window


class TestTheFormACellTakes:
    @pytest.mark.parametrize("name,program,form", _CELL_FORMS)
    def test_each_cells_static_shapes(self, name, program, form):
        """The rule of ``ops/moe.py`` at the sizes the benchmark serves:
        what the server says of its tick, and what its admission's trip
        takes by the same rule."""
        from torchkafka_tpu.ops import moe

        server, cfg, window = _cell_server(name)
        experts = server.metrics.summary()["expert_layer"]
        if program == "tick":
            assert experts["tick_form"] == form
            assert moe.expert_form(cfg, server._slots) == form
        else:
            trip = server._admit_chunk_rows * window
            assert moe.expert_form(cfg, trip) == form
            assert experts["grouped_matmul"] == (
                "kernel" if form == "grouped" else None
            )

    def test_fewer_slots_than_the_thresholds_keep_the_loop(self):
        """The rule is the static shapes', not the model's: out of stacks
        the floor is the one read against the loop, and Mellum2 with a pair
        an expert fewer than it asks keeps the compacted loop; so does
        Ling3's share with fewer LOCAL pairs a held expert."""
        from torchkafka_tpu.ops import moe

        floor = moe._GROUPED_MIN_PAIRS_OUT_OF_STACKS
        assert floor <= moe._GROUPED_MIN_PAIRS_PER_EXPERT
        _server, cfg, _window = _cell_server("mellum2-12b-a2.5b-8l")
        slots = floor * cfg.n_experts // cfg.expert_top_k
        assert moe.expert_form(cfg, slots) == "grouped"
        few, _cfg, _w = _cell_server("mellum2-12b-a2.5b-8l", slots - 1)
        assert few.metrics.summary()["expert_layer"]["tick_form"] == "compacted"
        _server, ling, _window = _cell_server("ling-3.0-flash-7l-ep8")
        slots = floor * ling.router_width // ling.expert_top_k
        assert slots == 256 and moe.expert_form(ling, slots) == "grouped"
        assert moe.expert_form(ling, slots - 1) == "compacted"

    @pytest.mark.parametrize("site,pairs,count,width,d,f,form", [
        ("ling3_tick", 384 * 8, 64, 512, 2560, 768, "grouped"),
        ("ling3_admission_trip", 6 * 512 * 8, 64, 512, 2560, 768, "compacted"),
        ("longcat_tick", 128 * 12, 16, 768, 6144, 2048, "compacted"),
        ("longcat_admission_trip", 6 * 512 * 12, 16, 768, 6144, 2048, "compacted"),
    ])
    def test_what_decides_each_held_share_site(
        self, monkeypatch, site, pairs, count, width, d, f, form
    ):
        """The four held-share sites of the cells by the rule's three
        conditions: the floor (local pairs a held expert), the kernels'
        VMEM, the absent pairs' rows against the held experts' weight
        rows. With the floors at zero only the VMEM still decides."""
        from torchkafka_tpu.ops import moe

        assert moe._form(pairs, count, width, True, d, f, 2) == form
        tm, ts = moe._gmm_rows(pairs)
        fits = moe._gmm_vmem(2, d, f, tm, ts, 2) <= moe._GMM_VMEM_BYTES
        assert fits == site.startswith("ling3")
        absent = pairs * (width - count) // width
        assert (absent <= moe._GROUPED_MAX_ABSENT_ROWS * count * 3 * f) == (
            site.endswith("tick")
        )
        monkeypatch.setattr(moe, "_GROUPED_MIN_PAIRS_PER_EXPERT", 0)
        assert moe._form(pairs, count, width, True, d, f, 2) == (
            "grouped" if fits else "compacted"
        )

    @pytest.mark.parametrize("rows,stacked,width,form", [
        (1, False, None, "all_experts"), (1, True, None, "compacted"),
        (64, False, None, "grouped"), (64, True, None, "grouped"),
        # a share of 8 of a router's 9 outputs: 3 pairs (under the floor),
        # 48 (5 a held expert, 5 absent rows), 192 (21 absent rows against
        # a sixteenth of the held experts' 288 weight rows)
        (1, True, 9, "compacted"), (16, True, 9, "grouped"),
        (64, True, 9, "compacted"),
    ])
    def test_the_form_named_is_the_form_traced(
        self, rng, rows, stacked, width, form
    ):
        """The rule's answer against the program ``routed_experts`` builds
        for the same shapes (3 pairs over 8 experts, and 192): the
        kernels' calls, the tile loop, or neither."""
        from torchkafka_tpu.ops import moe

        layer = _routed_layer(rng)
        mats = [layer[n] for n in ("w_gate", "w_up", "w_down")]
        if stacked:  # two layers' experts, this layer the second
            mats = [jnp.concatenate([m, m]) for m in mats]
        idx = jnp.zeros((rows, 3), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda x, w, *m: moe.routed_experts(
            x, idx, w, *m, at=(8, 8) if stacked else None, width=width
        ))(jnp.zeros((rows, 32)), jnp.zeros((rows, 3)), *mats)
        names = {e.primitive.name for e in jaxpr.jaxpr.eqns}
        assert moe._form(rows * 3, 8, width or 8, stacked, 32, 12, 4) == form
        assert ("pallas_call" in names) == (form == "grouped")
        assert ("while" in names) == (form == "compacted")
