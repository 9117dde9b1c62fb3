"""What ``cfg.remat`` keeps: the flash kernel's output and log-sum-exp.

The layer's ``jax.checkpoint`` saves the two residuals that
``ops.flash._flash_fwd`` names (``REMAT_SAVED``), so the differentiated
step runs the forward kernel once a layer: in the forward scan, and not
again in the backward scan's recompute. Held here by the jaxpr of the
gradient (where each Pallas call sits), by the numbers (the kept tensors
are the ones a recompute would have produced: every gradient leaf is that
of the bare ``jax.checkpoint``, bit for bit), and by what must not move:
layers that run no flash kernel, and every forward-only caller.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchkafka_tpu.models import Transformer, TransformerConfig
from torchkafka_tpu.models import transformer as tfm
from torchkafka_tpu.ops import flash
from torchkafka_tpu.parallel import make_mesh

CFG = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=128, dtype=jnp.float32, attn_impl="flash",
    remat=True, scan_unroll=1,
)
MESHES = {
    "no_mesh": None,
    "data2_tp2": {"data": 2, "tp": 2},
    "gpipe_data2_pp2": {"data": 2, "pp": 2},
}


def tokens(seq: int = 128, rows: int = 4) -> jax.Array:
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.integers(0, CFG.vocab_size, (rows, seq)), jnp.int32)


def model_of(cfg: TransformerConfig, axes: dict | None) -> Transformer:
    if axes is None:
        return Transformer(cfg)
    size = int(np.prod(list(axes.values())))
    return Transformer(cfg, make_mesh(axes, devices=jax.devices()[:size]))


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for x in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def pallas_calls(jaxpr, path: tuple = ()):
    """(names of the enclosing primitives, kernel name) of every Pallas
    call under ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield path, eqn.params["name"]
        for sub in _sub_jaxprs(eqn):
            yield from pallas_calls(sub, path + (eqn.primitive.name,))


def primitives(jaxpr) -> set[str]:
    found = set()
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in _sub_jaxprs(eqn):
            found |= primitives(sub)
    return found


def grad_jaxpr(model: Transformer, toks: jax.Array):
    params = model.init(jax.random.key(0))
    return jax.make_jaxpr(jax.grad(lambda p: model.loss(p, toks, toks)))(params).jaxpr


def grad_calls(model: Transformer, toks: jax.Array):
    return list(pallas_calls(grad_jaxpr(model, toks)))


def loss_and_grads(model: Transformer, toks: jax.Array):
    params = model.init(jax.random.key(0))
    return jax.jit(jax.value_and_grad(lambda p: model.loss(p, toks, toks)))(params)


@pytest.mark.parametrize("where", list(MESHES))
def test_the_forward_kernel_runs_once_a_layer(where):
    model = model_of(CFG, MESHES[where])
    assert model._use_flash
    calls = grad_calls(model, tokens())
    forward = [path for path, name in calls if name == "tk_flash_fwd"]
    assert len(forward) == 1, calls
    assert "remat2" not in forward[0]  # the forward pass's own, not a recompute
    if where == "data2_tp2":  # the kernel sits inside flash_attention_sharded
        assert model._flash_shard_mesh is not None
        assert forward[0][-1] == "shard_map"
    if where == "gpipe_data2_pp2":  # the layer is gpipe's ``layer_fn``
        assert forward[0][0] == "shard_map" and "scan" in forward[0]
    # Both backward kernels are there, once each, in the recompute's scope.
    for name in ("tk_flash_bwd_dq", "tk_flash_bwd_dkv"):
        assert [n for _, n in calls].count(name) == 1
        assert all("remat2" in path for path, n in calls if n == name)


@pytest.mark.parametrize("where", list(MESHES))
def test_a_bare_checkpoint_runs_it_twice(where, monkeypatch):
    """The probe sees what it is meant to: under the ``jax.checkpoint`` of
    before, the second forward kernel is in the backward's recompute."""
    monkeypatch.setattr(tfm, "_remat_layer", jax.checkpoint)
    calls = grad_calls(model_of(CFG, MESHES[where]), tokens())
    forward = [path for path, name in calls if name == "tk_flash_fwd"]
    assert len(forward) == 2
    assert sum("remat2" in path for path in forward) == 1


@pytest.mark.parametrize("where", list(MESHES))
def test_the_numbers_are_the_bare_checkpoint_s(where, monkeypatch):
    toks = tokens()
    loss, grads = loss_and_grads(model_of(CFG, MESHES[where]), toks)
    plain = dataclasses.replace(CFG, remat=False)
    loss_n, grads_n = loss_and_grads(model_of(plain, MESHES[where]), toks)
    monkeypatch.setattr(tfm, "_remat_layer", jax.checkpoint)
    loss_b, grads_b = loss_and_grads(model_of(CFG, MESHES[where]), toks)
    assert float(loss) == float(loss_b)
    assert abs(float(loss) - float(loss_n)) < 1e-5
    flat, flat_b, flat_n = (
        jax.tree_util.tree_leaves_with_path(g) for g in (grads, grads_b, grads_n)
    )
    for (path, a), (_, b), (_, n) in zip(flat, flat_b, flat_n):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), str(path))
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(n), atol=2e-5, err_msg=str(path)
        )


@pytest.mark.parametrize("case", ["sequence_does_not_tile", "moe_layer", "dense_attn"])
def test_other_layers_differentiate_as_before(case, monkeypatch):
    """A layer without the kernel names nothing: same program as under the
    bare checkpoint. An MoE layer keeps its router statistics."""
    cfg, seq = CFG, 128
    if case == "sequence_does_not_tile":
        seq = 96  # no block of 128 divides it: the dense fallback, both ways
    elif case == "moe_layer":
        cfg = dataclasses.replace(CFG, n_experts=4, expert_top_k=2)
    else:
        cfg = dataclasses.replace(CFG, attn_impl="dense")
    toks = tokens(seq)
    model = Transformer(cfg)
    jaxpr = grad_jaxpr(model, toks)
    calls = list(pallas_calls(jaxpr))
    assert [n for _, n in calls].count("tk_flash_fwd") == (case == "moe_layer")
    loss, grads = loss_and_grads(model, toks)
    if case != "moe_layer":
        assert calls == [] and "name" not in primitives(jaxpr)
    monkeypatch.setattr(tfm, "_remat_layer", jax.checkpoint)
    loss_b, grads_b = loss_and_grads(Transformer(cfg), toks)
    assert float(loss) == float(loss_b)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if case == "moe_layer":
        _, aux = Transformer(cfg)(
            model.init(jax.random.key(0)), toks, return_aux=True
        )
        assert float(aux) > 0


@pytest.mark.parametrize(
    "call", ["flash_attention", "flash_forward", "sharded", "model_forward"]
)
def test_forward_only_callers_name_nothing(call):
    """Serving never differentiates: its programs hold no ``name``
    primitive, so they are the programs of before."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 128, 4, 16)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(2, 128, 2, 16)), jnp.float32)
    if call == "flash_attention":
        jaxpr = jax.make_jaxpr(lambda q, k, v: flash.flash_attention(q, k, v))(q, kv, kv)
    elif call == "flash_forward":
        jaxpr = jax.make_jaxpr(
            lambda q, k, v: flash.flash_forward(q, k, v, scale=0.25)
        )(q, kv, kv)
    elif call == "sharded":
        mesh = make_mesh({"data": 2, "tp": 2}, devices=jax.devices()[:4])
        jaxpr = jax.make_jaxpr(
            lambda q, k, v: flash.flash_attention_sharded(q, k, v, mesh)
        )(q, kv, kv)
    else:
        model = Transformer(CFG)
        jaxpr = jax.make_jaxpr(model)(model.init(jax.random.key(0)), tokens())
    assert [n for _, n in pallas_calls(jaxpr.jaxpr)] == ["tk_flash_fwd"]
    assert "name" not in primitives(jaxpr.jaxpr)


def test_the_residuals_are_named_in_the_kernel_s_layout():
    """The names sit on the [B·H, S, D] output (its bits) and the [B·H, S,
    1] log-sum-exp the backward kernels read, not on the [B, S, H, D] view
    returned as the primal."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, 128, 4, 16)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(2, 128, 2, 16)), jnp.float32)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda q, k, v: flash.flash_attention(q, k, v).sum(), (0, 1, 2))
    )(q, kv, kv)
    named = {
        eqn.params["name"]: eqn.outvars[0].aval
        for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "name"
    }
    assert {n: a.shape for n, a in named.items()} == dict(
        zip(flash.REMAT_SAVED, [(8, 128, 16), (8, 128, 1)])
    )
    # The output is named as its bits: ``jax.checkpoint`` would pass a
    # floating residual that the forward pass reads through a
    # ``reduce_precision``, a pass over the tensor that changes nothing.
    assert [str(a.dtype) for a in named.values()] == ["uint32", "float32"]
