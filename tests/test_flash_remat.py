"""What ``cfg.remat`` keeps: the flash kernel's output and log-sum-exp,
and under a ``tp`` axis the residual stream after the attention block.

The layer's ``jax.checkpoint`` saves the two residuals that
``ops.flash._flash_fwd`` names (``REMAT_SAVED``), so the differentiated
step runs the forward kernel once a layer: in the forward scan, and not
again in the backward scan's recompute. Held here by the jaxpr of the
gradient (where each Pallas call sits), by the numbers (the kept tensors
are the ones a recompute would have produced: every gradient leaf is that
of the bare ``jax.checkpoint``, bit for bit), and by what must not move:
layers that run no flash kernel, and every forward-only caller.

Under a mesh with a ``tp`` axis the layer also names the residual stream
after its attention block, the output projection summed over ``tp`` and
added (``transformer.REMAT_SAVED_TP``), and the policy keeps it: the
backward's recompute runs neither the ``wo`` product nor its all-reduce
again. Held by the compiled gradient's text (no all-reduce under
``rematted_computation``), by where the names sit in the gradient's jaxpr
(a kept name is outside every ``remat2``), and by the numbers as above.
With no ``tp`` axis nothing more is named: the kept names are
``REMAT_SAVED`` alone.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchkafka_tpu.models import Transformer, TransformerConfig
from torchkafka_tpu.models import transformer as tfm
from torchkafka_tpu.ops import flash
from torchkafka_tpu.models.transformer import param_specs, shardings_for_mesh
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


def pallas_calls(jaxpr, path: tuple = (), primitive: str = "pallas_call"):
    """(names of the enclosing primitives, kernel name) of every Pallas
    call under ``jaxpr``; with ``primitive="name"``, the same of every
    ``checkpoint_name`` and the name it gives."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield path, eqn.params["name"]
        for sub in _sub_jaxprs(eqn):
            yield from pallas_calls(sub, path + (eqn.primitive.name,), primitive)


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


# --- under a tp axis: the stream after the attention block is kept --------

WITH_TP = flash.REMAT_SAVED + (tfm.REMAT_SAVED_TP,)
KEPT = {
    "no_mesh": (None, flash.REMAT_SAVED),
    "gpipe_data2_pp2": ({"data": 2, "pp": 2}, flash.REMAT_SAVED),
    "data4": ({"data": 4}, flash.REMAT_SAVED),
    "data2_fsdp2": ({"data": 2, "fsdp": 2}, flash.REMAT_SAVED),
    "data2_tp2": ({"data": 2, "tp": 2}, WITH_TP),
    "gpipe_pp2_tp2": ({"pp": 2, "tp": 2}, WITH_TP),
}


def names_in(jaxpr):
    """(names of the enclosing primitives, name) of every
    ``checkpoint_name`` under ``jaxpr``."""
    return pallas_calls(jaxpr, primitive="name")


def saved_under_remat_saved(fn):
    """The policy of before this file's second half: ``REMAT_SAVED``
    alone, whatever the layer names."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(
            *flash.REMAT_SAVED
        ),
    )


def reductions(model: Transformer, toks: jax.Array) -> list[str]:
    """The ``op_name`` of every all-reduce in the compiled gradient, the
    parameters laid out by ``param_specs`` as a train step's are."""
    params = jax.device_put(
        model.init(jax.random.key(0)),
        shardings_for_mesh(model.mesh, param_specs(model.cfg)),
    )
    text = jax.jit(
        jax.grad(lambda p: model.loss(p, toks, toks))
    ).lower(params).compile().as_text()
    return [
        re.search(r'op_name="([^"]*)"', line).group(1)
        for line in text.split("\n")
        if re.search(r" all-reduce(-start)?\(", line)
    ]


@pytest.mark.parametrize("where", list(KEPT))
def test_what_is_kept_is_what_the_mesh_calls_for(where):
    """In the gradient's jaxpr a kept value is named in the forward pass,
    outside every ``remat2``; a value named and NOT kept would be named
    again inside the recompute."""
    axes, kept = KEPT[where]
    named = list(names_in(grad_jaxpr(model_of(CFG, axes), tokens())))
    assert all("remat2" not in path for path, _ in named), named
    assert tuple(name for _, name in named) == kept


def test_the_backward_repeats_no_reduction():
    """data 2 x tp 2, compiled: the forward's reduction of the output
    projection once, in the forward loop, and no all-reduce inside the
    backward's recompute."""
    found = reductions(model_of(CFG, MESHES["data2_tp2"]), tokens())
    assert not [op for op in found if "rematted_computation" in op], found
    proj = [op for op in found if "bshe,hed->bsd" in op]
    assert len(proj) == 1 and "transpose(jvp" not in proj[0], found


def test_keeping_flash_s_names_alone_repeats_one(monkeypatch):
    """The probe sees what it is meant to: with the policy of before, the
    recompute holds the output projection's all-reduce a second time (one
    all-reduce more in all), and the layer's name sits inside the
    ``remat2``."""
    kept = reductions(model_of(CFG, MESHES["data2_tp2"]), tokens())
    monkeypatch.setattr(tfm, "_remat_layer", saved_under_remat_saved)
    model = model_of(CFG, MESHES["data2_tp2"])
    found = reductions(model, tokens())
    again = [op for op in found if "rematted_computation" in op]
    assert len(again) == 1 and "bshe,hed->bsd" in again[0], found
    assert len(found) == len(kept) + 1
    named = list(names_in(grad_jaxpr(model, tokens())))
    assert [
        name for path, name in named if "remat2" in path
    ] == [tfm.REMAT_SAVED_TP]


def test_gpipe_s_layer_under_tp_has_the_bare_checkpoint_s_numbers(monkeypatch):
    """The second call site: gpipe's ``layer_fn`` under pp 2 x tp 2 keeps
    the reduced stream too, and what it keeps is what the recompute would
    have produced."""
    toks, axes = tokens(), KEPT["gpipe_pp2_tp2"][0]
    loss, grads = loss_and_grads(model_of(CFG, axes), toks)
    monkeypatch.setattr(tfm, "_remat_layer", jax.checkpoint)
    loss_b, grads_b = loss_and_grads(model_of(CFG, axes), toks)
    assert float(loss) == float(loss_b)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(grads),
        jax.tree_util.tree_leaves_with_path(grads_b),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), str(path))


@pytest.mark.parametrize("call", ["forward", "gradient"])
def test_without_remat_a_tp_mesh_names_nothing_more(call):
    """Serving and a step without ``cfg.remat`` keep nothing, so name
    nothing: under tp their programs are the programs of before."""
    plain = dataclasses.replace(CFG, remat=False)
    model = model_of(plain, MESHES["data2_tp2"])
    if call == "forward":
        jaxpr = jax.make_jaxpr(model)(model.init(jax.random.key(0)), tokens())
        assert "name" not in primitives(jaxpr.jaxpr)
    else:
        named = [n for _, n in names_in(grad_jaxpr(model, tokens()))]
        assert tuple(named) == flash.REMAT_SAVED
