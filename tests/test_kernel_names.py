"""Every Pallas kernel in ``ops/`` carries a fixed name (the device trace
names the kernel's operation after it, so a reader finds the kernel
whatever jaxpr wraps the call): the jaxpr of each public wrapper, at toy
shapes on the CPU, holds a ``pallas_call`` of exactly that name."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchkafka_tpu.ops import flash, kvattn, moe, qmatmul

B, H, K, M, DH, BS = 2, 2, 1, 128, 128, 128
F32, I8 = jnp.float32, jnp.int8


def _q():
    return jnp.zeros((B, 1, H, DH), F32)


def _kmajor_cache():
    pay, scale = jnp.zeros((B, K, M, DH), I8), jnp.ones((B, K, M), F32)
    return pay, scale, pay, scale


def dynlen():
    pos = jnp.full((B,), 5, jnp.int32)
    return lambda: kvattn.int8_decode_attention_dynlen(
        _q(), *_kmajor_cache(), pos, interpret=True
    )


def paged():
    pay, scale = jnp.zeros((4, K, BS, DH), I8), jnp.ones((4, K, BS), F32)
    table = jnp.asarray(np.arange(B * 2).reshape(B, 2), jnp.int32)
    pos = jnp.full((B,), 5, jnp.int32)
    return lambda: kvattn.int8_paged_decode_attention(
        _q(), pay, scale, pay, scale, table, pos, interpret=True
    )


def _flash_operands():
    x = jnp.zeros((1, 128, H, DH), F32)
    return x, x, x


def flash_fwd():
    return lambda: flash.flash_attention(*_flash_operands(), interpret=True)


def flash_bwd():
    loss = lambda q, k, v: flash.flash_attention(  # noqa: E731
        q, k, v, interpret=True
    ).sum()
    return lambda: jax.grad(loss, argnums=(0, 1, 2))(*_flash_operands())


def qmm():
    x, q = jnp.zeros((8, 128), F32), jnp.zeros((128, 128), I8)
    return lambda: qmatmul.quantized_matmul(
        x, q, jnp.ones((128,), F32), interpret=True
    )


def flash_unequal():
    x = jnp.zeros((1, 128, H, 192), F32)
    return lambda: flash.flash_forward(
        x, x, x[..., :128], scale=1.0, interpret=True
    )


def grouped_matmul():
    x, idx = jnp.zeros((16, 32), F32), jnp.zeros((16, 2), jnp.int32)
    gate, down = jnp.zeros((4, 32, 16), F32), jnp.zeros((4, 16, 32), F32)
    return lambda: moe.grouped_experts(
        x, idx, jnp.ones((16, 2), F32), gate, gate, down
    )


def pallas_names(jaxpr) -> list[str]:
    """The names of the ``pallas_call``s anywhere in a jaxpr."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(pallas_names(sub))
    return names


@pytest.mark.parametrize("wrapper,names", [
    (dynlen, ["tk_kvattn_dynlen"]),
    (paged, ["tk_kvattn_paged"]),
    (flash_fwd, ["tk_flash_fwd"]),
    (flash_bwd, ["tk_flash_bwd_dkv", "tk_flash_bwd_dq", "tk_flash_fwd"]),
    (qmm, ["tk_qmatmul"]),
    (flash_unequal, ["tk_flash_fwd"]),
    (grouped_matmul, ["tk_gmm_down", "tk_gmm_gate_up"]),
], ids=lambda p: p.__name__ if callable(p) else None)
def test_the_wrapper_calls_its_kernel_by_its_fixed_name(wrapper, names):
    jaxpr = jax.make_jaxpr(wrapper())().jaxpr
    assert sorted(set(pallas_names(jaxpr))) == names
