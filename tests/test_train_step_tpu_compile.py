"""Rehearsal compiles for the described v5e of the two training steps the
benchmark runs (``internlm2-1.8b`` on 2x2, its 20-layer cut on one chip),
held to what ``cfg.remat`` keeps since PR 32: ONE flash forward kernel a
layer (three Pallas calls in the step: forward, dq, dkv; the recompute's
second forward is gone), the kept output and log-sum-exp stacked by an
in-place write of one layer's slice, and a program that fits its chip.
Since PR 51 the 2x2 step also keeps, under its ``tp`` axis, the residual
stream after the attention block, reduction done: no all-reduce inside
the backward's recompute, one fewer than under ``REMAT_SAVED`` alone, one
more ``[L, B, S, d_model]`` stack, and still inside the chip; the one-chip
step keeps what it kept.
Nothing runs, so no number here is a measurement.

Beside them the grouped expert matmul's two kernels (``ops/moe.py``, PR
36) at the widths of the two admissions that run them and of the one tick
that does (Mellum2's, PR 39), about two seconds each: what Mosaic refuses
(a block over the scoped VMEM, a contraction it does not take) shows here
and not on the chip.

And the dense int8 read under its live mask (``ops/kvattn.py``, PR 47)
at Mistral's widths, at each block the chip read: the order's indexed
blocks and the [K, block] scale slices are Mosaic's to refuse.

A file of its own because ``tests/chipbench/`` belongs to the accepted
benchmark and is not edited: the topology is described inside a fixture,
never at import, and where this worker cannot load the TPU's library
(another file's worker holds it and ``ALLOW_MULTIPLE_LIBTPU_LOAD`` is not
set) the tests skip.
"""

from __future__ import annotations

import json
import os
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

GIB = 2**30
HBM_BYTES = 15.75 * GIB  # what the v5e compiler allows a program
# configuration, rows a step, the kept stacks as one device holds them:
# [L, B·H, S, D], the bf16 output's bits, and [L, B·H, S, 1] in f32.
CELLS = {
    "one_chip": ("internlm2-1.8b-1chip", 2, r"u16\[20,32,4096,128\]", r"f32\[20,32,4096,1\]"),
    "2x2": ("internlm2-1.8b", 4, r"u16\[24,16,4096,128\]", r"f32\[24,16,4096,1\]"),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def compile_step(topo, cell: str):
    """``cell``'s step program compiled for the described chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchkafka_tpu as tk
    from chipbench.models import dense_decoder as model
    from torchkafka_tpu.models import make_train_step
    from torchkafka_tpu.models.transformer import (
        batch_spec, init_params, opt_shardings_like, param_specs,
        shardings_for_mesh,
    )

    name, rows, _, _ = CELLS[cell]
    conf = json.loads((REPO / "chipbench/configs" / f"{name}.json").read_text())
    honest = jax.default_backend
    jax.default_backend = lambda: "tpu"  # flash compiles, not interprets
    try:
        axes = conf["deployment"]["mesh"]
        chips = int(np.prod(list(axes.values())))
        mesh = tk.make_mesh(axes, devices=list(topo.devices)[:chips])
        cfg = model.program_config(conf, 4096, remat=conf["deployment"]["remat"])
        assert cfg.remat
        opt = model.optimizer(conf)
        _init_fn, step_fn = make_train_step(cfg, mesh, opt)
        p_sh = shardings_for_mesh(mesh, param_specs(cfg))

        def init(rng):
            p = init_params(rng, cfg)
            return p, opt.init(p)

        p_shapes, o_shapes = jax.eval_shape(init, jax.random.key(0))
        o_sh = opt_shardings_like(
            o_shapes, p_shapes, p_sh, NamedSharding(mesh, P())
        )

        def sds(s, sh):
            return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

        tokens = jax.ShapeDtypeStruct(
            (rows, 4096), jnp.int32,
            sharding=NamedSharding(mesh, batch_spec(mesh)),
        )
        compiled = step_fn.lower(
            jax.tree.map(sds, p_shapes, p_sh),
            jax.tree.map(sds, o_shapes, o_sh), tokens, tokens,
        ).compile()
        return compiled
    finally:
        jax.default_backend = honest


_COMPILED: dict = {}


def compiled_once(topo, cell: str):
    """``cell``'s step as the tree compiles it, once a worker."""
    if cell not in _COMPILED:
        _COMPILED[cell] = compile_step(topo, cell)
    return _COMPILED[cell]


@pytest.fixture(scope="module", params=list(CELLS))
def step(request, topo):
    """(cell, the step program compiled for the described chips)."""
    return request.param, compiled_once(topo, request.param)


def all_reduces(text: str) -> list[str]:
    """The ``op_name`` of every all-reduce of a compiled program."""
    return [
        re.search(r'op_name="([^"]*)"', line).group(1)
        for line in text.split("\n")
        if re.search(r" all-reduce(-start)?\(", line)
    ]


def opcodes_of(text: str, shape: str) -> set[str]:
    """The opcodes of every operation whose result has ``shape``, inside
    fused computations too."""
    found = set()
    for line in text.split("\n"):
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([a-z\-]+)\(", line)
        if m and re.match(shape, m.group(1)):
            found.add(m.group(2))
    return found


def test_one_forward_kernel_a_layer(step):
    cell, compiled = step
    text = compiled.as_text()
    # tk_flash_fwd in the forward loop, tk_flash_bwd_dq and _dkv in the
    # backward loop: the bare checkpoint held a fourth, the forward again.
    assert text.count("tpu_custom_call") == 3
    for kernel in ("tk_flash_fwd", "tk_flash_bwd_dq", "tk_flash_bwd_dkv"):
        assert kernel in text
    assert ("all-reduce(" in text) == (cell == "2x2")


def test_the_kept_stacks_are_written_in_place(step):
    cell, compiled = step
    text = compiled.as_text()
    for shape in CELLS[cell][2:]:
        ops = opcodes_of(text, shape)
        # One layer's slice goes into the stack by dynamic-update-slice and
        # comes out by a dynamic-slice fused into its reader: no operation
        # makes a second stack.
        assert "dynamic-update-slice" in ops
        assert ops <= {  # no copy, no transpose, no concatenate
            "custom-call",  # AllocateBuffer, once, before the forward loop
            "dynamic-update-slice", "fusion", "get-tuple-element",
            "parameter", "bitcast",
        }, ops


def test_the_recompute_reduces_nothing(step):
    """Under ``tp`` the remat keeps the residual stream after the attention
    block, its output projection reduced and added: the forward's
    reduction of that projection stays, once, and the backward loop's
    recompute holds no all-reduce. One more stack of the layer input's
    shape says where it is kept; the one-chip step has the layer inputs'
    stack alone."""
    cell, compiled = step
    text = compiled.as_text()
    found = all_reduces(text)
    assert not [op for op in found if "rematted_computation" in op], found
    layers, rows = (24, 2) if cell == "2x2" else (20, 2)
    stacks = [
        line for line in text.split("\n")
        if re.search(rf" = bf16\[{layers},{rows},4096,2048\]\S* custom-call\(", line)
        and "AllocateBuffer" in line
    ]
    if cell == "2x2":
        assert len([op for op in found if "bshe,hed->bsd" in op]) == 1, found
        assert len(stacks) == 2, stacks
    else:
        assert found == [] and len(stacks) == 1, stacks


def test_keeping_flash_s_names_alone_reduces_once_more(topo, monkeypatch):
    """The 2x2 step under the policy of before PR 51: the output
    projection's all-reduce a second time, inside the recompute."""
    import jax

    from torchkafka_tpu.models import transformer as tfm
    from torchkafka_tpu.ops.flash import REMAT_SAVED

    kept = all_reduces(compiled_once(topo, "2x2").as_text())
    monkeypatch.setattr(tfm, "_remat_layer", lambda fn: jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(*REMAT_SAVED)
    ))
    before = all_reduces(compile_step(topo, "2x2").as_text())
    again = [op for op in before if "rematted_computation" in op]
    assert len(again) == 1 and "bshe,hed->bsd" in again[0], before
    assert len(before) == len(kept) + 1


def test_the_step_fits_its_chips(step, capsys):
    cell, compiled = step
    m = compiled.memory_analysis()
    footprint = (
        m.argument_size_in_bytes + m.temp_size_in_bytes
        + m.output_size_in_bytes - m.alias_size_in_bytes
    )
    with capsys.disabled():
        print(
            f"\n{cell}: arguments {m.argument_size_in_bytes / GIB:.3f} GiB, "
            f"temporaries {m.temp_size_in_bytes / GIB:.3f} GiB, "
            f"footprint {footprint / GIB:.3f} GiB of {HBM_BYTES / GIB:.2f}; "
            f"peak {m.peak_memory_in_bytes / GIB:.3f} GiB"
        )
    # The sum the benchmark's own compile test holds (it counts every
    # stack the forward loop hands the backward loop twice, PERF.md §6),
    # and the peak the compiler itself holds against the chip.
    assert footprint < HBM_BYTES
    assert m.peak_memory_in_bytes < footprint


# tokens a trip, top-k, experts a layer, layers stacked, D, F
GROUPED = {
    "mellum2-12b-a2.5b-8l": (4096, 8, 64, 8, 2304, 896),
    "kanana-2-30b-a3b-7l": (3072, 6, 128, 1, 2048, 768),
    # PR 39: a decode tick of Mellum2's 128 slots, 1,024 sorted rows (16 an
    # expert) out of the same [512, ...] stacks.
    "mellum2-12b-a2.5b-8l.tick": (128, 8, 64, 8, 2304, 896),
}


@pytest.mark.parametrize("ambient", [None, "highest"])
@pytest.mark.parametrize("name", list(GROUPED))
def test_the_grouped_matmul_kernels_compile_at_the_admissions_widths(
    topo, monkeypatch, name, ambient
):
    """``grouped_experts`` over a trip's pairs in bf16, the stacks taken
    whole: two Mosaic calls under their names and no stack-shaped result
    (a slice or a copy of a stack would be one). An ambient float32
    matmul precision is float32 operands' alone and refuses nothing."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from torchkafka_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, k, e, layers, d, f = GROUPED[name]
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def fn(x, idx, w, gate, up, down, base):
        return moe.grouped_experts(x, idx, w, gate, up, down, (base, e))

    with jax.default_matmul_precision(ambient or "default"):
        text = jax.jit(fn).lower(
            sds((n, d)), sds((n, k), jnp.int32), sds((n, k), jnp.float32),
            sds((layers * e, d, f)), sds((layers * e, d, f)),
            sds((layers * e, f, d)), sds((), jnp.int32),
        ).compile().as_text()
    calls = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert len(calls) == 2
    assert "tk_gmm_gate_up" in calls[0] + calls[1]
    assert "tk_gmm_down" in calls[0] + calls[1]
    stack = rf"bf16\[{layers * e},({d},{f}|{f},{d})\]"
    assert opcodes_of(text, stack) <= {"parameter"}


@pytest.mark.parametrize("block", [None, 512, 128], ids=["default", "512", "128"])
def test_the_dynlen_read_compiles_under_a_live_mask(topo, block):
    """``tk_kvattn_dynlen`` as Mistral's tick calls it (48 slots, 8 kv
    heads of 128, a pool of 1,024, the stacked pool and this tick's rows,
    the tick's ``act``): ONE Mosaic call under its name, and the pool goes
    through it where it lies, no pool-shaped result beside the call's own
    aliased outputs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from torchkafka_tpu.ops.kvattn import (
        dynlen_block, int8_decode_attention_dynlen,
    )

    layers, slots, heads, kv, m, dh = 32, 48, 32, 8, 1024, 128
    assert dynlen_block(m) == 256
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def fn(q, kq, ks, vq, vs, pos, live, layer, *rows):
        return int8_decode_attention_dynlen(
            q, kq, ks, vq, vs, pos, layer=layer, rows=rows, live=live,
            block=block, interpret=False,
        )

    payload = sds((layers, slots, kv, m, dh), jnp.int8)
    scales = sds((layers, slots, kv, m), jnp.float32)
    row, row_scale = sds((slots, kv, dh), jnp.int8), sds((slots, kv), jnp.float32)
    text = jax.jit(fn, donate_argnums=(1, 2, 3, 4)).lower(
        sds((slots, 1, heads, dh), jnp.bfloat16), payload, scales, payload,
        scales, sds((slots,), jnp.int32), sds((slots,), jnp.bool_),
        sds((), jnp.int32), row, row_scale, row, row_scale,
    ).compile().as_text()
    calls = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "tk_kvattn_dynlen" in calls[0]
    pool = rf"s8\[({layers},{slots}|{layers * slots}),{kv},{m},{dh}\]"
    assert opcodes_of(text, pool) <= {"parameter", "bitcast", "get-tuple-element"}
