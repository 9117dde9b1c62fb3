"""Rehearsal compile for the described v5e of the cell
``granite4h.multi-session-drain``: its tick and its admit at the published
widths and the deployment's 128 slots, compiled by the TPU's compiler
with no chip attached, held to the chip's memory and to what they must
and must not contain (ONE pass over a Mamba-2 layer's state a tick, the
kernel ``tk_ssd_step`` with the state aliased in place; no state-shaped
or pool-shaped copy inside a loop; the held experts summed by the grouped
kernels in a tick; no re-laid copy of the stacked expert weights). Nothing
runs, so no number here is a measurement. The footprints it reads are
those written into the configuration's file.

A file of its own because ``test_chipbench_tpu_compile.py`` belongs to
the accepted benchmark and is not edited; the helpers are
``test_chipbench_longcat_compile.py``'s. Where this worker cannot load the
TPU's library the tests skip.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from test_chipbench_longcat_compile import (  # noqa: E402, F401
    HBM_BYTES, footprint, results, topo,
)

CONF = json.loads(
    (REPO / "chipbench/configs/granite-4.0-h-small-10l-ep4.json").read_text()
)
STATE = r"f32\[9,128,128,64,128\]"
STATE_VIEW = r"f32\[9,128,8192,128\]"  # the admit's, H and P merged
TAILS = r"bf16\[9,128,25344\]"  # a slot's three rows in ONE row
POOL = r"bf16\[(1,)?128,4096,1024\]"
POOL_VIEW = r"bf16\[1,128,4194304\]"  # the admit's, a slot's rows merged
STACKED = r"bf16\[(10,18|180),(4096,768|768,4096)\]"
LAYER_SLICE = r"bf16\[18,(4096,768|768,4096)\]"


@pytest.fixture(scope="module")
def programs(topo):
    """(tick, admit) compiled for one described chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import torchkafka_tpu as tk
    from chipbench.models import granite_decoder as model
    from torchkafka_tpu.serve import StreamingGenerator

    honest = jax.default_backend
    jax.default_backend = lambda: "tpu"  # the kernels compile, not interpret
    try:
        dep = CONF["deployment"]
        slots, window, new = dep["slots"], dep["prompt_window"], dep["max_new"]
        cfg = model.program_config(CONF, window + new)
        one = SingleDeviceSharding(topo.devices[0])
        broker = tk.InMemoryBroker()
        broker.create_topic("p", partitions=2)
        consumer = tk.MemoryConsumer(broker, "p", group_id="g")
        p_shapes = jax.eval_shape(lambda: model.serving_params(CONF, 0))
        held = {}

        def build():
            params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), p_shapes)
            held["server"] = server = StreamingGenerator(
                consumer, params, cfg, slots=slots, prompt_len=window,
                max_new=new, ticks_per_sync=dep["ticks_per_sync"],
                kv_dtype=dep["kv_dtype"], kv_kernel=dep["kv_kernel"],
            )
            return (server._caches, server._last_tok, server._pos,
                    server._gen, server._slot_keys)

        state = jax.eval_shape(build)
        server = held["server"]
        summary = server.metrics.summary()
        assert summary["linear_state"]["step"] == "kernel"
        assert summary["linear_state"]["kind"] == "ssd"
        assert summary["expert_layer"]["tick_form"] == "grouped"

        def sds(s):
            return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)

        params = jax.tree.map(sds, p_shapes)
        caches, last, pos, gen, keys = jax.tree.map(sds, state)
        assert [c.shape for c in caches] == [
            (9, slots, 128, 64, 128), (9, slots, 3 * 8448),
            (1, slots, window + new, 1024), (1, slots, window + new, 1024),
        ]
        mask = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one)
        prompts = jax.ShapeDtypeStruct((slots, window), jnp.int32, sharding=one)

        def jitted(fn):
            return next(
                c.cell_contents for c in fn.__closure__
                if hasattr(c.cell_contents, "lower")
            )

        tick = jitted(server._tick_fn).lower(
            params, caches, last, pos, gen, mask, keys
        ).compile()
        admit = jitted(server._admit_fn).lower(
            params, caches, last, pos, gen, prompts, mask, keys
        ).compile()
        return tick, admit
    finally:
        jax.default_backend = honest


def test_the_128_slots_fit_the_chip_and_need_no_step_down(programs):
    tick, admit = programs
    assert footprint(tick) < HBM_BYTES and footprint(admit) < HBM_BYTES
    # Weights and slot memory: ISSUE 45's 12.95 GB, three quarters of the
    # chip, of which the state is the largest part after the weights.
    args = admit.memory_analysis().argument_size_in_bytes
    assert 12.9e9 < args < 13.0e9 and args > 0.75 * 17.18e9
    written = CONF["deployment"]["compiled_for_a_described_v5e"]
    assert written["arguments_gb"] == pytest.approx(args / 1e9, abs=0.01)
    for name, compiled in (("jit_tick_block", tick), ("jit_admit", admit)):
        assert written[f"{name}_footprint_gib"] == pytest.approx(
            footprint(compiled) / 2**30, abs=0.06
        )


def calls(compiled, name: str) -> list[str]:
    """The custom calls whose own name is ``name`` (not those that take
    one's result)."""
    return [
        line for line in compiled.as_text().split("\n")
        if line.strip().startswith(f"%{name}") and "custom-call(" in line
    ]


def test_a_tick_passes_over_a_layer_s_state_once(programs):
    """Nine calls of ``tk_ssd_step`` a tick, each with the state aliased
    in place; nothing state-shaped is copied, selected or scattered
    anywhere in either program; the admit writes a trip's three rows
    through the view with H and P merged (``serve.py::_build``,
    ``merged_put``: without it the whole state is laid out again, 4.5 GiB
    in and out of every admission, and the program does not fit)."""
    tick, admit = programs
    steps = calls(tick, "tk_ssd_step")
    assert len(steps) == 9
    assert all("output_to_operand_aliasing" in c for c in steps)
    assert "tk_ssd_step" not in admit.as_text()
    for compiled in (tick, admit):
        for shape in (STATE, STATE_VIEW):
            every = results(compiled, shape, nested=True)
            assert not {"copy", "select", "scatter", "reshape"} & set(every), every
    assert results(admit, STATE_VIEW, nested=True).count(
        "dynamic-update-slice"
    ) == 3


def test_the_tails_and_the_kv_rows_are_written_in_place(programs):
    tick, admit = programs
    # The conv tails lie three rows in ONE row. As [3, 8448] the device
    # pads the 3 to 4, and the compiler, short of memory, kept the tails
    # "compressed" between their uses: fourteen copies of every layer's
    # tails a tick, 10 ms by its own estimate. Nothing is compressed now.
    for compiled in (tick, admit):
        assert "remat_compressed" not in compiled.as_text()
    assert results(tick, TAILS, nested=True).count("dynamic-update-slice") >= 9
    assert results(admit, TAILS, nested=True).count("dynamic-update-slice") == 3
    assert "copy" not in results(admit, TAILS)
    # The attention layer's K row and V row by one scatter each a tick,
    # into pools no tick copies; the admit writes a trip's three rows'
    # windows into each pool through the merged view, which costs one copy
    # of each pool in and out of an admission (written into the file).
    for compiled in (tick, admit):
        assert "select" not in results(compiled, POOL, nested=True)
    assert "copy" not in results(tick, POOL)
    assert results(tick, POOL, nested=True).count("scatter") == 2
    assert results(admit, POOL_VIEW).count("reshape") == 2
    assert results(admit, POOL_VIEW, nested=True).count(
        "dynamic-update-slice"
    ) == 6


def test_the_tick_sums_its_held_experts_by_the_grouped_kernels(programs):
    """Ten expert layers a tick, each one ``tk_gmm_gate_up`` and one
    ``tk_gmm_down`` over the 18 held experts' stacks; the admission keeps
    the loop of one-expert tiles (its absent pairs' rows outweigh the
    bound)."""
    tick, admit = programs
    for name in ("tk_gmm_gate_up", "tk_gmm_down"):
        assert len(calls(tick, name)) == 10
        assert name not in admit.as_text()


def test_no_stacked_expert_weight_is_copied(programs):
    """The held experts' stacked tensors are read where they lie: an
    expert is reached by ONE dynamic index that fuses into the product
    reading it (``transformer.scan_hybrid``)."""
    for compiled in programs:
        kinds = set(results(compiled, STACKED))
        assert kinds <= {"parameter", "get-tuple-element", "bitcast"}, kinds
        assert results(compiled, LAYER_SLICE) == []
