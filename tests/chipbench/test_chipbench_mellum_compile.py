"""Rehearsal compile for the described v5e of the cell
``mellum2.repo-context-drain``: its tick and its admit at the published
widths and the deployment's 128 slots, compiled by the TPU's compiler
with no chip attached, held to the chip's memory and to what they must
not contain (a pool-shaped copy inside a loop, a re-laid copy of the
stacked expert weights). Nothing runs, so no number here is a
measurement. The footprints it reads are those written into the
configuration's file.

A file of its own because ``test_chipbench_tpu_compile.py`` belongs to
the accepted benchmark and is not edited; the helpers are
``test_chipbench_longcat_compile.py``'s. Where this worker cannot load
the TPU's library the tests skip.
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
    (REPO / "chipbench/configs/mellum2-12b-a2.5b-8l.json").read_text()
)
FULL = r"bf16\[2,128,5120,512\]"
RING = r"bf16\[6,128,1024,512\]"
STACKED = r"bf16\[(8,64|512),(2304,896|896,2304)\]"
LAYER_SLICE = r"bf16\[64,(2304,896|896,2304)\]"


@pytest.fixture(scope="module")
def programs(topo):
    """(tick, admit) compiled for one described chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import torchkafka_tpu as tk
    from chipbench.models import mellum_decoder as model
    from torchkafka_tpu.serve import StreamingGenerator

    honest = jax.default_backend
    jax.default_backend = lambda: "tpu"  # flash compiles, not interprets
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

        def sds(s):
            return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)

        params = jax.tree.map(sds, p_shapes)
        caches, last, pos, gen, keys = jax.tree.map(sds, state)
        assert [c.shape for c in caches] == [
            (2, slots, window + new, 512), (2, slots, window + new, 512),
            (6, slots, 1024, 512), (6, slots, 1024, 512),
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
    # Weights and both pools: ISSUE 34's 11.88 GB, seven tenths of the chip.
    args = admit.memory_analysis().argument_size_in_bytes
    assert 11.8e9 < args < 12.0e9 and args > 0.68 * 17.18e9
    written = CONF["deployment"]["compiled_for_a_described_v5e"]
    for name, compiled in (("jit_tick_block", tick), ("jit_admit", admit)):
        assert written[f"{name}_footprint_gib"] == pytest.approx(
            footprint(compiled) / 2**30, abs=0.06
        )


def test_both_pools_are_written_in_place(programs):
    """Neither program holds a whole-pool copy or select: both pools are
    the carry of the tick's loops and of the admit's."""
    tick, admit = programs
    for pool in (FULL, RING):
        for compiled in (tick, admit):
            assert "copy" not in results(compiled, pool)
            assert "select" not in results(compiled, pool, nested=True)
    # A tick writes a row a layer by a scatter into its kind's pool (K and
    # V; the period's three window layers and one full layer are unrolled
    # in the scan's body); the admit writes a row's window and its ring.
    assert results(tick, RING, nested=True).count("scatter") == 6
    assert results(tick, FULL, nested=True).count("scatter") == 2
    assert results(admit, RING, nested=True).count("dynamic-update-slice") == 2
    assert results(admit, FULL, nested=True).count("dynamic-update-slice") == 2
    # Two full-causal and six windowed flash calls an admission: a call a
    # layer of the period, inside the scan over periods (beside the
    # grouped matmul's own custom calls).
    calls = [
        line for line in admit.as_text().split("\n")
        if "custom-call(" in line and "tk_flash_fwd" in line
    ]
    assert sum("tk_flash_fwd_win" in c for c in calls) == 3 and len(calls) == 4


def test_no_stacked_expert_weight_is_copied(programs):
    """The experts' stacked tensors are read where they lie: a layer's
    are reached by ONE dynamic index that fuses into the product reading
    it (``transformer.scan_periods``)."""
    for compiled in programs:
        kinds = set(results(compiled, STACKED))
        assert kinds <= {"parameter", "get-tuple-element", "bitcast"}, kinds
        assert results(compiled, LAYER_SLICE) == []
