"""Rehearsal compile for the described v5e of the cell
``lfm2.record-enrichment-drain``: its tick and its admit at the published
widths and the deployment's 512 slots, compiled by the TPU's compiler
with no chip attached, held to the chip's memory and to what they must
and must not contain (a slot memory WITHOUT a state tensor; the conv
tails, a slot's two rows in one row, written in place and never kept
"compressed" between layers; one scatter a tick into each K/V pool, which
no tick copies; all 32 experts summed by the grouped kernels at 64 pairs
an expert; no re-laid copy of the stacked expert weights). Nothing runs,
so no number here is a measurement. The footprints it reads are those
written into the configuration's file.

A file of its own because ``test_chipbench_tpu_compile.py`` belongs to
the accepted benchmark and is not edited; the helpers are
``test_chipbench_longcat_compile.py``'s and
``test_chipbench_granite_compile.py``'s. Where this worker cannot load the
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

from test_chipbench_granite_compile import calls  # noqa: E402
from test_chipbench_longcat_compile import (  # noqa: E402, F401
    HBM_BYTES, footprint, results, topo,
)

CONF = json.loads(
    (REPO / "chipbench/configs/lfm2-8b-a1b-10l.json").read_text()
)
TAILS = r"bf16\[8,512,4096\]"  # a slot's two rows in ONE row
POOL = r"bf16\[(2,)?512,2048,512\]"
STACKED = r"bf16\[(8,32|256),(2048,1792|1792,2048)\]"
LAYER_SLICE = r"bf16\[32,(2048,1792|1792,2048)\]"


@pytest.fixture(scope="module")
def programs(topo):
    """(tick, admit) compiled for one described chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import torchkafka_tpu as tk
    from chipbench.models import lfm2_decoder as model
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
        assert summary["linear_state"]["kind"] == "conv"
        assert summary["linear_state"]["bytes_state"] == 0
        assert summary["linear_state"]["layers"] == 8
        assert summary["kv_pool"]["full_layers"] == 2
        assert summary["expert_layer"]["tick_form"] == "grouped"
        assert summary["expert_layer"]["experts_held"] == [0, 32]

        def sds(s):
            return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)

        params = jax.tree.map(sds, p_shapes)
        caches, last, pos, gen, keys = jax.tree.map(sds, state)
        # NO state tensor: the tails and the two attention layers' rows.
        assert [c.shape for c in caches] == [
            (8, slots, 2 * 2048), (2, slots, window + new, 512),
            (2, slots, window + new, 512),
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


def test_the_512_slots_fit_the_chip_and_need_no_step_down(programs):
    tick, admit = programs
    assert footprint(tick) < HBM_BYTES and footprint(admit) < HBM_BYTES
    # Weights and slot memory: ISSUE 52's 10.7 GB, two thirds of the chip
    # (the driver's floor for a new cell is a quarter).
    args = admit.memory_analysis().argument_size_in_bytes
    assert 10.7e9 < args < 10.75e9 and args > 0.6 * 17.18e9
    written = CONF["deployment"]["compiled_for_a_described_v5e"]
    assert written["arguments_gb"] == pytest.approx(args / 1e9, abs=0.01)
    for name, compiled in (("jit_tick_block", tick), ("jit_admit", admit)):
        assert written[f"{name}_footprint_gib"] == pytest.approx(
            footprint(compiled) / 2**30, abs=0.06
        )


def test_the_tails_and_the_kv_rows_are_written_in_place(programs):
    tick, admit = programs
    # The tails lie two rows in ONE row and are never kept "compressed"
    # between their uses (``linear_attn.slot_shapes`` has the trap); no
    # step kernel passes over anything: the tick rolls them in fusions.
    for compiled in (tick, admit):
        text = compiled.as_text()
        assert "remat_compressed" not in text
        assert "tk_ssd_step" not in text and "tk_kda_step" not in text
        assert "select" not in results(compiled, POOL, nested=True)
    # (one write a convolution layer of each scan's body: the leading
    # group's one and a period's three; the scans run 2 and 2 times)
    assert results(tick, TAILS, nested=True).count("dynamic-update-slice") == 4
    assert "tk_gconv_step" in tick.as_text()
    assert "tk_gconv_seq" in admit.as_text()
    # Each attention layer's K row and V row by one scatter a tick, into
    # pools no tick copies.
    assert "copy" not in results(tick, POOL)
    assert results(tick, POOL, nested=True).count("scatter") == 2


def test_the_tick_sums_all_32_experts_by_the_grouped_kernels(programs):
    """Eight expert layers a tick, each one ``tk_gmm_gate_up`` and one
    ``tk_gmm_down`` over the 32 experts' stacks, at 64 pairs an expert: a
    period's four in the scan's body, which runs twice."""
    tick, _admit = programs
    for name in ("tk_gmm_gate_up", "tk_gmm_down"):
        assert len(calls(tick, name)) == 4


def test_no_stacked_expert_weight_is_copied(programs):
    for compiled in programs:
        kinds = set(results(compiled, STACKED))
        assert kinds <= {"parameter", "get-tuple-element", "bitcast"}, kinds
        assert results(compiled, LAYER_SLICE) == []
