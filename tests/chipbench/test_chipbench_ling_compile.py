"""Rehearsal compile for the described v5e of the cell
``ling3.long-decode-drain``: its tick and its admit at the published
widths and the deployment's 384 slots, compiled by the TPU's compiler
with no chip attached, held to the chip's memory and to what they must
and must not contain (ONE pass over a linear layer's state a tick, the
kernel ``tk_kda_step`` with the state aliased in place; no state-shaped
or pool-shaped copy inside a loop; no re-laid copy of the stacked expert
weights). Nothing runs, so no number here is a measurement. The
footprints it reads are those written into the configuration's file.

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
    (REPO / "chipbench/configs/ling-3.0-flash-7l-ep8.json").read_text()
)
STATE = r"f32\[6,384,32,128,128\]"
TAILS = r"bf16\[6,384,3,12288\]"
POOL = r"bf16\[(1,)?384,4096,576\]"
STACKED = r"bf16\[(6,64|384),(2560,768|768,2560)\]"
LAYER_SLICE = r"bf16\[64,(2560,768|768,2560)\]"


@pytest.fixture(scope="module")
def programs(topo):
    """(tick, admit) compiled for one described chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import torchkafka_tpu as tk
    from chipbench.models import ling_decoder as model
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
        assert server.metrics.summary()["linear_state"]["step"] == "kernel"

        def sds(s):
            return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)

        params = jax.tree.map(sds, p_shapes)
        caches, last, pos, gen, keys = jax.tree.map(sds, state)
        assert [c.shape for c in caches] == [
            (6, slots, 32, 128, 128), (6, slots, 3, 12288),
            (1, slots, window + new, 576),
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


def test_the_384_slots_fit_the_chip_and_need_no_step_down(programs):
    tick, admit = programs
    assert footprint(tick) < HBM_BYTES and footprint(admit) < HBM_BYTES
    # Weights and slot memory: ISSUE 41's 12.42 GB, three quarters of the
    # chip, of which the state is the largest part.
    args = admit.memory_analysis().argument_size_in_bytes
    assert 12.4e9 < args < 12.5e9 and args > 0.72 * 17.18e9
    written = CONF["deployment"]["compiled_for_a_described_v5e"]
    for name, compiled in (("jit_tick_block", tick), ("jit_admit", admit)):
        assert written[f"{name}_footprint_gib"] == pytest.approx(
            footprint(compiled) / 2**30, abs=0.06
        )


def test_a_tick_passes_over_a_layer_s_state_once(programs):
    """Six calls of ``tk_kda_step`` a tick (the dense layer's, the
    period's five), each with the state aliased in place; nothing
    state-shaped is copied, selected or scattered anywhere in either
    program, and the admit writes a trip's six rows where they belong."""
    tick, admit = programs
    calls = [
        line for line in tick.as_text().split("\n")
        if "custom-call(" in line and "tk_kda_step" in line
    ]
    assert len(calls) == 6
    assert all("output_to_operand_aliasing" in c for c in calls)
    assert "tk_kda_step" not in admit.as_text()
    for compiled in (tick, admit):
        every = results(compiled, STATE, nested=True)
        assert not {"copy", "select", "scatter"} & set(every), every
    assert results(admit, STATE, nested=True).count("dynamic-update-slice") == 6


def test_the_tails_and_the_latent_pool_are_written_in_place(programs):
    tick, admit = programs
    # The tick converts the latent pool's layout once in and once out of
    # the BLOCK of 128 ticks (as the other latent cells' do; PERF.md):
    # nothing pool-shaped is copied or selected inside its loops.
    assert results(tick, r"bf16\[1,384,4096,576\]").count("copy") == 2
    assert "copy" not in results(admit, POOL)
    assert "copy" not in results(tick, TAILS)
    for compiled in (tick, admit):
        assert "select" not in results(compiled, POOL, nested=True)
    assert results(tick, TAILS, nested=True).count("dynamic-update-slice") == 6
    # The latent layer's row by one scatter a tick; the admit writes a
    # trip's six rows' windows.
    assert results(tick, POOL, nested=True).count("scatter") == 1
    assert results(admit, POOL, nested=True).count("dynamic-update-slice") == 6


def test_no_stacked_expert_weight_is_copied(programs):
    """The held experts' stacked tensors are read where they lie: an
    expert is reached by ONE dynamic index that fuses into the product
    reading it (``transformer.scan_hybrid``)."""
    for compiled in programs:
        kinds = set(results(compiled, STACKED))
        assert kinds <= {"parameter", "get-tuple-element", "bitcast"}, kinds
        assert results(compiled, LAYER_SLICE) == []
