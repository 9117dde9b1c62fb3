"""Rehearsal compile for the described v5e of the cell
``longcat.reasoning-drain``: its tick and its admit at the published
widths and the deployment's 128 slots, compiled by the TPU's compiler
with no chip attached, held to the chip's memory and to what they must
not contain (a pool-shaped copy inside a loop, a re-laid copy of the
stacked expert or dense weights). Nothing runs, so no number here is a
measurement.

A file of its own because ``test_chipbench_tpu_compile.py`` belongs to
the accepted benchmark and is not edited: the topology is described
inside a fixture, never at import, and where this worker cannot load the
TPU's library (another file's worker holds it and
``ALLOW_MULTIPLE_LIBTPU_LOAD`` is not set) the tests skip.
"""

from __future__ import annotations

import json
import os
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

HBM_BYTES = 15.75 * 2**30  # what the v5e compiler allows a program
CONF = json.loads(
    (REPO / "chipbench/configs/longcat-flash-omni-4l-ep32.json").read_text()
)
POOL = r"bf16\[8,128,2048,576\]"
STACKED = r"bf16\[4,(16,(6144,2048|2048,6144)|2,(6144,12288|12288,6144))\]"
# One layer's slice of the blocks' dense weights or of the held experts.
LAYER_SLICE = (
    r"bf16\[(2,(6144,12288|12288,6144|64,128,6144)|16,(6144,2048|2048,6144))\]"
)


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


@pytest.fixture(scope="module")
def programs(topo):
    """(tick, admit) compiled for one described chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import torchkafka_tpu as tk
    from chipbench.models import longcat_decoder as model
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
        assert caches[0].shape == (8, slots, window + new, 576)
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


def footprint(compiled) -> float:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.temp_size_in_bytes
        + m.output_size_in_bytes - m.alias_size_in_bytes
    )


def results(compiled, shape: str, nested: bool = False) -> list[str]:
    """The opcodes of the operations whose result has ``shape`` and is a
    buffer of its own: those outside fused computations (inside one, a
    slice or a bitcast is a way of reading, not a copy). ``nested``: those
    inside fused computations too."""
    found, fused = [], False
    for line in compiled.as_text().split("\n"):
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            fused = "fused_computation" in head.group(2)
            continue
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([a-z\-]+)\(", line)
        if m and (nested or not fused) and re.search(shape, m.group(1)):
            found.append(m.group(2))
    return found


def test_the_128_slots_fit_the_chip_and_need_no_step_down(programs):
    tick, admit = programs
    assert footprint(tick) < HBM_BYTES and footprint(admit) < HBM_BYTES
    # Weights and pool: ISSUE 31's 12.76 GB, four fifths of the chip.
    args = admit.memory_analysis().argument_size_in_bytes
    assert 12.7e9 < args < 12.8e9 and args > 0.75 * 16e9
    # The compiled programs leave room: a gibibyte and a half for the
    # admit, most of one for the tick (whose temporaries are the pool's
    # padded copy at the block's boundary).
    assert footprint(admit) < HBM_BYTES - 1.5 * 2**30
    assert footprint(tick) < HBM_BYTES - 0.75 * 2**30


def test_the_pool_is_written_in_place(programs):
    tick, admit = programs
    in_tick, in_admit = results(tick, POOL), results(admit, POOL)
    # The tick converts the pool's layout once in and once out of the
    # BLOCK of 128 ticks (as the other latent cell's does; PERF.md):
    # nothing pool-shaped is copied or selected inside its loops.
    assert in_tick.count("copy") == 2 and "copy" not in in_admit
    every = results(tick, POOL, nested=True) + results(admit, POOL, nested=True)
    assert "select" not in every
    # A block's row by a scatter, two blocks a layer; the admit writes a
    # trip's six rows where they belong.
    assert results(tick, POOL, nested=True).count("scatter") == 2
    assert results(admit, POOL, nested=True).count("dynamic-update-slice") == 6
    assert admit.as_text().count("tpu_custom_call") == 2  # flash, a block each


def test_no_stacked_weight_is_copied(programs):
    """The held experts' and the dense FFNs' stacked tensors are read
    where they lie: a re-laid copy of one is 1.5 GB hoisted out of the
    layer loop (what the shared all-experts product cost at 128 rows,
    ``ops/moe.py::all_experts``)."""
    for compiled in programs:
        kinds = set(results(compiled, STACKED))
        assert kinds <= {"parameter", "get-tuple-element", "bitcast"}, kinds
        # Nor is a layer's slice of them made before it is used: a block
        # and an expert are reached by ONE dynamic index that fuses into
        # the product (``transformer._double_scan``). As a scan's slice
        # indexed again, 14 ms of a 36 ms tick were such copies.
        assert results(compiled, LAYER_SLICE) == []
