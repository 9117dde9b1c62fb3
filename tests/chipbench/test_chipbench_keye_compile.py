"""Rehearsal compile for the described v5e of the cell
``keye2.long-document-drain``: its tick and its admit at the published
widths and the deployment's 48 slots of 12,288 positions, compiled by the
TPU's compiler with no chip attached, held to the chip's memory and to
what they must and must not contain (eight calls each of ``tk_dsa_index``
and ``tk_dsa_attend`` a tick, the rows fetched out of the pool where it
lies; the admission's ``tk_flash_fwd_sel``; a token's row and index key
scattered in place; no pool-shaped copy or select anywhere; no re-laid
copy of the stacked expert weights). Nothing runs, so no number here is a
measurement. The footprints it reads are those written into the
configuration's file.

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
    (REPO / "chipbench/configs/keye-vl-2.0-30b-a3b-8l-ep8.json").read_text()
)
ROWS = r"s32\[8,48,12288,4,128\]"  # a position's K|V row, a tile of its own
KEYS = r"bf16\[8,48,64,12288\]"  # the index keys, positions along the lanes
STACKED = r"bf16\[(8,16|128),(2048,768|768,2048)\]"
LAYER_SLICE = r"bf16\[16,(2048,768|768,2048)\]"


@pytest.fixture(scope="module")
def programs(topo):
    """(tick, admit) compiled for one described chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import torchkafka_tpu as tk
    from chipbench.models import keye_decoder as model
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
        summary = held["server"].metrics.summary()
        assert summary["kv_backend"]["layout"] == "indexed"
        assert summary["kv_pool"]["topk"] == 2048
        assert summary["kv_pool"]["bytes_full"] == 9_663_676_416
        assert summary["kv_pool"]["bytes_index"] == 603_979_776
        assert summary["expert_layer"]["tick_form"] == "compacted"

        def sds(s):
            return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)

        params = jax.tree.map(sds, p_shapes)
        caches, last, pos, gen, keys = jax.tree.map(sds, state)
        assert [(c.shape, str(c.dtype)) for c in caches] == [
            ((8, slots, window + new, 4, 128), "int32"),
            ((8, slots, 64, window + new), "bfloat16"),
        ]
        mask = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one)
        prompts = jax.ShapeDtypeStruct((slots, window), jnp.int32, sharding=one)

        def jitted(fn):
            return next(
                c.cell_contents for c in fn.__closure__
                if hasattr(c.cell_contents, "lower")
            )

        server = held["server"]
        tick = jitted(server._tick_fn).lower(
            params, caches, last, pos, gen, mask, keys
        ).compile()
        admit = jitted(server._admit_fn).lower(
            params, caches, last, pos, gen, prompts, mask, keys
        ).compile()
        return tick, admit
    finally:
        jax.default_backend = honest


def test_the_48_slots_fit_the_chip_and_need_no_step_down(programs):
    tick, admit = programs
    assert footprint(tick) < HBM_BYTES and footprint(admit) < HBM_BYTES
    # Weights and slot memory: ISSUE 49's 11.97 GB, 70% of the chip.
    args = admit.memory_analysis().argument_size_in_bytes
    assert 11.9e9 < args < 12.05e9 and args > 0.25 * 17.18e9
    written = CONF["deployment"]["compiled_for_a_described_v5e"]
    assert written["arguments_gb"] == pytest.approx(args / 1e9, abs=0.01)
    for name, compiled in (("jit_tick_block", tick), ("jit_admit", admit)):
        assert written[f"{name}_footprint_gib"] == pytest.approx(
            footprint(compiled) / 2**30, abs=0.06
        )


def calls(compiled, name: str) -> list[str]:
    """The custom calls whose own name is ``name``."""
    return [
        line for line in compiled.as_text().split("\n")
        if line.strip().startswith(f"%{name}") and "custom-call(" in line
    ]


def test_a_tick_scores_and_reads_by_the_kernels_and_copies_no_pool(programs):
    """The layer scan's body holds ONE call each of ``tk_dsa_index`` and
    ``tk_dsa_attend`` (eight layers, one body), the admit one
    ``tk_flash_fwd_sel`` and neither decode kernel; the rows' pool lies as
    tiles of a position (``T(4,128)``: no padding), written by one scatter
    a tick and one dynamic-update-slice an admission trip; nothing
    pool-shaped is copied or selected in either program."""
    tick, admit = programs
    for name in ("tk_dsa_index", "tk_dsa_attend"):
        assert len(calls(tick, name)) == 1
        assert name not in admit.as_text()
    assert len(calls(admit, "tk_flash_fwd_sel")) == 1
    assert "tk_flash_fwd_sel" not in tick.as_text()
    assert "s32[8,48,12288,4,128]{4,3,2,1,0:T(4,128)}" in tick.as_text()
    for compiled in (tick, admit):
        for shape in (ROWS, KEYS):
            every = results(compiled, shape, nested=True)
            assert not {"copy", "select"} & set(every), every
    for shape in (ROWS, KEYS):
        assert results(tick, shape, nested=True).count("scatter") == 1
        assert results(admit, shape, nested=True).count(
            "dynamic-update-slice"
        ) == 1


def test_no_stacked_expert_weight_is_copied(programs):
    """The held experts' stacked tensors are read where they lie: an
    expert is reached by ONE dynamic index that fuses into the product
    reading it (``transformer.scan_periods``)."""
    for compiled in programs:
        kinds = set(results(compiled, STACKED))
        assert kinds <= {"parameter", "get-tuple-element", "bitcast"}, kinds
        assert results(compiled, LAYER_SLICE) == []
