"""Rehearsal compiles for the described v5e: each cell's step or tick
program at the published widths, compiled by the TPU's compiler with no
chip attached, held to the chip's memory and to the kernels and
collectives it must contain. Nothing runs, so no number here is a
measurement. The one file that describes a TPU topology; it does so
inside a fixture, never at import, so every xdist worker collects the
same tests and only the worker given this file loads libtpu.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

HBM_BYTES = 15.75 * 2**30  # what the v5e compiler allows a program


def conf_of(name: str) -> dict:
    return json.loads((REPO / "chipbench/configs" / f"{name}.json").read_text())


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


@pytest.fixture()
def as_on_tpu(monkeypatch):
    """The program asks ``jax.default_backend()`` whether to compile its
    kernels or interpret them: for a compile for the described chip the
    test answers for it."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def footprint(compiled) -> float:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.temp_size_in_bytes
        + m.output_size_in_bytes - m.alias_size_in_bytes
    )


def test_serving_tick_and_admit_fit_one_chip(topo, as_on_tpu):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import torchkafka_tpu as tk
    from chipbench.models import dense_decoder as model
    from torchkafka_tpu.serve import StreamingGenerator

    conf = conf_of("mistral-7b-v0.3-w8")
    dep = conf["deployment"]
    slots, window, new = dep["slots"], dep["prompt_window"], dep["max_new"]
    cfg = model.program_config(conf, window + new)
    one = SingleDeviceSharding(topo.devices[0])
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=2)
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    p_shapes = jax.eval_shape(lambda: model.serving_params(conf, 0))
    held = {}

    def build():
        params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), p_shapes)
        server = StreamingGenerator(
            consumer, params, cfg, slots=slots, prompt_len=window,
            max_new=new, ticks_per_sync=dep["ticks_per_sync"],
            kv_dtype=dep["kv_dtype"], kv_kernel=dep["kv_kernel"],
        )
        held["server"] = server
        return (server._caches, server._last_tok, server._pos, server._gen,
                server._slot_keys)

    state = jax.eval_shape(build)
    server = held["server"]
    assert server._kv_backend.kernel is True  # 'auto' engages at this pool

    def sds(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)

    params = jax.tree.map(sds, p_shapes)
    caches, last, pos, gen, keys = jax.tree.map(sds, state)
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
    assert "tpu_custom_call" in tick.as_text()  # the Pallas dyn-len read
    assert footprint(tick) < HBM_BYTES
    admit = jitted(server._admit_fn).lower(
        params, caches, last, pos, gen, prompts, mask, keys
    ).compile()
    assert footprint(admit) < HBM_BYTES
    # Weights and pool alone are over half the chip: the cell is of a
    # deployment's size.
    assert admit.memory_analysis().argument_size_in_bytes > 0.5 * 16e9


@pytest.mark.parametrize("name,rows", [
    ("internlm2-1.8b-1chip", 2), ("internlm2-1.8b", 4),
])
def test_training_step_fits_its_chips(topo, as_on_tpu, name, rows):
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

    conf = conf_of(name)
    axes = conf["deployment"]["mesh"]
    chips = int(np.prod(list(axes.values())))
    mesh = tk.make_mesh(axes, devices=list(topo.devices)[:chips])
    cfg = model.program_config(conf, 4096, remat=conf["deployment"]["remat"])
    opt = model.optimizer(conf)
    _init_fn, step_fn = make_train_step(cfg, mesh, opt)
    p_sh = shardings_for_mesh(mesh, param_specs(cfg))

    def init(rng):
        p = init_params(rng, cfg)
        return p, opt.init(p)

    p_shapes, o_shapes = jax.eval_shape(init, jax.random.key(0))
    o_sh = opt_shardings_like(o_shapes, p_shapes, p_sh, NamedSharding(mesh, P()))

    def sds(s, sh):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    tokens = jax.ShapeDtypeStruct(
        (rows, 4096), jnp.int32, sharding=NamedSharding(mesh, batch_spec(mesh))
    )
    compiled = step_fn.lower(
        jax.tree.map(sds, p_shapes, p_sh), jax.tree.map(sds, o_shapes, o_sh),
        tokens, tokens,
    ).compile()
    text = compiled.as_text()
    assert footprint(compiled) < HBM_BYTES  # bytes on each device
    assert text.count("tpu_custom_call") >= 3  # flash forward and backward
    if chips > 1:
        assert "all-reduce(" in text  # tp and the data-axis gradient sum
    else:
        assert "all-reduce(" not in text
        # The one-chip cut is the deepest even depth with a gibibyte to
        # spare: two more layers would leave under half of one.
        assert footprint(compiled) > HBM_BYTES - 2.0 * 2**30
