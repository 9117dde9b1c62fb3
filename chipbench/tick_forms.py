"""A reading for PERF.md, beside ``control.py``'s: one decode tick of a
routed-expert cell with each form of its expert sum.

    python3 chipbench/tick_forms.py --workload <cell> [--seed 7]

``ops/moe.py`` takes the sorted, grouped matmul where an expert averages
``_GROUPED_MIN_PAIRS_PER_EXPERT`` token-choice pairs or more and the
all-experts einsum below that, by the static shapes alone. This builds the
cell's server twice, once as it is and once with the threshold at zero
(the grouped form at every row count), puts the slots at the spread of
positions a steady drain has, and times two blocks of ticks and one
admission of each after a first of each that compiles. The threshold is
set from these readings (PERF.md). The benchmark's runs never run it;
like ``run.py`` it measures on a TPU only (``rehearsal`` is the tests'
path on the CPU, with ``--slots --window --new --ticks`` cutting the
deployment to a toy).
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from chipbench import common  # noqa: E402
from chipbench import run as runner  # noqa: E402


def time_form(conf: dict, params, cfg, grouped_min: int | None) -> dict:
    import jax
    import jax.numpy as jnp

    import torchkafka_tpu as tk
    from torchkafka_tpu.ops import moe
    from torchkafka_tpu.serve import StreamingGenerator

    dep = conf["deployment"]
    slots, window, ticks = dep["slots"], dep["prompt_window"], dep["ticks_per_sync"]
    as_built = moe._GROUPED_MIN_PAIRS_PER_EXPERT
    if grouped_min is not None:
        moe._GROUPED_MIN_PAIRS_PER_EXPERT = grouped_min
    try:
        broker = tk.InMemoryBroker()
        broker.create_topic("p", partitions=1)
        consumer = tk.MemoryConsumer(broker, "p", group_id="g")
        srv = StreamingGenerator(
            consumer, params, cfg, slots=slots, prompt_len=window,
            max_new=dep["max_new"], ticks_per_sync=ticks,
            kv_dtype=dep["kv_dtype"], kv_kernel=dep["kv_kernel"],
        )
        prompts = jnp.asarray(np.random.default_rng(7).integers(
            1, cfg.vocab_size, (slots, window), dtype=np.int32
        ))
        every = jnp.ones((slots,), bool)
        state = (srv._caches, srv._last_tok, srv._pos, srv._gen)
        times = []
        for _ in range(2):  # the first compiles
            t = time.perf_counter()
            state = srv._admit_fn(*state, prompts, every, srv._slot_keys)
            jax.block_until_ready(state)
            times.append(time.perf_counter() - t)
        # A steady drain's spread of positions, from the window on.
        room = max(dep["max_new"] - 2 * ticks - 1, 1)
        pos = jnp.asarray(window + (np.arange(slots) * 37) % room, jnp.int32)
        state = srv._tick_fn(state[0], state[1], pos, state[3], every, srv._slot_keys)
        jax.block_until_ready(state[:4])
        t = time.perf_counter()
        for _ in range(2):
            state = srv._tick_fn(*state[:4], every, srv._slot_keys)
        jax.block_until_ready(state[:4])
        tick_ms = 1e3 * (time.perf_counter() - t) / (2 * ticks)
        srv.close()
        consumer.close()
        return {"tick_ms": tick_ms, "admit_s": times[1]}
    finally:
        moe._GROUPED_MIN_PAIRS_PER_EXPERT = as_built
        gc.collect()


def main(argv=None, root: Path = ROOT, rehearsal: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    for cut in ("slots", "window", "new", "ticks"):
        ap.add_argument(f"--{cut}", type=int)
    args = ap.parse_args(argv)
    try:
        _bench, cell, conf, _mix = runner.load_cell(root, args.workload)
        runner.take_devices(cell, root, rehearsal)
    except common.Refused as e:
        common.stderr(f"chipbench: refused: {e}")
        return 3
    import importlib

    dep = conf["deployment"]
    for cut, key in (("slots", "slots"), ("window", "prompt_window"),
                     ("new", "max_new"), ("ticks", "ticks_per_sync")):
        if getattr(args, cut) is not None:
            dep[key] = getattr(args, cut)
    model = importlib.import_module(conf["model"])
    cfg = model.program_config(conf, dep["prompt_window"] + dep["max_new"])
    params = model.serving_params(conf, args.seed)
    pairs = dep["slots"] * cfg.expert_top_k / cfg.n_experts
    for name, grouped_min in (("as_built", None), ("grouped", 0)):
        row = time_form(conf, params, cfg, grouped_min)
        print(json.dumps({"reading": {
            "form": name, "pairs_per_expert_a_tick": pairs, **row,
        }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
