"""A reading for PERF.md, beside ``control.py``'s: how often the program's
expert routing is the reference's.

    python3 chipbench/routing.py --workload <cell> --seeds 1,2,3 [--rows 4] [--floor 0.9] [--control 1]

Top-k routing over near-tied scores can flip between the program's
bfloat16 activations and the reference's float32 ones with no fault in
either. For each seed this draws ``--rows`` rows of seeded tokens as long
as the deployment's pool, runs them through the PROGRAM's own forward (the
configuration's dtypes, its flash prefill and routed expert matmul,
``models/generate.py::latent_forward``) and through the plain reference,
and prints the share of (token, expert layer) routings whose chosen sets
are equal, and the share of (token, layer, choice) pairs the two have in
common. The first share is held to ``--floor`` (PERF.md gives the floor
taken from the sound runs' readings): the exit code is 1 if a seed reads
below it. With ``--control 1`` the reference with its attention and
expert matmuls in 8-bit floating point is put in the program's place and
its shares are printed beside the program's. It is a reading for
PERF.md, not part of ``correct`` (``loops/serve_latent.py`` holds the
cached rows, which the layers' precision moves far more than a routing);
the benchmark's runs never run it; like ``run.py`` it measures on a TPU
only (``rehearsal`` is the tests' toy path on the CPU).
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from chipbench import common  # noqa: E402
from chipbench import run as runner  # noqa: E402


def shares(mine: np.ndarray, theirs: np.ndarray) -> dict:
    """Two sorted routings [L, B, T, K]: the share of equal sets, of
    pairs in common, and the first share layer by layer."""
    same_set = (mine == theirs).all(-1)
    common_pairs = sum(
        (mine[..., i: i + 1] == theirs).any(-1).sum()
        for i in range(mine.shape[-1])
    )
    return {
        "routing_set_agreement": float(same_set.mean()),
        "routing_pair_agreement": float(common_pairs / mine.size),
        "by_layer": [float(v) for v in same_set.mean(axis=(1, 2))],
    }


def agreement(
    conf: dict, seed: int, rows: int, length: int, control: bool = False
) -> dict:
    import importlib

    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models.generate import latent_forward
    from torchkafka_tpu.models.transformer import Transformer

    model = importlib.import_module(conf["model"])
    reference = importlib.import_module(conf["reference"])
    arch = model.Arch.from_conf(conf)
    tokens = np.random.default_rng([int(seed), 0x707E]).integers(
        1, arch.vocab, (rows, length), dtype=np.int32
    )
    cfg = model.program_config(conf, length)
    params = model.serving_params(conf, seed)
    forward = jax.jit(lambda p, t: latent_forward(p, Transformer(cfg), t)[2])
    mine = np.sort(np.asarray(forward(params, jnp.asarray(tokens))), axis=-1)
    del params, forward
    gc.collect()
    dtype = model.dtype_of(conf["deployment"]["param_dtype"])
    _x, routing = reference.forward(seed, arch, dtype, tokens)
    theirs = np.sort(np.stack([np.asarray(r) for r in routing]), axis=-1)
    out = {
        "seed": seed, "tokens": int(tokens.size),
        "expert_layers": int(mine.shape[0]), **shares(mine, theirs),
    }
    if control:
        _x, low = reference.forward(seed, arch, dtype, tokens, lowp="layers")
        low = np.sort(np.stack([np.asarray(r) for r in low]), axis=-1)
        out["control_layers"] = shares(low, theirs)
    return out


def main(argv=None, root: Path = ROOT, rehearsal: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--floor", type=float, default=0.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        _bench, cell, conf, _mix = runner.load_cell(root, args.workload)
        runner.take_devices(cell, root, rehearsal)
    except common.Refused as e:
        common.stderr(f"chipbench: refused: {e}")
        return 3
    dep = conf["deployment"]
    held = True
    for seed in (int(s) for s in args.seeds.split(",")):
        row = agreement(
            conf, seed, args.rows, dep["prompt_window"] + dep["max_new"],
            control=bool(args.control),
        )
        row["floor"] = args.floor
        held = held and row["routing_set_agreement"] >= args.floor
        print(json.dumps({"reading": row}), flush=True)
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
