"""Readings for the limits of ``correct``: the program's numbers and the
control's, seed by seed, in one process.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> [--control 1]

For each seed it runs the cell as ``run.py`` does (a window of
``--seconds``, then the comparison with the plain reference) and prints
every number compared; with ``--control 1`` it also puts the reference in
the precision below the configuration's in the program's place and prints
what that reads. The limits in ``chipbench/workloads/*.json`` are set from
these readings (PERF.md gives them). The benchmark's own runs never run
the control. Like ``run.py`` it measures on a TPU only.
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

from chipbench import common  # noqa: E402
from chipbench import run as runner  # noqa: E402


def main(argv=None, root: Path = ROOT, rehearsal: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        _bench, cell, conf, mix = runner.load_cell(root, args.workload)
        devices = runner.take_devices(cell, root, rehearsal)
    except common.Refused as e:
        common.stderr(f"chipbench: refused: {e}")
        return 3
    loop = common.load_named("loops", mix["loop"], root)
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = common.RunContext(
            cell=cell, conf=conf, mix=mix, seed=seed, seconds=args.seconds,
            trace=False, devices=devices, t_start=time.perf_counter(),
            rehearsal=rehearsal, root=root,
        )
        run = loop.run(ctx)
        row = {"seed": seed, "correct": ctx.checks.correct,
               "compared": ctx.checks.rows}
        if args.control:
            row["control"] = loop.control(ctx, run)
        print(json.dumps({"reading": row}), flush=True)
        readings.append(row)
        del run, ctx
        gc.collect()
    return 0 if all(r["correct"] for r in readings) else 1


if __name__ == "__main__":
    sys.exit(main())
