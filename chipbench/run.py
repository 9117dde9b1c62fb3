"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It reads BENCHMARK.json, finds the cell, its configuration's file, its
traffic file (``chipbench/workloads/<cell>.json``), the loop that file
names (``chipbench/loops/<loop>.py``) and one reader for each metric
(``chipbench/e2e_metrics/<name>.py``, ``chipbench/layer_metrics/<name>.py``).
It names no cell, configuration or metric itself.

It measures on a TPU and nowhere else: with no TPU, or fewer chips than
the cell asks for, it prints no result and exits with code 3. The last
line of standard output is the result; every earlier line is for the
reader of the log.
"""

from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import common  # noqa: E402


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise common.Refused(f"BENCHMARK.json names no {what} {name!r}")


def metrics_for(bench: dict, section: str, cell: str) -> list[dict]:
    return [
        m for m in bench[section]
        if "workloads" not in m or cell in m["workloads"]
    ]


def load_cell(root: Path, name: str):
    """BENCHMARK.json, the cell, its configuration's file, its traffic
    file."""
    bench = common.load_json(root / "BENCHMARK.json")
    cell = find(bench["workloads"], name, "workload")
    conf_entry = find(bench["configs"], cell["config"], "configuration")
    conf = common.load_json(root / conf_entry["file"])
    mix = common.load_json(
        root / "chipbench" / "workloads" / f"{cell['name']}.json"
    )
    if mix["traffic"]["kind"] != cell["traffic"]:
        raise common.Refused(
            f"{cell['name']}: traffic {cell['traffic']!r} in "
            f"BENCHMARK.json, {mix['traffic']['kind']!r} in its file"
        )
    return bench, cell, conf, mix


def take_devices(cell: dict, root: Path, rehearsal: bool):
    """The cell's chips, or a refusal: a measurement has no fallback."""
    import jax

    devices = jax.devices()
    if not rehearsal:
        if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
            raise common.Refused(
                f"{cell['name']} measures on {cell['chips']} TPU chip(s) "
                f"and found {len(devices)} x {devices[0].platform!r}; "
                "there is no fallback"
            )
        common.say("compile_cache", cache_dir(root))
    return devices[: cell["chips"]]


def cache_dir(root: Path) -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else at a fixed path inside the checkout (the path is part of
    the cache's key)."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        env = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", env)
    # Cache every program, however quick to compile: a run's set-up is
    # then the same from the second run on.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return env


def main(argv=None, root: Path = ROOT, rehearsal: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    now = time.perf_counter()
    # Set-up counts from the process's start, by the kernel's stamp (the
    # interpreter's own start-up is set-up too).
    t_start = now if rehearsal else now - max(
        common.process_age_s(), now - T_IMPORT
    )

    try:
        bench, cell, conf, mix = load_cell(root, args.workload)
        devices = take_devices(cell, root, rehearsal)
        facts = common.device_facts(devices)
        common.say("device", facts)

        ctx = common.RunContext(
            cell=cell, conf=conf, mix=mix, seed=args.seed,
            seconds=args.seconds, trace=bool(args.trace), devices=devices,
            t_start=t_start, rehearsal=rehearsal, root=root,
        )
        loop = common.load_named("loops", mix["loop"], root)
        run = loop.run(ctx)
    except common.Refused as e:
        common.stderr(f"chipbench: refused: {e}")
        return 3
    except ModuleNotFoundError as e:
        # A directory that holds the benchmark and not the program.
        common.stderr(f"chipbench: refused: nothing to measure here: {e}")
        return 5

    run.update(
        cell=cell, conf=conf, mix=mix, chips=cell["chips"], seed=args.seed,
        setup_s=ctx.setup_s, t0=ctx.t0, t_close=ctx.t_close,
        window_s=ctx.t_close - ctx.t0, spans=ctx.spans,
        device_kind=facts["kind"], root=root,
        # A rehearsal exercises the readers and throws their values away.
        peaks=common.load_peaks(
            "TPU v5 lite" if rehearsal else facts["kind"]
        ),
    )
    common.say("phases_s", ctx.phases)
    common.say("compile", {
        "in_window": ctx.compiles_in_window, "all": ctx.watch.compiles,
        "cache_hits": ctx.watch.cache_hits,
        "cache_misses": ctx.watch.cache_misses,
    })
    if ctx.compiles_in_window and not rehearsal:
        common.stderr(
            f"chipbench: refused: {ctx.compiles_in_window} program(s) "
            "compiled inside the measured window"
        )
        return 4

    def read(section: str, kind: str) -> dict:
        out = {}
        for m in metrics_for(bench, section, cell["name"]):
            value = common.load_named(kind, m["name"], root).read(run)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    e2e = read("end_to_end", "e2e_metrics")
    if args.trace:
        layer = read("per_layer", "layer_metrics")
        common.say("end_to_end_in_the_traced_run", e2e)
    ctx.checks.report()
    metrics = layer if args.trace else e2e
    if rehearsal:
        # A rehearsal on the CPU: counts and checks, no device metric and
        # no verdict under a device's name.
        print(json.dumps({
            "rehearsal": True, "cell": cell["name"],
            "checks_passed": ctx.checks.correct,
            "metric_names": sorted(metrics),
            "attempted": run["attempted"], "failed": run["failed"],
        }), flush=True)
        return 0
    device = {**facts, "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {
        "correct": ctx.checks.correct, "attempted": run["attempted"],
        "failed": run["failed"], "metrics": metrics, "device": device,
    }
    if args.trace:
        tr = run["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in tr["device_ops"]],
            "idle_gaps": [[n, s] for n, s in tr["idle_gaps"]],
        }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
