"""Pod commit-barrier cost curve: 1/2/4/8 localhost processes.

Measures what the north-star extrapolation ("per-host ingest × hosts, the
barrier amortises", PERF.md) actually costs: steady-state ingest throughput
per process and per-commit barrier latency as the pod grows, on real
``jax.distributed`` processes (localhost coordinator, CPU backend — the
same coordination path a TPU pod takes over DCN, minus the wire).

Every process streams its own partitions of a deterministic broker, runs a
jitted global-mean step (a real cross-host psum) per batch, and commits
EVERY batch through the pod barrier (worst-case cadence — production
commits every N batches, so per-commit cost amortises further).

Usage: python benchmarks/bench_pod.py [--procs 1,2,4,8] [--batches 40]
Prints one markdown table row per pod size, plus a JSON line per size.

``--overhead`` instead runs the PAIRED resilience measurement: the same
poll+commit drain loop over a raw MemoryConsumer vs the identical
consumer wrapped in ``ResilientConsumer`` with no faults firing —
interleaved repetitions, medians reported — so the wrapper's no-fault
hot-path cost (one breaker ``allow()`` + one try/except + one
``record_success()`` per op) is a measured number in PERF.md, not a
claim.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

BATCH = 256
SEQ = 16
N_PARTS = 8
TOPIC = "podbench"


def build_broker(tk, n_records: int):
    """Deterministic content: every process builds identical topic state."""
    import numpy as np

    broker = tk.InMemoryBroker()
    broker.create_topic(TOPIC, partitions=N_PARTS)
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 1000, size=(64, SEQ), dtype=np.int32)
    broker.produce_many(
        TOPIC, (payload[i % 64].tobytes() for i in range(n_records))
    )
    return broker


def worker(
    pid: int, nproc: int, port: int, outdir: str, n_batches: int,
    commit_every: int,
) -> None:
    import jax

    from torchkafka_tpu.utils.devices import force_cpu_devices

    force_cpu_devices(2)
    if nproc > 1:
        jax.distributed.initialize(
            coordinator_address=f"localhost:{port}",
            num_processes=nproc,
            process_id=pid,
        )
    import jax.numpy as jnp
    import numpy as np

    import torchkafka_tpu as tk

    # Each process consumes a disjoint stride of partitions (8/nproc of
    # them); records are spread round-robin, so sizing the topic at
    # n_batches × BATCH × nproc gives every process exactly n_batches
    # full batches.
    n_records = n_batches * BATCH * nproc
    broker = build_broker(tk, n_records)
    consumer = tk.MemoryConsumer(
        broker,
        TOPIC,
        group_id="podbench",
        assignment=tk.partitions_for_process(TOPIC, N_PARTS, pid, nproc),
    )
    mesh = tk.make_mesh({"data": 2 * nproc})

    @jax.jit
    def step(x):
        return jnp.mean(x)  # global mean: a true cross-host reduction

    commit_s: list[float] = []
    drain_s: list[float] = []  # device-queue retirement wait (pipeline
    # drain): the step this commit gates, plus everything queued behind it
    barrier_s: list[float] = []  # sync_global_devices + offset commit
    # alone, measured AFTER the retirement wait already completed — the
    # true coordination cost (VERDICT r5 weak #5: the cadence-16 "commit"
    # numbers were drain + barrier conflated)
    batch_times: list[float] = []
    n = 0
    commits_seen = 0
    with tk.KafkaStream(
        consumer,
        tk.fixed_width(SEQ, np.int32),
        batch_size=BATCH,
        mesh=mesh,
        idle_timeout_ms=3000,
        owns_consumer=True,
    ) as stream:
        t_prev = None
        for batch, token in stream:
            loss = step(batch.data)
            n += 1
            # Commit cadence: every batch is the worst case (barrier per
            # batch); production commits every k batches and a later
            # token's offsets subsume the earlier uncommitted ones.
            if n % commit_every == 0 or n >= n_batches:
                t0 = time.perf_counter()
                # SPLIT the commit wall into its two physically distinct
                # parts. 1) retirement: wait out the pipelined device
                # queue behind this step (block_until_ready + the same
                # one-scalar fetch the strict barrier demands).
                jax.block_until_ready(loss)
                float(jax.device_get(loss))
                t1 = time.perf_counter()
                # 2) barrier+commit: the pod-wide sync_global_devices and
                # the offset commit, with nothing left to retire (the
                # barrier's own block_until_ready returns immediately).
                ok = token.commit(wait_for=loss)
                t2 = time.perf_counter()
                assert ok, f"commit failed at batch {n}"
                commits_seen += 1
                # Steady state only: skip compile/pipeline fill, the FIRST
                # commit at any cadence (its cold path — first host fetch,
                # first lock — measured ~50× the steady cost, and at deep
                # cadences it used to be half the sample set), AND the
                # final flush commit (it waits out the whole remaining
                # device queue, which is drain cost, not barrier cost).
                if (
                    n > 2 and commits_seen > 1
                    and n % commit_every == 0 and n < n_batches
                ):
                    drain_s.append(t1 - t0)
                    barrier_s.append(t2 - t1)
                    commit_s.append(t2 - t0)
            else:
                t2 = time.perf_counter()
            if n > 2 and t_prev is not None:
                batch_times.append(t2 - t_prev)
            t_prev = t2
            if n >= n_batches:
                break

    import numpy as np

    cs = np.asarray(commit_s)
    ds = np.asarray(drain_s)
    bs = np.asarray(barrier_s)
    bt = np.asarray(batch_times)
    if not cs.size:
        raise SystemExit(
            f"no steady-state commits at cadence {commit_every} over "
            f"{n_batches} batches — raise --batches above 2+2×cadence"
        )
    out = {
        "pid": pid,
        "nproc": nproc,
        "commit_every": commit_every,
        "batches": n,
        "commit_samples": int(cs.size),
        "rows_per_s": BATCH / float(bt.mean()) if bt.size else 0.0,
        "commit_p50_ms": float(np.percentile(cs, 50) * 1e3),
        "commit_p99_ms": float(np.percentile(cs, 99) * 1e3),
        "commit_mean_ms": float(cs.mean() * 1e3),
        # The split (same commit points): retirement wait vs barrier.
        "drain_p50_ms": float(np.percentile(ds, 50) * 1e3),
        "drain_mean_ms": float(ds.mean() * 1e3),
        "barrier_p50_ms": float(np.percentile(bs, 50) * 1e3),
        "barrier_p99_ms": float(np.percentile(bs, 99) * 1e3),
        "barrier_mean_ms": float(bs.mean() * 1e3),
        "stream_metrics": stream.metrics.summary(),
    }
    with open(os.path.join(outdir, f"pod_{nproc}_{pid}.json"), "w") as f:
        json.dump(out, f)


def _validate(nproc: int, n_batches: int, commit_every: int) -> None:
    """Shared guard for main()'s up-front sweep check and run_pod."""
    if N_PARTS % nproc:
        # Uneven partition strides give members unequal batch counts; the
        # short member stops committing while the rest wedge in the pod
        # barrier until the watchdog kills them. Fail fast instead.
        raise SystemExit(f"--procs must divide {N_PARTS} partitions, got {nproc}")
    if n_batches < 2 + 3 * commit_every:
        # 3×: the first steady-cadence commit is ALSO discarded (cold
        # path), so a sample needs the third commit to land before the
        # final-flush batch.
        raise SystemExit(
            f"--batches {n_batches} leaves no steady-state commit samples "
            f"at cadence {commit_every}"
        )


def run_overhead(n_records: int = 200_000, reps: int = 5) -> dict:
    """Paired resilience-on/off poll+commit drain over one broker.

    Reps interleave (raw, wrapped, raw, wrapped, ...) so OS noise and
    allocator state hit both arms equally; each rep drains the full topic
    under a fresh consumer group (positions reset, the log does not).
    Reports median rows/s per arm and the per-(poll+commit) overhead."""
    import uuid

    import numpy as np

    import torchkafka_tpu as tk
    from torchkafka_tpu.resilience import ResilientConsumer

    broker = tk.InMemoryBroker()
    broker.create_topic(TOPIC, partitions=N_PARTS)
    payload = b"\x00" * 64
    broker.produce_many(TOPIC, (payload for _ in range(n_records)))
    tps = [tk.TopicPartition(TOPIC, p) for p in range(N_PARTS)]

    def one_pass(wrap: bool) -> dict:
        consumer = tk.MemoryConsumer(
            broker, TOPIC, group_id=f"ovh-{uuid.uuid4().hex[:8]}",
            assignment=tps,
        )
        if wrap:
            consumer = ResilientConsumer(consumer)
        rows = ops = 0
        t0 = time.perf_counter()
        while True:
            recs = consumer.poll(max_records=512, timeout_ms=0)
            ops += 1
            if not recs:
                break
            rows += len(recs)
            consumer.commit()
            ops += 1
        dt = time.perf_counter() - t0
        consumer.close()
        assert rows == n_records, f"drained {rows} != produced {n_records}"
        return {"rows_per_s": rows / dt, "ops": ops, "dt": dt}

    one_pass(False)  # warmup both code paths outside the timed reps
    one_pass(True)
    raw, wrapped = [], []
    for _ in range(reps):
        raw.append(one_pass(False))
        wrapped.append(one_pass(True))
    r = float(np.median([x["rows_per_s"] for x in raw]))
    w = float(np.median([x["rows_per_s"] for x in wrapped]))
    dt_r = float(np.median([x["dt"] for x in raw]))
    dt_w = float(np.median([x["dt"] for x in wrapped]))
    ops = raw[0]["ops"]
    return {
        "mode": "resilience-overhead",
        "records": n_records,
        "reps": reps,
        "ops_per_rep": ops,
        "raw_rows_per_s": r,
        "resilient_rows_per_s": w,
        "ratio": w / r,
        "overhead_us_per_op": (dt_w - dt_r) / ops * 1e6,
    }


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def run_pod(nproc: int, n_batches: int, outdir: str, commit_every: int) -> dict:
    _validate(nproc, n_batches, commit_every)
    port = _free_port()
    env = dict(os.environ)  # workers force the CPU backend themselves
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    procs = []
    for pid in range(nproc):
        log = open(os.path.join(outdir, f"pod_{nproc}_{pid}.log"), "wb")
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, os.path.abspath(__file__), "--worker",
                    str(pid), str(nproc), str(port), outdir,
                    "--batches", str(n_batches),
                    "--commit-every", str(commit_every),
                ],
                env=env, stdout=log, stderr=subprocess.STDOUT,
            )
        )
    deadline = time.time() + 600
    for p in procs:
        p.wait(timeout=max(1, deadline - time.time()))
    assert all(p.returncode == 0 for p in procs), (
        f"pod {nproc}: exit codes {[p.returncode for p in procs]} "
        f"(see {outdir}/pod_{nproc}_*.log)"
    )
    import numpy as np

    per = []
    for pid in range(nproc):
        with open(os.path.join(outdir, f"pod_{nproc}_{pid}.json")) as f:
            per.append(json.load(f))
    return {
        "nproc": nproc,
        "commit_every": commit_every,
        "rows_per_s_per_proc": float(np.mean([p["rows_per_s"] for p in per])),
        "rows_per_s_total": float(np.sum([p["rows_per_s"] for p in per])),
        "commit_p50_ms": float(np.median([p["commit_p50_ms"] for p in per])),
        "commit_p99_ms": float(np.max([p["commit_p99_ms"] for p in per])),
        "commit_mean_ms": float(np.mean([p["commit_mean_ms"] for p in per])),
        "drain_mean_ms": float(np.mean([p["drain_mean_ms"] for p in per])),
        "drain_p50_ms": float(np.median([p["drain_p50_ms"] for p in per])),
        "barrier_mean_ms": float(np.mean([p["barrier_mean_ms"] for p in per])),
        "barrier_p50_ms": float(np.median([p["barrier_p50_ms"] for p in per])),
        "barrier_p99_ms": float(np.max([p["barrier_p99_ms"] for p in per])),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", nargs=4, metavar=("PID", "NPROC", "PORT", "OUT"))
    ap.add_argument("--procs", default="1,2,4,8")
    ap.add_argument("--batches", type=int, default=40)
    ap.add_argument("--commit-every", type=int, default=1)
    ap.add_argument("--cadences", default="1,16")
    ap.add_argument("--overhead", action="store_true",
                    help="paired resilience-on/off poll+commit overhead "
                    "measurement (no faults firing) instead of the pod sweep")
    ap.add_argument("--records", type=int, default=200_000,
                    help="--overhead: records drained per repetition")
    ap.add_argument("--reps", type=int, default=5,
                    help="--overhead: interleaved repetitions per arm")
    args = ap.parse_args()
    if args.overhead:
        r = run_overhead(args.records, args.reps)
        print("| records | raw rows/s | resilient rows/s | ratio | "
              "overhead/op |")
        print("|---|---|---|---|---|")
        print(
            f"| {r['records']:,} | {r['raw_rows_per_s']:,.0f} | "
            f"{r['resilient_rows_per_s']:,.0f} | {r['ratio']:.3f} | "
            f"{r['overhead_us_per_op']:.2f} us |"
        )
        print(json.dumps(r), file=sys.stderr)
        return
    if args.worker:
        pid, nproc, port, outdir = args.worker
        worker(
            int(pid), int(nproc), int(port), outdir, args.batches,
            args.commit_every,
        )
        return

    import tempfile

    # Validate the whole sweep up front — an invalid (procs, cadence) pair
    # must not abort mid-sweep after earlier pods already spent minutes.
    proc_list = [int(x) for x in args.procs.split(",")]
    cadence_list = [int(x) for x in args.cadences.split(",")]
    for nproc in proc_list:
        for cadence in cadence_list:
            _validate(nproc, args.batches, cadence)
    outdir = tempfile.mkdtemp(prefix="tk-pod-bench-")
    print(f"logs/results in {outdir}", file=sys.stderr)
    # drain = pipeline-retirement wait; barrier = sync_global_devices +
    # offset commit with nothing left to retire. Their sum is the old
    # conflated "commit" wall (still printed for continuity).
    print("| procs | commit cadence | rows/s/proc | rows/s total | "
          "drain mean | drain p50 | barrier mean | barrier p50 | "
          "barrier p99 | commit(=drain+barrier) mean |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for nproc in proc_list:
        for cadence in cadence_list:
            r = run_pod(nproc, args.batches, outdir, cadence)
            print(
                f"| {r['nproc']} | every {r['commit_every']} | "
                f"{r['rows_per_s_per_proc']:,.0f} | "
                f"{r['rows_per_s_total']:,.0f} | "
                f"{r['drain_mean_ms']:.2f} ms | {r['drain_p50_ms']:.2f} ms | "
                f"{r['barrier_mean_ms']:.2f} ms | "
                f"{r['barrier_p50_ms']:.2f} ms | "
                f"{r['barrier_p99_ms']:.2f} ms | "
                f"{r['commit_mean_ms']:.2f} ms |"
            )
            print(json.dumps(r), file=sys.stderr)


if __name__ == "__main__":
    main()
