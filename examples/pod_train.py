"""Multi-host pod training: N jax.distributed processes, one script.

Every process runs the SAME program — its own consumer over disjoint
partitions, host-local batches assembled into global mesh-sharded arrays,
and the commit barrier guaranteeing offsets commit only after the step
retired on every chip of every host (the TPU-native replacement for the
reference's signal-based cross-process commit protocol,
/root/reference/src/auto_commit.py:59-72).

Two ways to run it:

  # Self-spawned local pod (CPU devices; demonstrates the real
  # multi-process protocol on one machine):
  python examples/pod_train.py --spawn 2 --steps 20

  # On a real TPU pod slice, run one copy per host with the standard env
  # (JAX infers the topology; no --spawn, no flags):
  python examples/pod_train.py --steps 200

Swap `make_consumer` for `tk.KafkaConsumer(...)` against a real cluster —
partition assignment via `tk.partitions_for_process` stays the same.
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # repo checkout

TOPIC = "events"
N_PARTS = 8
SEQ = 32
VOCAB = 1024
RECORDS = 4096


def build_broker(tk):
    """Deterministic stand-in for a shared Kafka cluster: every process
    builds identical content (same seed), so their disjoint partition
    views compose exactly like one real broker's would."""
    import numpy as np

    broker = tk.InMemoryBroker()
    broker.create_topic(TOPIC, partitions=N_PARTS)
    rng = np.random.default_rng(0)
    for i in range(RECORDS):
        toks = rng.integers(0, VOCAB, SEQ, dtype=np.int32)
        broker.produce(TOPIC, toks.tobytes(), partition=i % N_PARTS)
    return broker


def make_consumer(tk, jax):
    broker = build_broker(tk)
    return tk.MemoryConsumer(
        broker,
        TOPIC,
        group_id="pod-trainer",
        assignment=tk.partitions_for_process(
            TOPIC, N_PARTS, jax.process_index(), jax.process_count()
        ),
    )


def train(args) -> None:
    import jax

    from torchkafka_tpu.utils.devices import (
        enable_compile_cache,
        force_cpu_devices,
    )

    enable_compile_cache()
    if args.coordinator:  # self-spawned worker: join the local pod
        force_cpu_devices(2)
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.nproc,
            process_id=args.pid,
        )

    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchkafka_tpu as tk
    from torchkafka_tpu.models import TransformerConfig, make_train_step

    pid, nproc = jax.process_index(), jax.process_count()
    n_dev = len(jax.devices())
    mesh = tk.make_mesh({"data": n_dev})
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=SEQ,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32,
    )
    init_fn, step_fn = make_train_step(cfg, mesh, optax.adamw(1e-3))
    params, opt_state = init_fn(jax.random.key(0))

    consumer = make_consumer(tk, jax)
    local_batch = args.batch  # rows THIS process contributes per step
    with tk.ShutdownSignal() as stop, tk.KafkaStream(
        consumer,
        tk.fixed_width(SEQ, np.int32),
        batch_size=local_batch,
        mesh=mesh,
        idle_timeout_ms=2000,
        owns_consumer=True,
    ) as stream:
        step = 0
        mask = jnp.ones((local_batch * nproc, SEQ), jnp.int32)  # loop-invariant
        for batch, token in stream:
            params, opt_state, loss = step_fn(
                params, opt_state, batch.data, mask
            )
            # The barrier inside: offsets commit only after every host's
            # chips retired this step (all-hosts-or-nobody).
            token.commit(wait_for=loss)
            if pid == 0 and step % 5 == 0:
                print(f"step {step}  loss {float(loss):.4f}", flush=True)
            step += 1
            if step >= args.steps:
                break
            # Pod drain must be a GLOBAL decision: a slice preemption
            # SIGTERMs every member, but the notices land at slightly
            # different moments — a member that drained alone would leave
            # the rest wedged in the next commit barrier (watchdog exit
            # 42, the hard-kill path). All-gather the flags so every
            # member breaks at the same step boundary.
            if nproc > 1:
                from jax.experimental import multihost_utils

                drain = bool(
                    multihost_utils.process_allgather(
                        np.array([stop.requested])
                    ).any()
                )
            else:
                drain = stop.requested
            if drain:
                if pid == 0:
                    print(f"preempted: pod drained cleanly at step {step}",
                          flush=True)
                break
    if pid == 0:
        print(f"done: {step} steps, metrics: {stream.metrics.summary()}")
    if args.coordinator:
        jax.distributed.shutdown()


def spawn(args) -> int:
    """Fork N copies of this script as a localhost pod and wait."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)  # workers pick the CPU backend themselves
    procs = []
    for pid in range(args.spawn):
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, os.path.abspath(__file__),
                    "--coordinator", f"localhost:{port}",
                    "--nproc", str(args.spawn), "--pid", str(pid),
                    "--steps", str(args.steps), "--batch", str(args.batch),
                ],
                env=env,
            )
        )
    codes = [p.wait() for p in procs]
    if any(codes):
        raise SystemExit(f"pod failed: exit codes {codes}")
    print(f"pod of {args.spawn} processes completed cleanly")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spawn", type=int, default=0,
                    help="fork a local pod of this many processes")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8,
                    help="host-local rows per step")
    ap.add_argument("--coordinator", default="",
                    help="(internal) jax.distributed coordinator address")
    ap.add_argument("--nproc", type=int, default=1)
    ap.add_argument("--pid", type=int, default=0)
    args = ap.parse_args()
    if args.spawn:
        spawn(args)
    else:
        train(args)


if __name__ == "__main__":
    main()
