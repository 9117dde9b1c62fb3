"""End-to-end example: Kafka-streamed training with commit-after-step,
checkpoint/resume, and a sharded transformer.

Runs anywhere (defaults to the in-memory broker + whatever devices exist;
CPU works: JAX_PLATFORMS=cpu python examples/train_stream.py). Swap
`make_consumer` for `tk.KafkaConsumer(...)` to point at a real cluster.

    python examples/train_stream.py --steps 50 --ckpt /tmp/tk-ckpt

Kill it anywhere; rerun with the same --ckpt and it resumes from the last
checkpoint with the stream seeked to exactly the matching offsets.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # repo checkout

import jax
import jax.numpy as jnp
import numpy as np
import optax

import torchkafka_tpu as tk
from torchkafka_tpu.models import TransformerConfig, make_train_step
from torchkafka_tpu.utils.devices import enable_compile_cache

TOPIC = "tokens"
N_PARTS = 8
SEQ = 128
VOCAB = 8192


def make_broker(n_records: int) -> tk.InMemoryBroker:
    """Stand-in for a real Kafka cluster: one topic of token records."""
    broker = tk.InMemoryBroker()
    broker.create_topic(TOPIC, partitions=N_PARTS)
    rng = np.random.default_rng(0)
    broker.produce_many(
        TOPIC,
        (rng.integers(0, VOCAB, SEQ, dtype=np.int32).tobytes() for _ in range(n_records)),
    )
    return broker


def make_consumer(broker: tk.InMemoryBroker) -> tk.MemoryConsumer:
    # Mesh-aligned static assignment: this process owns its stride of
    # partitions. On a pod, jax.process_index()/count() spread them.
    return tk.MemoryConsumer(
        broker,
        TOPIC,
        group_id="example-trainer",
        assignment=tk.partitions_for_process(
            TOPIC, N_PARTS, jax.process_index(), jax.process_count()
        ),
    )


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--ckpt", default="/tmp/tk-example-ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    args = ap.parse_args()

    n_dev = len(jax.devices())
    mesh = tk.make_mesh({"data": n_dev})
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=256, n_layers=4, n_heads=8, n_kv_heads=4,
        d_ff=704, max_seq_len=SEQ,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32,
    )
    optimizer = optax.adamw(3e-4)
    init_fn, step_fn = make_train_step(cfg, mesh, optimizer)

    broker = make_broker(args.steps * args.batch * 2)
    consumer = make_consumer(broker)
    ckpt = tk.StreamCheckpointer(args.ckpt)

    if ckpt.latest_step() is not None:
        # Resume: weights AND stream position restored as one unit.
        template = jax.tree_util.tree_map(np.asarray, init_fn(jax.random.key(0)))
        (params, opt_state), start = ckpt.resume(consumer, template=template)
        start += 1
        print(f"resumed at step {start}")
    else:
        params, opt_state = init_fn(jax.random.key(0))
        start = 0

    try:
        with tk.ShutdownSignal() as stop, tk.KafkaStream(
            consumer,
            tk.fixed_width(SEQ, np.int32),
            batch_size=args.batch,
            mesh=mesh,
            idle_timeout_ms=2000,
            owns_consumer=True,
        ) as stream:
            step = start
            fut = None
            for batch, token in stream:
                mask = jnp.broadcast_to(
                    jnp.asarray(batch.valid_mask()[:, None]), batch.data.shape
                ).astype(jnp.int32)
                params, opt_state, loss = step_fn(params, opt_state, batch.data, mask)
                # Pipelined commit-after-step: offsets become durable only once
                # this step's loss is device-complete on every host.
                fut = token.commit_async(wait_for=loss)
                if step % 10 == 0:
                    print(f"step {step}  loss {float(loss):.4f}")
                # One read for both branches: a signal landing between two
                # separate reads could break WITHOUT the checkpoint below.
                draining = stop.requested
                at_ckpt = step and step % args.ckpt_every == 0
                if at_ckpt or draining:
                    fut.result()  # offsets for this state are durable
                    # Non-blocking: the write drains while training continues;
                    # save_async snapshots the state before returning.
                    ckpt.save_async(step, (params, opt_state), token.offsets)
                    print(f"checkpoint @ step {step} (async)")
                if draining:
                    # Cooperative preemption drain (SIGTERM grace window):
                    # this step is committed + checkpointed, so the resume
                    # replays NOTHING instead of a commit-cadence's worth.
                    print(f"preempted: drained cleanly at step {step}")
                    break
                step += 1
                if step - start >= args.steps:
                    break
            if fut is not None:
                fut.result()
    finally:
        # The finalizer thread is a daemon: exiting (even on an exception)
        # without joining it could kill the commit rename mid-flight.
        ckpt.wait_until_finished()
    print(f"done at step {step}; metrics: {stream.metrics.summary()}")


if __name__ == "__main__":
    main()
