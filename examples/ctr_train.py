"""Streaming CTR: train a DLRM-style recommender straight off a Kafka topic.

The production shape of the reference's ingest loop: click events (label,
dense features, hashed categorical ids) stream in; embedding tables shard
row-wise over the mesh's ``tp`` axis; offsets commit only after the step
that consumed each batch retires (at-least-once, zero loss on crash).

    python examples/ctr_train.py --steps 40 --batch 1024
    JAX_PLATFORMS=cpu python examples/ctr_train.py --steps 10 --batch 64

Swap `make_broker`/`MemoryConsumer` for `tk.KafkaConsumer(...)` against a
real cluster; the record layout is ``models.recsys.parse_record``'s.
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
from torchkafka_tpu.models.recsys import (
    DLRMConfig,
    count_params,
    make_dlrm_train_step,
    make_chunk_processor,
)
from torchkafka_tpu.utils.devices import enable_compile_cache

N_PARTS = 8


def make_broker(cfg: DLRMConfig, n_records: int) -> tk.InMemoryBroker:
    """Synthetic click stream with a learnable rule (so loss visibly
    drops): label = f(dense sum, first categorical's parity)."""
    broker = tk.InMemoryBroker()
    broker.create_topic("clicks", partitions=N_PARTS)
    rng = np.random.default_rng(0)

    highs = np.asarray(cfg.vocab_sizes)

    def records():
        for _ in range(n_records):
            dense = rng.normal(size=cfg.dense_dim).astype(np.float32)
            cats = rng.integers(0, highs, dtype=np.int32)  # one call, [C]
            label = np.float32(dense.sum() + (cats[0] % 2) > 0.5)
            yield label.tobytes() + dense.tobytes() + cats.tobytes()

    broker.produce_many("clicks", records())
    return broker


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=1024)
    args = ap.parse_args()

    n_dev = len(jax.devices())
    tp = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    mesh = tk.make_mesh({"data": n_dev // tp, "tp": tp})
    cfg = DLRMConfig()  # 8 tables x 100k x 64: the tables are the bytes

    # Each process consumes its stride of partitions, so the topic needs
    # steps*batch records PER PROCESS for every host to reach --steps.
    broker = make_broker(cfg, args.steps * args.batch * jax.process_count())
    consumer = tk.MemoryConsumer(
        broker, "clicks", group_id="ctr-trainer",
        assignment=tk.partitions_for_process(
            "clicks", N_PARTS, jax.process_index(), jax.process_count()
        ),
    )
    init_fn, step_fn = make_dlrm_train_step(cfg, mesh, optax.adam(1e-2))
    params, opt = init_fn(jax.random.key(0))
    print(f"DLRM {count_params(params) / 1e6:.1f}M params, mesh {dict(mesh.shape)}")

    with tk.KafkaStream(
        consumer,
        # Chunked columnar decode: one native call per poll chunk (the
        # thread pool is unused on this path, so no transform_threads).
        make_chunk_processor(cfg),
        batch_size=args.batch,
        mesh=mesh,
        idle_timeout_ms=2000,
        owns_consumer=True,
    ) as stream:
        step = 0
        for batch, token in stream:
            mask = jnp.asarray(batch.valid_mask(), jnp.float32)
            params, opt, loss = step_fn(
                params, opt, batch.data["dense"], batch.data["cats"],
                batch.data["label"], mask,
            )
            token.commit(wait_for=loss)
            if step % 5 == 0:
                print(f"step {step}  loss {float(loss):.4f}")
            step += 1
            if step >= args.steps:
                break
    print(f"done: {step} steps; metrics: {stream.metrics.summary()}")


if __name__ == "__main__":
    main()
