"""End-to-end example: continuous-batching serving from a prompt topic.

Prompts stream in from Kafka; a fixed pool of decode slots generates
continuations, admitting a new prompt the moment a slot finishes (EOS or
length), and each prompt's offset commits only after ITS generation
completed — out-of-order completions are safe (interval ledger), and a
crash re-delivers exactly the unfinished prompts.

Runs anywhere (in-memory broker; CPU works:
JAX_PLATFORMS=cpu python examples/serve_prompts.py --prompts 24).
Swap `make_broker`/`MemoryConsumer` for `tk.KafkaConsumer(...)` to point at
a real cluster.

    python examples/serve_prompts.py --prompts 64 --slots 8 --max-new 32
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # repo checkout

import jax
import numpy as np

import torchkafka_tpu as tk
from torchkafka_tpu.models import TransformerConfig
from torchkafka_tpu.models.transformer import init_params
from torchkafka_tpu.serve import StreamingGenerator
from torchkafka_tpu.utils.devices import enable_compile_cache, force_cpu_devices

TOPIC = "prompts"
PROMPT_LEN = 32
VOCAB = 2048


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--prompts", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--eos", type=int, default=None,
                    help="optional EOS token id (slots recycle early)")
    ap.add_argument("--tp", type=int, default=1,
                    help="model-sharded serving: kv heads over a tp axis "
                    "of this size, remaining devices on data (slots). "
                    "Try --cpu-devices 8 --tp 2 anywhere.")
    ap.add_argument("--cpu-devices", type=int, default=None,
                    help="force the CPU backend with this many virtual "
                    "devices (a mesh to shard over without chips)")
    args = ap.parse_args()
    if args.cpu_devices:
        force_cpu_devices(args.cpu_devices)

    broker = tk.InMemoryBroker()
    broker.create_topic(TOPIC, partitions=2)
    broker.create_topic("completions", partitions=2)
    rng = np.random.default_rng(0)
    for i in range(args.prompts):
        broker.produce(
            TOPIC,
            rng.integers(0, VOCAB, PROMPT_LEN, dtype=np.int32).tobytes(),
            partition=i % 2,
        )

    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq_len=PROMPT_LEN + args.max_new,
    )
    params = init_params(jax.random.key(0), cfg)
    mesh = None
    if args.tp > 1:
        n_dev = len(jax.devices())
        if n_dev % args.tp:
            raise SystemExit(f"--tp {args.tp} does not divide {n_dev} devices")
        mesh = tk.make_mesh({"data": n_dev // args.tp, "tp": args.tp})
        print(f"serving model-sharded over {dict(mesh.shape)}", file=sys.stderr)
    consumer = tk.MemoryConsumer(broker, TOPIC, group_id="serve-demo")
    producer = tk.MemoryProducer(broker)
    with StreamingGenerator(
        consumer, params, cfg,
        slots=args.slots, prompt_len=PROMPT_LEN, max_new=args.max_new,
        eos_id=args.eos, commit_every=args.slots, mesh=mesh,
        # consume→generate→produce: completions become durable on their
        # topic BEFORE the prompts that produced them commit.
        output_producer=producer, output_topic="completions",
    ) as server:  # exit commits completed work (crash semantics unchanged)
        print(f"compiling ({args.slots} slots)...", file=sys.stderr)
        server.warmup()

        t0 = time.perf_counter()
        toks = 0
        for i, (rec, out) in enumerate(server.run(max_records=args.prompts)):
            toks += len(out)
            print(
                f"#{i:3d} {rec.topic}@{rec.partition}:{rec.offset} "
                f"-> {len(out)} tokens: {out[:8].tolist()}{'...' if len(out) > 8 else ''}"
            )
        dt = time.perf_counter() - t0
    committed = sum(
        broker.committed("serve-demo", tk.TopicPartition(TOPIC, p)) or 0
        for p in (0, 1)
    )
    out_c = tk.MemoryConsumer(broker, "completions", group_id="audit")
    published = len(out_c.poll(max_records=10_000, timeout_ms=200))
    out_c.close()
    print(
        f"\n{args.prompts} completions, {toks} tokens in {dt:.2f}s "
        f"({toks / dt:,.0f} tok/s); {committed} offsets committed; "
        f"{published} completions on the output topic\n"
        f"metrics: {server.metrics.summary()}",
        file=sys.stderr,
    )
    consumer.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
