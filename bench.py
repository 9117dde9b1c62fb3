"""Headline benchmark: sustained ingest throughput, records/sec.

Measures the BASELINE.md metric (records/sec sustained ingest through the
full transactional loop: poll → transform → batch → device → step → barrier →
commit) for two implementations over the SAME in-memory Kafka-semantics
broker and the SAME records:

- **baseline**: the reference's architecture — our drop-in compat layer
  running the reference's exact single-process pattern (KafkaDataset
  subclass → torch DataLoader collation → auto_commit generator,
  /root/reference/README.md:86-102). The reference publishes no numbers
  (SURVEY.md §6), so its own design measured on the same hardware IS the
  baseline.
- **ours**: the TPU-native KafkaStream (threaded poll/transform pipeline,
  fixed-shape batcher, async device transfer, commit tokens), with each
  batch consumed by a REAL device step — an embedding gather and a
  three-matmul bf16 MLP tower over the ingested tokens (``_device_step``;
  not the transformer) — and offsets committed via the barrier (async,
  every COMMIT_EVERY batches).

Runs on a TPU only: ``main`` starts at the device gate
(``utils.devices.require_tpu``) and a failure in any trial fails the run.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "records/sec", "vs_baseline": N,
   "platform": "tpu", "device_kind": "...", "device_count": N, ...}

Trial protocol (VERDICT r2): trials are INTERLEAVED ours/baseline pairs over
EQUAL record counts, each pair preceded by a host-to-device transfer probe,
and ``vs_baseline`` is the MEDIAN OF PER-PAIR RATIOS — adjacent runs sample
the same host conditions, so the ratio stays stable even when absolute
throughput moves across the run (every trial's transfer rate is emitted
for post-hoc normalisation).

Env knobs: BENCH_RECORDS (default 1_000_000 — both sides),
BENCH_BASELINE_RECORDS (override the baseline side only), BENCH_BATCH
(default 32768), BENCH_SEQ (tokens/record, 32), BENCH_TRIALS (default 5),
BENCH_SLICES (alternating slices per trial, 4), BENCH_COMMIT_EVERY (16),
BENCH_WIRE (ours' wire format: "pack15" — 15-bit packed tokens, device-side
unpack, the framework's sub-byte codec — or "uint16"; default pack15).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

SEQ = int(os.environ.get("BENCH_SEQ", "32"))
# Equal records per side by default: asymmetric trial lengths sample
# drifting host conditions differently even when interleaved (the r2
# spread problem).
N_OURS = int(os.environ.get("BENCH_RECORDS", "1000000"))
N_BASE = int(os.environ.get("BENCH_BASELINE_RECORDS", str(N_OURS)))
# Batch 32768 = ~2 MB uint16 host→device transfers: each transfer and
# dispatch has a fixed cost, which a large batch amortises over more rows.
BATCH = int(os.environ.get("BENCH_BATCH", "32768"))
COMMIT_EVERY = int(os.environ.get("BENCH_COMMIT_EVERY", "16"))
N_PARTS = 8
# Ours' wire format. "pack15": tokens < 32768 ride the wire as a dense
# 15-bit stream (fixed_width wire_bits=15 → C-packed on host, unpacked
# on-device by ops.bitpack — a framework codec the reference pattern has no
# analog for) = 60 bytes/record vs uint16's 64. The baseline side always
# ships uint16 — the narrowest NUMPY-native cast a torch user would write;
# sub-byte packing requires the codec itself, which IS part of the ingest
# architecture under test.
WIRE = os.environ.get("BENCH_WIRE", "pack15")
if WIRE not in ("pack15", "uint16"):
    raise SystemExit(f"BENCH_WIRE must be pack15|uint16, got {WIRE!r}")


def fill_broker(tk, n_records: int):
    """One topic, N_PARTS partitions, fixed-width int32-token payloads."""
    broker = tk.InMemoryBroker()
    broker.create_topic("bench", partitions=N_PARTS)
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 32000, size=(256, SEQ), dtype=np.int32)
    # Round so the total divides evenly into BATCH-row batches: the stream
    # then ends on a full batch and the timed region has no idle-flush tail.
    step = max(BATCH // N_PARTS, 1) if BATCH % N_PARTS == 0 else 1
    per_part = max(n_records // N_PARTS // step, 1) * step
    for p in range(N_PARTS):
        broker.produce_many(
            "bench",
            (payload[i % 256].tobytes() for i in range(per_part)),
            partition=p,
        )
    return broker, per_part * N_PARTS


_STEP_CACHE: dict = {}


def _device_step(packed: bool = False):
    """A REAL device step: embed the ingested tokens and run a bf16 MLP
    tower (~34 GFLOP/batch of MXU matmuls) to a scalar loss — not a
    decorative reduction. MXU-shaped on purpose: seq-32 records make
    per-head [32, 32] attention matmuls (scenario 3 trains the full
    transformer and reports MFU at seq 512); an ingest-side consumer of
    short records is matmul-tower shaped. Sized so the bench stays an
    ingest benchmark: a few ms per batch, overlapped with host polling via
    the async dispatch queue.

    ``packed``: the batch arrives as the 15-bit wire stream and the step's
    first op is the on-device unpack (ops.bitpack) — bit twiddling is free
    next to the matmul tower, which is the codec's whole premise."""
    import jax
    import jax.numpy as jnp

    key = "step-packed" if packed else "step"
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]
    d_embed, d_h = 128, 512
    ks = jax.random.split(jax.random.key(0), 4)
    params = {
        "embed": jax.random.normal(ks[0], (512, d_embed), jnp.bfloat16) * 0.02,
        "w1": jax.random.normal(ks[1], (SEQ * d_embed, d_h), jnp.bfloat16) * 0.02,
        "w2": jax.random.normal(ks[2], (d_h, d_h), jnp.bfloat16) * 0.02,
        "w3": jax.random.normal(ks[3], (d_h, 1), jnp.bfloat16) * 0.02,
    }

    @jax.jit
    def step(tokens):
        if packed:
            from torchkafka_tpu.ops.bitpack import unpack_bits

            tokens = unpack_bits(tokens, 15, SEQ)
        x = params["embed"][tokens % 512].reshape(tokens.shape[0], -1)
        h = jax.nn.gelu(x @ params["w1"])
        h = jax.nn.gelu(h @ params["w2"])
        return jnp.mean((h @ params["w3"]).astype(jnp.float32) ** 2)

    _STEP_CACHE[key] = step
    return step


_BROKERS: dict = {}
# Unique consumer-group id per bench invocation: groups carry committed
# offsets on the shared broker, so a later trial reusing a group would
# resume at the end instead of replaying from 0.
_GROUP_SEQ = iter(range(10**9))


def _shared_broker(side: str, n_records: int):
    """Fill each side's broker ONCE and re-read it with a fresh consumer
    group per trial: refilling 600k records per trial put ~30s between the
    two sides of an interleaved pair, long enough for the machine's
    conditions to drift and reopen the ratio spread the pairing exists to
    close."""
    import torchkafka_tpu as tk

    if side not in _BROKERS:
        _BROKERS[side] = fill_broker(tk, n_records)
    return _BROKERS[side]


def bench_ours(n_records: int) -> float:
    import jax
    import jax.numpy as jnp

    import torchkafka_tpu as tk

    broker, total = _shared_broker("ours", n_records)
    consumer = tk.MemoryConsumer(
        broker,
        "bench",
        group_id=f"bench-tpu-{next(_GROUP_SEQ)}",
        assignment=tk.partitions_for_process("bench", N_PARTS, 0, 1),
    )

    # Token ids are < 32000: host→device wire bytes are the scarce
    # resource. pack15 ships them as a dense 15-bit stream (60 B/record);
    # uint16 is the byte-aligned fallback (64 B/record).
    packed = WIRE == "pack15"
    processor = (
        tk.fixed_width(SEQ, dtype=np.int32, wire_bits=15)
        if packed
        else tk.fixed_width(SEQ, dtype=np.int32, wire_dtype=np.uint16)
    )
    step = _device_step(packed=packed)

    rows = 0
    acc = None
    with tk.KafkaStream(
        consumer,
        processor,
        batch_size=BATCH,
        mesh=None,
        pad_policy="pad",
        prefetch=4,
        max_poll_records=16384,
        idle_timeout_ms=2000,
        transform_threads=0,
        owns_consumer=True,
    ) as stream:
        # Warm the compile AND the host→device transfer route outside the
        # timed region (a scalar fetch: the value cannot arrive before the
        # step ran). jnp.zeros would materialise on-device and leave the
        # transfer path cold for the first batch.
        if packed:
            from torchkafka_tpu.native import packed_width

            warm_in = np.zeros((BATCH, packed_width(SEQ, 15)), np.uint8)
        else:
            warm_in = np.zeros((BATCH, SEQ), np.uint16)
        float(step(jnp.asarray(warm_in)))
        fut = None
        n_batches = 0
        t0 = time.perf_counter()
        for batch, token in stream:
            acc = step(batch.data)
            rows += batch.valid_count
            n_batches += 1
            # Commit cadence: every COMMIT_EVERY batches (async, FIFO commit
            # thread) — a later token's offsets subsume the uncommitted
            # earlier ones, so this is the standard Kafka commit-interval
            # pattern with an at-least-once window of COMMIT_EVERY batches.
            # Proving step retirement costs a device fetch that waits for
            # the step, so per-batch cadence is a latency benchmark, not a
            # throughput one.
            if n_batches % COMMIT_EVERY == 0 or rows >= total:
                fut = token.commit_async(wait_for=acc)
            if rows >= total:  # deterministic end: no idle-timeout tail in the timing
                break
        if fut is not None:
            assert fut.result(timeout=120)  # last commit durable inside the timing
        elapsed = time.perf_counter() - t0
    assert rows == total, f"consumed {rows} != produced {total}"
    return rows / elapsed


def bench_reference_pattern(n_records: int) -> float:
    """The reference's single-process flow via the compat layer
    (/root/reference/README.md:86-102): DataLoader batching + commit-per-batch.

    SAME device step and SAME uint16 wire cast as ours — reference users
    ship their batches to an accelerator too, so both loops pay identical
    transfer + compute costs and the ratio isolates the INGEST ARCHITECTURE
    (threaded chunk pipeline + async commits vs DataLoader iteration +
    per-batch signal commits), not the host-to-device link."""
    import jax
    import jax.numpy as jnp
    import torch
    from torch.utils.data import DataLoader

    import torchkafka_tpu as tk
    from torchkafka_tpu.compat import KafkaDataset, auto_commit

    broker, total = _shared_broker("ref", n_records)

    class BenchDataset(KafkaDataset):
        def _process(self, record):
            return torch.from_numpy(
                np.frombuffer(record.value, dtype=np.int32).copy()
            )

        @classmethod
        def new_consumer(cls, *args, **kwargs):
            kwargs.pop("_is_placeholder", None)
            return tk.MemoryConsumer(
                broker,
                *args,
                assignment=tk.partitions_for_process("bench", N_PARTS, 0, 1),
                consumer_timeout_ms=500,
                **kwargs,
            )

    dataset = BenchDataset("bench", group_id=f"bench-ref-{next(_GROUP_SEQ)}")
    loader = DataLoader(dataset, batch_size=BATCH)
    step = _device_step()
    # Warm compile + transfer route outside timing (symmetric with ours).
    float(step(jnp.asarray(np.zeros((BATCH, SEQ), np.uint16))))
    rows = 0
    acc = None
    t0 = time.perf_counter()
    for batch in auto_commit(loader):
        rows += int(batch.shape[0])
        # The user's work: same uint16 wire cast, same transfer, same MLP
        # step as ours (torch -> numpy -> device is the torch-user path).
        acc = step(jnp.asarray(batch.numpy().astype(np.uint16)))
        if rows >= total:  # symmetric deterministic end
            break
    if acc is not None:
        float(acc)  # strict completion proof inside the timing, like ours
    elapsed = time.perf_counter() - t0
    assert rows == total, f"consumed {rows} != produced {total}"
    return rows / elapsed


def probe_wire_mb_s() -> float:
    """Measured host→device throughput for one batch-sized transfer (median
    of 3) — context for the headline: the rate at which batches can reach
    the device at all."""
    import time as _time

    import jax
    import jax.numpy as jnp

    a = np.random.default_rng(0).integers(0, 100, (BATCH, SEQ), dtype=np.uint16)
    s = jax.jit(lambda x: jnp.sum(x, dtype=jnp.int32))
    int(s(jnp.asarray(a)))  # warm compile + connection
    mb = a.nbytes / 1e6
    rates = []
    for i in range(3):
        t0 = _time.perf_counter()
        int(s(jax.device_put(a + i)))
        rates.append(mb / (_time.perf_counter() - t0))
    return float(np.median(rates))


def main() -> None:
    from torchkafka_tpu.utils.devices import enable_compile_cache, require_tpu

    enable_compile_cache()
    platform, device_kind, device_count = require_tpu()
    trials = int(os.environ.get("BENCH_TRIALS", "5"))
    # Headline = MEDIAN over trials (robust to scheduler noise without
    # crediting the best outlier); best and spread reported alongside so
    # the distribution is visible.
    slices = max(1, int(os.environ.get("BENCH_SLICES", "4")))
    n_o, n_b = N_OURS // slices, N_BASE // slices
    # Untimed warmup slice per side, BEFORE the first transfer probe (r3:
    # the only losing pair was the FIRST — first-contact costs land there
    # otherwise: broker fill + allocator growth, XLA compiles, transfer-
    # route ramp, branch-cold Python; and the probe must sample pair 1's
    # conditions, not pre-warmup conditions). Result discarded.
    bench_ours(n_o)
    bench_reference_pattern(n_b)
    # INTERLEAVED ours/baseline pairs: the machine's conditions drift
    # minute-to-minute, so adjacent runs sample (nearly) the same ones and
    # the PER-PAIR ratio cancels the drift that swamps absolute numbers.
    # A transfer probe before each pair records the conditions it ran
    # under. Any trial or probe that raises fails the run.
    ours_all: list[float] = []
    base_all: list[float] = []
    pair_ratios: list[float] = []
    wires: list[float] = []
    # Each trial runs SLICES slices per side, alternating O/B/O/B…: the two
    # sides of a slice pair execute within seconds of each other, so the
    # per-trial ratio (sum of timed regions per side) samples near-identical
    # conditions even when they move across the run.
    for _ in range(trials):
        wires.append(probe_wire_mb_s())
        o_time = b_time = 0.0
        for _ in range(slices):
            o_time += n_o / bench_ours(n_o)
            b_time += n_b / bench_reference_pattern(n_b)
        o = slices * n_o / o_time
        b = slices * n_b / b_time
        ours_all.append(o)
        base_all.append(b)
        pair_ratios.append(o / b)
    ours_sorted = sorted(ours_all)
    base = float(np.median(base_all))
    ours = float(np.median(ours_all))
    ratios = sorted(pair_ratios)
    wire_med = float(np.median(wires))
    print(
        json.dumps(
            {
                "metric": "sustained_ingest_throughput",
                "value": round(ours, 1),
                "unit": "records/sec",
                "platform": platform,
                "device_kind": device_kind,
                "device_count": device_count,
                # Median of per-interleaved-pair ratios: robust to drift
                # across the run (each pair saw the same conditions).
                "vs_baseline": round(float(np.median(ratios)), 3),
                "trials": trials,
                "spread": [round(ours_sorted[0], 1), round(ours_sorted[-1], 1)],
                "best": round(ours_sorted[-1], 1),
                "baseline_median": round(base, 1),
                "pair_ratios": [round(r, 3) for r in pair_ratios],
                "ratio_spread": [round(ratios[0], 3), round(ratios[-1], 3)],
                "records_per_trial": [N_OURS, N_BASE],
                "wire_format": WIRE,
                "wire_mb_s": round(wire_med, 1),
                "wire_mb_s_per_pair": [round(w, 1) for w in wires],
            }
        )
    )
    print(
        f"ours median={ours:,.0f} rec/s (min {ours_sorted[0]:,.0f}, max "
        f"{ours_sorted[-1]:,.0f})  reference-pattern median={base:,.0f} rec/s  "
        f"pair ratios={[f'{r:.2f}' for r in pair_ratios]}  "
        f"records={N_OURS:,}/{N_BASE:,} batch={BATCH} seq={SEQ} "
        f"device-step=mlp-tower  wire(median)={wire_med:.1f} MB/s",
        file=sys.stderr,
    )


if __name__ == "__main__":
    main()
