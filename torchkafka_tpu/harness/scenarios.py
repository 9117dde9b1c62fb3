"""The harness scenarios (BASELINE.json's five configs + net-new ones),
each returning a metrics dict.

| # | Scenario | Reference analog |
|---|----------|------------------|
| 1 | single-process float records, batch 4, 1 partition | README MyDataset flow (/root/reference/README.md:86-102) |
| 2 | JSON → tokenized int32, 8 partitions, threaded transform | README multiproc flow (/root/reference/README.md:104-132) |
| 3 | mesh-sharded global batch, transformer train, commit-after-step | none (new capability) |
| 4 | image bytes → on-device decode/resize → ResNet-50 inference | none |
| 5 | prompt topic → KV-cache generate → commit post-generation | none |
| 6 | scenario 1 at batch 256 | isolates the reference's toy batch-4 choice |
| 7 | continuous-batching serving (slot recycling, EOS) | none |
| 8 | streaming CTR: DLRM train, tp-sharded embedding tables | none |
| 9 | ragged text → length-bucketed batches → per-width train steps | none |
| 10 | serving fleet: QoS admission + graceful drain | none |
| 11 | chaos soak: broker outage + poison prompt → recovery + DLQ | none |
| 12 | prefix-cache fleet: per-tenant system prompts, paged KV reuse | none |
| 13 | warm failover: seeded replica kill + journal resume | none |
| 14 | chunked-prefill prompt storm (bounded decode latency) | none |
| 15 | traced fleet: per-tenant SLOs + Prometheus endpoint | none |
| 16 | Zipf burst storm: windowed SLOs + burn-rate shedding | none |
| 17 | real-process fleet: SIGKILL mid-storm, zombie fencing | none |
| 18 | exactly-once output: transactional SIGKILL storm | none |
| 19 | durable broker: uncleanly killed + WAL-recovered mid-storm | none |
| 20 | sharded paged serving: paged+int8+kernel-probe on a {data,tp} mesh | none |

Every scenario runs the full transactional loop (poll → transform → batch →
device → step → barrier → commit) and reports ``records_per_s`` plus commit
latency percentiles from the stream's own metrics.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable

import numpy as np

_SIZES = ("tiny", "full")


def _result(name: str, rows: int, elapsed: float, stream, extra: dict | None = None) -> dict:
    out = {
        **stream.metrics.summary(),
        "scenario": name,
        "records": rows,
        "elapsed_s": round(elapsed, 3),
        "records_per_s": round(rows / elapsed, 1) if elapsed > 0 else None,
    }
    if extra:
        out.update(extra)
    return out


def _drain(
    stream, step: Callable[[Any], Any] | None, total: int,
    sync_commit: bool = False,
) -> tuple[int, float]:
    """Run the transactional loop until ``total`` rows are consumed; the
    last commit is durable inside the timed region. ``sync_commit`` commits
    inline instead of through the FIFO commit thread — pair it with a
    ``prefetch=0`` stream for latency-shaped loops (sub-ms batches), where
    a per-batch executor handoff costs more than the commit itself."""
    rows = 0
    fut = None
    t0 = time.perf_counter()
    for batch, token in stream:
        wait = step(batch) if step is not None else None
        if sync_commit:
            token.commit(wait_for=wait)
        else:
            fut = token.commit_async(wait_for=wait)
        rows += batch.valid_count
        if rows >= total:
            break
    if fut is not None:
        fut.result(timeout=600)
    return rows, time.perf_counter() - t0


_PAIR_GROUP_SEQ = iter(range(10**9))


def _paired_host_ratio(
    broker, topic: str, n_parts: int, ours_slice, ref_process, batch_size: int,
    n_slice: int, slices: int = 2,
) -> dict:
    """Alternating ours/reference-pattern slices over the SAME broker
    records (VERDICT r3 item 6): host-bound absolute numbers swing up to 15× with
    box contention across rounds, but adjacent slices sample the same
    conditions, so the per-pair ratio is the stable signal. Reports the
    median of per-pair ratios plus both sides' rates.

    ``ours_slice(group_id, n) -> (rows, elapsed)`` runs the framework path;
    ``ref_process(record) -> torch tensor/pytree`` defines the reference
    analog, executed through the REAL compat stack (KafkaDataset subclass →
    DataLoader → auto_commit, /root/reference/README.md:86-102) with
    commit-per-batch, the reference's own cadence."""
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.compat import KafkaDataset, auto_commit

    def ref_slice(group_id: str, n: int) -> tuple[int, float]:
        from torch.utils.data import DataLoader

        class RefDataset(KafkaDataset):
            def _process(self, record):
                return ref_process(record)

            @classmethod
            def new_consumer(cls, *args, **kwargs):
                kwargs.pop("_is_placeholder", None)
                return tk.MemoryConsumer(
                    broker, *args,
                    assignment=tk.partitions_for_process(topic, n_parts, 0, 1),
                    consumer_timeout_ms=500, **kwargs,
                )

        dataset = RefDataset(topic, group_id=group_id)
        loader = DataLoader(dataset, batch_size=batch_size)
        rows = 0
        t0 = _time.perf_counter()
        for batch in auto_commit(loader):
            first = batch[0] if isinstance(batch, (list, tuple)) else batch
            rows += int(first.shape[0])
            if rows >= n:
                break
        elapsed = _time.perf_counter() - t0
        dataset.close()
        return rows, elapsed

    ratios, ours_rates, ref_rates = [], [], []
    for _ in range(slices):
        o_rows, o_t = ours_slice(f"pair-ours-{next(_PAIR_GROUP_SEQ)}", n_slice)
        r_rows, r_t = ref_slice(f"pair-ref-{next(_PAIR_GROUP_SEQ)}", n_slice)
        ours_rates.append(o_rows / o_t)
        ref_rates.append(r_rows / r_t)
        ratios.append(ours_rates[-1] / ref_rates[-1])
    return {
        "vs_reference_pattern": round(float(np.median(ratios)), 3),
        "pair_ratios": [round(r, 3) for r in ratios],
        "ours_rows_per_s": round(float(np.median(ours_rates)), 1),
        "reference_pattern_rows_per_s": round(float(np.median(ref_rates)), 1),
    }


def scenario_1(size: str = "tiny", batch_size: int = 4, name: str = "1:single-process") -> dict:
    """Single-process, 1 partition, batch 4: the reference's README flow —
    each record becomes a float32[8] row (torch.rand(8) analog,
    /root/reference/README.md:40-44). Batch 4 is faithful to the reference's
    example (README.md:84,97) and is iteration-bound by design; scenario 6
    reruns this flow at batch 256 so the comparison is not an artifact of
    the reference's toy batch size. Host-bound, so the headline is the
    PAIRED ratio (see ``_paired_host_ratio``), not the weather-dependent
    absolute rate."""
    import torch

    import torchkafka_tpu as tk

    n = 512 if size == "tiny" else 200_000
    broker = tk.InMemoryBroker()
    broker.create_topic("t1", partitions=1)
    rng = np.random.default_rng(0)
    broker.produce_many("t1", (rng.random(8).astype(np.float32).tobytes() for _ in range(n)))
    consumer = tk.MemoryConsumer(
        broker, "t1", group_id="s1", assignment=[tk.TopicPartition("t1", 0)]
    )
    # Batch 4 is latency-shaped: a per-batch thread handoff + commit-thread
    # submit cost more than the 4-row batch itself, so small batches take
    # the stream's documented synchronous mode (prefetch=0, inline commit)
    # — symmetric with the reference pattern, which is also single-threaded.
    # Large batches (scenario 6) keep the pipelined mode.
    latency_shaped = batch_size < 64
    stream_kw = dict(
        to_device=False, idle_timeout_ms=1000, owns_consumer=True,
        prefetch=0 if latency_shaped else 2,
    )
    with tk.KafkaStream(
        consumer, tk.fixed_width(8, np.float32), batch_size=batch_size,
        # Host-only, like the reference it mirrors (its DataLoader yields CPU
        # torch tensors); shipping batch-of-4 arrays to an accelerator per
        # iteration would benchmark the host-to-device copy, not the loop.
        **stream_kw,
    ) as stream:
        rows, elapsed = _drain(
            stream, None, n // batch_size * batch_size,
            sync_commit=latency_shaped,
        )

    def ours_slice(group_id: str, n_s: int):
        c = tk.MemoryConsumer(
            broker, "t1", group_id=group_id,
            assignment=[tk.TopicPartition("t1", 0)],
        )
        with tk.KafkaStream(
            c, tk.fixed_width(8, np.float32), batch_size=batch_size,
            **stream_kw,
        ) as s:
            return _drain(s, None, n_s, sync_commit=latency_shaped)

    paired = _paired_host_ratio(
        broker, "t1", 1, ours_slice,
        lambda rec: torch.from_numpy(
            np.frombuffer(rec.value, dtype=np.float32).copy()
        ),
        batch_size, (n // 2) // batch_size * batch_size,
    )
    return _result(name, rows, elapsed, stream, {"batch_size": batch_size, **paired})


def scenario_6(size: str = "tiny") -> dict:
    """Scenario 1 at a realistic batch size (256): same records, same
    host-only loop — isolates how much of scenario 1's number is the
    reference's example batch of 4."""
    return scenario_1(size, batch_size=256, name="6:single-process-b256")


def scenario_2(size: str = "tiny") -> dict:
    """JSON records → tokenized int32[seq], 8 partitions, chunked transform
    (the multiproc DataLoader analog — thread/chunk parallel instead of
    process parallel). Host-bound: paired against the torch-user analog
    (json.loads + per-record tokenize in ``_process``), host-only on both
    sides so the pair isolates the transform architecture."""
    import torch

    import torchkafka_tpu as tk

    n, seq = (2048, 32) if size == "tiny" else (500_000, 128)
    broker = tk.InMemoryBroker()
    broker.create_topic("t2", partitions=8)
    rng = np.random.default_rng(0)
    words = ["stream", "kafka", "tpu", "offset", "commit", "batch", "mesh"]
    broker.produce_many(
        "t2",
        (
            json.dumps({"text": " ".join(rng.choice(words, 6))}).encode()
            for _ in range(n)
        ),
    )
    consumer = tk.MemoryConsumer(
        broker, "t2", group_id="s2",
        assignment=tk.partitions_for_process("t2", 8, 0, 1),
    )
    with tk.KafkaStream(
        consumer, tk.json_tokens("text", seq), batch_size=256,
        to_device=True, idle_timeout_ms=1000, owns_consumer=True,
    ) as stream:
        rows, elapsed = _drain(stream, None, n // 256 * 256)

    def ours_slice(group_id: str, n_s: int):
        c = tk.MemoryConsumer(
            broker, "t2", group_id=group_id,
            assignment=tk.partitions_for_process("t2", 8, 0, 1),
        )
        with tk.KafkaStream(
            c, tk.json_tokens("text", seq), batch_size=256,
            to_device=False, idle_timeout_ms=1000, owns_consumer=True,
        ) as s:
            return _drain(s, None, n_s)

    def ref_process(rec):
        text = json.loads(rec.value)["text"].encode()
        row = np.full((seq,), 0, np.int32)
        take = min(len(text), seq)
        row[:take] = np.frombuffer(text[:take], np.uint8)
        return torch.from_numpy(row)

    paired = _paired_host_ratio(
        broker, "t2", 8, ours_slice, ref_process, 256,
        (n // 2) // 256 * 256,
    )
    return _result("2:json-tokenize", rows, elapsed, stream, paired)


def scenario_3(size: str = "tiny") -> dict:
    """Mesh-sharded global batches training the flagship transformer with
    commit-after-step — the heart of the TPU-native design (BASELINE
    north star; no reference analog)."""
    import jax
    import jax.numpy as jnp
    import optax

    import torchkafka_tpu as tk
    from torchkafka_tpu.models import TransformerConfig, make_train_step

    n_dev = len(jax.devices())
    mesh = tk.make_mesh({"data": n_dev})
    seq = 64 if size == "tiny" else 512
    cfg = (
        TransformerConfig(vocab_size=512, d_model=64, n_layers=2, n_heads=4,
                          n_kv_heads=2, d_ff=128, max_seq_len=seq, dtype=jnp.float32)
        if size == "tiny"
        else TransformerConfig(max_seq_len=seq)
    )
    steps = 8 if size == "tiny" else 50
    local_batch = 2 * n_dev if size == "tiny" else 8 * n_dev
    n = steps * local_batch

    broker = tk.InMemoryBroker()
    parts = max(n_dev, 4)
    broker.create_topic("t3", partitions=parts)
    rng = np.random.default_rng(0)
    broker.produce_many(
        "t3",
        (rng.integers(0, cfg.vocab_size, seq, dtype=np.int32).tobytes() for _ in range(n)),
    )
    consumer = tk.MemoryConsumer(
        broker, "t3", group_id="s3",
        assignment=tk.partitions_for_process("t3", parts, 0, 1),
    )
    init_fn, step_fn = make_train_step(cfg, mesh, optax.adamw(1e-3))
    params, opt_state = init_fn(jax.random.key(0))
    state = {"params": params, "opt": opt_state, "losses": []}

    def step(batch):
        mask = (np.arange(batch.batch_size) < batch.valid_count).astype(np.int32)
        mask = jnp.broadcast_to(jnp.asarray(mask)[:, None], batch.data.shape)
        state["params"], state["opt"], loss = step_fn(
            state["params"], state["opt"], batch.data, mask
        )
        state["losses"].append(loss)
        return loss

    with tk.KafkaStream(
        consumer, tk.fixed_width(seq, np.int32), batch_size=local_batch,
        mesh=mesh, idle_timeout_ms=2000, owns_consumer=True,
    ) as stream:
        rows, elapsed = _drain(stream, step, n)
    losses = [float(x) for x in state["losses"]]
    extra = {"mesh": dict(mesh.shape), "first_loss": round(losses[0], 4),
             "last_loss": round(losses[-1], 4)}
    extra.update(_train_mfu(cfg, state, step_fn, local_batch, seq, n_dev))
    return _result("3:mesh-train", rows, elapsed, stream, extra)


def _train_mfu(cfg, state, step_fn, batch: int, seq: int, n_dev: int) -> dict:
    """Pure train-step time (ingest excluded) and an MFU estimate.

    FLOPs/step ≈ 6·N_params·tokens (fwd+bwd matmul rule of thumb)
    + 6·L·d_model·B·S² (causal attention, fwd+bwd); peak = the published
    bf16 rate of the chip this process holds (``device_peaks``) × the
    mesh's device count. Timed with ``utils.timing.device_step_seconds``
    — the step chained inside ONE jitted fori_loop, sloped over two loop
    lengths, so the host's per-dispatch cost is not in the number."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models.transformer import count_params
    from torchkafka_tpu.utils.devices import device_peaks
    from torchkafka_tpu.utils.timing import device_step_seconds

    if jax.default_backend() != "tpu":
        return {}
    n_params = count_params(state["params"])
    tokens = jnp.zeros((batch, seq), jnp.int32)
    mask = jnp.ones((batch, seq), jnp.int32)
    step_s, slope_ok = device_step_seconds(
        step_fn, state["params"], state["opt"], tokens, mask
    )
    if not slope_ok:
        return {"params_m": round(n_params / 1e6, 1), "slope_ok": False}
    flops = 6 * n_params * batch * seq + 6 * cfg.n_layers * cfg.d_model * batch * seq**2
    mfu = flops / step_s / (device_peaks().bf16_flops * n_dev)
    return {
        "params_m": round(n_params / 1e6, 1),
        "step_ms": round(step_s * 1e3, 2),
        "flops_per_step_g": round(flops / 1e9, 1),
        "mfu_pct": round(mfu * 100, 2),
        "slope_ok": True,
    }


def scenario_4(size: str = "tiny") -> dict:
    """PNG topic → host C++ decode (zlib inflate + defilter) → on-device
    resize → ResNet-50 inference, commit per batch (BASELINE config 4; no
    reference analog — but the host decompression is exactly the per-record
    CPU work the reference's ``_process`` hook exists for,
    /root/reference/src/kafka_dataset.py:173-186). VERDICT r2: a reshape is
    not a decode; this measures through a real compressed-image path and
    reports the host-decode vs device-infer split."""
    import time as _time

    import jax
    import jax.numpy as jnp

    import torchkafka_tpu as tk
    from torchkafka_tpu.models import resnet
    from torchkafka_tpu.transform.image import encode_png_rgb

    h = w = 64
    out_size = 64 if size == "tiny" else 224
    n, batch = (64, 8) if size == "tiny" else (8192, 64)
    broker = tk.InMemoryBroker()
    broker.create_topic("t4", partitions=4)
    rng = np.random.default_rng(0)
    # Smooth sinusoid field + low noise: compresses ~1.8x under Paeth —
    # photo-like, not white noise (incompressible at 1.0x) — so inflate and
    # defiltering do real work per record. Paeth is both the realistic
    # adaptive-encoder choice and the most expensive filter to reverse.
    yy, xx = np.mgrid[0:h, 0:w]
    base = (96 + 80 * np.sin(xx / 9.0) + 60 * np.cos(yy / 7.0))[:, :, None] + (
        np.array([0, 20, 40])
    )
    payloads = [
        encode_png_rgb(
            np.clip(base + rng.integers(0, 4, (h, w, 3)), 0, 255).astype(
                np.uint8
            ),
            filters=4,
        )
        for _ in range(min(n, 256))
    ]
    png_bytes = float(np.mean([len(p) for p in payloads]))
    broker.produce_many("t4", (payloads[i % len(payloads)] for i in range(n)))
    consumer = tk.MemoryConsumer(
        broker, "t4", group_id="s4",
        assignment=tk.partitions_for_process("t4", 4, 0, 1),
    )
    params = resnet.init_params(jax.random.key(0))

    @jax.jit
    def infer(imgs):
        return jnp.argmax(
            resnet.forward(params, resnet.preprocess(imgs, out_size)), axis=-1
        )

    jax.block_until_ready(infer(jnp.zeros((batch, h, w, 3), jnp.uint8)))
    with tk.KafkaStream(
        consumer, tk.png_images(h, w), batch_size=batch,
        to_device=True, idle_timeout_ms=2000, owns_consumer=True,
    ) as stream:
        rows, elapsed = _drain(stream, lambda b: infer(b.data), n)

    # Decode/infer split, each measured standalone on one batch's worth.
    from torchkafka_tpu import native

    chunk = (payloads * -(-batch // len(payloads)))[:batch]
    t0 = _time.perf_counter()
    native.decode_png_rgb(chunk, h, w)
    decode_ms = (_time.perf_counter() - t0) * 1e3
    imgs_dev = jnp.asarray(np.zeros((batch, h, w, 3), np.uint8))
    int(infer(imgs_dev)[0])  # warm with this exact sharding
    t0 = _time.perf_counter()
    int(infer(imgs_dev)[0])  # strict: scalar fetch
    infer_ms = (_time.perf_counter() - t0) * 1e3

    # Chained on-device iterations (VERDICT r3 item 2): the single-dispatch
    # number above bundles the dispatch and fetch with compute — honest
    # as "what one poll-to-answer costs" but useless for judging the conv
    # stack. Two chain lengths run the forward in ONE dispatch each, every
    # iteration data-dependent on the last (the label sum perturbs the next
    # input, so XLA cannot hoist them); the SLOPE between the two timings
    # cancels the constant dispatch+fetch overhead that otherwise floors
    # any divide-by-K estimate. Conv MFU uses the analytic
    # ResNet-50 count (2·4.089 GFLOP/image at 224², scaled by resolution);
    # XLA's cost analysis counts a fori_loop body once, not per trip.
    def _chained(k):
        def fn(imgs):
            def body(_, carry):
                s, _lab = carry
                x = imgs + (s % 2).astype(imgs.dtype)
                lab = jnp.argmax(
                    resnet.forward(params, resnet.preprocess(x, out_size)),
                    axis=-1,
                ).astype(jnp.int32)
                return jnp.sum(lab).astype(jnp.int32), lab

            from jax import lax as _lax

            return _lax.fori_loop(
                0, k, body,
                (jnp.int32(0), jnp.zeros((imgs.shape[0],), jnp.int32)),
            )[0]

        return jax.jit(fn)

    extra_infer: dict = {}
    if jax.default_backend() == "tpu":
        from torchkafka_tpu.utils.devices import device_peaks
        from torchkafka_tpu.utils.timing import two_point_slope

        k_short, k_long = 8, 40
        fns = {k: _chained(k) for k in (k_short, k_long)}
        for fn in fns.values():
            int(fn(imgs_dev))  # warm/compile both chain lengths first
        # Interleave short/long timings so drift between the two chain
        # lengths cannot flip the slope's sign.
        shorts, longs = [], []
        for _ in range(3):
            t0 = _time.perf_counter()
            int(fns[k_short](imgs_dev))
            shorts.append(_time.perf_counter() - t0)
            t0 = _time.perf_counter()
            int(fns[k_long](imgs_dev))
            longs.append(_time.perf_counter() - t0)
        per_iter_s, overhead_s, slope_ok = two_point_slope(
            float(np.median(shorts)), float(np.median(longs)),
            k_short, k_long,
        )
        flops = 2 * 4.089e9 * batch * (out_size / 224) ** 2
        extra_infer = {
            "slope_ok": slope_ok,
            "dispatch_overhead_ms": round(overhead_s * 1e3, 1),
            "conv_flops_per_batch_g": round(flops / 1e9, 1),
        }
        if slope_ok:
            extra_infer.update({
                "device_infer_ms_chained": round(per_iter_s * 1e3, 2),
                "dispatch_share_pct": round(
                    100 * (1 - per_iter_s * 1e3 / infer_ms), 1
                ) if infer_ms else None,
                "conv_mfu_pct": round(
                    100 * flops / per_iter_s / device_peaks().bf16_flops, 1
                ),
            })
        else:
            # Drift swamped the slope — flag, don't fabricate.
            extra_infer.update({
                "device_infer_ms_chained": None,
                "dispatch_share_pct": None,
                "conv_mfu_pct": None,
            })
    return _result(
        "4:png-resnet-infer", rows, elapsed, stream,
        {
            "image": f"png {h}x{w}->{out_size}",
            "png_bytes_avg": round(png_bytes),
            "compression": round(h * w * 3 / png_bytes, 2),
            "native_decode": native.available(),
            "host_decode_ms_per_batch": round(decode_ms, 2),
            "device_infer_ms_per_batch": round(infer_ms, 2),
            **extra_infer,
        },
    )


def _serving_model(size: str, model_scale: str | None, prompt_len: int,
                   max_new: int, quantized: bool | None = None):
    """(cfg, params, label) for the serving scenarios. ``model_scale`` is
    the VERDICT-r3 scale flag: None keeps the historical tiny/45m configs
    (comparable across rounds); '45m' | '1b' | '8b' draws from the model
    zoo at true serving bytes — '8b' in int8 (the only way 8B fits one
    16 GB chip), the rest bf16 params (so counted bytes == streamed
    bytes in the rooflines). ``quantized`` overrides the per-scale
    default (--quantized serves ANY scale weight-only int8 — decode is
    bytes-bound, so halving bytes vs bf16 raises the roofline
    ceiling); it requires a model_scale, and '8b' cannot un-quantize
    (validated here so direct scenario_5/7 calls get the same guards as
    the CLI)."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models import TransformerConfig
    from torchkafka_tpu.models.transformer import init_params

    if quantized is not None and model_scale is None:
        raise ValueError(
            "quantized requires a model_scale (the tiny/default configs "
            "ignore dtype knobs; accepting it would silently serve bf16)"
        )
    if model_scale is None:
        cfg = (
            TransformerConfig(vocab_size=512, d_model=64, n_layers=2,
                              n_heads=4, n_kv_heads=2, d_ff=128,
                              max_seq_len=prompt_len + max_new,
                              dtype=jnp.float32)
            if size == "tiny"
            else TransformerConfig(max_seq_len=prompt_len + max_new)
        )
        return cfg, init_params(jax.random.key(0), cfg), "default"
    import sys
    import time as _time

    from torchkafka_tpu.models.zoo import random_serving_params, zoo_config

    cfg = zoo_config(model_scale, max_seq_len=prompt_len + max_new)
    if quantized is None:
        quantized = model_scale == "8b"
    elif model_scale == "8b" and not quantized:
        raise ValueError(
            "8b serves int8 only: bf16 8B params are ~16 GB and cannot fit "
            "one 16 GB chip next to the KV pool (and '8b' labels int8 in "
            "every published table)"
        )
    t0 = _time.perf_counter()
    params = random_serving_params(
        jax.random.key(0), cfg, quantized=quantized
    )
    jax.block_until_ready(params)
    label = f"{model_scale}-int8" if quantized and model_scale != "8b" else model_scale
    print(
        f"[scale {label}] params materialised in "
        f"{_time.perf_counter() - t0:.1f}s",
        file=sys.stderr, flush=True,
    )
    return cfg, params, label


def scenario_5(
    size: str = "tiny", model_scale: str | None = None,
    quantized: bool | None = None,
) -> dict:
    """Prompt topic → KV-cache generation → commit offsets only after the
    whole generation retires (BASELINE config 5; no reference analog).
    ``model_scale`` (45m | 1b | 8b) serves the zoo models at true HBM
    footprint and adds device-side decode timing (prefill measured
    separately — it is compute-bound, decode is bandwidth-bound; folding
    them together hides which one you are)."""
    import time as _time

    import jax
    import jax.numpy as jnp

    import torchkafka_tpu as tk
    from torchkafka_tpu.models.generate import generate, prefill
    from torchkafka_tpu.models.zoo import params_nbytes

    prompt_len, max_new = (16, 8) if size == "tiny" else (128, 64)
    n, batch = (64, 8) if size == "tiny" else (1024, 32)
    if model_scale == "1b":
        n, batch = 128, 16
    elif model_scale == "8b":
        n, batch = 48, 16
    cfg, params, label = _serving_model(
        size, model_scale, prompt_len, max_new, quantized
    )
    broker = tk.InMemoryBroker()
    broker.create_topic("t5", partitions=2)
    rng = np.random.default_rng(0)
    broker.produce_many(
        "t5",
        (rng.integers(0, cfg.vocab_size, prompt_len, dtype=np.int32).tobytes()
         for _ in range(n)),
    )
    consumer = tk.MemoryConsumer(
        broker, "t5", group_id="s5",
        assignment=tk.partitions_for_process("t5", 2, 0, 1),
    )
    gen = jax.jit(lambda p, t: generate(p, cfg, t, max_new))
    jax.block_until_ready(gen(params, jnp.zeros((batch, prompt_len), jnp.int32)))
    generated = []

    def step(b):
        out = gen(params, b.data)
        generated.append(out)
        return out

    with tk.KafkaStream(
        consumer, tk.fixed_width(prompt_len, np.int32), batch_size=batch,
        to_device=True, idle_timeout_ms=2000, owns_consumer=True,
    ) as stream:
        rows, elapsed = _drain(stream, step, n)
    toks = rows * max_new
    extra = {
        "model_scale": label,
        "params_bytes_g": round(params_nbytes(params) / 1e9, 3),
        "generated_tokens": toks,
        "tokens_per_s": round(toks / elapsed, 1) if elapsed else None,
    }
    if model_scale is not None and jax.default_backend() == "tpu":
        # Device-side split: prefill alone, then whole-generate, both as
        # median-of-3 strict-fetch timings; decode tok/s comes from the
        # difference. Large models run long enough per call that dispatch
        # jitter is noise here.
        toks_dev = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32
        )
        pf = jax.jit(lambda p, t: prefill(p, cfg, t, prompt_len + max_new)[0])
        float(jax.device_get(pf(params, toks_dev)[0, 0]))  # warm/compile
        pf_times, gen_times = [], []
        for _ in range(3):
            t0 = _time.perf_counter()
            out = pf(params, toks_dev)
            float(jax.device_get(out[0, 0]))  # scalar fetch, not [B, V]
            pf_times.append(_time.perf_counter() - t0)
            t0 = _time.perf_counter()
            out = gen(params, toks_dev)
            int(jax.device_get(out[0, 0]))
            gen_times.append(_time.perf_counter() - t0)
        pf_s, gen_s = float(np.median(pf_times)), float(np.median(gen_times))
        extra.update({
            "device_prefill_ms": round(pf_s * 1e3, 1),
            "device_generate_ms": round(gen_s * 1e3, 1),
        })
        decode_s = gen_s - pf_s
        if decode_s <= 0.25 * gen_s:
            # Both timings are single dispatches whose wall is
            # max(dispatch + fetch, device work) — NOT their sum — so the
            # difference carries no information once the device work sits
            # under the fixed cost (the 45M scale: both walls read about
            # the fixed cost and the delta is jitter). Flag unless decode
            # dominates the generate wall, like two_point_slope's
            # slope_ok (the benchmark's traced ``tick_ms.tput`` is the
            # robust decode number at every scale).
            extra.update({"split_ok": False, "device_decode_tok_s": None})
        else:
            extra.update({
                "split_ok": True,
                "device_decode_tok_s": round(batch * max_new / decode_s, 1),
            })
    return _result("5:generate", rows, elapsed, stream, extra)


def scenario_7(
    size: str = "tiny", model_scale: str | None = None,
    serve_eos: bool = False, quantized: bool | None = None,
    kv_int8: bool = False, kv_kernel: bool | str = "auto",
    spec: bool = False, spec_k: int = 4,
    spec_draft_layers: int | None = None,
    temperature: float = 0.0, top_k: int | None = None,
    top_p: float | None = None,
) -> dict:
    """Continuous-batching serving (serve.StreamingGenerator): same prompt
    topic shape as scenario 5, but slots recycle as generations hit EOS —
    an EOS id picked from a probe generation so a real fraction of prompts
    stops early. Reports completions/s and tokens/s; offsets commit per
    completion through the interval ledger. (No reference analog.)

    ``model_scale`` (45m | 1b | 8b): serve the zoo models at true HBM
    footprint (the decode tick against the HBM-bandwidth bound is the
    benchmark's to read: ``kvattn.roofline_pct``, ``tick_ms.tput``). EOS
    is off at scale BY DEFAULT (every slot runs full max_new, one dispatch
    per generation — the throughput ceiling); ``serve_eos=True``
    (--serve-eos) turns it ON at scale with
    ``ticks_per_sync=8``, so completed slots readmit MID-generation-block
    — the continuous-batching row (VERDICT r4 weak #4), with
    ``readmissions`` counting slots refilled while others were in
    flight and ``truncated_by_eos`` proving early stops.

    ``spec`` (--spec): serve through ``SpecStreamingGenerator`` — the
    layer-truncated self-draft proposes ``spec_k`` tokens per slot per
    round, one multi-query verify advances every slot by its accepted
    length. Token-exact vs the plain path by construction (greedy), so
    the row reports the same completions plus the MEASURED acceptance
    (``spec_stats``). ``spec_draft_layers`` defaults to half the
    target's layers."""
    import time as _time

    import jax
    import jax.numpy as jnp

    import torchkafka_tpu as tk
    from torchkafka_tpu.models.generate import generate
    from torchkafka_tpu.serve import StreamingGenerator

    prompt_len, max_new = (16, 8) if size == "tiny" else (128, 64)
    n, slots = (24, 8) if size == "tiny" else (512, 32)
    if model_scale == "1b":
        n, slots = 128, 16
    elif model_scale == "8b":
        n, slots = 48, 16
    cfg, params, label = _serving_model(
        size, model_scale, prompt_len, max_new, quantized
    )
    broker = tk.InMemoryBroker()
    broker.create_topic("t7", partitions=2)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (n, prompt_len), dtype=np.int32)
    for i in range(n):
        broker.produce("t7", prompts[i].tobytes(), partition=i % 2)
    if model_scale is None or serve_eos:
        # Probe a few lockstep continuations and use the MODAL generated
        # token as EOS: random-init models repeat attractor tokens, so the
        # mode truncates a meaningful fraction of the stream and visibly
        # exercises slot recycling (decode positions >= 1 only; prefill's
        # token 0 is emitted unconditionally, matching the server's EOS
        # rule).
        probe = np.asarray(
            jax.jit(lambda p, t: generate(p, cfg, t, max_new))(
                params, jnp.asarray(prompts[:8])
            )
        )
        toks, counts = np.unique(probe[:, 1:], return_counts=True)
        eos_id = int(toks[counts.argmax()])
    else:
        eos_id = None

    consumer = tk.MemoryConsumer(broker, "t7", group_id="s7")
    if spec:
        from torchkafka_tpu.serve_spec import SpecStreamingGenerator

        # A speculative round advances a slot by 1..spec_k+1 tokens, so a
        # full-accept generation completes in ceil((max_new-1)/(k+1))
        # rounds; block at that length — low-acceptance streams just take
        # more blocks through the host loop.
        ticks_per_sync = max(1, -(-(max_new - 1) // (spec_k + 1)))
        server = SpecStreamingGenerator(
            consumer, params, cfg, slots=slots, prompt_len=prompt_len,
            max_new=max_new, eos_id=eos_id, commit_every=slots,
            k=spec_k, draft_layers=spec_draft_layers,
            ticks_per_sync=ticks_per_sync,
        )
    else:
        ticks_per_sync = (
            max(1, max_new - 1) if eos_id is None
            else (8 if model_scale is not None else max(1, max_new // 2))
        )
        server = StreamingGenerator(
            consumer, params, cfg, slots=slots, prompt_len=prompt_len,
            max_new=max_new, eos_id=eos_id, commit_every=slots,
            kv_dtype="int8" if kv_int8 else None,
            kv_kernel=kv_kernel,
            # --temperature/--top-k/--top-p: the sampled serving path
            # (models.generate.sample_logits — static-shape top-k/nucleus).
            temperature=temperature, top_k=top_k, top_p=top_p,
            # Dispatch + sync latency are paid once per tick block. With
            # EOS off at scale, ONE dispatch per generation
            # is strictly better (max_new - 1: prefill emits token 0, so a
            # generation completes after max_new - 1 decode ticks — a
            # max_new-tick block would spend its last tick fully
            # done-latched). With EOS on: at scale, 8-tick blocks bound how
            # long a completed slot idles before readmission (the
            # continuous-batching row); tiny sizes keep half-generation
            # blocks.
            ticks_per_sync=ticks_per_sync,
        )
    import sys
    import time as _wt

    _t0 = _wt.perf_counter()
    server.warmup()  # compile outside the timed region, like scenario 5
    if model_scale is not None:
        print(
            f"[scale {model_scale}] serve warmup (admit+tick compile) in "
            f"{_wt.perf_counter() - _t0:.1f}s",
            file=sys.stderr, flush=True,
        )
    toks = 0
    done = 0
    truncated = 0
    t0 = _time.perf_counter()
    for _rec, out in server.run(max_records=n):
        toks += int(out.shape[0])
        done += 1
        truncated += int(out.shape[0] < max_new)
    elapsed = _time.perf_counter() - t0
    consumer.close()
    committed = sum(
        broker.committed("s7", tk.TopicPartition("t7", p)) or 0 for p in (0, 1)
    )
    return {
        "scenario": "7:continuous-serve" + ("+spec" if spec else ""),
        "model_scale": label,
        **({"spec": server.spec_stats()} if spec else {}),
        "records": done,
        "elapsed_s": round(elapsed, 3),
        "records_per_s": round(done / elapsed, 1) if elapsed else None,
        "generated_tokens": toks,
        "tokens_per_s": round(toks / elapsed, 1) if elapsed else None,
        "truncated_by_eos": truncated,
        "readmissions": server.metrics.readmissions.count,
        "eos_mode": "on" if eos_id is not None else "off(one-dispatch)",
        **({"sampling": {
            "temperature": temperature, "top_k": top_k, "top_p": top_p,
        }} if (temperature != 0.0 or top_k is not None or top_p is not None)
            else {}),
        "ticks_per_sync": ticks_per_sync,
        "kv_dtype": "int8" if kv_int8 else "compute",
        "kv_kernel": server._kv_kernel,
        "slots": slots,
        "committed": committed,
        "commit_failures": server.metrics.commit_failures.count,
        "dropped": server.metrics.dropped.count,
        "commit": server.metrics.commit_latency.summary(),
    }


def scenario_10(size: str = "tiny", replicas: int = 2) -> dict:
    """Serving fleet (torchkafka_tpu/fleet): N replicas as one consumer
    group over the prompt topic, QoS admission in front (two tenants —
    one token-bucket rate-limited — and both priority lanes), finished by
    a mid-run graceful drain plus a restarted fleet serving the remainder
    with zero replayed completions. The tier-1 smoke for the fleet's
    admission + drain paths: tiny model, seconds on CPU."""
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.fleet import QoSConfig, ServingFleet

    prompt_len, max_new = (16, 8) if size == "tiny" else (64, 32)
    n = 24 if size == "tiny" else 128
    parts = 4
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    broker = tk.InMemoryBroker()
    broker.create_topic("t10", partitions=parts)
    rng = np.random.default_rng(0)
    # KEYED production (no explicit partition): tenants land on disjoint
    # partitions via the key hash, and the lane rides the tenant. That
    # per-partition homogeneity is what keeps admission FIFO per partition
    # — the invariant the replay-free drain depends on (QoS reordering
    # WITHIN a partition trades drain replay-freedom for priority; see the
    # fleet README section). crc32: 'throttled'→p3, 'open'→p0 of 4.
    produced: list[tuple[int, int]] = []
    for i in range(n):
        key = b"throttled" if i % 3 == 0 else b"open"
        rec = broker.produce(
            "t10",
            rng.integers(0, cfg.vocab_size, prompt_len,
                         dtype=np.int32).tobytes(),
            key=key,
            headers=(
                ("lane", b"batch" if key == b"throttled" else b"interactive"),
            ),
        )
        produced.append((rec.partition, rec.offset))
    qos = QoSConfig(
        # Low enough that the throttled tenant provably queues behind its
        # bucket during the run, high enough that the smoke stays fast.
        tenant_rates={"throttled": 4.0}, burst=1.0,
        max_queue_depth=64, resume_queue_depth=16,
    )

    def build(group_stage_kw):
        return ServingFleet(
            lambda rid: tk.MemoryConsumer(broker, "t10", group_id="s10"),
            params, cfg, replicas=replicas, prompt_len=prompt_len,
            max_new=max_new, slots=4, qos=qos, **group_stage_kw,
        )

    fleet = build({"commit_every": 4})
    fleet.warmup()
    t0 = _time.perf_counter()
    run1: list = []
    for item in fleet.serve(idle_timeout_ms=2000):
        run1.append(item)
        if len(run1) == n // 2:
            fleet.drain()  # graceful: finish in-flight, commit, leave
    drained_states = [rep.state for rep in fleet.replicas]
    fleet2 = build({"commit_every": 4})
    run2 = fleet2.serve_all(idle_timeout_ms=2000)
    fleet2.close()
    elapsed = _time.perf_counter() - t0
    keys1 = {(r.partition, r.offset) for _rid, r, _t in run1}
    keys2 = {(r.partition, r.offset) for _rid, r, _t in run2}
    s = fleet.metrics.summary(fleet.replicas)
    done = len(run1) + len(run2)
    gens = [rep.gen for rep in fleet.replicas + fleet2.replicas]
    return {
        "scenario": "10:serving-fleet",
        "model_scale": label,
        "replicas": replicas,
        "records": done,
        "elapsed_s": round(elapsed, 3),
        "records_per_s": round(done / elapsed, 1) if elapsed else None,
        "drained_states": drained_states,
        "drains": s["drains"],
        "coverage_complete": keys1 | keys2 == set(produced),
        "zero_replayed_after_drain": not (keys1 & keys2),
        "tenants": s["tenants"],
        "lanes": {
            lane: {"p50_ms": round(v["p50_ms"], 3), "count": v["count"]}
            for lane, v in s["lanes"].items()
        },
        "backpressure_pauses": s["backpressure_pauses"],
        "commit": s["commit"],
        "commit_failures": sum(
            g.metrics.commit_failures.count for g in gens
        ),
        "dropped": sum(g.metrics.dropped.count for g in gens),
    }


def scenario_11(size: str = "tiny", replicas: int = 2) -> dict:
    """Chaos-soak smoke (torchkafka_tpu/resilience): a 2-replica serving
    fleet over ``ResilientConsumer(ChaosConsumer(MemoryConsumer))`` hits
    a broker-outage window mid-serve plus one poisoned (corrupted)
    prompt. The circuit must open then close (metrics-observable), every
    non-poisoned prompt must complete exactly once with the committed
    watermark at every log end, and the poisoned prompt must land in the
    DLQ topic with an acknowledged produce — the resilience layer's
    tier-1 guard, seconds on CPU; the full differential lives in
    tests/test_resilience.py."""
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.fleet import ServingFleet
    from torchkafka_tpu.resilience import (
        CLOSED, CircuitBreaker, PoisonQuarantine, ResilientConsumer,
        RetryPolicy,
    )
    from torchkafka_tpu.source.records import TopicPartition

    prompt_len, max_new = (16, 8) if size == "tiny" else (64, 32)
    n, parts = (16, 4) if size == "tiny" else (96, 4)
    poison = ("t11", 2, 1)  # (topic, partition, offset) of the bad prompt
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    broker = tk.InMemoryBroker()
    broker.create_topic("t11", partitions=parts)
    broker.create_topic("t11-dlq", partitions=1)
    rng = np.random.default_rng(0)
    produced = []
    for i in range(n):
        rec = broker.produce(
            "t11",
            rng.integers(0, cfg.vocab_size, prompt_len,
                         dtype=np.int32).tobytes(),
            partition=i % parts,
        )
        produced.append((rec.partition, rec.offset))
    quarantine = PoisonQuarantine(
        tk.MemoryProducer(broker), "t11-dlq", budget=2
    )
    chaos_list, rc_list = [], []

    def factory(rid):
        chaos = tk.ChaosConsumer(
            tk.MemoryConsumer(broker, "t11", group_id="s11"),
            seed=rid,
            outages=[(6, 6)],  # ops 6-11: poll AND commit raise
            corrupt_offsets={poison},
        )
        rc = ResilientConsumer(
            chaos,
            policy=RetryPolicy(
                max_attempts=2, base_delay_s=0.001, max_delay_s=0.002,
                deadline_s=5.0, seed=rid,
            ),
            breaker=CircuitBreaker(failure_threshold=2, reset_timeout_s=0.02),
        )
        chaos_list.append(chaos)
        rc_list.append(rc)
        return rc

    fleet = ServingFleet(
        factory, params, cfg, replicas=replicas, prompt_len=prompt_len,
        max_new=max_new, slots=2, commit_every=4,
        gen_kwargs={"quarantine": quarantine},
    )
    fleet.warmup()
    t0 = _time.perf_counter()
    served: list = []
    served_during_open = 0
    for _rid, rec, _toks in fleet.serve(idle_timeout_ms=2000):
        if any(rc.breaker.state != CLOSED for rc in rc_list):
            served_during_open += 1
        served.append((rec.partition, rec.offset))
    # Settle: cadence commits that failed survivably during the outage
    # stay pending (pending_commit > 0); retry against the healed broker.
    deadline = _time.monotonic() + 10.0
    while any(rep.gen.pending_commit for rep in fleet.replicas):
        for rep in fleet.replicas:
            if rep.gen.pending_commit:
                rep.gen.flush_commits()
        if _time.monotonic() > deadline:
            break
        _time.sleep(0.005)
    fleet.close()
    elapsed = _time.perf_counter() - t0
    expect = {(p, o) for p, o in produced if ("t11", p, o) != poison}
    committed_complete = all(
        broker.committed("s11", TopicPartition("t11", p))
        == broker.end_offset(TopicPartition("t11", p))
        for p in range(parts)
    )
    gens = [rep.gen for rep in fleet.replicas]
    return {
        "scenario": "11:chaos-soak",
        "model_scale": label,
        "replicas": replicas,
        "records": len(served),
        "elapsed_s": round(elapsed, 3),
        "records_per_s": round(len(served) / elapsed, 1) if elapsed else None,
        "exactly_once": set(served) == expect and len(served) == len(expect),
        "duplicates": fleet.metrics.duplicates.count,
        "committed_complete": committed_complete,
        "dlq_records": broker.end_offset(TopicPartition("t11-dlq", 0)),
        "quarantined": sum(g.metrics.quarantined.count for g in gens),
        "served_during_open": served_during_open,
        "outage_faults": sum(c.injected_outage_faults for c in chaos_list),
        "retries": sum(rc.metrics.retries.count for rc in rc_list),
        "circuit_opens": sum(rc.metrics.circuit_opens.count for rc in rc_list),
        "circuit_closes": sum(
            rc.metrics.circuit_closes.count for rc in rc_list
        ),
        "commit_failures": sum(
            g.metrics.commit_failures.count for g in gens
        ),
        "dropped": sum(g.metrics.dropped.count for g in gens),
    }


def scenario_12(size: str = "tiny", replicas: int = 2) -> dict:
    """Prefix-cache serving smoke (torchkafka_tpu/kvcache): a
    DUPLICATE-HEAVY prompt topic — three tenants, each with a fixed
    system prompt prefix, keyed production routing every tenant to one
    partition ('alpha'→p2, 'beta'→p3, 'gamma'→p1 of 4 via crc32, the
    scenario-10 keying idiom) — through a 2-replica fleet whose
    generators run the PAGED pool with radix prefix reuse
    (``kv_pages=``). Per replica, only each tenant's FIRST prompt pays a
    full prefill; every later one links the cached system-prompt blocks
    and prefills the suffix. The tier-1 guard for the cache-on fleet
    path: coverage + commit exactness (token-exactness vs cache-off is
    tests/test_kvcache.py's differential)."""
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.fleet import ServingFleet
    from torchkafka_tpu.source.records import TopicPartition

    prompt_len, max_new = (16, 8) if size == "tiny" else (64, 32)
    n = 24 if size == "tiny" else 128
    block = 4 if size == "tiny" else 16
    sys_len = 3 * block  # tenant system prompt: 3 whole shareable blocks
    parts = 4
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    broker = tk.InMemoryBroker()
    broker.create_topic("t12", partitions=parts)
    rng = np.random.default_rng(0)
    tenants = ("alpha", "beta", "gamma")
    system = {
        t: rng.integers(0, cfg.vocab_size, sys_len, dtype=np.int32)
        for t in tenants
    }
    produced = []
    for i in range(n):
        t = tenants[i % len(tenants)]
        prompt = np.concatenate([
            system[t],
            rng.integers(0, cfg.vocab_size, prompt_len - sys_len,
                         dtype=np.int32),
        ])
        rec = broker.produce("t12", prompt.tobytes(), key=t.encode())
        produced.append((rec.partition, rec.offset))
    slots = 4
    pages = {
        "block_size": block,
        # Per-replica pool: all slots' worst case + sink + cache headroom
        # for the three tenants' system prompts.
        "num_blocks": slots * -(-(prompt_len + max_new) // block) + 16,
    }
    fleet = ServingFleet(
        lambda rid: tk.MemoryConsumer(broker, "t12", group_id="s12"),
        params, cfg, replicas=replicas, prompt_len=prompt_len,
        max_new=max_new, slots=slots, commit_every=4,
        gen_kwargs={"kv_pages": pages},
    )
    fleet.warmup()
    t0 = _time.perf_counter()
    served = fleet.serve_all(idle_timeout_ms=2000)
    elapsed = _time.perf_counter() - t0
    keys = {(r.partition, r.offset) for _rid, r, _t in served}
    committed_complete = all(
        broker.committed("s12", TopicPartition("t12", rec_p))
        == broker.end_offset(TopicPartition("t12", rec_p))
        for rec_p in {p for p, _ in produced}
    )
    s = fleet.metrics.summary(fleet.replicas)
    cache = s["prefix_cache"]
    gens = [rep.gen for rep in fleet.replicas]
    fleet.close()
    return {
        "scenario": "12:prefix-cache-fleet",
        "model_scale": label,
        "replicas": replicas,
        "records": len(served),
        "elapsed_s": round(elapsed, 3),
        "records_per_s": round(len(served) / elapsed, 1) if elapsed else None,
        "coverage_complete": keys == set(produced),
        "committed_complete": committed_complete,
        "tenants": len(tenants),
        "system_prompt_tokens": sys_len,
        "cache": cache,
        "prefill_tokens": cache["prefill_tokens"],
        "prefill_tokens_dense": n * prompt_len,
        "prefill_savings_pct": round(
            100 * (1 - cache["prefill_tokens"] / (n * prompt_len)), 1
        ),
        "commit_failures": sum(
            g.metrics.commit_failures.count for g in gens
        ),
        "dropped": sum(g.metrics.dropped.count for g in gens),
    }


def scenario_13(size: str = "tiny", replicas: int = 2) -> dict:
    """Warm-failover smoke (torchkafka_tpu/journal): a 2-replica fleet
    with per-replica decode journals, a SEEDED mid-generation replica
    kill (ReplicaChaos), and the survivor warm-resuming the victim's
    in-flight prompts from its on-disk journal. Audited against a
    no-kill reference fleet over the same prompts: coverage total,
    commits complete, completions BYTE-IDENTICAL record-for-record
    (duplicates allowed, divergence not), and the journal provably used
    (warm resumes + journal-served > 0). The full cadence/mode
    differential is tests/test_journal.py."""
    import tempfile
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.fleet import ReplicaChaos, ServingFleet
    from torchkafka_tpu.source.records import TopicPartition

    prompt_len, max_new = (8, 16) if size == "tiny" else (32, 32)
    n = 16 if size == "tiny" else 64
    parts = 4
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (n, prompt_len),
                           dtype=np.int32)

    def build(group: str):
        broker = tk.InMemoryBroker()
        broker.create_topic("t13", partitions=parts)
        for i in range(n):
            broker.produce("t13", prompts[i].tobytes(), partition=i % parts)
        return broker

    def serve(broker, group, journal_dir, chaos):
        fleet = ServingFleet(
            lambda rid: tk.MemoryConsumer(broker, "t13", group_id=group),
            params, cfg, replicas=replicas, prompt_len=prompt_len,
            max_new=max_new, slots=2,
            # A large cadence keeps the victim's completions uncommitted,
            # so the kill provably exercises redelivery + warm resume.
            commit_every=100,
            journal_dir=journal_dir, journal_cadence=1,
        )
        fleet.warmup()
        got: dict = {}
        duplicates_identical = True
        for _rid, rec, toks in fleet.serve(idle_timeout_ms=2000,
                                           chaos=chaos):
            key = (rec.partition, rec.offset)
            if key in got and not np.array_equal(got[key], toks):
                duplicates_identical = False
            got[key] = toks
        for rep in fleet.replicas:
            if rep.runnable:
                rep.gen.flush_commits()
        summary = fleet.metrics.summary(fleet.replicas)
        fleet.close()
        return got, summary, duplicates_identical

    with tempfile.TemporaryDirectory() as td:
        ref, _, _ = serve(build("ref13"), "ref13", None, None)
        t0 = _time.perf_counter()
        chaos = ReplicaChaos(seed=5, min_completions=2, max_completions=5)
        broker = build("s13")
        got, s, dup_ok = serve(
            broker, "s13", os.path.join(td, "journals"), chaos
        )
        elapsed = _time.perf_counter() - t0
        committed_complete = all(
            broker.committed("s13", TopicPartition("t13", p))
            == broker.end_offset(TopicPartition("t13", p))
            for p in range(parts)
        )
    identical = set(got) == set(ref) and all(
        np.array_equal(got[k], ref[k]) for k in ref
    )
    jn = s["journal"]
    return {
        "scenario": "13:warm-failover",
        "model_scale": label,
        "replicas": replicas,
        "records": len(got),
        "elapsed_s": round(elapsed, 3),
        "killed": chaos.killed,
        "replica_deaths": s["replica_deaths"],
        "coverage_complete": set(got) == set(ref),
        "committed_complete": committed_complete,
        "identical_to_no_kill": identical,
        "duplicates_identical": dup_ok,
        "journal_handoffs": jn["handoffs"],
        "warm_resumes": jn["warm_resumes"],
        "tokens_restored": jn["tokens_restored"],
        "served_from_journal": jn["served_from_journal"],
        "resume_rejected": jn["resume_rejected"],
    }


def scenario_14(size: str = "tiny", prefill_chunk: int | None = None) -> dict:
    """Chunked-prefill prompt-storm smoke (serve.py kv_pages chunked
    mode): a 4x-oversubscribed admission wave — duplicate-heavy tenant
    prompts, all produced up front — through a paged server whose
    admission is CHUNKED into the decode tick (one static program per
    tick carrying a bounded chunk of queued suffix tokens alongside all
    decode slots). The tier-1 guard for the PR-6 latency property:
    decode inter-token latency must stay EXACTLY one tick per token for
    every in-flight slot while the storm drains FIFO through the chunk
    queue (``max_decode_stall_ticks == 0``), with coverage/commit
    exactness and the chunk counters live. ``prefill_chunk`` defaults
    to one block per tick — small enough that the storm provably queues
    (admission_stall_ticks > 0). The exactness differential across
    chunk widths is tests/test_kvcache.py."""
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.serve import StreamingGenerator
    from torchkafka_tpu.source.records import TopicPartition

    prompt_len, max_new = (16, 8) if size == "tiny" else (64, 32)
    block = 4 if size == "tiny" else 16
    slots = 4
    n = 4 * slots  # the 4x storm
    chunk = prefill_chunk if prefill_chunk else block
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    broker = tk.InMemoryBroker()
    broker.create_topic("t14", partitions=4)
    rng = np.random.default_rng(0)
    sys_len = 2 * block
    system = rng.integers(0, cfg.vocab_size, sys_len, dtype=np.int32)
    for i in range(n):
        prompt = np.concatenate([
            system,
            rng.integers(0, cfg.vocab_size, prompt_len - sys_len,
                         dtype=np.int32),
        ])
        broker.produce("t14", prompt.tobytes(), partition=i % 4)

    activation: dict = {}
    act_order: list = []
    enq_order: list = []

    class Instrumented(StreamingGenerator):
        def admit_records(self, records):
            before = len(self._prefill_queue)
            out = super().admit_records(records)
            enq_order.extend(
                (e.rec.partition, e.rec.offset)
                for e in self._prefill_queue[before:]
            )
            return out

        def _activate_chunk_finishers(self, finishers):
            for e, _row in finishers:
                key = (e.rec.partition, e.rec.offset)
                activation[key] = self._tick_counter
                act_order.append(key)
            super()._activate_chunk_finishers(finishers)

    consumer = tk.MemoryConsumer(broker, "t14", group_id="s14")
    server = Instrumented(
        consumer, params, cfg, slots=slots, prompt_len=prompt_len,
        max_new=max_new, commit_every=4, ticks_per_sync=1,
        kv_pages={
            "block_size": block,
            "num_blocks": slots * -(-(prompt_len + max_new) // block) + 12,
            "prefill_chunk": chunk,
        },
    )
    server.warmup()
    t0 = _time.perf_counter()
    completion: dict = {}
    for rec, toks in server.run(max_records=n):
        completion[(rec.partition, rec.offset)] = (
            server._tick_counter, int(np.asarray(toks).shape[0])
        )
    elapsed = _time.perf_counter() - t0
    committed_complete = all(
        broker.committed("s14", TopicPartition("t14", p))
        == broker.end_offset(TopicPartition("t14", p))
        for p in range(4)
    )
    # Zero decode stall: each record's decode span is exactly its token
    # count minus the activation tick's token 0.
    stalls = [
        done_tick - activation[k] - (n_toks - 1)
        for k, (done_tick, n_toks) in completion.items()
    ]
    m = server.metrics
    cs = m.chunk_summary()
    cache = m.cache_summary()
    consumer.close()
    return {
        "scenario": "14:chunked-prefill-storm",
        "model_scale": label,
        "records": len(completion),
        "elapsed_s": round(elapsed, 3),
        "storm_factor": n // slots,
        "prefill_chunk": chunk,
        "coverage_complete": len(completion) == n,
        "committed_complete": committed_complete,
        "max_decode_stall_ticks": max(stalls) if stalls else None,
        "fifo_activation": act_order == enq_order,
        "chunk_ticks": cs["chunk_ticks"],
        "prefill_tokens_per_tick": cs["prefill_tokens_per_tick"],
        "admission_stall_ticks": cs["stall_ticks"],
        "chunk_utilization": cs["utilization"],
        "queue_tokens_end": cs["queue_tokens"],
        "prefix_hit_rate": cache["hit_rate"],
        "prefill_tokens": cache["prefill_tokens"],
        "prefill_tokens_dense": n * prompt_len,
    }


def scenario_15(size: str = "tiny", replicas: int = 2) -> dict:
    """SLO observability smoke (torchkafka_tpu/obs): a keyed-tenant
    2-replica fleet — three tenants on fixed system prompts (the
    scenario-12 cache shape), both QoS lanes — served with the record
    lifecycle tracer on, then the SLO report production watches: per-
    tenant/per-lane time-to-first-token and inter-token-latency p50/p99,
    admission queue wait, e2e poll→commit, and the prefix-cache hit
    rate, all read back from ``FleetMetrics.summary()``. Plus the
    endpoint smoke: a ``MetricsExporter`` on an ephemeral port scraped
    over real HTTP, every metrics class (fleet + per-replica serve +
    SLO tracer) riding the one /metrics exposition. The tier-1 guard
    for the obs stack; trace determinism lives in tests/test_obs.py."""
    import time as _time
    import urllib.request

    import torchkafka_tpu as tk
    from torchkafka_tpu.fleet import QoSConfig, ServingFleet
    from torchkafka_tpu.obs import MetricsExporter
    from torchkafka_tpu.source.records import TopicPartition

    prompt_len, max_new = (16, 8) if size == "tiny" else (64, 32)
    n = 24 if size == "tiny" else 128
    block = 4 if size == "tiny" else 16
    sys_len = 2 * block
    parts = 4
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    broker = tk.InMemoryBroker()
    broker.create_topic("t15", partitions=parts)
    rng = np.random.default_rng(0)
    tenants = ("alpha", "beta", "gamma")
    system = {
        t: rng.integers(0, cfg.vocab_size, sys_len, dtype=np.int32)
        for t in tenants
    }
    produced = []
    for i in range(n):
        t = tenants[i % len(tenants)]
        prompt = np.concatenate([
            system[t],
            rng.integers(0, cfg.vocab_size, prompt_len - sys_len,
                         dtype=np.int32),
        ])
        rec = broker.produce(
            "t15", prompt.tobytes(), key=t.encode(),
            headers=(
                ("lane", b"interactive" if t == "alpha" else b"batch"),
            ),
        )
        produced.append((rec.partition, rec.offset))
    slots = 4
    pages = {
        "block_size": block,
        "num_blocks": slots * -(-(prompt_len + max_new) // block) + 16,
    }
    fleet = ServingFleet(
        lambda rid: tk.MemoryConsumer(broker, "t15", group_id="s15"),
        params, cfg, replicas=replicas, prompt_len=prompt_len,
        max_new=max_new, slots=slots, qos=QoSConfig(), commit_every=4,
        gen_kwargs={"kv_pages": pages}, obs=True,
    )
    fleet.warmup()
    t0 = _time.perf_counter()
    served = fleet.serve_all(idle_timeout_ms=2000)
    elapsed = _time.perf_counter() - t0
    keys = {(r.partition, r.offset) for _rid, r, _t in served}
    committed_complete = all(
        broker.committed("s15", TopicPartition("t15", p))
        == broker.end_offset(TopicPartition("t15", p))
        for p in {p for p, _ in produced}
    )
    s = fleet.metrics.summary(fleet.replicas)
    slo = s["slo"]

    def pct(leaf):
        return {
            "count": leaf["count"],
            "p50_ms": round(leaf["p50_ms"], 3),
            "p99_ms": round(leaf["p99_ms"], 3),
        }

    report = {
        t: {
            "ttft": pct(slo["ttft"]["by_tenant"].get(
                t, {"count": 0, "p50_ms": 0.0, "p99_ms": 0.0})),
            "itl": pct(slo["itl"]["by_tenant"].get(
                t, {"count": 0, "p50_ms": 0.0, "p99_ms": 0.0})),
        }
        for t in tenants
    }
    # The endpoint smoke: every metrics class through ONE exposition,
    # scraped over real HTTP on an ephemeral port.
    exporter = MetricsExporter()
    exporter.add(lambda: fleet.metrics.render_prometheus(
        replicas=fleet.replicas))
    for rep in fleet.replicas:
        exporter.add(rep.gen.metrics)
    exporter.add(fleet.tracer)
    with exporter:
        with urllib.request.urlopen(exporter.url, timeout=10) as resp:
            endpoint_status = resp.status
            body = resp.read().decode("utf-8")
    fleet.close()
    fleet.tracer.close()
    trace_summary = fleet.tracer.summary()
    return {
        "scenario": "15:slo-observability",
        "model_scale": label,
        "replicas": replicas,
        "records": len(served),
        "elapsed_s": round(elapsed, 3),
        "records_per_s": round(len(served) / elapsed, 1) if elapsed else None,
        "coverage_complete": keys == set(produced),
        "committed_complete": committed_complete,
        "tenant_slo": report,
        "ttft": pct(slo["ttft"]["all"]),
        "itl": pct(slo["itl"]["all"]),
        "queue_wait": pct(slo["queue_wait"]["all"]),
        "e2e": pct(slo["e2e"]["all"]),
        "lanes_observed": sorted(slo["ttft"]["by_lane"]),
        "replicas_observed": sorted(slo["ttft"]["by_replica"]),
        "cache_hit_rate": s["prefix_cache"]["hit_rate"],
        "trace_events": trace_summary["events"],
        "trace_stages": trace_summary["stages"],
        "open_records_end": trace_summary["open_records"],
        "endpoint_status": endpoint_status,
        "endpoint_bytes": len(body),
        "endpoint_series": sum(
            1 for line in body.splitlines()
            if line and not line.startswith("#")
        ),
        "endpoint_has": {
            name: (name in body) for name in (
                "torchkafka_fleet_ttft_ms",
                "torchkafka_fleet_itl_ms",
                "torchkafka_fleet_tenant_admitted_total",
                "torchkafka_serve_tokens_total",
                "torchkafka_slo_trace_events_total",
            )
        },
        "dropped": sum(
            rep.gen.metrics.dropped.count for rep in fleet.replicas
        ),
        "commit_failures": sum(
            rep.gen.metrics.commit_failures.count for rep in fleet.replicas
        ),
    }


def _merge_tenant_cache(metrics_list) -> dict:
    """Per-tenant prefix-cache hit rates merged across replicas
    (count-weighted, like the fleet's global cache view)."""
    merged: dict[str, dict] = {}
    for m in metrics_list:
        for t, v in m.tenant_cache_summary().items():
            agg = merged.setdefault(t, {"hits": 0, "misses": 0})
            agg["hits"] += v["hits"]
            agg["misses"] += v["misses"]
    for agg in merged.values():
        total = agg["hits"] + agg["misses"]
        agg["hit_rate"] = round(agg["hits"] / total, 4) if total else None
    return merged


def scenario_16(size: str = "tiny", replicas: int = 2) -> dict:
    """Traffic-observatory smoke (torchkafka_tpu/workload + obs/burn): a
    seeded Zipf 3-tenant Poisson burst storm — heavy-tailed prompt-
    suffix and output lengths, mixed QoS lanes, keyed partition pinning
    — driven on a ManualClock through a 2-replica traced fleet with the
    paged cache + chunked prefill on, a burn-rate monitor evaluating a
    TTFT SLO per round, and per-record output budgets enforced via the
    ``max_new`` header. Prints the per-tenant goodput / burn-rate report
    production watches; the tier-1 guard asserts non-degenerate
    per-tenant SLOs, trace balance, and zero lost records. The same-seed
    byte-identity differential lives in tests/test_workload.py."""
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.fleet import QoSConfig, ServingFleet
    from torchkafka_tpu.obs import SLOTarget
    from torchkafka_tpu.resilience import ManualClock
    from torchkafka_tpu.source.records import TopicPartition
    from torchkafka_tpu.workload import WorkloadConfig, WorkloadGenerator
    from torchkafka_tpu.workload.generator import header_max_new

    prompt_len, max_new = (16, 8) if size == "tiny" else (64, 32)
    n = 24 if size == "tiny" else 128
    block = 4 if size == "tiny" else 16
    parts = 4
    slots = 2  # small pool: the burst storm provably queues
    tick_dt = 0.002
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    wcfg = WorkloadConfig(
        tenants=3, zipf_s=1.2, total_records=n,
        arrival_rate=1500.0, burst_mean=4.0,  # a storm: well over service
        interactive_fraction=0.4,
        mean_suffix=max(4.0, prompt_len / 3),
        mean_output=max_new * 0.75,
        seed=16,
    )
    gen = WorkloadGenerator(
        wcfg, prompt_len=prompt_len, max_new=max_new,
        vocab_size=cfg.vocab_size,
    )
    mc = ManualClock()
    broker = tk.InMemoryBroker()
    broker.create_topic("t16", partitions=parts)
    pages = {
        "block_size": block,
        "num_blocks": slots * -(-(prompt_len + max_new) // block) + 16,
    }
    targets = [SLOTarget(
        metric="ttft", threshold_s=tick_dt * 12, objective=0.75,
        fast_window_s=tick_dt * 32, slow_window_s=tick_dt * 128,
        min_samples=4,
    )]
    fleet = ServingFleet(
        gen.consumer_factory(broker, "t16", "s16", clock=mc),
        params, cfg, replicas=replicas, prompt_len=prompt_len,
        max_new=max_new, slots=slots, qos=QoSConfig(), commit_every=4,
        clock=mc.now,
        gen_kwargs={"kv_pages": pages, "max_new_of": header_max_new},
        obs=True, slo_targets=targets,
    )
    fleet.warmup()
    t0 = _time.perf_counter()
    drive = gen.drive(fleet, broker, "t16", clock=mc, tick_dt=tick_dt)
    elapsed = _time.perf_counter() - t0
    served_keys = set(drive["served_keys"])
    produced = {
        (p, o) for p in range(parts)
        for o in range(broker.end_offset(TopicPartition("t16", p)))
    }
    committed_complete = all(
        broker.committed("s16", TopicPartition("t16", p))
        == broker.end_offset(TopicPartition("t16", p))
        for p in {p for p, _ in produced}  # keyed: only pinned partitions
    )
    s = fleet.metrics.summary(fleet.replicas)
    slo = s["slo"]
    mon = fleet.monitor.summary()

    def pct(leaf):
        return {
            "count": leaf["count"],
            "p50_ms": round(leaf["p50_ms"], 3),
            "p99_ms": round(leaf["p99_ms"], 3),
        }

    zero = {"count": 0, "p50_ms": 0.0, "p99_ms": 0.0}
    tenant_slo = {
        t: {
            "ttft": pct(slo["ttft"]["by_tenant"].get(t, zero)),
            "itl": pct(slo["itl"]["by_tenant"].get(t, zero)),
        }
        for t in gen.tenant_names
    }
    out_lens = sorted(
        {len(np.asarray(t)) for _rid, _r, t in drive["completions"]}
    )
    trace_summary = fleet.tracer.summary()
    fleet.close()
    fleet.tracer.close()
    return {
        "scenario": "16:traffic-observatory",
        "model_scale": label,
        "replicas": replicas,
        "records": drive["unique_served"],
        "elapsed_s": round(elapsed, 3),
        "records_per_s": (
            round(drive["unique_served"] / elapsed, 1) if elapsed else None
        ),
        "schedule_digest": gen.schedule_digest()[:16],
        "tenant_arrivals": gen.tenant_counts(),
        "all_arrived": drive["all_arrived"],
        "coverage_complete": served_keys == produced,
        "committed_complete": committed_complete,
        "duplicates": drive["duplicates"],
        "synthetic_span_s": round(drive["end_time_s"], 3),
        "tenant_slo": tenant_slo,
        "ttft": pct(slo["ttft"]["all"]),
        "itl": pct(slo["itl"]["all"]),
        "queue_wait": pct(slo["queue_wait"]["all"]),
        "e2e": pct(slo["e2e"]["all"]),
        "lanes_observed": sorted(slo["ttft"]["by_lane"]),
        "goodput": s["goodput"],
        "burn_states": mon["states"],
        "burn_transitions": mon["transitions"],
        "burn_evaluations": mon["evaluations"],
        "overload_deferrals": sum(
            v["deferred"] for v in s["goodput"]["tenants"].values()
        ),
        "output_len_spread": out_lens,
        "output_capped": s["serving"]["output_capped"],
        "step_time": {
            "ticks": s["serving"]["ticks"],
            "p50_ms": round(s["serving"]["step_time"]["p50_ms"], 3),
            "p99_ms": round(s["serving"]["step_time"]["p99_ms"], 3),
        },
        "cache_hit_rate": s["prefix_cache"]["hit_rate"],
        "tenant_cache": _merge_tenant_cache(
            [rep.gen.metrics for rep in fleet.replicas]
        ),
        "trace_events": trace_summary["events"],
        "trace_stages": trace_summary["stages"],
        "open_records_end": trace_summary["open_records"],
        "dropped": sum(
            rep.gen.metrics.dropped.count for rep in fleet.replicas
        ),
        "commit_failures": sum(
            rep.gen.metrics.commit_failures.count for rep in fleet.replicas
        ),
    }


def scenario_17(size: str = "tiny", replicas: int = 2) -> dict:
    """Process-fleet kill storm (torchkafka_tpu/fleet/supervisor): R
    REAL OS-process replicas over the socket broker — each with its own
    BrokerClient, its own jit state, its own on-disk decode journal —
    under heartbeat leases; one replica is SIGKILLed mid-storm while it
    provably holds uncommitted served work. The supervisor fences the
    corpse, the rebalance re-delivers its partitions, and the survivor
    loads the victim's journal FROM DISK across the process boundary to
    resume warm. Audited: zero lost records (committed watermark covers
    every prompt after drain), every completion — duplicates included —
    BYTE-IDENTICAL to an in-process no-kill reference, duplicates within
    the fleet-wide uncommitted-work bound, the victim's journal provably
    handed off, and a post-mortem commit forged from the victim's stale
    generation REJECTED with the watermark unmoved. The full matrix
    (crash points, SIGSTOP zombies, elastic scale) lives in
    tests/test_procfleet.py and tests/test_crash_matrix.py."""
    import tempfile
    import time as _time

    import jax

    import torchkafka_tpu as tk
    from torchkafka_tpu.errors import CommitFailedError
    from torchkafka_tpu.fleet import ProcessFleet
    from torchkafka_tpu.models.transformer import init_params
    from torchkafka_tpu.serve import StreamingGenerator
    from torchkafka_tpu.source.records import TopicPartition

    prompt_len, max_new = (8, 16) if size == "tiny" else (32, 32)
    n = 12 if size == "tiny" else 48
    parts, slots, commit_every = 4, 2, 4
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    model_spec = dict(
        seed=0, vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
        max_seq_len=cfg.max_seq_len,
    )
    rng = np.random.default_rng(17)
    prompts = rng.integers(0, cfg.vocab_size, (n, prompt_len),
                           dtype=np.int32)

    # In-process no-kill reference: greedy decode is a pure function of
    # (params, prompt), so one local server defines byte-truth for every
    # process in the fleet.
    rb = tk.InMemoryBroker()
    rb.create_topic("t17", partitions=parts)
    for i in range(n):
        rb.produce("t17", prompts[i].tobytes(), partition=i % parts,
                   key=str(i).encode())
    rc = tk.MemoryConsumer(rb, "t17", group_id="ref17")
    ref_gen = StreamingGenerator(
        rc, params, cfg, slots=slots, prompt_len=prompt_len,
        max_new=max_new, commit_every=commit_every, ticks_per_sync=1,
    )
    ref = {rec.key: toks for rec, toks in ref_gen.run(idle_timeout_ms=400)}
    rc.close()

    t0 = _time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        fleet = ProcessFleet(
            model_spec, topic="t17", prompt_len=prompt_len,
            max_new=max_new, workdir=td, replicas=replicas,
            partitions=parts, slots=slots, commit_every=commit_every,
            session_timeout_s=3.0, heartbeat_interval_s=0.2,
            journal_cadence=1, respawn=False, group="s17",
        )
        try:
            fleet.start()
            fleet.wait_ready(timeout_s=300)
            ready_s = _time.perf_counter() - t0
            # Produce AFTER every member joined: the storm hits a settled
            # 2-way partition split, not whichever process won the warmup
            # race.
            for i in range(n):
                fleet.broker.produce(
                    "t17", prompts[i].tobytes(), partition=i % parts,
                    key=str(i).encode(),
                )

            def key_offset(key: bytes) -> tuple[int, int]:
                i = int(key.decode())
                return i % parts, i // parts

            def uncommitted_output_of(member: str) -> bool:
                wm = {
                    p: fleet.broker.committed(
                        "s17", TopicPartition("t17", p)
                    ) or 0
                    for p in range(parts)
                }
                for key, copies in fleet.results().items():
                    p, off = key_offset(key)
                    if off >= wm[p] and any(m == member for m, _ in copies):
                        return True
                return False

            # SIGKILL a replica the moment it provably holds SERVED,
            # UNCOMMITTED work (an output past the watermark): the death
            # then must exercise redelivery AND the journal handoff.
            victim = None
            deadline = _time.monotonic() + 240
            while victim is None:
                if _time.monotonic() > deadline:
                    raise TimeoutError(
                        "no kill opportunity arose\n" + fleet.diagnose()
                    )
                done = len(fleet.results()) >= n
                for inc in fleet.live():
                    if done:
                        break
                    if uncommitted_output_of(inc.member):
                        victim = fleet.kill_replica(inc.idx)
                        break
                if done and victim is None:
                    raise RuntimeError(
                        "storm finished before any replica held "
                        "uncommitted served work — shrink commit_every"
                    )
                _time.sleep(0.01)

            # Survivors absorb (instant supervisor fencing on the reaped
            # corpse; the lease is the fallback), then drain commits all.
            fleet.wait(
                lambda f: set(f.results())
                == {str(i).encode() for i in range(n)},
                timeout_s=240,
            )
            fleet.drain()
            fleet.wait(
                lambda f: all(not i.running for i in f.incarnations),
                timeout_s=120,
            )
            fleet.poll_once()
            zero_lost = fleet.fully_committed()

            res = fleet.results()
            duplicates = sum(len(v) - 1 for v in res.values())
            # Every member's uncommitted work re-delivers at the eager
            # rebalance (the victim's AND the survivors'), so the bound
            # is fleet-wide.
            dup_bound = replicas * (commit_every + slots)
            identical = set(res) == set(ref) and all(
                np.array_equal(toks, ref[k])
                for k, copies in res.items() for _m, toks in copies
            )

            # The zombie-fencing acceptance: a post-mortem commit from
            # the killed member's stale generation bounces, watermark
            # unmoved.
            wm_before = {
                p: fleet.broker.committed("s17", TopicPartition("t17", p))
                for p in range(parts)
            }
            try:
                fleet.broker.commit(
                    "s17", {TopicPartition("t17", 0): 1},
                    member_id=victim["member"],
                    generation=victim["generation"],
                )
                zombie_rejected = False
            except CommitFailedError:
                zombie_rejected = True
            wm_after = {
                p: fleet.broker.committed("s17", TopicPartition("t17", p))
                for p in range(parts)
            }
            vic_inc = [
                i for i in fleet.incarnations
                if i.member == victim["member"]
            ][0]
            worker_m = fleet.worker_metrics()
            warm_used = sum(
                m["warm_resumes"] + m["served_from_journal"]
                for m in worker_m
            )
            membership = fleet.broker.membership("s17")
            elapsed = _time.perf_counter() - t0
        finally:
            fleet.close()
    return {
        "scenario": "17:process-fleet-kill-storm",
        "model_scale": label,
        "replicas": replicas,
        "records": n,
        "ready_s": round(ready_s, 2),
        "elapsed_s": round(elapsed, 2),
        "victim": victim["member"],
        "victim_sigkilled": vic_inc.exit_code == -9,
        "fence_reason": vic_inc.fence_reason,
        "fence_count": membership["fence_count"],
        "zero_lost": zero_lost,
        "identical_to_no_kill": identical,
        "duplicates": duplicates,
        "duplicate_bound": dup_bound,
        "duplicates_within_bound": duplicates <= dup_bound,
        "journal_handoff_entries": vic_inc.handoff_entries,
        "warm_resumes_plus_journal_served": warm_used,
        "zombie_commit_rejected": zombie_rejected,
        "watermark_unmoved_by_zombie": wm_before == wm_after,
        "exit_codes": {
            i.member: (None if i.proc is None else i.proc.returncode)
            for i in fleet.incarnations
        },
    }


def scenario_18(size: str = "tiny", replicas: int = 2) -> dict:
    """Exactly-once under SIGKILL: the scenario-17 kill storm upgraded
    to transactional output (``ProcessFleet(exactly_once=True)``). Each
    replica process serves through a ``TransactionalProducer`` whose
    transactional id is keyed by replica INDEX — one transaction per
    commit window covering that window's completions AND offsets. One
    replica is SIGKILLed while it provably holds outputs in an OPEN
    (uncommitted) transaction; the supervisor fences it, bumping the
    producer epoch, which ABORTS the in-flight transaction — so a
    ``read_committed`` consumer of the output topic observes ZERO
    duplicates and zero losses (asserted equal, not bounded), every
    committed completion byte-identical to the no-kill reference. A
    commit forged from the victim's stale epoch raises
    ``ProducerFencedError`` with the watermark and committed view
    untouched. The at-least-once duplicates are still VISIBLE in the
    read_uncommitted view (the aborted copies hold their offsets) —
    exactly Kafka's shape, reported for contrast."""
    import tempfile
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.errors import ProducerFencedError
    from torchkafka_tpu.fleet import ProcessFleet
    from torchkafka_tpu.serve import StreamingGenerator
    from torchkafka_tpu.source.records import TopicPartition

    prompt_len, max_new = (8, 16) if size == "tiny" else (32, 32)
    n = 10 if size == "tiny" else 48
    parts, slots, commit_every = 2, 2, 4
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    model_spec = dict(
        seed=0, vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
        max_seq_len=cfg.max_seq_len,
    )
    rng = np.random.default_rng(18)
    prompts = rng.integers(0, cfg.vocab_size, (n, prompt_len),
                           dtype=np.int32)

    # In-process no-kill reference (greedy decode is a pure function of
    # (params, prompt)).
    rb = tk.InMemoryBroker()
    rb.create_topic("t18", partitions=parts)
    for i in range(n):
        rb.produce("t18", prompts[i].tobytes(), partition=i % parts,
                   key=str(i).encode())
    rc = tk.MemoryConsumer(rb, "t18", group_id="ref18")
    ref_gen = StreamingGenerator(
        rc, params, cfg, slots=slots, prompt_len=prompt_len,
        max_new=max_new, commit_every=commit_every, ticks_per_sync=1,
    )
    ref = {rec.key: toks for rec, toks in ref_gen.run(idle_timeout_ms=400)}
    rc.close()

    all_keys = {str(i).encode() for i in range(n)}
    t0 = _time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        fleet = ProcessFleet(
            model_spec, topic="t18", prompt_len=prompt_len,
            max_new=max_new, workdir=td, replicas=replicas,
            partitions=parts, slots=slots, commit_every=commit_every,
            session_timeout_s=3.0, heartbeat_interval_s=0.2,
            journal_cadence=1, respawn=False, group="s18",
            exactly_once=True,
        )
        try:
            fleet.start()
            fleet.wait_ready(timeout_s=300)
            ready_s = _time.perf_counter() - t0
            for i in range(n):
                fleet.broker.produce(
                    "t18", prompts[i].tobytes(), partition=i % parts,
                    key=str(i).encode(),
                )

            from torchkafka_tpu.journal import DecodeJournal

            def uncommitted_served_work(inc) -> bool:
                """True when the incarnation's on-disk journal holds a
                FINISHED completion whose offset the committed watermark
                has not passed: served work whose output has NOT reached
                a committed transaction (in exactly-once mode staged
                outputs are invisible until their transaction commits,
                so the journal — pruned at every commit — is the
                outside-observable evidence). Killing here forces the
                abort + journal-handoff + re-serve-exactly-once path."""
                try:
                    entries = DecodeJournal.load(inc.journal_path)
                except Exception:
                    return False
                for (topic, p, off), e in entries.items():
                    if not e.finished or topic != "t18":
                        continue
                    wm = fleet.broker.committed(
                        "s18", TopicPartition("t18", p)
                    ) or 0
                    if off >= wm:
                        return True
                return False

            victim = None
            deadline = _time.monotonic() + 240
            while victim is None:
                if _time.monotonic() > deadline:
                    raise TimeoutError(
                        "no kill opportunity arose\n" + fleet.diagnose()
                    )
                done = len(fleet.results("read_committed")) >= n
                for inc in fleet.live():
                    if done:
                        break
                    if uncommitted_served_work(inc):
                        victim = fleet.kill_replica(inc.idx)
                        break
                if done and victim is None:
                    raise RuntimeError(
                        "storm finished before any replica held "
                        "uncommitted served work — shrink commit_every"
                    )
                _time.sleep(0.01)

            def covered(f) -> bool:
                """Every prompt either already in the committed view or
                FINISHED in a live member's journal (staged in its
                outbox — the drain flush will commit it). Unlike
                scenario 17's raw-coverage wait, nothing of the victim's
                aborted work counts: only work that can still reach the
                committed view."""
                committed = set(f.results("read_committed"))
                if committed >= all_keys:
                    return True
                pending = set()
                for inc in f.live():
                    try:
                        entries = DecodeJournal.load(inc.journal_path)
                    except Exception:
                        continue
                    for (topic, p, off), e in entries.items():
                        if e.finished and topic == "t18":
                            pending.add(str(off * parts + p).encode())
                return committed | pending >= all_keys

            fleet.wait(covered, timeout_s=240)
            fleet.drain()
            fleet.wait(
                lambda f: all(not i.running for i in f.incarnations),
                timeout_s=120,
            )
            fleet.poll_once()
            zero_lost = fleet.fully_committed()

            committed_res = fleet.results("read_committed")
            uncommitted_res = fleet.results()
            # THE exactly-once assertion: the committed view holds each
            # completion EXACTLY once — zero duplicates, not a bound.
            committed_dups = sum(
                len(v) - 1 for v in committed_res.values()
            )
            raw_dups = sum(len(v) - 1 for v in uncommitted_res.values())
            aborted_copies = (
                sum(len(v) for v in uncommitted_res.values())
                - sum(len(v) for v in committed_res.values())
            )
            identical = set(committed_res) == set(ref) and all(
                np.array_equal(toks, ref[k])
                for k, copies in committed_res.items()
                for _m, toks in copies
            )

            # The epoch-fencing acceptance: a commit forged from the
            # victim's stale epoch bounces, watermark + committed view
            # untouched. (The supervisor's fence already bumped the
            # victim's transactional id to a newer epoch.)
            txn_id = f"s18-r{victim['idx']:03d}"
            pid, cur_epoch = fleet.broker.init_producer_id(txn_id)
            wm_before = {
                p: fleet.broker.committed("s18", TopicPartition("t18", p))
                for p in range(parts)
            }
            try:
                fleet.broker.commit_txn(pid, cur_epoch - 1)
                zombie_rejected = False
            except ProducerFencedError:
                zombie_rejected = True
            wm_after = {
                p: fleet.broker.committed("s18", TopicPartition("t18", p))
                for p in range(parts)
            }
            committed_after_forgery = fleet.results("read_committed")
            vic_inc = [
                i for i in fleet.incarnations
                if i.member == victim["member"]
            ][0]
            worker_m = fleet.worker_metrics()
            warm_used = sum(
                m["warm_resumes"] + m["served_from_journal"]
                for m in worker_m
            )
            membership = fleet.broker.membership("s18")
            elapsed = _time.perf_counter() - t0
        finally:
            fleet.close()
    return {
        "scenario": "18:exactly-once-kill-storm",
        "model_scale": label,
        "replicas": replicas,
        "records": n,
        "ready_s": round(ready_s, 2),
        "elapsed_s": round(elapsed, 2),
        "victim": victim["member"],
        "victim_sigkilled": vic_inc.exit_code == -9,
        "fence_count": membership["fence_count"],
        "zero_lost": zero_lost,
        "identical_to_no_kill": identical,
        "committed_duplicates": committed_dups,
        "read_uncommitted_duplicates": raw_dups,
        "aborted_copies_in_log": aborted_copies,
        "journal_handoff_entries": vic_inc.handoff_entries,
        "warm_resumes_plus_journal_served": warm_used,
        "zombie_txn_commit_rejected": zombie_rejected,
        "watermark_unmoved_by_zombie": wm_before == wm_after,
        "committed_view_unmoved_by_zombie": (
            {k: len(v) for k, v in committed_after_forgery.items()}
            == {k: len(v) for k, v in committed_res.items()}
        ),
        "exit_codes": {
            i.member: (None if i.proc is None else i.proc.returncode)
            for i in fleet.incarnations
        },
    }


def scenario_19(size: str = "tiny", replicas: int = 2) -> dict:
    """Broker death mid-storm: the last unfenced process joins the fault
    model. A 2-process ``exactly_once`` fleet serves over a DURABLE
    broker (``ProcessFleet(wal_dir=...)`` — every produce/commit/
    membership/transaction event write-ahead logged, source/wal.py);
    once a worker's journal proves served-but-uncommitted work exists,
    the broker is killed UNCLEANLY (``restart_broker(crash=True)``: the
    listener and every connection drop mid-RPC, the in-memory state is
    abandoned un-flushed) and held down long enough that the workers'
    circuit breakers OPEN. The supervisor then recovers a fresh broker
    from the WAL on the SAME port: records, offsets, generations,
    producer epochs, and memberships (fresh leases) come back; open
    transactions abort. Workers ride the outage on the reconnect stack
    (RetryPolicy → BrokerUnavailableError → CircuitBreaker) and resume
    — no fencing, no respawn. Audited: zero lost records, committed-view
    duplicates EXACTLY zero, every committed completion byte-identical
    to a no-restart reference, and every worker's breaker opened during
    the outage then closed after recovery (the open-then-close
    transition counters in the worker metrics dumps)."""
    import tempfile
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.fleet import ProcessFleet
    from torchkafka_tpu.journal import DecodeJournal
    from torchkafka_tpu.serve import StreamingGenerator
    from torchkafka_tpu.source.records import TopicPartition

    prompt_len, max_new = (8, 16) if size == "tiny" else (32, 32)
    n = 12 if size == "tiny" else 48
    parts, slots, commit_every = 4, 2, 4
    down_s = 2.5
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    model_spec = dict(
        seed=0, vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
        max_seq_len=cfg.max_seq_len,
    )
    rng = np.random.default_rng(19)
    prompts = rng.integers(0, cfg.vocab_size, (n, prompt_len),
                           dtype=np.int32)
    all_keys = {str(i).encode() for i in range(n)}

    # In-process no-restart reference (greedy decode is a pure function
    # of (params, prompt)).
    rb = tk.InMemoryBroker()
    rb.create_topic("t19", partitions=parts)
    for i in range(n):
        rb.produce("t19", prompts[i].tobytes(), partition=i % parts,
                   key=str(i).encode())
    rc = tk.MemoryConsumer(rb, "t19", group_id="ref19")
    ref_gen = StreamingGenerator(
        rc, params, cfg, slots=slots, prompt_len=prompt_len,
        max_new=max_new, commit_every=commit_every, ticks_per_sync=1,
    )
    ref = {rec.key: toks for rec, toks in ref_gen.run(idle_timeout_ms=400)}
    rc.close()

    t0 = _time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        import os as _os

        fleet = ProcessFleet(
            model_spec, topic="t19", prompt_len=prompt_len,
            max_new=max_new, workdir=td, replicas=replicas,
            partitions=parts, slots=slots, commit_every=commit_every,
            session_timeout_s=8.0, heartbeat_interval_s=0.2,
            journal_cadence=1, respawn=False, group="s19",
            exactly_once=True,
            wal_dir=_os.path.join(td, "wal"), wal_durability="batch",
            # Short client retries so the outage is FELT (and ridden)
            # by the resilience stack instead of silently absorbed
            # inside the transport: the breakers must provably open.
            resilient=True, reconnect_attempts=2,
            reconnect_deadline_s=0.4,
        )
        try:
            fleet.start()
            fleet.wait_ready(timeout_s=300)
            ready_s = _time.perf_counter() - t0
            for i in range(n):
                fleet.broker.produce(
                    "t19", prompts[i].tobytes(), partition=i % parts,
                    key=str(i).encode(),
                )

            def uncommitted_served_work(inc) -> bool:
                """Scenario 18's kill criterion, re-aimed at the broker:
                a FINISHED journal entry past the committed watermark
                proves in-flight transactional work exists for the crash
                to strand."""
                try:
                    entries = DecodeJournal.load(inc.journal_path)
                except Exception:  # noqa: BLE001 - mid-write race
                    return False
                for (topic, p, off), e in entries.items():
                    if not e.finished or topic != "t19":
                        continue
                    wm = fleet.broker.committed(
                        "s19", TopicPartition("t19", p)
                    ) or 0
                    if off >= wm:
                        return True
                return False

            deadline = _time.monotonic() + 240
            while not any(
                uncommitted_served_work(i) for i in fleet.live()
            ):
                if _time.monotonic() > deadline:
                    raise TimeoutError(
                        "no crash opportunity arose\n" + fleet.diagnose()
                    )
                if len(fleet.results("read_committed")) >= n:
                    raise RuntimeError(
                        "storm finished before any worker held "
                        "uncommitted served work — shrink commit_every"
                    )
                _time.sleep(0.01)

            recovery = fleet.restart_broker(crash=True, down_s=down_s)

            def covered(f) -> bool:
                committed = set(f.results("read_committed"))
                if committed >= all_keys:
                    return True
                pending = set()
                for inc in f.live():
                    try:
                        entries = DecodeJournal.load(inc.journal_path)
                    except Exception:  # noqa: BLE001 - mid-write race
                        continue
                    for (topic, p, off), e in entries.items():
                        if e.finished and topic == "t19":
                            pending.add(str(off * parts + p).encode())
                return committed | pending >= all_keys

            fleet.wait(covered, timeout_s=240)
            fleet.drain()
            fleet.wait(
                lambda f: all(not i.running for i in f.incarnations),
                timeout_s=120,
            )
            fleet.poll_once()
            zero_lost = fleet.fully_committed()

            committed_res = fleet.results("read_committed")
            committed_dups = sum(
                len(v) - 1 for v in committed_res.values()
            )
            identical = set(committed_res) == set(ref) and all(
                np.array_equal(toks, ref[k])
                for k, copies in committed_res.items()
                for _m, toks in copies
            )
            worker_m = fleet.worker_metrics()
            elapsed = _time.perf_counter() - t0
        finally:
            fleet.close()
    return {
        "scenario": "19:broker-crash-recovery-storm",
        "model_scale": label,
        "replicas": replicas,
        "records": n,
        "ready_s": round(ready_s, 2),
        "elapsed_s": round(elapsed, 2),
        "broker_down_s": down_s,
        "broker_restarts": fleet.metrics.broker_restarts.count,
        "recovery": recovery,
        "zero_lost": zero_lost,
        "identical_to_no_restart": identical,
        "committed_duplicates": committed_dups,
        "workers_survived_unfenced": all(
            m["exit"] == 0 for m in worker_m
        ) and len(worker_m) == replicas,
        "breaker_opens": {
            m["member"]: m["circuit_opens"] for m in worker_m
        },
        "breaker_closes": {
            m["member"]: m["circuit_closes"] for m in worker_m
        },
        "heartbeat_outages": sum(
            m["heartbeat_outages"] for m in worker_m
        ),
        "exit_codes": {
            i.member: (None if i.proc is None else i.proc.returncode)
            for i in fleet.incarnations
        },
    }


def scenario_20(size: str = "tiny", replicas: int = 2) -> dict:
    """Sharded paged serving smoke (PR 13, ROADMAP item 1): a 2-replica
    in-process fleet whose generators compose the four KV-backend axes
    at once — PAGED block tables + radix prefix reuse, INT8 payloads,
    the Pallas read under its ``auto`` probe, and a {data, tp}
    host-device MESH (kv heads + weights over tp; the paged per-slot
    state rides replicated, serve.py ``pin_paged``). Three keyed
    tenants with fixed system prompts (the scenario-12 shape) so the
    radix tree does real work while sharded. The tier-1 guard for the
    composed path: coverage + commit exactness and a non-degenerate
    cache hit rate (token-exactness vs single-device serving is
    tests/test_kvcache.py's sharded differential)."""
    import time as _time

    import jax

    import torchkafka_tpu as tk
    from torchkafka_tpu.fleet import ServingFleet
    from torchkafka_tpu.source.records import TopicPartition

    prompt_len, max_new = (16, 8) if size == "tiny" else (64, 32)
    n = 24 if size == "tiny" else 128
    block = 4 if size == "tiny" else 16
    sys_len = 3 * block
    parts = 4
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    n_dev = len(jax.devices())
    tp = 2 if n_dev >= 2 and cfg.n_kv_heads % 2 == 0 else 1
    data = 2 if n_dev >= 2 * tp else 1
    mesh = tk.make_mesh(
        {"data": data, "tp": tp}, devices=jax.devices()[: data * tp]
    )
    broker = tk.InMemoryBroker()
    broker.create_topic("t20", partitions=parts)
    rng = np.random.default_rng(0)
    tenants = ("alpha", "beta", "gamma")
    system = {
        t: rng.integers(0, cfg.vocab_size, sys_len, dtype=np.int32)
        for t in tenants
    }
    produced = []
    for i in range(n):
        t = tenants[i % len(tenants)]
        prompt = np.concatenate([
            system[t],
            rng.integers(0, cfg.vocab_size, prompt_len - sys_len,
                         dtype=np.int32),
        ])
        rec = broker.produce("t20", prompt.tobytes(), key=t.encode())
        produced.append((rec.partition, rec.offset))
    # 2 slots/replica: the auto chunk width follows slots × prompt_len,
    # and the fused program's compile time follows the chunk width —
    # the tier-1 smoke budget lever (coverage is unchanged; admissions
    # just wave through in more quanta).
    slots = 2 if size == "tiny" else 4
    pages = {
        "block_size": block,
        "num_blocks": slots * -(-(prompt_len + max_new) // block) + 16,
    }
    fleet = ServingFleet(
        lambda rid: tk.MemoryConsumer(broker, "t20", group_id="s20"),
        params, cfg, replicas=replicas, prompt_len=prompt_len,
        max_new=max_new, slots=slots, commit_every=4,
        gen_kwargs={
            "kv_pages": pages, "kv_dtype": "int8", "kv_kernel": "auto",
            "mesh": mesh,
        },
    )
    fleet.warmup()
    t0 = _time.perf_counter()
    served = fleet.serve_all(idle_timeout_ms=2000)
    elapsed = _time.perf_counter() - t0
    keys = {(r.partition, r.offset) for _rid, r, _t in served}
    committed_complete = all(
        broker.committed("s20", TopicPartition("t20", rec_p))
        == broker.end_offset(TopicPartition("t20", rec_p))
        for rec_p in {p for p, _ in produced}
    )
    s = fleet.metrics.summary(fleet.replicas)
    cache = s["prefix_cache"]
    gens = [rep.gen for rep in fleet.replicas]
    kv_backend = gens[0].metrics.summary()["kv_backend"]
    fleet.close()
    return {
        "scenario": "20:sharded-paged-int8-fleet",
        "model_scale": label,
        "replicas": replicas,
        "mesh": {"data": data, "tp": tp},
        "kv_backend": kv_backend,
        "records": len(served),
        "elapsed_s": round(elapsed, 3),
        "records_per_s": round(len(served) / elapsed, 1) if elapsed else None,
        "coverage_complete": keys == set(produced),
        "committed_complete": committed_complete,
        "tenants": len(tenants),
        "system_prompt_tokens": sys_len,
        "cache": cache,
        "prefill_tokens": cache["prefill_tokens"],
        "prefill_tokens_dense": n * prompt_len,
        "prefill_savings_pct": round(
            100 * (1 - cache["prefill_tokens"] / (n * prompt_len)), 1
        ),
        "commit_failures": sum(
            g.metrics.commit_failures.count for g in gens
        ),
        "dropped": sum(g.metrics.dropped.count for g in gens),
    }


def scenario_21(size: str = "tiny", replicas: int = 2) -> dict:
    """Disaggregated serving under prefill-worker death (fleet/prefill):
    1 PREFILL worker + R decode replicas as REAL OS processes over the
    socket broker — the prefill worker consumes the prompt topic in its
    own group, fills paged KV, and publishes handoffs; decode replicas
    route admission through the handoff shelf and ADOPT (no prompt pass
    on the decode path). Mid-storm the prefill worker is SIGKILLed:
    unpublished handoffs vanish, the decode replicas' routing patience
    expires and they fall back to local prefills — the optimization
    degrades, correctness does not. Audited: zero lost records, every
    completion byte-identical to an in-process monolithic paged
    reference, adoptions provably happened before the kill, decode tick
    time never stalled (p99 reported from worker metric dumps), and the
    prefill group's offsets never covered an unpublished handoff."""
    import tempfile
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.fleet import ProcessFleet
    from torchkafka_tpu.serve import StreamingGenerator
    from torchkafka_tpu.source.records import TopicPartition

    prompt_len, max_new = (8, 16) if size == "tiny" else (32, 32)
    n = 24 if size == "tiny" else 64  # 4x oversubscription of 2x2 slots
    parts, slots, commit_every = 4, 2, 4
    pages = {"block_size": 4, "num_blocks": 64}
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    model_spec = dict(
        seed=0, vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
        max_seq_len=cfg.max_seq_len,
    )
    rng = np.random.default_rng(21)
    prompts = rng.integers(0, cfg.vocab_size, (n, prompt_len),
                           dtype=np.int32)
    prompts[:, :4] = np.arange(4)  # shared system prefix (radix shape)

    # In-process monolithic paged reference: byte-truth for the fleet.
    rb = tk.InMemoryBroker()
    rb.create_topic("t21", partitions=parts)
    for i in range(n):
        rb.produce("t21", prompts[i].tobytes(), partition=i % parts,
                   key=str(i).encode())
    rc = tk.MemoryConsumer(rb, "t21", group_id="ref21")
    ref_gen = StreamingGenerator(
        rc, params, cfg, slots=slots, prompt_len=prompt_len,
        max_new=max_new, commit_every=commit_every, ticks_per_sync=1,
        kv_pages=dict(pages),
    )
    ref = {rec.key: toks for rec, toks in ref_gen.run(idle_timeout_ms=400)}
    rc.close()

    t0 = _time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        fleet = ProcessFleet(
            model_spec, topic="t21", prompt_len=prompt_len,
            max_new=max_new, workdir=td, replicas=replicas,
            partitions=parts, slots=slots, commit_every=commit_every,
            session_timeout_s=5.0, heartbeat_interval_s=0.2,
            journal_cadence=2, respawn=False, group="s21",
            kv_pages=pages, prefill_replicas=1, route_patience=1500,
        )
        try:
            fleet.start()
            fleet.wait_ready(timeout_s=300)
            ready_s = _time.perf_counter() - t0
            for i in range(n):
                fleet.broker.produce(
                    "t21", prompts[i].tobytes(), partition=i % parts,
                    key=str(i).encode(),
                )
            ho_tp = TopicPartition(fleet.handoff_topic, 0)

            # SIGKILL the prefill worker MID-storm: after some handoffs
            # are provably on the transfer plane, before all are.
            deadline = _time.monotonic() + 240
            while True:
                published = fleet.broker.end_offset(ho_tp)
                if published >= 6:
                    break
                if _time.monotonic() > deadline:
                    raise TimeoutError(
                        "no handoffs ever published\n" + fleet.diagnose()
                    )
                _time.sleep(0.005)
            victim = fleet.kill_prefill(0)
            published_at_kill = fleet.broker.end_offset(ho_tp)

            fleet.wait(
                lambda f: set(f.results())
                == {str(i).encode() for i in range(n)},
                timeout_s=240,
            )
            fleet.drain()
            fleet.wait(
                lambda f: all(not i.running for i in f.incarnations),
                timeout_s=120,
            )
            fleet.poll_once()
            zero_lost = fleet.fully_committed()

            res = fleet.results()
            duplicates = sum(len(v) - 1 for v in res.values())
            identical = set(res) == set(ref) and all(
                np.array_equal(toks, ref[k])
                for k, copies in res.items() for _m, toks in copies
            )
            # The prefill group never committed past its published
            # handoffs (the mid-transfer at-least-once contract).
            published_keys = {
                r.key for r in fleet.broker.fetch(ho_tp, 0, 100000)
            }
            prefill_wm_ok = True
            for p in range(parts):
                tp = TopicPartition("t21", p)
                wm = fleet.broker.committed("s21-prefill", tp) or 0
                for off in range(wm):
                    if str(off * parts + p).encode() not in published_keys:
                        prefill_wm_ok = False
            decode_m = [
                m for m in fleet.worker_metrics()
                if m.get("role") != "prefill"
            ]
            adopted = sum(m.get("adopted_slots", 0) for m in decode_m)
            routed = sum(m.get("prefill_routed", 0) for m in decode_m)
            fallback_tokens = sum(
                m.get("prefill_tokens", 0) for m in decode_m
            )
            step_p99 = max(
                (m.get("step_p99_ms") or 0.0) for m in decode_m
            ) if decode_m else None
            elapsed = _time.perf_counter() - t0
        finally:
            fleet.close()
    return {
        "scenario": "21:disaggregated-prefill-kill-storm",
        "model_scale": label,
        "decode_replicas": replicas,
        "prefill_workers": 1,
        "records": n,
        "ready_s": round(ready_s, 2),
        "elapsed_s": round(elapsed, 2),
        "victim": victim["member"],
        "handoffs_published_at_kill": int(published_at_kill),
        "zero_lost": zero_lost,
        "identical_to_monolithic": identical,
        "duplicates": duplicates,
        "adopted_slots": adopted,
        "prefill_routed": routed,
        "decode_fallback_prefill_tokens": fallback_tokens,
        "decode_step_p99_ms": step_p99,
        "prefill_watermark_never_past_published": prefill_wm_ok,
    }


def scenario_22(size: str = "tiny", replicas: int = 1) -> dict:
    """Closed-loop autoscaling under a step-load storm (fleet/autoscale,
    ROADMAP item 2): a ManualClock in-process fleet starts at
    ``replicas`` decode members with the burn-rate + queue-depth
    controller ON; the workload steps to 6× offered load mid-run and
    back. Asserted shape: the controller scales UP under the step
    (hysteresis bounding the decision count under Poisson burst noise),
    the SLO RECOVERS under the added capacity (burn state back to ok,
    with the recovery instant on record), capacity is handed back warm
    AFTER the step ends (scale-down decisions strictly later than
    t_off; drains commit — zero lost), and the WHOLE control loop —
    arrivals, burn transitions, controller decisions, scale events,
    completions, ledger — replays byte-identically at the same seed
    (the scenario runs twice and compares)."""
    import torchkafka_tpu as tk
    from torchkafka_tpu.fleet import (
        AutoscaleController, FleetAutoscaler, QoSConfig, RolePolicy,
        ServingFleet,
    )
    from torchkafka_tpu.obs import SLOTarget
    from torchkafka_tpu.resilience import ManualClock
    from torchkafka_tpu.source.records import TopicPartition
    from torchkafka_tpu.workload import (
        WorkloadConfig, WorkloadGenerator, header_max_new, step_load,
    )

    prompt_len, max_new = (16, 8) if size == "tiny" else (64, 32)
    n = 32 if size == "tiny" else 96
    parts, slots, commit_every = 4, 2, 4
    tick_dt = 0.002
    t_on, t_off, factor = 0.04, 0.14, 6.0
    max_replicas = 3
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)

    def run_once():
        import time as _time

        wcfg = WorkloadConfig(
            tenants=3, zipf_s=1.2, total_records=n, arrival_rate=260.0,
            burst_mean=3.0, interactive_fraction=0.5,
            mean_suffix=max(4.0, prompt_len / 3),
            mean_output=max_new * 0.75, seed=22,
            rate_schedule=step_load(t_on, factor, t_off),
        )
        gen = WorkloadGenerator(
            wcfg, prompt_len=prompt_len, max_new=max_new,
            vocab_size=cfg.vocab_size,
        )
        mc = ManualClock()
        broker = tk.InMemoryBroker()
        broker.create_topic("t22", partitions=parts)
        pages = {
            "block_size": 4,
            "num_blocks": slots * -(-(prompt_len + max_new) // 4) + 16,
        }
        targets = [SLOTarget(
            metric="ttft", threshold_s=tick_dt * 12, objective=0.75,
            fast_window_s=tick_dt * 32, slow_window_s=tick_dt * 128,
            min_samples=4,
        )]
        fleet = ServingFleet(
            gen.consumer_factory(broker, "t22", "s22", clock=mc),
            params, cfg, replicas=replicas, prompt_len=prompt_len,
            max_new=max_new, slots=slots, commit_every=commit_every,
            clock=mc.now, qos=QoSConfig(),
            gen_kwargs={"kv_pages": pages, "max_new_of": header_max_new},
            obs=True, slo_targets=targets,
        )
        ctrl = AutoscaleController({
            "decode": RolePolicy(
                min_replicas=replicas, max_replicas=max_replicas,
                queue_high=4.0, queue_low=1.0,
                up_cooldown_s=tick_dt * 8, down_cooldown_s=tick_dt * 24,
                down_confirm=6,
            ),
        }, clock=mc.now, tracer=fleet.tracer, metrics=fleet.metrics)
        scaler = FleetAutoscaler(fleet, ctrl)
        peak = {"live": replicas}

        def on_round(f, _served):
            scaler.step()
            peak["live"] = max(peak["live"], f.live_count())

        fleet.warmup()
        t0 = _time.perf_counter()
        report = gen.drive(
            fleet, broker, "t22", clock=mc, tick_dt=tick_dt,
            settle_rounds=200, on_round=on_round,
        )
        wall_s = _time.perf_counter() - t0
        order = [
            (rid, rec.partition, rec.offset,
             tuple(np.asarray(t).tolist()))
            for rid, rec, t in report["completions"]
        ]
        committed = {
            p: broker.committed("s22", TopicPartition("t22", p)) or 0
            for p in range(parts)
        }
        produced = {
            (p, o) for p in range(parts)
            for o in range(broker.end_offset(TopicPartition("t22", p)))
        }
        # Burn recovery instant: the last transition back to "ok" on
        # the global ttft scope, read off the typed event stream.
        burn_ok_t = None
        for e in fleet.tracer.events:
            if e.stage == "burn_state":
                attrs = dict(e.attrs)
                if attrs["dim"] == "" and attrs["to"] == "ok":
                    burn_ok_t = e.t
        out = {
            "order": order,
            "events": list(fleet.tracer.events),
            "committed": committed,
            "produced": produced,
            "report": report,
            "decisions": list(ctrl.decisions),
            "digest": ctrl.decision_digest(),
            "ctrl": ctrl.summary(),
            "goodput": fleet.monitor.goodput_summary(),
            "end_burn": fleet.monitor.worst_state(),
            "burn_ok_t": burn_ok_t,
            "transitions": fleet.monitor.transitions,
            "drains": fleet.metrics.drains.count,
            "peak_live": peak["live"],
            "wall_s": wall_s,
        }
        fleet.close()
        fleet.tracer.close()
        return out

    a = run_once()
    b = run_once()
    replay_identical = (
        a["order"] == b["order"]
        and a["events"] == b["events"]
        and a["committed"] == b["committed"]
        and a["digest"] == b["digest"]
    )
    served = {(p, o) for _rid, p, o, _t in a["order"]}
    ups = [d for d in a["decisions"] if d.direction == "up"]
    downs = [d for d in a["decisions"] if d.direction == "down"]
    g = a["goodput"]
    return {
        "scenario": "22:autoscaled-step-storm",
        "model_scale": label,
        "records": n,
        "step": {"t_on": t_on, "t_off": t_off, "factor": factor},
        "replay_identical": replay_identical,
        "zero_lost": served == a["produced"] and a["report"]["all_arrived"],
        "duplicates": a["report"]["duplicates"],
        "peak_live": a["peak_live"],
        "scale_ups": len(ups),
        "scale_downs": len(downs),
        "decisions": a["ctrl"]["decisions"],
        "by_reason": a["ctrl"]["by_reason"],
        "first_up_t": round(ups[0].t_s, 4) if ups else None,
        "first_down_t": round(downs[0].t_s, 4) if downs else None,
        "downs_after_step_end": all(d.t_s > t_off for d in downs),
        "final_target": a["ctrl"]["targets"]["decode"],
        "burn_transitions": a["transitions"],
        "burn_recovered_t": (
            round(a["burn_ok_t"], 4) if a["burn_ok_t"] is not None
            else None
        ),
        "end_burn_state": a["end_burn"],
        "drained_members": a["drains"],
        "goodput_ratio": g["goodput_ratio"],
        "within_slo": g["within_slo"],
        "completed": g["completed"],
        "wall_s": round(a["wall_s"] + b["wall_s"], 2),
    }


def scenario_8(size: str = "tiny") -> dict:
    """Streaming CTR: DLRM-style recommender trained from a Kafka event
    stream — label + dense features + hashed categorical ids per record,
    row-sharded embedding tables over tp, commit-after-step. The canonical
    production consumer of the reference's ingest loop (no reference
    analog: it ships no model code)."""
    import jax
    import jax.numpy as jnp
    import optax

    import torchkafka_tpu as tk
    from torchkafka_tpu.models.recsys import (
        DLRMConfig, count_params, make_chunk_processor, make_dlrm_train_step,
        record_nbytes,
    )

    n_dev = len(jax.devices())
    tp = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    mesh = tk.make_mesh({"data": n_dev // tp, "tp": tp})
    cfg = (
        DLRMConfig(dense_dim=4, vocab_sizes=(64, 32, 128), embed_dim=8,
                   bottom_mlp=(16, 8), top_mlp=(32, 1))
        if size == "tiny"
        else DLRMConfig()  # 8 tables x 100k rows x 64 — tables are the bytes
    )
    steps = 24 if size == "tiny" else 40
    local_batch = 4 * n_dev if size == "tiny" else 4096
    n = steps * local_batch

    broker = tk.InMemoryBroker()
    parts = max(n_dev, 4)
    broker.create_topic("ctr", partitions=parts)
    rng = np.random.default_rng(0)

    def _records():
        for _ in range(n):
            dense = rng.normal(size=cfg.dense_dim).astype(np.float32)
            cats = np.array(
                [rng.integers(0, v) for v in cfg.vocab_sizes], np.int32
            )
            label = np.float32(dense.sum() > 0)
            yield label.tobytes() + dense.tobytes() + cats.tobytes()

    broker.produce_many("ctr", _records())
    consumer = tk.MemoryConsumer(
        broker, "ctr", group_id="s8",
        assignment=tk.partitions_for_process("ctr", parts, 0, 1),
    )
    init_fn, step_fn = make_dlrm_train_step(cfg, mesh, optax.adam(1e-2))
    params, opt_state = init_fn(jax.random.key(0))
    state = {"params": params, "opt": opt_state, "losses": []}

    def step(batch):
        mask = jnp.asarray(batch.valid_mask(), jnp.float32)
        state["params"], state["opt"], loss = step_fn(
            state["params"], state["opt"], batch.data["dense"],
            batch.data["cats"], batch.data["label"], mask,
        )
        state["losses"].append(loss)
        return loss

    with tk.KafkaStream(
        consumer, make_chunk_processor(cfg), batch_size=local_batch,
        mesh=mesh, idle_timeout_ms=2000, owns_consumer=True,
    ) as stream:
        rows, elapsed = _drain(stream, step, n)
    losses = [float(x) for x in state["losses"]]
    q = max(1, len(losses) // 4)

    # Ingest-vs-step decomposition (VERDICT r2): an end-to-end number that
    # can't state its split can't guide optimization. (a) PURE train step:
    # the fori-chained device slope. (b) PURE ingest: re-read the same
    # broker under a fresh group with no device step.
    dense0 = jnp.zeros((local_batch, cfg.dense_dim), jnp.float32)
    cats0 = jnp.zeros((local_batch, len(cfg.vocab_sizes)), jnp.int32)
    label0 = jnp.zeros((local_batch,), jnp.float32)
    mask0 = jnp.ones((local_batch,), jnp.float32)
    # Pure device step via the shared fori-chained slope (see _train_mfu's
    # docstring for why Python-loop chains measure dispatch, not device).
    from torchkafka_tpu.utils.timing import device_step_seconds

    step_s, step_slope_ok = device_step_seconds(
        step_fn, state["params"], state["opt"], dense0, cats0, label0, mask0
    )
    c2 = tk.MemoryConsumer(
        broker, "ctr", group_id="s8-ingest",
        assignment=tk.partitions_for_process("ctr", parts, 0, 1),
    )
    with tk.KafkaStream(
        c2, make_chunk_processor(cfg), batch_size=local_batch,
        mesh=mesh, idle_timeout_ms=2000, owns_consumer=True,
    ) as s2:
        rows2, elapsed2 = _drain(s2, None, n)
    ingest_rps = rows2 / elapsed2 if elapsed2 else 0.0

    # Paired ingest-only ratio vs the torch-user analog (per-record struct
    # parse through the compat DataLoader path), host-only on both sides.
    import torch

    k_cats = len(cfg.vocab_sizes)

    def ours_slice(group_id: str, n_s: int):
        c = tk.MemoryConsumer(
            broker, "ctr", group_id=group_id,
            assignment=tk.partitions_for_process("ctr", parts, 0, 1),
        )
        with tk.KafkaStream(
            c, make_chunk_processor(cfg), batch_size=local_batch,
            to_device=False, idle_timeout_ms=2000, owns_consumer=True,
        ) as s:
            return _drain(s, None, n_s)

    def ref_process(rec):
        v = rec.value
        d = 4 + 4 * cfg.dense_dim
        return (
            torch.from_numpy(np.frombuffer(v[:4], np.float32).copy()),
            torch.from_numpy(np.frombuffer(v[4:d], np.float32).copy()),
            torch.from_numpy(np.frombuffer(v[d : d + 4 * k_cats], np.int32).copy()),
        )

    paired = _paired_host_ratio(
        broker, "ctr", parts, ours_slice, ref_process, local_batch,
        (n // 2) // local_batch * local_batch,
    )
    return _result(
        "8:streaming-ctr", rows, elapsed, stream,
        {
            "mesh": dict(mesh.shape),
            "record_bytes": record_nbytes(cfg),
            "params_m": round(count_params(state["params"]) / 1e6, 1),
            # Degenerate slope → flag, never publish the floored value
            # (two_point_slope's contract).
            "step_slope_ok": step_slope_ok,
            "step_ms_pure": round(step_s * 1e3, 2) if step_slope_ok else None,
            "ingest_only_rows_per_s": round(ingest_rps, 1),
            **paired,
            "step_share_pct": round(
                100 * (steps * step_s) / elapsed, 1
            ) if (elapsed and step_slope_ok) else None,
            "first_loss": round(losses[0], 4),
            "last_loss": round(losses[-1], 4),
            # Every step sees a FRESH batch (true streaming), so single-step
            # losses are noisy; head/tail quartile means are the trend.
            "head_loss_mean": round(float(np.mean(losses[:q])), 4),
            "tail_loss_mean": round(float(np.mean(losses[-q:])), 4),
        },
    )


def scenario_9(size: str = "tiny") -> dict:
    """Ragged text topic → length-bucketed batches → per-width train steps,
    commit-after-step. Demonstrates the static-shape answer to variable-
    length streams (SURVEY §7 hard part (a)): one cached XLA compile per
    bucket width instead of padding every record to the maximum.

    PAIRED (VERDICT r4 weak #6): the same records replay pad-to-max in the
    SAME invocation (every row padded to the top width, same model, same
    step), so ``vs_padmax`` is a MEASURED end-to-end ratio under the same
    box conditions — not the self-referential ``bucket_efficiency`` token
    count (still reported: it is the analytic ceiling the measured ratio
    should approach as steps dominate)."""
    import jax
    import jax.numpy as jnp
    import optax

    import torchkafka_tpu as tk
    from torchkafka_tpu.models import TransformerConfig, make_train_step

    n_dev = len(jax.devices())
    mesh = tk.make_mesh({"data": n_dev})
    buckets = (16, 32, 64) if size == "tiny" else (64, 128, 256, 512)
    max_w = buckets[-1]
    cfg = (
        TransformerConfig(vocab_size=512, d_model=64, n_layers=2, n_heads=4,
                          n_kv_heads=2, d_ff=128, max_seq_len=max_w,
                          dtype=jnp.float32)
        if size == "tiny"
        else TransformerConfig(max_seq_len=max_w)
    )
    n = 256 if size == "tiny" else 6144
    local_batch = 2 * n_dev if size == "tiny" else 8 * n_dev

    broker = tk.InMemoryBroker()
    parts = max(n_dev, 4)
    broker.create_topic("t9", partitions=parts)
    rng = np.random.default_rng(0)
    # Zipf-ish length mix: mostly short, a long tail — the shape that makes
    # pad-to-max wasteful and bucketing worthwhile.
    lengths = np.minimum(
        (rng.pareto(1.2, n) * 0.15 * max_w + 5).astype(int), max_w
    )
    broker.produce_many(
        "t9",
        (
            rng.integers(0, cfg.vocab_size, k).astype(np.int32).tobytes()
            for k in lengths
        ),
    )
    init_fn, step_fn = make_train_step(cfg, mesh, optax.adamw(1e-3))

    def padmax_processor(rec):
        row = np.frombuffer(rec.value, np.int32)
        out = np.zeros(max_w, np.int32)
        out[: row.shape[0]] = row
        return {"tokens": out, "length": np.int32(row.shape[0])}

    def run_pass(tag: str, bucketed: bool):
        """One full stream+train pass over the SAME topic (fresh group —
        re-reads from offset 0). Shared step_fn: the pad-to-max pass
        reuses the bucketed pass's top-width XLA compile and vice versa,
        so neither side pays compilation the other did not."""
        consumer = tk.MemoryConsumer(
            broker, "t9", group_id=f"s9-{tag}",
            assignment=tk.partitions_for_process("t9", parts, 0, 1),
        )
        params, opt_state = init_fn(jax.random.key(0))
        state = {"p": params, "o": opt_state, "losses": []}
        rows_by_width: dict[int, int] = {}
        batches_by_width: dict[int, int] = {}

        def step(batch):
            toks = jnp.asarray(batch.data["tokens"])
            w = toks.shape[1]
            rows_by_width[w] = rows_by_width.get(w, 0) + batch.valid_count
            batches_by_width[w] = batches_by_width.get(w, 0) + 1
            # Mask: real rows AND real (pre-pad) positions within each row.
            ln = np.asarray(batch.data["length"])
            mask = (
                np.arange(w)[None, :] < ln[:, None]
            ) & batch.valid_mask()[:, None]
            state["p"], state["o"], loss = step_fn(
                state["p"], state["o"], toks,
                jnp.asarray(mask.astype(np.int32)),
            )
            state["losses"].append(loss)
            return loss

        processor = (
            (lambda rec: np.frombuffer(rec.value, np.int32))
            if bucketed else padmax_processor
        )
        with tk.KafkaStream(
            consumer,
            processor,
            batch_size=local_batch,
            pad_policy="pad",
            mesh=mesh,
            idle_timeout_ms=2000,
            owns_consumer=True,
            **({"buckets": buckets} if bucketed else {}),
        ) as stream:
            rows, elapsed = _drain(stream, step, n)
        losses = [float(x) for x in state["losses"]]
        return rows, elapsed, losses, rows_by_width, batches_by_width, stream

    # Warmup pass (untimed-in-the-ratio; first-contact compiles land here),
    # then bucketed and pad-to-max back-to-back — both sides sample the
    # same minutes of box weather.
    run_pass("warm", bucketed=True)
    rows, elapsed, losses, rows_by_width, batches_by_width, stream = run_pass(
        "bucketed", bucketed=True
    )
    p_rows, p_elapsed, p_losses, _pw, p_batches, _ = run_pass(
        "padmax", bucketed=False
    )
    assert p_rows == rows, (p_rows, rows)
    bucketed_tokens = sum(w * r for w, r in rows_by_width.items())
    extra = {
        "mesh": dict(mesh.shape),
        "buckets": list(buckets),
        "rows_per_width": {
            int(w): int(r) for w, r in sorted(rows_by_width.items())
        },
        "bucket_efficiency": round(bucketed_tokens / (rows * max_w), 3),
        # MEASURED same-invocation ratio: pad-to-max elapsed over
        # bucketed elapsed on identical records and model (>1 =
        # bucketing wins end-to-end). Where the loop is dispatch-bound
        # (both sides run ~the same batch count) this reads ≈1 regardless
        # of the device saving — the device-level ratio below isolates
        # the step.
        "vs_padmax": round(p_elapsed / elapsed, 2) if elapsed else None,
        "padmax_records_per_s": (
            round(p_rows / p_elapsed, 1) if p_elapsed else None
        ),
        "first_loss": round(losses[0], 4),
        "last_loss": round(losses[-1], 4),
        "padmax_last_loss": round(p_losses[-1], 4),
    }
    if jax.default_backend() == "tpu":
        # DEVICE-level paired step cost: fori-chained slope per width
        # (utils.timing.device_step_seconds — one dispatch per window),
        # weighted by the
        # batch counts the bucketed pass ACTUALLY ran vs every batch at
        # the top width. This is the measured train-step ratio the
        # analytic bucket_efficiency predicts.
        from torchkafka_tpu.utils.timing import device_step_seconds

        dp, do = init_fn(jax.random.key(1))
        rng2 = np.random.default_rng(5)
        step_s: dict[int, float] = {}
        # TWO rounds per width, keep the min of the rounds whose SLOPE
        # HELD: the first measurement after the e2e passes absorbs
        # queue-drain/cache cold-start (observed: a width-64 step reading
        # 10.3 ms while width-128 read 4.3 in the same run), min-of-rounds
        # is the standard de-noise for step walls on a drifting chip, and
        # a degenerate round (ok=False → floored 1e-9) must be DISCARDED,
        # not min'd in — two_point_slope's contract is flag-don't-publish.
        for _ in range(2):
            for w in buckets:
                toks = jnp.asarray(
                    rng2.integers(0, cfg.vocab_size, (local_batch, w)),
                    jnp.int32,
                )
                msk = jnp.ones((local_batch, w), jnp.int32)
                s, ok = device_step_seconds(step_fn, dp, do, toks, msk)
                if ok:
                    step_s[w] = min(step_s.get(w, float("inf")), s)
        slopes_ok = len(step_s) == len(buckets)
        extra.update({
            "device_step_ms_per_width": {
                int(w): round(s * 1e3, 2) for w, s in sorted(step_s.items())
            },
            "batches_per_width": {
                int(w): int(b) for w, b in sorted(batches_by_width.items())
            },
            "device_slopes_ok": slopes_ok,
        })
        if slopes_ok:
            bucketed_dev = sum(
                step_s[w] * b for w, b in batches_by_width.items()
            )
            # The padmax side's own batch count (bucket fragmentation
            # gives the bucketed pass a couple more part-full batches).
            padmax_dev = step_s[max_w] * sum(p_batches.values())
            extra.update({
                "bucketed_device_step_s": round(bucketed_dev, 2),
                "padmax_device_step_s": round(padmax_dev, 2),
                "vs_padmax_device": (
                    round(padmax_dev / bucketed_dev, 2)
                    if bucketed_dev else None
                ),
            })
        else:
            # No valid slope for some width in either round: publishing a
            # ratio built on floored values would fabricate the headline.
            extra.update({
                "bucketed_device_step_s": None,
                "padmax_device_step_s": None,
                "vs_padmax_device": None,
            })
    return _result("9:ragged-bucketed-train", rows, elapsed, stream, extra)


def scenario_23(size: str = "tiny", replicas: int = 2) -> dict:
    """Quorum-cell leader death mid-storm (ISSUE 17): the broker itself
    becomes highly available. A 2-process ``exactly_once`` fleet serves
    over a 3-REPLICA broker cell (``ProcessFleet(broker_replicas=3,
    wal_durability="quorum")`` — every acked mutation majority-held
    across WAL replicas before the client hears back). Once a worker's
    journal proves served-but-uncommitted transactional work exists, the
    LEADER is dropped the way SIGKILL would drop it (listener gone
    mid-conversation, WAL abandoned un-flushed) and the cell runs its
    epoch-bumped election: the longest-prefix follower replays through
    PR-11 recovery (dangling transactions aborted, LSO recomputed) and
    takes over the SAME advertised port. Workers reconnect through their
    retry stacks, unfenced — promotion, not restart, so there is no
    ride-through window to hold open. Audited: zero lost records,
    committed-view duplicates EXACTLY zero, every committed completion
    byte-identical to a no-kill reference, and the deposed leader's
    forged late append REJECTED by the bumped epoch
    (``StaleEpochError``) — the cell-level twin of scenario 18's fenced
    zombie commit."""
    import tempfile
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.errors import StaleEpochError
    from torchkafka_tpu.fleet import ProcessFleet
    from torchkafka_tpu.journal import DecodeJournal
    from torchkafka_tpu.serve import StreamingGenerator
    from torchkafka_tpu.source.records import TopicPartition

    prompt_len, max_new = (8, 16) if size == "tiny" else (32, 32)
    n = 12 if size == "tiny" else 48
    parts, slots, commit_every = 4, 2, 4
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    model_spec = dict(
        seed=0, vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
        max_seq_len=cfg.max_seq_len,
    )
    rng = np.random.default_rng(23)
    prompts = rng.integers(0, cfg.vocab_size, (n, prompt_len),
                           dtype=np.int32)
    all_keys = {str(i).encode() for i in range(n)}

    # In-process no-kill reference (greedy decode is a pure function of
    # (params, prompt)).
    rb = tk.InMemoryBroker()
    rb.create_topic("t23", partitions=parts)
    for i in range(n):
        rb.produce("t23", prompts[i].tobytes(), partition=i % parts,
                   key=str(i).encode())
    rc = tk.MemoryConsumer(rb, "t23", group_id="ref23")
    ref_gen = StreamingGenerator(
        rc, params, cfg, slots=slots, prompt_len=prompt_len,
        max_new=max_new, commit_every=commit_every, ticks_per_sync=1,
    )
    ref = {rec.key: toks for rec, toks in ref_gen.run(idle_timeout_ms=400)}
    rc.close()

    t0 = _time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        import os as _os

        fleet = ProcessFleet(
            model_spec, topic="t23", prompt_len=prompt_len,
            max_new=max_new, workdir=td, replicas=replicas,
            partitions=parts, slots=slots, commit_every=commit_every,
            session_timeout_s=8.0, heartbeat_interval_s=0.2,
            journal_cadence=1, respawn=False, group="s23",
            exactly_once=True,
            wal_dir=_os.path.join(td, "cell"), wal_durability="quorum",
            broker_replicas=3,
            # Short client retries so the failover gap is FELT by the
            # resilience stack (and provably ridden), not absorbed.
            resilient=True, reconnect_attempts=2,
            reconnect_deadline_s=0.4,
        )
        try:
            fleet.start()
            fleet.wait_ready(timeout_s=300)
            ready_s = _time.perf_counter() - t0
            for i in range(n):
                fleet.broker.produce(
                    "t23", prompts[i].tobytes(), partition=i % parts,
                    key=str(i).encode(),
                )

            def uncommitted_served_work(inc) -> bool:
                """Scenario 19's kill criterion, re-aimed at the leader:
                a FINISHED journal entry past the committed watermark
                proves in-flight transactional work exists for the
                election to strand — the committed view must not move."""
                try:
                    entries = DecodeJournal.load(inc.journal_path)
                except Exception:  # noqa: BLE001 - mid-write race
                    return False
                for (topic, p, off), e in entries.items():
                    if not e.finished or topic != "t23":
                        continue
                    wm = fleet.broker.committed(
                        "s23", TopicPartition("t23", p)
                    ) or 0
                    if off >= wm:
                        return True
                return False

            deadline = _time.monotonic() + 240
            while not any(
                uncommitted_served_work(i) for i in fleet.live()
            ):
                if _time.monotonic() > deadline:
                    raise TimeoutError(
                        "no kill opportunity arose\n" + fleet.diagnose()
                    )
                if len(fleet.results("read_committed")) >= n:
                    raise RuntimeError(
                        "storm finished before any worker held "
                        "uncommitted served work — shrink commit_every"
                    )
                _time.sleep(0.01)

            failover = fleet.kill_leader()

            # The deposed leader's late write: a forged frame carrying
            # the OLD epoch must be rejected by every follower, never
            # applied — zombie fencing at the cell level.
            forged_rejected = False
            try:
                fleet._cell.forge_deposed_frame()
            except StaleEpochError:
                forged_rejected = True

            def covered(f) -> bool:
                committed = set(f.results("read_committed"))
                if committed >= all_keys:
                    return True
                pending = set()
                for inc in f.live():
                    try:
                        entries = DecodeJournal.load(inc.journal_path)
                    except Exception:  # noqa: BLE001 - mid-write race
                        continue
                    for (topic, p, off), e in entries.items():
                        if e.finished and topic == "t23":
                            pending.add(str(off * parts + p).encode())
                return committed | pending >= all_keys

            fleet.wait(covered, timeout_s=240)
            fleet.drain()
            fleet.wait(
                lambda f: all(not i.running for i in f.incarnations),
                timeout_s=120,
            )
            fleet.poll_once()
            zero_lost = fleet.fully_committed()

            committed_res = fleet.results("read_committed")
            committed_dups = sum(
                len(v) - 1 for v in committed_res.values()
            )
            identical = set(committed_res) == set(ref) and all(
                np.array_equal(toks, ref[k])
                for k, copies in committed_res.items()
                for _m, toks in copies
            )
            cell_status = fleet._cell.status()
            worker_m = fleet.worker_metrics()
            elapsed = _time.perf_counter() - t0
        finally:
            fleet.close()
    return {
        "scenario": "23:quorum-leader-failover-storm",
        "model_scale": label,
        "replicas": replicas,
        "broker_replicas": 3,
        "records": n,
        "ready_s": round(ready_s, 2),
        "elapsed_s": round(elapsed, 2),
        "leader_elections": fleet.metrics.leader_elections.count,
        "failover": {
            "victim_idx": failover["victim_idx"],
            "winner_idx": failover["winner_idx"],
            "old_epoch": failover["old_epoch"],
            "epoch": failover["epoch"],
            "candidates": failover["candidates"],
            "election_ms": round(failover["election_ms"], 2),
            "failover_ms": round(failover["failover_ms"], 2),
            "recovery": failover["recovery"],
        },
        "cell_epoch": cell_status["epoch"],
        "zero_lost": zero_lost,
        "identical_to_no_kill": identical,
        "committed_duplicates": committed_dups,
        "deposed_append_rejected": forged_rejected,
        "workers_survived_unfenced": all(
            m["exit"] == 0 for m in worker_m
        ) and len(worker_m) == replicas,
        "exit_codes": {
            i.member: (None if i.proc is None else i.proc.returncode)
            for i in fleet.incarnations
        },
    }


def scenario_24(size: str = "tiny", replicas: int = 2) -> dict:
    """Rolling weight hot-swap with canary auto-rollback (ISSUE 18): the
    model itself becomes a live, versioned resource. A 2-process
    ``exactly_once`` fleet serves a storm while the supervisor drives
    TWO rollouts over the broker control plane. First a DIVERGENT v1
    (different weights) is published to the checkpoint topic and rolled
    out: the canary replica shadow-serves a deterministic slice under
    v1, token-diffs against its own live incumbent output, and the
    controller AUTOMATICALLY rolls back on divergence — no replica ever
    serves v1 into the committed view. Then a CLEAN v2 (byte-identical
    weights, new version) rolls out to completion: canary passes,
    replicas drain-swap one at a time (quiesce → close the commit
    window → journal the version → rebind, zero recompile), and the
    fleet's incumbent advances. Audited: zero lost records,
    committed-view duplicates EXACTLY zero, every committed completion
    byte-identical to a no-rollout reference, and every output's "mv"
    version tag ∈ {0, 2} — the divergent version left no trace."""
    import tempfile
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.fleet import ProcessFleet
    from torchkafka_tpu.fleet.proc import build_model
    from torchkafka_tpu.journal import DecodeJournal
    from torchkafka_tpu.serve import StreamingGenerator
    from torchkafka_tpu.source.records import TopicPartition

    prompt_len, max_new = (8, 16) if size == "tiny" else (32, 32)
    parts, slots, commit_every = 4, 2, 4
    pool = 400  # prompt pool upper bound; the storm produces on demand
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    model_spec = dict(
        seed=0, vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
        max_seq_len=cfg.max_seq_len,
    )
    rng = np.random.default_rng(24)
    prompts = rng.integers(0, cfg.vocab_size, (pool, prompt_len),
                           dtype=np.int32)

    t0 = _time.perf_counter()
    with tempfile.TemporaryDirectory() as td:
        fleet = ProcessFleet(
            model_spec, topic="t24", prompt_len=prompt_len,
            max_new=max_new, workdir=td, replicas=replicas,
            partitions=parts, slots=slots, commit_every=commit_every,
            session_timeout_s=8.0, heartbeat_interval_s=0.2,
            journal_cadence=1, respawn=False, group="s24",
            out_topic="out24", exactly_once=True, rollout=True,
            rollout_topic="roll24", ckpt_topic="ckpt24",
            idle_exit_ms=None,
        )
        nkeys = 0

        def produce(n: int) -> None:
            nonlocal nkeys
            for _ in range(n):
                if nkeys >= pool:
                    raise RuntimeError("prompt pool exhausted")
                fleet.broker.produce(
                    "t24", prompts[nkeys].tobytes(),
                    partition=nkeys % parts, key=str(nkeys).encode(),
                )
                nkeys += 1

        def feed() -> None:
            """Keep the storm alive WITHOUT flooding: the canary needs
            live completions to compare, but an unthrottled producer
            outruns tiny-model decode and bloats the reference replay —
            top the uncommitted backlog back up to a small constant."""
            backlog = nkeys - len(fleet.results("read_committed"))
            if backlog < 12:
                produce(2)

        try:
            fleet.start()
            fleet.wait_ready(timeout_s=300)
            ready_s = _time.perf_counter() - t0
            produce(8)

            # --- rollout 1: DIVERGENT weights → canary auto-rollback --
            _, divergent = build_model(dict(model_spec, seed=1))
            fleet.publish_checkpoint(1, divergent)
            drv1 = fleet.start_rollout(1, canary_slice=3)
            deadline = _time.monotonic() + 180
            while not fleet.rollout_done:
                if _time.monotonic() > deadline:
                    raise TimeoutError(
                        "divergent rollout never resolved\n"
                        + fleet.diagnose()
                    )
                fleet.poll_once()
                feed()  # the canary compares LIVE traffic
                _time.sleep(0.05)
            phase1 = drv1.controller.phase
            reason1 = drv1.controller.rollback_reason
            versions1 = dict(drv1.controller.member_versions)
            rollback_s = _time.perf_counter() - t0 - ready_s

            # --- rollout 2: CLEAN weights (same bytes, new version) →
            # canary passes, every replica drain-swaps, incumbent
            # advances ---------------------------------------------------
            _, clean = build_model(model_spec)
            fleet.publish_checkpoint(2, clean)
            drv2 = fleet.start_rollout(2, canary_slice=3)
            deadline = _time.monotonic() + 180
            while not fleet.rollout_done:
                if _time.monotonic() > deadline:
                    raise TimeoutError(
                        "clean rollout never completed\n" + fleet.diagnose()
                    )
                fleet.poll_once()
                feed()
                _time.sleep(0.05)
            phase2 = drv2.controller.phase
            versions2 = dict(drv2.controller.member_versions)
            fleet_version = fleet.model_version

            # Serve out the tail BEFORE draining: drain abandons
            # queued-but-unadmitted records (loss-free by re-delivery,
            # but this fleet is about to exit for good), so wait until
            # every produced key is either committed or finished in a
            # live worker's journal — then the drain only has to flush.
            tail_keys = {str(i).encode() for i in range(nkeys)}

            def covered(f) -> bool:
                done = set(f.results("read_committed"))
                if done >= tail_keys:
                    return True
                for inc in f.live():
                    try:
                        entries = DecodeJournal.load(inc.journal_path)
                    except Exception:  # noqa: BLE001 - mid-write race
                        continue
                    for (topic, p, off), e in entries.items():
                        if e.finished and topic == "t24":
                            done.add(str(off * parts + p).encode())
                return done >= tail_keys

            fleet.wait(covered, timeout_s=240)
            fleet.drain()
            fleet.wait(lambda f: not f.live(), timeout_s=120)
            fleet.poll_once()
            zero_lost = fleet.fully_committed()

            committed_res = fleet.results(isolation="read_committed")
            committed_dups = sum(
                len(v) - 1 for v in committed_res.values()
            )
            all_keys = {str(i).encode() for i in range(nkeys)}
            none_lost = set(committed_res) == all_keys

            # Version tags on the committed view: the divergent v1 must
            # have left NO committed trace; everything is v0 or v2.
            tags: dict = {}
            for p in range(fleet.broker.partitions_for("out24")):
                recs, _ = fleet.broker.fetch_stable(
                    TopicPartition("out24", p), 0, 10**6,
                )
                for rec in recs:
                    mv = dict(rec.headers or ()).get("mv", b"?")
                    tags[mv.decode()] = tags.get(mv.decode(), 0) + 1
            divergent_leaked = "1" in tags
            tags_consistent = set(tags) <= {"0", "2"}

            # No-rollout byte-truth: v2's weights ARE v0's, so one
            # seed-0 greedy reference covers every committed output
            # regardless of which side of the swap served it.
            rb = tk.InMemoryBroker()
            rb.create_topic("r24", partitions=parts)
            for i in range(nkeys):
                rb.produce("r24", prompts[i].tobytes(),
                           partition=i % parts, key=str(i).encode())
            rcons = tk.MemoryConsumer(rb, "r24", group_id="ref24")
            ref_gen = StreamingGenerator(
                rcons, params, cfg, slots=slots, prompt_len=prompt_len,
                max_new=max_new, commit_every=commit_every,
                ticks_per_sync=1,
            )
            ref = {
                rec.key: toks
                for rec, toks in ref_gen.run(idle_timeout_ms=400)
            }
            rcons.close()
            identical = all(
                np.array_equal(toks, ref[k])
                for k, copies in committed_res.items()
                for _m, toks in copies
            )
            worker_m = fleet.worker_metrics()
            elapsed = _time.perf_counter() - t0
        finally:
            fleet.close()
    return {
        "scenario": "24:rolling-hot-swap-canary-rollback",
        "model_scale": label,
        "replicas": replicas,
        "records": nkeys,
        "ready_s": round(ready_s, 2),
        "elapsed_s": round(elapsed, 2),
        "divergent_rollout": {
            "phase": phase1,
            "rollback_reason": reason1,
            "member_versions": versions1,
            "resolved_s": round(rollback_s, 2),
        },
        "clean_rollout": {
            "phase": phase2,
            "member_versions": versions2,
        },
        "fleet_model_version": fleet_version,
        "version_tags": tags,
        "divergent_version_leaked": divergent_leaked,
        "version_tags_consistent": tags_consistent,
        "zero_lost": bool(zero_lost and none_lost),
        "identical_to_no_rollout": identical,
        "committed_duplicates": committed_dups,
        "workers_survived": all(m["exit"] == 0 for m in worker_m)
        and len(worker_m) == replicas,
    }


def scenario_25(size: str = "tiny", replicas: int = 2) -> dict:
    """Online draft distillation, the loop closed (ISSUE 19): a
    speculative serving fleet TEACHES ITS OWN DRAFT from live traffic
    and rides out a traffic drift. A 2-replica in-process spec fleet
    serves a Zipf workload whose hot set ROTATES mid-run
    (``hot_set_rotation`` — the rank→tenant remap moves which shared
    context prefixes dominate, i.e. real prompt-content drift). Decode
    replicas stage committed (prompt, tokens) completions onto the
    distill topic inside their commit windows; a DistillTrainer pumped
    on the same scheduling rounds trains the layer-truncated draft on
    that corpus and publishes versioned checkpoints; the fleet's
    DistillController (ManualClock hysteresis) auto-refreshes every
    replica's draft via ``swap_draft_params`` between ticks — no
    quiesce. Measured per phase: α with the distilled draft on
    stationary traffic RISES above the untrained-truncation baseline,
    DEGRADES at the drift instant (the distilled draft specialised to
    the old hot set), and RECOVERS after the post-drift refresh
    (α_post > α_drift — the closed loop's whole point). Audited:
    committed tokens BYTE-IDENTICAL to a never-distilled reference
    fleet on the same workload seed (a draft refresh changes only the
    proposer; the target's verification commits), zero duplicates."""
    import tempfile
    import time as _time

    import torchkafka_tpu as tk
    from torchkafka_tpu.distill import DistillPolicy, DistillTrainer
    from torchkafka_tpu.fleet import ServingFleet
    from torchkafka_tpu.resilience import ManualClock
    from torchkafka_tpu.serve_spec import SpecStreamingGenerator
    from torchkafka_tpu.source.producer import MemoryProducer
    from torchkafka_tpu.workload import WorkloadConfig, WorkloadGenerator

    prompt_len, max_new = (8, 16) if size == "tiny" else (16, 32)
    total = 240 if size == "tiny" else 480
    t_drift = 0.45  # synthetic seconds; ~half the schedule
    cfg, params, label = _serving_model(size, None, prompt_len, max_new)
    wl_cfg = WorkloadConfig(
        # Steep Zipf (rank-1 ≈ 70% of traffic) + near-pure context
        # prompts: maximally learnable pre-drift, maximally WRONG after
        # the rotation — the crispest α signal the loop can get.
        tenants=6, zipf_s=2.0, total_records=total, arrival_rate=230.0,
        burst_mean=2.0, interactive_fraction=1.0, mean_suffix=1.5,
        seed=25,
        # Shift 3 of 6: every popularity rank lands on a different
        # tenant, so the post-drift hot set shares NO context prefix
        # with what the draft distilled on.
        hot_set_rotation=((t_drift, 3),),
    )

    def run(distill: bool) -> dict:
        wl = WorkloadGenerator(
            wl_cfg, prompt_len=prompt_len, max_new=max_new,
            vocab_size=cfg.vocab_size,
        )
        broker = tk.InMemoryBroker()
        broker.create_topic("t25", partitions=4)
        broker.create_topic("d25", partitions=1)
        broker.create_topic("ck25", partitions=1)
        clock = ManualClock()
        gen_kwargs = dict(
            k=3, draft_layers=1, ticks_per_sync=4,
            distill_topic="d25",
            distill_producer=MemoryProducer(broker),
        )
        fleet = ServingFleet(
            wl.consumer_factory(broker, "t25", "s25", resilient=False),
            params, cfg, replicas=replicas, prompt_len=prompt_len,
            max_new=max_new, slots=4, commit_every=4,
            generator_cls=SpecStreamingGenerator, gen_kwargs=gen_kwargs,
            clock=clock.now, obs=True,
        )
        trainer = None
        driver = None
        refreshes: list[tuple[float, int]] = []  # (t_s, version)
        rounds: list[tuple[float, int, int]] = []  # (t_s, acc, prop)
        if distill:
            tcons = tk.MemoryConsumer(broker, "d25", group_id="tr25")
            trainer = DistillTrainer(
                tcons, params, cfg, seq_len=prompt_len + max_new,
                batch_size=8, draft_layers=1, learning_rate=5e-3,
                broker=broker, ckpt_topic="ck25", publish_every=6,
                metrics=fleet.metrics,
            )
            driver = fleet.start_distill(
                policy=DistillPolicy(
                    window_rounds=24, min_proposed=32,
                    # Track the trainer: every published version rolls
                    # once the SYNTHETIC-clock cooldown allows — sized
                    # so refreshes land a few times per phase.
                    cooldown_s=0.10, refresh_on_publish=True,
                ),
                broker=broker, ckpt_topic="ck25",
            )

        def hook(f, served):
            if trainer is not None:
                # Pump the trainer a bounded chunk per scheduling round
                # (the in-process twin of the distill worker's chunked
                # loop), then push any fresh versions at the controller.
                trainer.run(max_steps=2, idle_timeout_ms=1)
                driver.note_version(trainer.published)
                driver.on_round(f, served)
            acc = prop = 0
            for rep in f.replicas:
                if rep.runnable:
                    st = rep.gen.spec_stats()
                    acc += st["accepted"]
                    prop += st["proposed"]
            rounds.append((clock.now(), acc, prop))
            if driver is not None and driver.controller.refreshes > len(
                refreshes
            ):
                refreshes.append(
                    (clock.now(), driver.controller.applied_version)
                )

        try:
            res = wl.drive(
                fleet, broker, "t25", clock=clock, tick_dt=0.002,
                idle_timeout_ms=4000, on_round=hook, settle_rounds=60,
            )
        finally:
            fleet.close()
            if trainer is not None:
                tcons.close()
        committed = {
            (rec.partition, rec.offset): np.asarray(toks).tobytes()
            for _rid, rec, toks in res["completions"]
        }
        return {
            "res": res, "committed": committed, "rounds": rounds,
            "refreshes": refreshes,
            "trainer": trainer.report() if trainer else None,
            "controller": {
                "refreshes": driver.controller.refreshes,
                "applied_version": driver.controller.applied_version,
                "alpha_window": driver.controller.alpha_window,
            } if driver else None,
            "metrics": fleet.metrics.summary(),
        }

    def alpha_between(rounds, t0, t1) -> tuple[float | None, int]:
        """α over rounds with t0 <= t < t1, from cumulative counters."""
        inside = [(a, p) for t, a, p in rounds if t0 <= t < t1]
        if len(inside) < 2:
            return None, 0
        d_acc = inside[-1][0] - inside[0][0]
        d_prop = inside[-1][1] - inside[0][1]
        return (
            (d_acc / d_prop if d_prop else None), d_prop,
        )

    t0 = _time.perf_counter()
    live = run(distill=True)
    ref = run(distill=False)
    elapsed = _time.perf_counter() - t0

    refreshes = live["refreshes"]
    pre = [t for t, _v in refreshes if t < t_drift]
    # The RECOVERY refresh: the first applied once the trainer has had
    # a grace window to consume post-drift corpus. Refreshes landing
    # within the grace carry mostly pre-drift gradients — they belong
    # to the degraded phase, not the recovery.
    grace = 0.10
    post = [t for t, _v in refreshes if t >= t_drift + grace]
    end = live["rounds"][-1][0]
    t_rec = post[0] if post else end
    # Phase α from the recorded cumulative counters: distilled-
    # stationary (the LATE pre-drift window — the draft at its most
    # specialised), drifted-stale (drift → recovery refresh), and
    # recovered (recovery refresh → end).
    alpha_pre, n_pre = alpha_between(
        live["rounds"], max(t_drift - 0.2, pre[0] if pre else 0.0),
        t_drift,
    )
    alpha_drift, n_drift = alpha_between(live["rounds"], t_drift, t_rec)
    alpha_post, n_post = alpha_between(live["rounds"], t_rec, end + 1.0)
    # The committed-view differential: byte-identical tokens at every
    # (partition, offset) the two runs share — and both served all.
    same_keys = set(live["committed"]) == set(ref["committed"])
    identical = same_keys and all(
        live["committed"][k] == ref["committed"][k]
        for k in live["committed"]
    )
    return {
        "scenario": "25:online-draft-distillation",
        "model_scale": label,
        "replicas": replicas,
        "records": total,
        "elapsed_s": round(elapsed, 2),
        "drift_t_s": t_drift,
        "refreshes": [(round(t, 4), v) for t, v in refreshes],
        "refreshes_pre_drift": len(pre),
        "refreshes_post_drift": len(post),
        "trainer": live["trainer"],
        "alpha_pre": round(alpha_pre, 4) if alpha_pre is not None else None,
        "alpha_drift": (
            round(alpha_drift, 4) if alpha_drift is not None else None
        ),
        "alpha_post": (
            round(alpha_post, 4) if alpha_post is not None else None
        ),
        "alpha_windows_proposed": [n_pre, n_drift, n_post],
        "alpha_degraded_at_drift": (
            alpha_pre is not None and alpha_drift is not None
            and alpha_drift < alpha_pre
        ),
        "alpha_recovered": (
            alpha_drift is not None and alpha_post is not None
            and alpha_post > alpha_drift
        ),
        "identical_to_no_distill": identical,
        "committed_duplicates": live["res"]["duplicates"],
        "all_arrived": live["res"]["all_arrived"]
        and ref["res"]["all_arrived"],
        "distill_metrics": live["metrics"].get("distill"),
    }


SCENARIOS = {
    1: scenario_1,
    2: scenario_2,
    3: scenario_3,
    4: scenario_4,
    5: scenario_5,
    6: scenario_6,
    7: scenario_7,
    8: scenario_8,
    9: scenario_9,
    10: scenario_10,
    11: scenario_11,
    12: scenario_12,
    13: scenario_13,
    14: scenario_14,
    15: scenario_15,
    16: scenario_16,
    17: scenario_17,
    18: scenario_18,
    19: scenario_19,
    20: scenario_20,
    21: scenario_21,
    22: scenario_22,
    23: scenario_23,
    24: scenario_24,
    25: scenario_25,
}


def run_scenario(
    num: int, size: str = "tiny", *, model_scale: str | None = None,
    serve_eos: bool = False, quantized: bool | None = None,
    kv_int8: bool = False, kv_kernel: bool | str = "auto",
    spec: bool = False, spec_k: int = 4,
    spec_draft_layers: int | None = None,
    temperature: float = 0.0, top_k: int | None = None,
    top_p: float | None = None, replicas: int = 2,
    prefill_chunk: int | None = None,
) -> dict:
    if size not in _SIZES:
        raise ValueError(f"size must be one of {_SIZES}")
    if prefill_chunk is not None and num != 14:
        raise ValueError(
            "--prefill-chunk applies to scenario 14 (the chunked-prefill "
            "storm smoke)"
        )
    if num == 14:
        return SCENARIOS[14](size, prefill_chunk=prefill_chunk)
    if serve_eos and (num != 7 or model_scale is None):
        raise ValueError("--serve-eos applies to scenario 7 at a model scale")
    if quantized is not None and (model_scale is None or num not in (5, 7)):
        raise ValueError("--quantized applies to scenarios 5/7 at a model scale")
    if kv_int8 and num != 7:
        raise ValueError("--kv-int8 applies to scenario 7 (the slot pool)")
    if spec and num != 7:
        raise ValueError("--spec applies to scenario 7 (speculative serving)")
    if spec and kv_int8:
        raise ValueError(
            "--spec serves the compute-dtype pool (token-exactness is the "
            "contract); drop --kv-int8"
        )
    sampling = temperature != 0.0 or top_k is not None or top_p is not None
    if sampling and num != 7:
        raise ValueError(
            "--temperature/--top-k/--top-p apply to scenario 7 (the "
            "sampled serving path)"
        )
    if spec and sampling:
        raise ValueError(
            "--spec is greedy-only (the accept rule is the target's "
            "argmax); drop the sampling flags"
        )
    sample_kw = dict(temperature=temperature, top_k=top_k, top_p=top_p)
    spec_kw = dict(spec=spec, spec_k=spec_k, spec_draft_layers=spec_draft_layers)
    if num in (10, 11, 12, 13, 15, 16, 17, 18, 19, 20, 21, 23, 24, 25):
        return SCENARIOS[num](size, replicas=replicas)
    if num == 22:
        return SCENARIOS[22](size, replicas=1)
    if model_scale is not None:
        if num not in (5, 7):
            raise ValueError("model_scale applies to scenarios 5 and 7 only")
        if num == 7:
            return SCENARIOS[7](
                size, model_scale=model_scale, serve_eos=serve_eos,
                quantized=quantized, kv_int8=kv_int8, kv_kernel=kv_kernel,
                **spec_kw, **sample_kw,
            )
        return SCENARIOS[5](size, model_scale=model_scale, quantized=quantized)
    if kv_int8:
        return SCENARIOS[7](size, kv_int8=True, kv_kernel=kv_kernel, **sample_kw)
    if spec:
        return SCENARIOS[7](size, **spec_kw)
    if sampling:
        return SCENARIOS[7](size, **sample_kw)
    return SCENARIOS[num](size)
