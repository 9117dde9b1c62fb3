"""The serving loop: a prompt topic, ``StreamingGenerator.run()``, an output
topic, commits. One driver for every serving cell; the traffic kind says
whether the records wait in the topic before the window (a backlog) or
arrive on the wall clock (an open loop).

The program is driven through its own entry (``run()``), with its own
tracer attached for the per-request stamps. The benchmark supplies the
records, reads the output topic and the committed offsets back from the
broker, and compares a sample of what was served with the plain
reference once the window has closed and the server is freed.
"""

from __future__ import annotations

import gc
import struct
import threading
import time

import numpy as np

from chipbench import common, stats
from chipbench import weights as W

GROUP = "chipbench"
PROMPTS, OUTPUT = "prompts", "completions"
_HEAD = struct.Struct("<iq")


def encode_output(rec, toks) -> bytes:
    """Partition and offset of the prompt, then the tokens."""
    return _HEAD.pack(rec.partition, rec.offset) + np.asarray(
        toks, np.int32
    ).tobytes()


def budget_of(rec):
    """The answer budget a request carries in its ``max_new`` header (a
    copy of ``workload/generator.py::header_max_new``)."""
    for k, v in rec.headers:
        if k == "max_new":
            return int(v)
    return None


def _produce(broker, rec: dict):
    """A keyed producer whose partitioner is the plan's: a tenant's
    records stay on one partition."""
    return broker.produce(
        PROMPTS, rec["tokens"].tobytes(), key=rec["key"],
        partition=rec["partition"],
        headers=(("max_new", str(rec["max_new"]).encode()),),
    )


def stamped_consumer(tk, broker, stamps: list):
    """The program's in-process consumer, noting when each offset commit
    reached the broker: the cadence of commits is part of the delivery
    guarantee, and the benchmark reads it from its own side of the
    broker, not from the program's counters."""

    class Stamped(tk.MemoryConsumer):
        def commit(self, offsets=None):
            super().commit(offsets)
            stamps.append(time.perf_counter())

    return Stamped(broker, PROMPTS, group_id=GROUP)


def build_server(ctx, tk, params, cfg, consumer, producer, tracer):
    from torchkafka_tpu.serve import StreamingGenerator

    dep = ctx.conf["deployment"]
    return StreamingGenerator(
        consumer, params, cfg, slots=dep["slots"],
        prompt_len=dep["prompt_window"], max_new=dep["max_new"],
        ticks_per_sync=dep["ticks_per_sync"],
        commit_every=dep["commit_every"], kv_dtype=dep["kv_dtype"],
        kv_kernel=dep["kv_kernel"], output_producer=producer,
        output_topic=OUTPUT, encode_output=encode_output,
        max_new_of=budget_of, tracer=tracer,
    )


def run(ctx) -> dict:
    import jax

    import torchkafka_tpu as tk
    from torchkafka_tpu.obs import ObsConfig, RecordTracer

    conf, mix, dep = ctx.conf, ctx.mix, ctx.conf["deployment"]
    dims = W.Dims.from_conf(conf)
    window, max_new, slots = dep["prompt_window"], dep["max_new"], dep["slots"]
    cfg = ctx.model.program_config(conf, window + max_new)

    with ctx.phase("weights"):
        params = ctx.model.serving_params(conf, ctx.seed)
        jax.block_until_ready(params)

    broker = tk.InMemoryBroker()
    broker.create_topic(PROMPTS, partitions=dep["prompt_partitions"])
    broker.create_topic(OUTPUT, partitions=1)
    commit_stamps: list[float] = []
    consumer = stamped_consumer(tk, broker, commit_stamps)
    producer = tk.MemoryProducer(broker)
    tracer = RecordTracer(ObsConfig(
        clock=time.perf_counter, capacity=8_000_000, token_events=True,
    ))
    with ctx.phase("server"):
        server = build_server(ctx, tk, params, cfg, consumer, producer, tracer)
    backend = server.metrics.summary()["kv_backend"]
    ctx.say("kv_backend", backend)
    if dep.get("require_kernel_engaged") and backend["kernel_engaged"] != 1:
        raise common.Refused(
            f"the Pallas read does not serve: {backend}"
        )

    frame = {
        "prompt_window": window, "max_new": max_new, "vocab": dims.vocab,
        "seconds": ctx.seconds, "partitions": dep["prompt_partitions"],
    }
    plan = ctx.traffic.generate(mix["traffic"], ctx.seed, frame)
    records = plan["records"]

    # Warm-up: a few short requests through the whole path (admit, tick
    # block, sync, output, commit), so that every program and every small
    # host-side operation has compiled before the window.
    with ctx.phase("warmup"):
        warm = [
            _produce(broker, {**records[i % len(records)], "max_new": 2 + i})
            for i in range(int(mix.get("warmup_records", 3)))
        ]
        for _ in server.run(max_records=len(warm), idle_timeout_ms=200):
            pass
    warm_keys = {(r.partition, r.offset) for r in warm}
    counters = [server.metrics.summary()]

    sent: dict[tuple[int, int], dict] = {}
    stop_sending = threading.Event()

    def send_all(t0: float) -> None:
        for rec in records:
            wait = t0 + rec["due_s"] - time.perf_counter()
            if wait > 0 and stop_sending.wait(wait):
                return
            r = _produce(broker, rec)
            sent[(r.partition, r.offset)] = {
                "due": t0 + rec["due_s"], "sent": time.perf_counter(),
                "max_new": rec["max_new"], "prompt_len": len(rec["tokens"]),
                "tokens": rec["tokens"],
            }

    open_loop = bool(plan["open_loop"])
    grace = float(mix.get("grace_s", 0.0))
    if not open_loop:
        # The backlog: the topic holds every record before the window.
        send_all(time.perf_counter())
    ctx.open_window()
    t0 = ctx.t0
    sender = None
    if open_loop:
        sender = threading.Thread(target=send_all, args=(t0,), daemon=True)
        sender.start()
    deadline = t0 + ctx.seconds
    tp_out = tk.TopicPartition(OUTPUT, 0)
    with ctx.span("bench:serve_loop"):
        while True:
            gen = server.run(idle_timeout_ms=100)
            closed = False
            for _rec, _toks in gen:
                now = time.perf_counter()
                if not open_loop and now >= deadline:
                    closed = True  # the trace, if any, stops with the window
                    break
                ctx.trace_tick(now)
            gen.close()
            counters.append(server.metrics.summary())
            if closed:
                break
            now = time.perf_counter()
            ctx.trace_tick(now)
            if open_loop:
                due = sum(1 for v in list(sent.values()) if v["due"] <= deadline)
                # One output record a completion, the warm-up's too.
                done = broker.end_offset(tp_out) - len(warm)
                all_sent = sender is not None and not sender.is_alive()
                if now >= deadline and all_sent and done >= due:
                    break
                if now >= deadline + grace:
                    break
            elif now >= deadline:
                break
        # What the cadence of commits alone had made durable, read before
        # the flush that closes the window.
        t_before_flush = time.perf_counter()
        committed_before_flush = committed_offsets(tk, broker, dep)
        server.flush_commits()
    ctx.close_window()
    stop_sending.set()
    if sender is not None:
        sender.join()
    counters.append(server.metrics.summary())

    peak = common.memory_peak_bytes(ctx.devices)
    outputs = {}
    duplicates = 0
    for r in broker.fetch(tp_out, 0, broker.end_offset(tp_out)):
        p, o = _HEAD.unpack_from(r.value)
        if (p, o) in outputs:
            duplicates += 1
        outputs[(p, o)] = np.frombuffer(r.value[_HEAD.size:], np.int32)
    committed = committed_offsets(tk, broker, dep)
    end_offsets = {
        p: broker.end_offset(tk.TopicPartition(PROMPTS, p))
        for p in range(dep["prompt_partitions"])
    }
    events = list(tracer.events)
    dropped_events = tracer.dropped_events
    server.close()
    consumer.close()
    del server, params, tracer, consumer, producer
    gc.collect()

    requests = build_requests(events, sent, warm_keys)
    run = {
        "kind": "serve", "requests": requests, "slots": slots,
        "open_loop": open_loop, "deadline": deadline, "grace_s": grace,
        "memory_peak_bytes": peak, "counters": counters,
        "kv_backend": backend, "dims": dims,
        "prompt_window": window, "max_new": max_new,
        "t_before_flush": t_before_flush,
    }
    ctx.finish_trace(run)

    # ------------------------------------------------ attempted and failed
    in_window = [r for r in requests if r["due"] <= deadline]
    if open_loop:
        attempted = len(in_window)
        failed = sum(1 for r in in_window if r["finished"] is None)
    else:
        admitted = [r for r in requests if r["active"] is not None]
        attempted = len(admitted)
        failed = (
            sum(c["dropped"] for c in counters) + counters[-1]["quarantined"]
        )
    # --------------------------------------------------- the guarantees
    checks = ctx.checks
    finished = {
        (r["partition"], r["offset"]) for r in requests
        if r["finished"] is not None
    } | warm_keys
    for p, end in end_offsets.items():
        first_open = next(
            (o for o in range(end) if (p, o) not in finished), end
        )
        # After the last flush the committed offset is exactly the first
        # record of the partition that has not finished: no finished
        # record is left uncommitted that could be, none is committed
        # that did not finish.
        checks.exact(f"commit_watermark_gap.p{p}", committed[p] - first_open)
        if not open_loop:
            failed += max(0, first_open - committed[p])
    cadence = commit_cadence(
        requests, warm_keys, commit_stamps, t0, t_before_flush, end_offsets
    )
    for p, first_open in cadence["first_open"].items():
        # The cadence's own last commit, before any flush: it covered
        # every record that had finished in order by then, and no other.
        checks.exact(
            f"commit_watermark_gap_before_flush.p{p}",
            committed_before_flush[p] - first_open,
        )
    # At-least-once bounds what a crash replays by the cadence of
    # commits: after any sync fewer than ``commit_every`` completions
    # wait for a commit, so between two commits at most that and one
    # sync's completions (a slot each) can have finished.
    every = int(dep["commit_every"])
    checks.at_most(
        "completions_uncommitted_at_close", cadence["at_close"], every - 1
    )
    checks.at_most(
        "completions_between_commits", cadence["between"], every - 1 + slots
    )
    checks.exact(
        "commit_failures", sum(c["commit_failures"] for c in counters)
    )
    checks.exact("output_duplicates", duplicates)
    checks.exact(
        "outputs_missing",
        sum(1 for k in finished if k not in outputs),
    )
    checks.exact("tracer_events_dropped", dropped_events)
    bad_len = 0
    for r in requests:
        if r["finished"] is not None:
            got = outputs.get((r["partition"], r["offset"]))
            if got is None or len(got) != min(r["max_new"], max_new):
                bad_len += 1
    checks.exact("outputs_of_wrong_length", bad_len)
    if open_loop:
        def backlog(t):  # due and not yet in a slot
            return sum(
                1 for r in requests
                if r["due"] <= t and (r["active"] is None or r["active"] > t)
            )

        ctx.say("backlog", {
            "middle": backlog(t0 + ctx.seconds / 2), "end": backlog(deadline),
            "due_in_window": len(in_window),
            "finished_of_them": sum(
                1 for r in in_window if r["finished"] is not None
            ),
            "drained_s_after_window": max(
                [r["finished"] - deadline for r in in_window
                 if r["finished"] is not None] + [0.0]
            ),
        })
        ttft = [
            1e3 * (r["first"] - r["due"]) for r in in_window
            if r["first"] is not None
        ]
        tpot = [
            1e3 * t for t in (
                stats.tpot_s(r["first"], r["finished"], r["n_first"], r["n_tokens"])
                for r in in_window if r["finished"] is not None
            ) if t is not None
        ]
        ctx.say("tails", {
            "ttft_ms": {"median": common.pct(ttft, 50), "samples": len(ttft),
                        "beyond_p95": stats.samples_beyond(len(ttft), 95)},
            "tpot_ms": {"median": common.pct(tpot, 50), "samples": len(tpot),
                        "beyond_p95": stats.samples_beyond(len(tpot), 95)},
        })
        checks.at_most(
            "sender_late_p95_ms",
            1e3 * common.pct([r["sent"] - r["due"] for r in in_window], 95),
            float(mix.get("max_sender_late_ms", 50.0)),
        )

    # ------------------------------------------------ the plain reference
    done = [r for r in requests if r["finished"] is not None]
    ctx.say("served", {
        "requests_finished": len(done),
        "tokens_finished": sum(r["n_tokens"] for r in done),
        "admitted": sum(1 for r in requests if r["active"] is not None),
        "commits_in_window": cadence["commits"],
        "tokens_committed_before_flush": sum(
            r["n_tokens"] for r in done
            if r["committed"] is not None and r["committed"] <= t_before_flush
        ),
    })
    if done:
        with ctx.phase("reference"):
            run["sample"] = compare_with_reference(
                ctx, done, outputs, dims, window, max_new
            )
    else:
        checks.exact("requests_finished_is_zero", 1)
    run["attempted"], run["failed"] = attempted, failed
    return run


def committed_offsets(tk, broker, dep) -> dict[int, int]:
    return {
        p: broker.committed(GROUP, tk.TopicPartition(PROMPTS, p)) or 0
        for p in range(dep["prompt_partitions"])
    }


def commit_cadence(requests, warm_keys, stamps, t0, t_end, end_offsets) -> dict:
    """From the commits' stamps and the requests' ``finished`` stamps in
    ``[t0, t_end]``: the most completions between two successive commits,
    those after the last one, and for each partition the first offset
    that had not finished when the last commit was made (the warm-up's
    records finished before ``t0``)."""
    commits = sorted(t for t in stamps if t0 <= t <= t_end)
    edges = [t0, *commits, t_end]
    counts = [0] * (len(edges) - 1)
    done = sorted(
        r["finished"] for r in requests
        if r["finished"] is not None and t0 <= r["finished"] <= t_end
    )
    i = 0
    for t in done:
        while t > edges[i + 1]:
            i += 1
        counts[i] += 1
    t_last = commits[-1] if commits else t0
    by_then = {
        (r["partition"], r["offset"]) for r in requests
        if r["finished"] is not None and r["finished"] <= t_last
    } | set(warm_keys)
    first_open = {
        p: next((o for o in range(end) if (p, o) not in by_then), end)
        for p, end in end_offsets.items()
    }
    return {
        "commits": len(commits), "between": max(counts[:-1], default=0),
        "at_close": counts[-1], "first_open": first_open,
    }


def build_requests(events, sent, warm_keys) -> list[dict]:
    """One row a request, from the program's tracer and the sender's own
    stamps: due, sent, polled, active (admit dispatched), first (the first
    host sync that surfaced tokens, and how many), finished, committed."""
    rows: dict[tuple[int, int], dict] = {}
    for key, s in sent.items():
        rows[key] = {
            "partition": key[0], "offset": key[1], "due": s["due"],
            "sent": s["sent"], "max_new": s["max_new"],
            "prompt_len": s["prompt_len"], "prompt": s["tokens"],
            "polled": None, "active": None, "first": None, "n_first": 0,
            "syncs": [],
            "finished": None, "n_tokens": 0, "committed": None,
        }
    for e in events:
        key = (e.partition, e.offset)
        if e.topic != PROMPTS or key in warm_keys or key not in rows:
            continue
        r = rows[key]
        if e.stage == "polled" and r["polled"] is None:
            r["polled"] = e.t
        elif e.stage == "slot_active" and r["active"] is None:
            r["active"] = e.t
        elif e.stage == "tokens":
            n = dict(e.attrs)["n"]
            r["syncs"].append((e.t, n))
            if r["first"] is None:
                # The first sync surfaces the admission's own token
                # with the first block's.
                r["first"], r["n_first"] = e.t, n
        elif e.stage == "finished":
            r["finished"], r["n_tokens"] = e.t, dict(e.attrs)["tokens"]
            if r["first"] is None:
                r["first"], r["n_first"] = e.t, r["n_tokens"]
        elif e.stage == "committed":
            r["committed"] = e.t
    return sorted(rows.values(), key=lambda r: (r["due"], r["partition"], r["offset"]))


def compare_with_reference(ctx, done, outputs, dims, window, max_new,
                           lowp: bool = False) -> dict:
    """A sample of the finished requests, drawn from the seed with the
    longest in it, through the plain reference: the widest gap by which a
    served token's logit lies below the reference's best."""
    check = ctx.mix["check"]
    rng = np.random.default_rng([int(ctx.seed), 0xC4EC])
    longest = max(done, key=lambda r: r["n_tokens"])
    rest = [r for r in done if r is not longest]
    take = min(int(check["sample"]) - 1, len(rest))
    picked = [longest] + [
        rest[i] for i in rng.choice(len(rest), size=take, replace=False)
    ]
    toks = np.zeros((len(picked), window + max_new), np.int32)
    counts = []
    for i, r in enumerate(picked):
        served = outputs[(r["partition"], r["offset"])]
        toks[i, : r["prompt_len"]] = r["prompt"]
        toks[i, window: window + len(served)] = served
        counts.append(len(served))
    gap, top = ctx.reference.served_logit_gaps(
        ctx.seed, dims, toks, window - 1, max_new, lowp=lowp
    )
    gap, top = np.asarray(gap), np.asarray(top)
    valid = np.arange(max_new)[None, :] < np.asarray(counts)[:, None]
    widest = float(np.max(np.where(valid, gap, 0.0)))
    agree = float(np.mean(
        (top == toks[:, window: window + max_new])[valid]
    ))
    ctx.say("reference", {
        "requests": len(picked), "served_tokens": int(valid.sum()),
        "widest_logit_gap": widest, "first_choice_agreement": agree,
    })
    ctx.checks.at_most(
        "served_logit_gap", widest, float(check["max_logit_gap"])
    )
    return {"toks": toks, "valid": valid, "widest": widest}


def control(ctx, run) -> dict:
    """The control of the comparison: the reference in the precision
    below the configuration's (8-bit floating point operands), put in the
    program's place. At each position of the same prompts and served
    tokens, the gap of the token the lower precision puts first."""
    sample, dims = run["sample"], run["dims"]
    window, max_new = run["prompt_window"], run["max_new"]
    _gap, low_top = ctx.reference.served_logit_gaps(
        ctx.seed, dims, sample["toks"], window - 1, max_new, lowp=True
    )
    gap, _top = ctx.reference.served_logit_gaps(
        ctx.seed, dims, sample["toks"], window - 1, max_new,
        probe=np.asarray(low_top),
    )
    widest = float(np.max(np.where(sample["valid"], np.asarray(gap), 0.0)))
    return {"served_logit_gap": {
        "program": sample["widest"], "control": widest,
        "limit": float(ctx.mix["check"]["max_logit_gap"]),
    }}
