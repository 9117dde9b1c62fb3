"""The program's own profiler spans and scheduler counters
(``utils/tracing.py``, ``ServeMetrics``): a toy server, a toy
``KafkaStream`` loop and its commits run under ``jax.profiler`` on the CPU
and the trace is read back the way the benchmark reads one
(``jax.profiler.ProfileData``) — every span is there, on the thread that
did the work, and siblings do not overlap; the counters match a run
counted by hand."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchkafka_tpu as tk
from torchkafka_tpu.models.transformer import TransformerConfig, init_params
from torchkafka_tpu.serve import StreamingGenerator
from torchkafka_tpu.utils import tracing

P, MAX_NEW, VOCAB, SLOTS, TICKS = 8, 8, 64, 4, 4
PAGES = {"block_size": 4, "num_blocks": 40}

SERVE_SPANS = {
    tracing.SPAN_POLL, tracing.SPAN_ADMIT_PREP, tracing.SPAN_ADMIT,
    tracing.SPAN_TICK, tracing.SPAN_SYNC, tracing.SPAN_RETIRE,
    tracing.SPAN_OUTPUT_FLUSH, tracing.SPAN_COMMIT,
}
PRODUCER_SPANS = {
    tracing.SPAN_STREAM_POLL, tracing.SPAN_STREAM_TRANSFORM,
    tracing.SPAN_STREAM_TO_DEVICE,
}
COMMIT_SPANS = {
    tracing.SPAN_COMMIT_WAIT, tracing.SPAN_COMMIT_FETCH,
    tracing.SPAN_COMMIT_OFFSETS,
}


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=P + MAX_NEW, dtype=jnp.float32,
    )
    return cfg, init_params(jax.random.key(0), cfg)


def prompt_topic(n: int):
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=2)
    broker.create_topic("out", partitions=1)
    rng = np.random.default_rng(7)
    for i in range(n):
        broker.produce(
            "p", rng.integers(0, VOCAB, (P,), dtype=np.int32).tobytes(),
            partition=i % 2,
        )
    return broker


def toy_server(broker, model, **kw):
    cfg, params = model
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    return StreamingGenerator(
        consumer, params, cfg, slots=SLOTS, prompt_len=P, max_new=MAX_NEW,
        ticks_per_sync=TICKS, commit_every=2,
        output_producer=tk.MemoryProducer(broker), output_topic="out", **kw,
    )


def traced(tmp_path, work):
    """Run ``work`` under the profiler; the ``tk_*`` spans of each host
    thread as ``[(name, start_ns, end_ns)]``, one list a thread."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    threads = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events if e.name.startswith("tk_")
            ]
            if spans:
                threads.append(sorted(spans, key=lambda s: s[1]))
    return threads


def assert_siblings(spans) -> None:
    """No span of a thread starts before the one before it has ended."""
    for (a, _s, end), (b, start, _e) in zip(spans, spans[1:]):
        assert start >= end, f"{b} opens inside {a}"


def test_serving_spans_are_siblings_on_the_serving_thread(tmp_path, model):
    broker = prompt_topic(10)
    server = toy_server(broker, model)
    server.warmup()
    threads = traced(
        tmp_path, lambda: sum(1 for _ in server.run(max_records=10))
    )
    server.close()
    (spans,) = threads  # one thread does all of it
    assert {name for name, _s, _e in spans} == SERVE_SPANS
    assert_siblings(spans)
    count = lambda name: sum(1 for n, _s, _e in spans if n == name)  # noqa: E731
    # 10 records through 4 slots: three admissions; six blocks of 4 ticks
    # (8 tokens a record, the first of them the admission's).
    assert count(tracing.SPAN_ADMIT) == count(tracing.SPAN_ADMIT_PREP) == 3
    assert count(tracing.SPAN_TICK) == count(tracing.SPAN_SYNC) == 6
    assert count(tracing.SPAN_RETIRE) == 6
    # Completions come four, four and two at a time, each past the
    # cadence of 2: three commits, each behind its output flush.
    assert count(tracing.SPAN_COMMIT) == count(tracing.SPAN_OUTPUT_FLUSH) == 3
    order = [n for n, _s, _e in spans]
    first_admit = order.index(tracing.SPAN_ADMIT)
    assert order[first_admit - 1] == tracing.SPAN_ADMIT_PREP
    assert tracing.SPAN_POLL in order[:first_admit]
    assert order[order.index(tracing.SPAN_SYNC) + 1] == tracing.SPAN_RETIRE


def test_chunked_admission_is_all_preparation(tmp_path, model):
    broker = prompt_topic(6)
    server = toy_server(broker, model, kv_pages=PAGES)
    server.warmup()
    (spans,) = traced(
        tmp_path, lambda: sum(1 for _ in server.run(max_records=6))
    )
    server.close()
    names = {name for name, _s, _e in spans}
    assert tracing.SPAN_ADMIT not in names  # the fused tick prefills
    assert {tracing.SPAN_ADMIT_PREP, tracing.SPAN_CHUNK_PACK} <= names
    assert_siblings(spans)


@pytest.mark.parametrize("prefetch", [2, 0])
def test_stream_and_commit_spans_on_their_threads(tmp_path, prefetch):
    broker = tk.InMemoryBroker()
    broker.create_topic("t", partitions=2)
    rng = np.random.default_rng(3)
    for i in range(32):
        broker.produce(
            "t", rng.integers(0, VOCAB, (16,), dtype=np.int32).tobytes(),
            partition=i % 2,
        )
    consumer = tk.MemoryConsumer(broker, "t", group_id="g")
    step = jax.jit(lambda x: (x.astype(jnp.float32) ** 2).mean())
    steps = []

    def loop():
        with tk.KafkaStream(
            consumer, tk.fixed_width(16, np.int32), batch_size=4,
            idle_timeout_ms=200, prefetch=prefetch, owns_consumer=True,
        ) as stream:
            for batch, token in stream:
                steps.append(token.commit(wait_for=step(batch.data)))

    threads = traced(tmp_path, loop)
    assert steps == [True] * 8
    by_names = {frozenset(n for n, _s, _e in t): t for t in threads}
    if prefetch:
        # The producer thread polls, transforms and ships; the loop's own
        # thread waits for a batch and commits.
        producer = by_names[frozenset(PRODUCER_SPANS)]
        caller = by_names[frozenset(COMMIT_SPANS | {tracing.SPAN_STREAM_NEXT})]
        assert sum(
            1 for n, _s, _e in caller if n == tracing.SPAN_STREAM_NEXT
        ) == 9  # eight batches and the end of the stream
    else:
        # Synchronous mode: one thread, and no wait for another.
        (producer,) = threads
        caller = producer
        assert set(by_names) == {frozenset(PRODUCER_SPANS | COMMIT_SPANS)}
    assert len(threads) == len(by_names)
    for spans in threads:
        assert_siblings(spans)
    count = lambda t, name: sum(1 for n, _s, _e in t if n == name)  # noqa: E731
    assert count(producer, tracing.SPAN_STREAM_TO_DEVICE) == 8
    assert count(producer, tracing.SPAN_STREAM_TRANSFORM) >= 1
    for name in COMMIT_SPANS:
        assert count(caller, name) == 8
    # No pod here: the barrier never synchronises across processes.
    assert not any(
        n == tracing.SPAN_COMMIT_SYNC for t in threads for n, _s, _e in t
    )


def test_scheduler_counters_match_a_hand_counted_run(model):
    """Ten records of 8 tokens through 4 slots, 4 ticks a sync. Three
    admissions (4, 4 and 2 rows), each one chunk of 4 rows (an 8-token
    window: a chunk holds every slot); six tick blocks of 4 slots x 4
    ticks; 80 tokens, 10 of them the admissions' own, so 70 of the 96
    slot-ticks served a token."""
    broker = prompt_topic(10)
    server = toy_server(broker, model)
    served = sum(1 for _ in server.run(max_records=10))
    got = server.metrics.summary()["scheduler"]
    assert served == 10
    assert got == {
        "slot_ticks_run": 96, "slot_ticks_served": 70, "admit_calls": 3,
        "admit_rows": 10, "admit_rows_prefilled": 12,
    }
    # Cumulative: a second run() resets the rate clocks, not these.
    for i in range(2):
        broker.produce("p", np.zeros((P,), np.int32).tobytes(), partition=i)
    assert sum(1 for _ in server.run(max_records=2)) == 2
    again = server.metrics.summary()["scheduler"]
    assert again["admit_rows"] == 12 and again["admit_calls"] == 4
    assert again["slot_ticks_run"] == 96 + 2 * SLOTS * TICKS
    assert again["slot_ticks_served"] == 70 + 2 * (MAX_NEW - 1)
    text = server.metrics.render_prometheus()
    for name, value in again.items():
        assert f"torchkafka_serve_{name}_total {value}\n" in text
    server.close()


@pytest.mark.parametrize("records,chunks,prefilled", [
    (10, 5, 10),  # admissions of 4, 4 and 2 rows: 2, 2 and 1 chunks, no pad
    (3, 2, 4),  # one admission of 3 rows: the second chunk pads a row
    (1, 1, 2),  # one row prefills a chunk
])
def test_a_chunked_admission_prefills_what_its_chunks_hold(
    model, monkeypatch, records, chunks, prefilled
):
    """The chunk constant patched so that a chunk is 2 of the 4 slots: an
    admission prefills its rows rounded up to whole chunks, not the pool."""
    from torchkafka_tpu import serve

    monkeypatch.setattr(serve, "_ADMIT_CHUNK_TOKENS", 2 * P)
    server = toy_server(prompt_topic(records), model)
    assert sum(1 for _ in server.run(max_records=records)) == records
    got = server.metrics.summary()["scheduler"]
    assert got["admit_rows"] == records
    assert got["admit_rows_prefilled"] == prefilled == 2 * chunks
    assert got["slot_ticks_served"] == records * (MAX_NEW - 1)
    assert f"torchkafka_serve_admit_rows_prefilled_total {prefilled}\n" in (
        server.metrics.render_prometheus()
    )
    server.close()


def test_a_budget_clamps_the_served_count(model):
    """A record whose budget is 3 tokens holds its slot for a whole block
    of 4 ticks and is served 2 of them (the first token is the
    admission's): overshoot is run, not served."""
    broker = prompt_topic(1)
    server = toy_server(broker, model, max_new_of=lambda _rec: 3)
    (done,) = list(server.run(max_records=1))
    assert len(done[1]) == 3
    got = server.metrics.summary()["scheduler"]
    assert got["slot_ticks_run"] == SLOTS * TICKS
    assert got["slot_ticks_served"] == 2
    assert (got["admit_rows"], got["admit_rows_prefilled"]) == (1, SLOTS)
    server.close()


def test_paged_admission_prefills_only_what_it_admits(model):
    broker = prompt_topic(6)
    server = toy_server(broker, model, kv_pages=PAGES)
    assert sum(1 for _ in server.run(max_records=6)) == 6
    got = server.metrics.summary()["scheduler"]
    assert got["admit_rows"] == got["admit_rows_prefilled"] == 6
    assert got["slot_ticks_served"] == 6 * (MAX_NEW - 1)
    server.close()
