"""Profiler hooks: XLA traces and named spans for ingest AND serving.

The reference has no instrumentation at all (SURVEY.md §5 tracing row). On
TPU the tool that matters is the XLA profiler — these helpers wire the
host loops into it so a trace shows the named host stages on the timeline,
on the device trace's own clock:

    with tracing.trace_session("/tmp/trace"):
        for batch, token in stream:
            loss = train_step(batch.data)
            token.commit(wait_for=loss)
    # then: xprof / tensorboard --logdir /tmp/trace

Every span is a ``jax.profiler.TraceAnnotation`` opened through ``span``:
a few hundred nanoseconds with the profiler off. Each is opened once a
poll chunk, a batch, an admit, a tick block or a commit — never once a
record or a token. The names, with the function that opens each:

Serving (``serve.py``; siblings on the serving thread, none inside
another):

    tk_serve:poll          run(): consumer.poll + note_fetched
    tk_serve:admit_prep    admit_records(): decode, journal hints, the
                           [slots, prompt] batch and its transfer, up to
                           the dispatch (paged admission dispatches
                           nothing and is all preparation)
    tk_serve:admit         admit_records(): the prefill-admission
                           dispatch (dense)
    tk_serve:chunk_pack    step(): host packing of the fused tick's
                           prefill chunk
    tk_serve:tick          step(): the decode (or fused chunk) tick-block
                           dispatch
    tk_serve:sync          step(): the once-per-tick-block host sync
                           (device_get)
    tk_serve:retire        step(): from the sync's return to the end of
                           its bookkeeping: budget clamp, tracer and
                           journal, _retire_completion (output send, ledger)
    tk_serve:output_flush  _commit(): the output producer's flush and the
                           waits on the send handles
    tk_serve:commit        _commit(): consumer.commit(snapshot) — the
                           broker's offset commit call alone

Training ingest (``pipeline/stream.py``; poll, transform and to_device on
the producer thread — on the caller's in synchronous mode, prefetch=0 —
and next on the caller's):

    tk_stream:poll         _produce_loop() / _next_sync(): consumer.poll
    tk_stream:transform    _process_chunk(): ledger, processor, batcher
    tk_stream:to_device    _to_dev(): the batch's device transfer
    tk_stream:next         __next__(): the consumer's wait for a batch
                           from the producer thread

Commit (``commit/barrier.py``, ``commit/token.py``; on the thread that
called ``token.commit``):

    tk_commit:wait         CommitBarrier: jax.block_until_ready(wait_for)
    tk_commit:fetch        CommitBarrier (strict): the one-scalar
                           device_get that proves the step retired
    tk_commit:sync         CommitBarrier: sync_global_devices
                           (multi-process pods only)
    tk_commit:offsets      CommitToken.commit(): consumer.commit(offsets)

The Pallas kernels carry fixed names too (``pl.pallas_call(name=...)`` in
``ops/``): ``tk_kvattn_dynlen``, ``tk_kvattn_paged``, ``tk_flash_fwd``,
``tk_flash_fwd_win``, ``tk_flash_bwd_dq``, ``tk_flash_bwd_dkv``,
``tk_qmatmul``, ``tk_gmm_gate_up``, ``tk_gmm_down`` — the device trace
names each kernel's operation after them.

Record-level lifecycle tracing (who waited where, per record) is the
separate ``torchkafka_tpu.obs`` subsystem; these annotations are the
profiler-timeline complement.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Iterator

import jax

# Span names (one place, so the README recipe, the program and whatever
# reads a trace back agree).
SPAN_POLL = "tk_serve:poll"
SPAN_ADMIT_PREP = "tk_serve:admit_prep"
SPAN_ADMIT = "tk_serve:admit"
SPAN_CHUNK_PACK = "tk_serve:chunk_pack"
SPAN_TICK = "tk_serve:tick"
SPAN_SYNC = "tk_serve:sync"
SPAN_RETIRE = "tk_serve:retire"
SPAN_OUTPUT_FLUSH = "tk_serve:output_flush"
SPAN_COMMIT = "tk_serve:commit"
SPAN_STREAM_POLL = "tk_stream:poll"
SPAN_STREAM_TRANSFORM = "tk_stream:transform"
SPAN_STREAM_TO_DEVICE = "tk_stream:to_device"
SPAN_STREAM_NEXT = "tk_stream:next"
SPAN_COMMIT_WAIT = "tk_commit:wait"
SPAN_COMMIT_FETCH = "tk_commit:fetch"
SPAN_COMMIT_SYNC = "tk_commit:sync"
SPAN_COMMIT_OFFSETS = "tk_commit:offsets"


@contextlib.contextmanager
def trace_session(logdir: str) -> Iterator[None]:
    """Capture an XLA profiler trace for the enclosed block."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """Annotate a host-side region on the profiler's timeline."""
    return jax.profiler.TraceAnnotation(name)


def ingest_lag_ms(
    record_timestamp_ms: int,
    now_ms: float | None = None,
    clock: Callable[[], float] | None = None,
) -> float:
    """End-to-end lag: record append time -> now. The streaming SLO metric
    (how far behind the head of the topic the consumer is running).

    ``clock`` returns SECONDS on the same timeline record timestamps are
    stamped from (epoch seconds for real brokers) — inject a
    ``resilience.ManualClock.now`` and lag becomes exactly testable
    instead of wall-clock-dependent; ``now_ms`` overrides both (legacy
    spelling, kept for callers that already hold a reading)."""
    if now_ms is None:
        now_ms = (clock() if clock is not None else time.time()) * 1e3
    return max(0.0, now_ms - record_timestamp_ms) if record_timestamp_ms else 0.0
