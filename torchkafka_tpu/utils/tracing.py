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
``tk_qmatmul``, ``tk_gmm_gate_up``, ``tk_gmm_down``, ``tk_kda_step``,
``tk_ssd_step`` — the device trace names each kernel's operation after
them. Two names of the same style are NOT kernels: ``tk_gconv_step`` and
``tk_gconv_seq`` (``ops/gconv.py``) are ``jax.named_scope`` path elements
around the gated short convolution's own part (gates, taps, tail) of a
tick and of an admission, inside ``tk_attn_proj`` and ``tk_attn_flash``:
XLA fuses that part as it sees fit, and the benchmark finds its
operations by the name in their ``op_name``
(``chipbench/layer_metrics/_gconv.py``).

The device programs name their parts (``jit_admit``, ``jit_tick_block``,
``jit__step`` and whatever else traces the shared model code): twelve
``jax.named_scope`` names, the ``SCOPE_*`` constants below, opened through
``scope`` where the work is written, once, in the shared building blocks.
A scope costs a context manager while a program is traced and nothing in
the compiled program: it becomes the ``metadata={op_name=...}`` of the HLO
instructions traced inside it, and the optimised HLO is otherwise the
same. The profiler's operation line carries no scope; its file does, in
the ``/host:metadata`` plane, which holds the ``HloProto`` of every
module that ran (``chipbench/layer_metrics/_scopes.py`` reads it back and
joins it with the operation line by instruction name). Of nested scopes
the innermost counts; a Pallas call's own name (below) sits inside the
scope that holds it. The names, with what each holds and the functions
that open it:

    tk_embed           the token embedding and its multiplier
                       (transformer.embed_tokens): Transformer.trunk,
                       generate.prefill / _prefill_kinds / latent_forward
                       / _decode_one, serve.py's tick_block
    tk_attn_proj       the norm before attention, q/k/v or latent
                       projections, rope (YaRN), the output projection,
                       its residual and the norm after it:
                       transformer._rope, Transformer._gqa_qkv (_gqa's
                       and a hybrid's grouped-query layer's),
                       transformer.index_project / index_key (learned
                       sparse attention's indexer) /
                       _layer_capture, _double_layer, mla.project /
                       attend_full (the latent's up-projection) /
                       attend_absorbed (the absorbed products),
                       generate._project_qkv / _attn_tail_routing /
                       prefill's k and v, slot_pool._slot_layer_step_latent;
                       a linear layer's in-projections, convolution,
                       gates and gated norm (linear_attn._project /
                       _conv_qkv / _finish, the delta rule's;
                       _ssd_project / _ssd_conv / _ssd_finish, the
                       Mamba-2 mixer's) and linear_attn.layer_forward /
                       slot_layer_step's norm
    tk_kv_write        quantisation and the row or ring write:
                       slot_pool._quant_kv, _slot_layer_step*, admit's
                       put, generate.ring_rows, prefill's pools, a linear
                       layer's conv tail (linear_attn._kda_step /
                       _ssd_step)
    tk_kv_read         scores, softmax and values over CACHED positions,
                       a pool of one kind: generate._read_cached
                       (_attend_cached's read),
                       slot_pool._slot_layer_step_q
                       (the Pallas call tk_kvattn_dynlen, the row write
                       it holds too); a linear layer's pass over its
                       recurrent state, which IS its cache
                       (linear_attn._kda_step / _ssd_step: the Pallas
                       calls tk_kda_step / tk_ssd_step, or the
                       jax.numpy steps off the TPU)
    tk_kv_read_window  the same over a window layer's ring:
                       slot_pool._slot_layer_step(kind=) names it to
                       generate._attend_merged
    tk_kv_read_full    the same over a full layer's slab of a pool by
                       kind: likewise; and over the K and V rows of a
                       hybrid's grouped-query layer
                       (slot_pool.StatePool hands it the same step);
                       under learned sparse attention the index
                       scoring, the selection and the selected read
                       (slot_pool._slot_layer_step_indexed: the Pallas
                       calls tk_dsa_index and tk_dsa_attend around a
                       top-k)
    tk_kv_read_latent  the absorbed read of the latent pool:
                       mla._read_latent (attend_absorbed's read)
    tk_attn_flash      attention over a whole sequence, the flash kernels
                       and XLA's form: Transformer._attention,
                       mla._attend_whole (attend_full's attention), the
                       linear layers' chunked forms
                       (linear_attn._kda_sequence: kda.kda_chunk;
                       _ssd_sequence: ssd.ssd_chunk); learned sparse
                       attention's index scores, selection and selected
                       flash forward (Transformer._gqa:
                       dsa.sparse_prefill_attention, tk_flash_fwd_sel)
    tk_ffn             the dense FFN and the shared experts:
                       transformer._dense_mlp, moe.routed_moe_mlp
    tk_moe_route       router scores, bias, top-k, renormalisation, the
                       sort, group sizes and the counts of what was
                       routed: moe.route, grouped_experts,
                       compacted_experts, grouped_counts,
                       slot_pool._count_routing
    tk_moe_dispatch    rows gathered into expert order, a tile's gather,
                       the inverse gather, the weighted sum or
                       scatter-add: moe.grouped_experts,
                       compacted_experts, all_experts, routed_moe_mlp
                       (the zero experts' term)
    tk_moe_experts     the expert products themselves (tk_gmm_*, a tile's
                       three products, the all-experts einsum): moe._gmm,
                       compacted_experts::tile, all_experts
    tk_head            the final norm, the head's product, sampling:
                       Transformer.trunk (the final norm) / __call__,
                       generate.head_logits (prefill, _prefill_kinds,
                       latent_forward, _decode_one and serve.py's
                       tick_block call it) / sample_logits,
                       serve._pick_slots
    tk_loss            the blocked cross-entropy: Transformer.loss
    tk_optimizer       the optimizer's update and its addition to the
                       parameters: make_train_step::_step

``tk_flash_out``, ``tk_flash_lse`` and ``tk_attn_residual`` are the remat
policy's names (``ops/flash.py::REMAT_SAVED``,
``models/transformer.py::REMAT_SAVED_TP``), not tracing.

Importing this module puts metadata into the persistent compilation
cache's key (``jax_compilation_cache_include_metadata_in_key``; the note
above the constants says why): an executable read from the cache carries
the HLO it was compiled from, names and all.

Record-level lifecycle tracing (who waited where, per record) is the
separate ``torchkafka_tpu.obs`` subsystem; these annotations are the
profiler-timeline complement.
"""

from __future__ import annotations

import contextlib
import os
import re
import time
from typing import Callable, Iterator

import jax

# The persistent compilation cache leaves metadata out of its key by
# default, so a program whose HLO differs from an older tree's by its
# names alone is handed the executable the older tree compiled, and with it
# the older HLO: a profile then shows no scope (PR 38: a tick served from
# its parent's cache entry read 100% unscoped on the chip). With metadata
# in the key, a tree whose names or lines moved compiles its own entries.
# Source locations are metadata too, so the checkout's own path is taken
# out of them: a checkout that moves keeps its entries.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
if jax.config.jax_hlo_source_file_canonicalization_regex is None:
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        "^" + re.escape(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))) + os.sep),
    )

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

# Scope names of the device programs (the docstring lists who opens each).
SCOPE_EMBED = "tk_embed"
SCOPE_ATTN_PROJ = "tk_attn_proj"
SCOPE_KV_WRITE = "tk_kv_write"
SCOPE_KV_READ = "tk_kv_read"
SCOPE_KV_READ_WINDOW = "tk_kv_read_window"
SCOPE_KV_READ_FULL = "tk_kv_read_full"
SCOPE_KV_READ_LATENT = "tk_kv_read_latent"
SCOPE_ATTN_FLASH = "tk_attn_flash"
SCOPE_FFN = "tk_ffn"
SCOPE_MOE_ROUTE = "tk_moe_route"
SCOPE_MOE_DISPATCH = "tk_moe_dispatch"
SCOPE_MOE_EXPERTS = "tk_moe_experts"
SCOPE_HEAD = "tk_head"
SCOPE_LOSS = "tk_loss"
SCOPE_OPTIMIZER = "tk_optimizer"


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


def scope(name: str):
    """Name the device work traced inside: a ``jax.named_scope``."""
    return jax.named_scope(name)


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
