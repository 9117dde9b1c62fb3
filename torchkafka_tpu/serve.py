"""Continuous-batching generation server: the streaming-native serving loop.

Extends BASELINE config 5 (prompt topic → generate → commit-after-generation)
from lockstep batches to CONTINUOUS batching: a fixed pool of decode slots,
prompts admitted into free slots as earlier generations finish (EOS or
max_new), offsets marked done per COMPLETION and committed through the same
interval ledger the ingest pipeline uses — so a long generation never blocks
the commit watermark behind it, and at-least-once delivery holds per prompt.
No reference analog (the reference has no models, SURVEY.md §2); this is the
TPU-idiomatic serving pattern (static shapes, slot masks) the way vLLM-style
continuous batching is the GPU one.

XLA shape discipline: everything is static — the slot pool is [B] with
per-slot positions, the admission step always prefills a full [B, P] batch
(rows masked by an admit mask; wasted rows cost one prefill of padding),
and the decode tick advances all B slots with inactive slots masked out.
Slot kv-cache rows are recycled without clearing: a freed slot's stale tail
is overwritten position-by-position before each position becomes readable
(the decode step writes kv at ``pos`` before attending over ``[0, pos]``).
That write-before-attend recycling is an ASSERTED invariant, not a hope:
tests/test_kvcache.py poisons every not-yet-readable cache position after
a recycled admission and requires byte-identical outputs, on BOTH the
dense pool and the paged one (``kv_pages=`` — the block-pool +
radix-prefix-reuse mode, torchkafka_tpu/kvcache, where "stale tail" also
covers freed blocks re-allocated to other slots and idle slots' writes
routed to the sink block).

Citations: commit-exactly-what-completed mirrors the reference's
commit-after-batch contract (/root/reference/src/auto_commit.py:55-58)
generalised to out-of-order completions via the OffsetLedger.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
import zlib
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from torchkafka_tpu.commit.ledger import OffsetLedger
from torchkafka_tpu.errors import (
    BrokerUnavailableError,
    CommitFailedError,
    ConsumerClosedError,
    OutputDeliveryError,
    ProducerFencedError,
)
from torchkafka_tpu.journal import DecodeJournal, JournalEntry, value_crc
from torchkafka_tpu.kvcache import (
    SINK_BLOCK,
    BlockAllocator,
    HostTier,
    KVBackend,
    PagedKVConfig,
    RadixCache,
    TierConfig,
    resolve_kv_backend,
)
from torchkafka_tpu.kvcache.slot_pool import make_slot_pool
from torchkafka_tpu.resilience.crashpoint import crash_hook
from torchkafka_tpu.models.generate import (
    _attn_tail,
    _project_qkv,
    check_sampling_params,
    check_serving_mesh,
    head_logits,
    paged_pool_kmajor_sharding,
    paged_pool_sharding,
    paged_scale_kmajor_sharding,
    prefill,
    sample_logits,
    serving_shardings,
    slot_sharding,
)
from torchkafka_tpu.models.quant import embed_rows, load_weight
from torchkafka_tpu.models.transformer import (
    TransformerConfig,
    _arch_refusal,
    _rms_norm,
    _rope,
    embed_tokens,
)
from torchkafka_tpu.ops import moe
from torchkafka_tpu.source.records import Record, TopicPartition
from torchkafka_tpu.utils import tracing as xprof
from torchkafka_tpu.utils.metrics import Gauge, LatencyHistogram, RateMeter

_logger = logging.getLogger(__name__)

# Prompt tokens one trip of the dense admission prefills (``_build::admit``
# walks the admitted slots in chunks of ``_ADMIT_CHUNK_TOKENS // window``
# rows). A trip costs a pass over the weights and its rows: small chunks
# pad the last trip less, large ones stream the weights less often (a
# dense 7B gains down to 4 rows of 512, a routed-expert model wants 8: the
# v5e's readings of 2,048, 3,072, 4,096 and 8,192 are in PERF.md, PR 28).
# The program's shapes decide, never an option.
_ADMIT_CHUNK_TOKENS = 3072


@xprof.scope(xprof.SCOPE_HEAD)
def _pick_slots(logits, key_data, idx, *, temperature, top_k, top_p):
    """Per-slot sampling with per-(record, token-index) keys.

    ``logits``: [B, V]; ``key_data``: [B, W] uint32 — each row the raw
    key data of that slot's RECORD key (derived once at admit from the
    record's identity, ``StreamingGenerator._records_key_data``); ``idx``:
    [B] int32 — the gen-buffer index of the token being sampled. Row b
    draws with ``fold_in(record_key_b, idx_b)``, so a record's token i is
    the same draw no matter which slot, tick, replica, or process decodes
    it — the property warm failover's token-exactness stands on (a
    journal-resumed continuation replays the identical key sequence).
    Greedy (temperature 0) ignores the keys, as everywhere else."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    keys = jax.vmap(jax.random.fold_in)(
        jax.random.wrap_key_data(key_data), idx
    )
    return jax.vmap(
        lambda row, k: sample_logits(
            row, k, temperature=temperature, top_k=top_k, top_p=top_p
        )
    )(logits, keys)


class ServeMetrics:
    """Observability for the serving loop, mirroring StreamMetrics'
    shape (utils/metrics.py) so dashboards treat both uniformly."""

    def __init__(self) -> None:
        self.completions = RateMeter()
        self.tokens = RateMeter()
        self.truncated = RateMeter()  # stopped by EOS before max_new
        self.readmissions = RateMeter()  # slots refilled MID-STREAM (while
        # other generations were in flight) — continuous batching's defining
        # behavior; 0 in lockstep-equivalent runs
        self.dropped = RateMeter()  # undecodable prompts retired
        self.quarantined = RateMeter()  # poison prompts dead-lettered
        self.commit_failures = RateMeter()
        self.output_flush_failures = RateMeter()  # output topic not durable
        self.output_send_failures = RateMeter()  # sync send refusals (stall)
        self.dlq_delivery_failures = RateMeter()  # quarantine DLQ produces
        # that FAILED (the serve path fail-stops on them, but the count
        # outlives the crash on /metrics — a broken DLQ must page, not
        # only kill)
        # Exactly-once output (exactly_once=True): one transaction per
        # commit window. All zero in at-least-once mode.
        self.txn_commits = RateMeter()  # transactions committed (records
        # + offsets atomic)
        self.txn_aborts = RateMeter()  # transactions aborted (survivable
        # commit failure, send fault, or defensive abort)
        self.txn_held_outputs = Gauge()  # outbox entries a commit could
        # NOT yet publish: finished out of completion order, their
        # offsets above the in-order watermark — published by a later
        # window the moment the watermark passes them
        self.commit_latency = LatencyHistogram()  # full commit path: output
        # flush + durability waits + offset commit (see _commit docstring)
        self.slot_occupancy = Gauge()  # active slots / pool size at the
        # LAST sync only (a held slot counts, past its budget or not); the
        # share over a window is slot_ticks_served / slot_ticks_run
        # Per-tick serving step time (host-observed: chunk pack + device
        # dispatch + sync) and tokens surfaced per tick block — the
        # device-side "where did the tick go" companion to the obs
        # layer's host-side record spans.
        self.tick_time = LatencyHistogram()
        self.tokens_per_tick = Gauge()  # tokens the LAST tick block surfaced
        # Where the scheduler wastes work — cumulative (never reset), so a
        # reader takes the difference over its own window.
        self.slot_ticks_run = RateMeter()  # slots x ticks of every tick
        # block dispatched: what the device was asked to decode
        self.slot_ticks_served = RateMeter()  # tokens those ticks surfaced,
        # after the budget clamp (a slot's first token is its admission's,
        # not a tick's): idle slots and slots past their budget tick for
        # nothing
        self.admit_calls = RateMeter()  # admit_records calls that prefilled
        self.admit_rows = RateMeter()  # rows admitted by a prefill
        self.admit_rows_prefilled = RateMeter()  # rows the prefill programs
        # ran: chunks x rows a chunk on the dense path (the last chunk of
        # a call is padded to its static rows), the admitted rows alone on
        # the paths that prefill row by row
        # The routed expert layer and the latent pool (a latent-attention
        # config; all zero otherwise). Cumulative like the scheduler's.
        self.moe_experts_touched = RateMeter()  # sum over expert layers and
        # ticks of the experts that got at least one pair from a slot the
        # device held active (what a tick has to stream, counted on the
        # device and fetched with the sync's other arrays)
        self.moe_expert_load: np.ndarray = np.zeros((0,), np.int64)  # those
        # pairs by expert (by HELD expert, where a share is held), summed
        # over layers and ticks
        self.moe_assignments = RateMeter()  # the (token, choice) pairs of
        # those slot-ticks, counted with the load: the sum of the three
        # fates a pair can have. It chose an expert whose weights are here
        # (every pair, where the layer holds every expert), the identity
        # (``zero_experts``), or an expert on another chip
        # (``experts_held``), which adds nothing here.
        self.moe_zero_assignments = RateMeter()
        self.moe_local_assignments = RateMeter()
        self.moe_absent_assignments = RateMeter()
        # The admission's grouped expert matmul (ops/moe.py; None and zero
        # where the admit program does not hold it): what multiplies the
        # sorted pairs, the pairs it multiplied and the rows of the pieces
        # (128 rows) its kernels multiplied, every piece whole: rows /
        # tile_rows is the pieces' fill.
        self.grouped_matmul: str | None = None
        # The form a decode tick sums its routed experts by, decided from
        # the static shapes where the tick is built (ops/moe.py::
        # expert_form): "grouped", "compacted", "all_experts"; None: no
        # routed layer.
        self.tick_form: str | None = None
        self.moe_grouped_rows = RateMeter()
        self.moe_grouped_tile_rows = RateMeter()
        self.experts_held: list[int] | None = None  # [first, count]
        # Group-limited selection (``n_group``, ``topk_group``; 1 and 1
        # where the router has no groups).
        self.expert_groups: dict = {"n_group": 1, "topk_group": 1}
        # The linear layers' slot memory (a config with
        # ``linear_pattern``; empty otherwise): the recurrence's kind
        # ("kda", "ssd"), the layers, the bytes of the recurrent state and
        # of the conv tails, the state's dtype, what a tick passes over
        # the state by ("kernel": tk_kda_step, tk_ssd_step; "xla"), the
        # admission's form ("chunked") and its chunk's tokens.
        self.linear_state: dict = {}
        self.attn_blocks = 1  # attention blocks (cache rows) a layer
        self.latent_positions_valid = RateMeter()  # cached rows the served
        # ticks needed, summed over blocks: a tick at position p needs p
        self.latent_positions_read = RateMeter()  # rows the read fetched for
        # every slot of every tick run, needed or not
        # The pool by layer kind (a config with ``window_pattern``; empty
        # and zero otherwise): its shape, and the cached rows the served
        # ticks needed against those every tick run fetched, by kind and
        # summed over the kind's layers. A window layer's tick at position
        # p needs min(p + 1, window) rows and its read fetches the ring.
        self.kv_pool_static: dict = {}
        self.window_positions_valid = RateMeter()
        self.window_positions_read = RateMeter()
        self.full_positions_valid = RateMeter()
        self.full_positions_read = RateMeter()
        # The indexed pool (learned sparse attention, ``index_topk``; in
        # no other pool's summary), summed over the layers: index keys the served ticks
        # needed against those ``tk_dsa_index`` fetched; rows the slots
        # held, rows the model selects of them (``min(held, topk)``) and
        # rows ``tk_dsa_attend`` fetched.
        self.index_positions_valid = RateMeter()
        self.index_positions_read = RateMeter()
        self.sparse_positions_valid = RateMeter()
        self.sparse_positions_selected = RateMeter()
        self.sparse_positions_read = RateMeter()
        self.output_capped = RateMeter()  # slots finished by a per-record
        # output budget (max_new_of) below max_new, and not by EOS: latched
        # by the tick on the device, or cut by the host's clamp at the sync
        # where a tick program does not take the budget
        # Paged prefix cache (kv_pages=, torchkafka_tpu/kvcache): all zero
        # on the dense path.
        self.prefix_hits = RateMeter()  # admissions that reused cached blocks
        self.prefix_misses = RateMeter()  # admissions that prefilled in full
        self.prefix_tokens_saved = RateMeter()  # prompt tokens NOT re-prefilled
        self.prefill_tokens = RateMeter()  # prompt tokens actually prefilled
        self.cache_evictions = RateMeter()  # cached blocks LRU-evicted
        self.admission_deferrals = RateMeter()  # admissions deferred on pool
        # pressure (records re-offered FIFO once blocks free)
        self.cache_fallbacks = RateMeter()  # paged → dense cache-off fallbacks
        self.cache_pool_occupancy = Gauge()  # allocated / usable blocks
        # Tiered radix cache (kv_tier=, kvcache/tier.py): cold prefix
        # blocks demoted to host RAM instead of freed, promoted back on
        # radix hit. All zero without a tier.
        self.radix_demotions = RateMeter()  # blocks demoted HBM → host tier
        self.radix_promotions = RateMeter()  # blocks promoted tier → HBM
        self.tier_hits = RateMeter()  # prefix walks extended by the tier
        self.tier_occupancy_bytes = Gauge()  # host-RAM tier payload bytes
        # Disaggregated prefill (fleet/prefill.py): records held for a
        # prefill-worker handoff and slots admitted by adopting one
        # (decode never ran the prompt pass). All zero in monolithic
        # serving.
        self.prefill_routed = RateMeter()  # records first held awaiting a
        # handoff (the admission-queue routing decision)
        self.adopted_slots = RateMeter()  # slots filled by handoff adoption
        self.handoffs_published = RateMeter()  # prefill-role only: filled-KV
        # handoffs published onto the transfer plane
        # Online draft distillation (torchkafka_tpu/distill): the serve →
        # distill-topic → trainer → checkpoint-topic → swap loop. All
        # zero without a distill topic / spec serving.
        self.distill_published = RateMeter()  # committed completions
        # framed onto the distill topic (txn: counted at commit)
        self.distill_steps = RateMeter()  # trainer train steps (trainer
        # role only)
        self.distill_records = RateMeter()  # corpus records consumed into
        # train batches (trainer role only)
        self.spec_alpha_window = Gauge()  # windowed live acceptance α the
        # DistillController gates refreshes on (NaN-free: 0 until the
        # first window closes)
        self.draft_version = Gauge()  # draft checkpoint version currently
        # proposing (0 = the built-in / construction-time draft)
        self._draft_refreshes: dict[str, RateMeter] = {}  # draft
        # hot-swaps by reason ("alpha_drop", "forced", ...)
        # Chunked prefill (kv_pages): admission enqueues uncached
        # suffixes and every tick carries a bounded chunk of them
        # alongside decode. All zero with the dense pool.
        self.chunk_ticks = RateMeter()  # ticks that carried prefill chunk rows
        self.admission_stall_ticks = RateMeter()  # EXTRA ticks admissions
        # queued beyond the one-tick minimum (0 when every admission's
        # suffix fits the chunk a single tick carries — the prompt-storm
        # regression bound)
        self.admission_queue_tokens = Gauge()  # uncached suffix tokens still
        # queued for chunk prefill, sampled after each tick
        self.chunk_utilization = Gauge()  # cumulative prefill tokens /
        # (chunk ticks x chunk width): how full the static chunk rides
        # Decode journal / warm failover (torchkafka_tpu/journal): all zero
        # without a journal or resume hints.
        self.decoded_tokens = RateMeter()  # tokens produced by decode ticks
        # (prefilled/journal-restored tokens excluded — the cold-vs-warm
        # replay differential reads exactly this)
        self.warm_resumes = RateMeter()  # redelivered prompts resumed from
        # a journal hint (prompt + emitted tokens prefilled in one dispatch)
        self.journal_tokens_restored = RateMeter()  # emitted tokens NOT
        # re-decoded thanks to warm resume
        self.journal_served = RateMeter()  # finished-but-uncommitted
        # completions re-served straight from the journal (zero re-decode)
        self.resume_rejected = RateMeter()  # hints discarded (payload CRC /
        # sampling-contract mismatch, or an unsupported pool mode)
        # Per-tenant prefix-cache counters (lazy label children, tenant =
        # record key): the "cache hit by tenant locality" observable the
        # traffic bench reads. Empty on the dense path.
        self._tenant_prefix_hits: dict[str, RateMeter] = {}
        self._tenant_prefix_misses: dict[str, RateMeter] = {}
        # The resolved KV backend (kvcache.resolve_kv_backend): which
        # pool layout/dtype actually serves, whether the Pallas read
        # engaged, and — when it did not — the machine-readable reason,
        # so the kv_kernel="auto" threshold decision is observable on
        # /metrics instead of silent.
        self.kernel_engaged = Gauge()
        self._kernel_disabled: dict[str, RateMeter] = {}
        self._kv_backend: dict = {}

    def note_backend(self, backend: "KVBackend") -> None:
        """Record the resolved backend (called once per build; a paged
        pool that falls back to dense re-notes the dense resolution)."""
        self._kv_backend = backend.describe()
        self.kernel_engaged.set(1.0 if backend.kernel else 0.0)
        reason = backend.kernel_disabled_reason
        if reason is not None:
            self._kernel_disabled.setdefault(reason, RateMeter()).add(1)

    def kernel_disabled_summary(self) -> dict:
        return {r: m.count for r, m in sorted(self._kernel_disabled.items())}

    def tenant_prefix_hits(self, tenant: str) -> RateMeter:
        return self._tenant_prefix_hits.setdefault(tenant, RateMeter())

    def draft_refreshes(self, reason: str) -> RateMeter:
        return self._draft_refreshes.setdefault(reason, RateMeter())

    def distill_summary(self) -> dict:
        return {
            "published": self.distill_published.count,
            "steps": self.distill_steps.count,
            "records": self.distill_records.count,
            "alpha_window": round(self.spec_alpha_window.value, 4),
            "draft_version": int(self.draft_version.value),
            "refreshes": {
                r: m.count for r, m in sorted(self._draft_refreshes.items())
            },
        }

    def tenant_prefix_misses(self, tenant: str) -> RateMeter:
        return self._tenant_prefix_misses.setdefault(tenant, RateMeter())

    def tenant_cache_summary(self) -> dict:
        out = {}
        for t in sorted(
            set(self._tenant_prefix_hits) | set(self._tenant_prefix_misses)
        ):
            hits = self.tenant_prefix_hits(t).count
            misses = self.tenant_prefix_misses(t).count
            out[t] = {
                "hits": hits,
                "misses": misses,
                "hit_rate": (
                    round(hits / (hits + misses), 4)
                    if hits + misses else None
                ),
            }
        return out

    def reset(self) -> None:
        """Zero the rate clocks — called at run() start so compile/warmup
        time (minutes at the 8B-class scales) doesn't dilute rates."""
        for m in (
            self.completions, self.tokens, self.truncated,
            self.readmissions, self.dropped, self.commit_failures,
        ):
            m.reset()

    def summary(self) -> dict:
        return {
            "completions": self.completions.count,
            "completions_per_s": self.completions.rate(),
            "tokens": self.tokens.count,
            "tokens_per_s": self.tokens.rate(),
            "truncated_by_eos": self.truncated.count,
            "readmissions": self.readmissions.count,
            "dropped": self.dropped.count,
            "quarantined": self.quarantined.count,
            "commit_failures": self.commit_failures.count,
            "output_flush_failures": self.output_flush_failures.count,
            "output_send_failures": self.output_send_failures.count,
            "dlq_delivery_failures": self.dlq_delivery_failures.count,
            "txn": {
                "commits": self.txn_commits.count,
                "aborts": self.txn_aborts.count,
                "held_outputs": int(self.txn_held_outputs.value),
            },
            "commit": self.commit_latency.summary(),
            "slot_occupancy": round(self.slot_occupancy.value, 3),
            "ticks": self.tick_time.count,
            "step_time": self.tick_time.summary(),
            "tokens_per_tick": round(self.tokens_per_tick.value, 2),
            "scheduler": {
                "slot_ticks_run": self.slot_ticks_run.count,
                "slot_ticks_served": self.slot_ticks_served.count,
                "admit_calls": self.admit_calls.count,
                "admit_rows": self.admit_rows.count,
                "admit_rows_prefilled": self.admit_rows_prefilled.count,
            },
            "expert_layer": {
                "moe_assignments": self.moe_assignments.count,
                "moe_experts_touched": self.moe_experts_touched.count,
                "moe_expert_load": self.moe_expert_load.tolist(),
                "moe_zero_assignments": self.moe_zero_assignments.count,
                "moe_local_assignments": self.moe_local_assignments.count,
                "moe_absent_assignments": self.moe_absent_assignments.count,
                "experts_held": self.experts_held,
                "groups": self.expert_groups,
                "grouped_matmul": self.grouped_matmul,
                "tick_form": self.tick_form,
                "moe_grouped_rows": self.moe_grouped_rows.count,
                "moe_grouped_tile_rows": self.moe_grouped_tile_rows.count,
            },
            "linear_state": self.linear_state,
            "latent_pool": {
                "attn_blocks": self.attn_blocks,
                "latent_positions_valid": self.latent_positions_valid.count,
                "latent_positions_read": self.latent_positions_read.count,
            },
            "kv_pool": {
                **self.kv_pool_static,
                "window_positions_valid": self.window_positions_valid.count,
                "window_positions_read": self.window_positions_read.count,
                "full_positions_valid": self.full_positions_valid.count,
                "full_positions_read": self.full_positions_read.count,
                **({
                    name: getattr(self, name).count for name in (
                        "index_positions_valid", "index_positions_read",
                        "sparse_positions_valid", "sparse_positions_selected",
                        "sparse_positions_read",
                    )
                } if "index_layers" in self.kv_pool_static else {}),
            },
            "output_capped": self.output_capped.count,
            "prefix_cache": self.cache_summary(),
            "tenant_cache": self.tenant_cache_summary(),
            "disagg": self.disagg_summary(),
            "distill": self.distill_summary(),
            "chunked_prefill": self.chunk_summary(),
            "journal": self.journal_summary(),
            "kv_backend": {
                **self._kv_backend,
                "kernel_engaged": int(self.kernel_engaged.value),
                "kernel_disabled": self.kernel_disabled_summary(),
            },
        }

    def chunk_summary(self) -> dict:
        ticks = self.chunk_ticks.count
        return {
            "chunk_ticks": ticks,
            "prefill_tokens_per_tick": (
                round(self.prefill_tokens.count / ticks, 2) if ticks else None
            ),
            "stall_ticks": self.admission_stall_ticks.count,
            "queue_tokens": int(self.admission_queue_tokens.value),
            "utilization": round(self.chunk_utilization.value, 4),
        }

    def journal_summary(self) -> dict:
        return {
            "decoded_tokens": self.decoded_tokens.count,
            "warm_resumes": self.warm_resumes.count,
            "tokens_restored": self.journal_tokens_restored.count,
            "served_from_journal": self.journal_served.count,
            "resume_rejected": self.resume_rejected.count,
        }

    def cache_summary(self) -> dict:
        hits, misses = self.prefix_hits.count, self.prefix_misses.count
        return {
            "hits": hits,
            "misses": misses,
            "hit_rate": (
                round(hits / (hits + misses), 4) if hits + misses else None
            ),
            "prefix_tokens_saved": self.prefix_tokens_saved.count,
            "prefill_tokens": self.prefill_tokens.count,
            "evictions": self.cache_evictions.count,
            "deferrals": self.admission_deferrals.count,
            "fallbacks": self.cache_fallbacks.count,
            "pool_occupancy": round(self.cache_pool_occupancy.value, 3),
            "tier": {
                "demotions": self.radix_demotions.count,
                "promotions": self.radix_promotions.count,
                "hits": self.tier_hits.count,
                "occupancy_bytes": int(self.tier_occupancy_bytes.value),
            },
        }

    def disagg_summary(self) -> dict:
        return {
            "prefill_routed": self.prefill_routed.count,
            "adopted_slots": self.adopted_slots.count,
            "handoffs_published": self.handoffs_published.count,
        }

    def render_prometheus(self, prefix: str = "torchkafka_serve") -> str:
        """Prometheus text exposition — same conventions (and shared
        renderer) as StreamMetrics.render_prometheus."""
        from torchkafka_tpu.utils.metrics import (
            format_labels,
            render_exposition,
        )

        s = self.summary()
        pc = s["prefix_cache"]
        jn = s["journal"]
        cp = s["chunked_prefill"]
        kb = s["kv_backend"]
        return render_exposition(prefix, [
            # The resolved KV backend as an info-style gauge (value 1,
            # identity in the labels) plus the kernel engagement pair —
            # the "which pool actually serves, and why not the kernel"
            # observables.
            ("kv_backend_info", "gauge", [
                (format_labels(
                    layout=str(kb.get("layout", "dense")),
                    kv_dtype=str(kb.get("kv_dtype", "compute")),
                    row_write=str(kb.get("row_write", "scatter")),
                    sharding=f"data={kb.get('data', 1)},tp={kb.get('tp', 1)}",
                ), 1),
            ]),
            ("kv_kernel_engaged", "gauge", kb["kernel_engaged"]),
            ("kv_kernel_disabled_total", "counter", [
                (format_labels(reason=r), v)
                for r, v in kb["kernel_disabled"].items()
            ] or 0),
            ("chunk_ticks_total", "counter", cp["chunk_ticks"]),
            ("admission_stall_ticks_total", "counter", cp["stall_ticks"]),
            ("admission_queue_tokens", "gauge", cp["queue_tokens"]),
            ("chunk_utilization", "gauge", cp["utilization"]),
            ("prefill_tokens_per_chunk_tick", "gauge",
             cp["prefill_tokens_per_tick"] or 0.0),
            ("decoded_tokens_total", "counter", jn["decoded_tokens"]),
            ("warm_resumes_total", "counter", jn["warm_resumes"]),
            ("journal_tokens_restored_total", "counter", jn["tokens_restored"]),
            ("journal_served_total", "counter", jn["served_from_journal"]),
            ("resume_rejected_total", "counter", jn["resume_rejected"]),
            ("completions_total", "counter", s["completions"]),
            ("tokens_total", "counter", s["tokens"]),
            ("truncated_by_eos_total", "counter", s["truncated_by_eos"]),
            ("slot_readmissions_total", "counter", s["readmissions"]),
            ("dropped_prompts_total", "counter", s["dropped"]),
            ("quarantined_prompts_total", "counter", s["quarantined"]),
            ("commit_failures_total", "counter", s["commit_failures"]),
            ("output_flush_failures_total", "counter", s["output_flush_failures"]),
            ("output_send_failures_total", "counter", s["output_send_failures"]),
            ("dlq_delivery_failures_total", "counter", s["dlq_delivery_failures"]),
            ("txn_commits_total", "counter", s["txn"]["commits"]),
            ("txn_aborts_total", "counter", s["txn"]["aborts"]),
            ("txn_held_outputs", "gauge", s["txn"]["held_outputs"]),
            ("commit_latency_p50_milliseconds", "gauge", s["commit"]["p50_ms"]),
            ("commit_latency_p99_milliseconds", "gauge", s["commit"]["p99_ms"]),
            ("completions_per_second", "gauge", s["completions_per_s"]),
            ("tokens_per_second", "gauge", s["tokens_per_s"]),
            ("slot_occupancy", "gauge", s["slot_occupancy"]),
            ("serve_ticks_total", "counter", s["ticks"]),
            ("step_time_ms", "gauge", [
                ('percentile="p50"', s["step_time"]["p50_ms"]),
                ('percentile="p99"', s["step_time"]["p99_ms"]),
            ]),
            ("tokens_per_tick", "gauge", s["tokens_per_tick"]),
            *(
                (f"{name}_total", "counter", value)
                for name, value in s["scheduler"].items()
            ),
            *(
                (f"{name}_total", "counter", value)
                for section in ("expert_layer", "latent_pool", "kv_pool")
                for name, value in s[section].items()
                if name not in (
                    "moe_expert_load", "experts_held", "attn_blocks",
                    "grouped_matmul", "tick_form", "groups",
                    *self.kv_pool_static,
                )
            ),
            ("moe_expert_load_total", "counter", [
                (format_labels(expert=str(e)), v)
                for e, v in enumerate(s["expert_layer"]["moe_expert_load"])
            ] or 0),
            ("output_capped_total", "counter", s["output_capped"]),
            ("tenant_prefix_cache_hits_total", "counter", [
                (format_labels(tenant=t), v["hits"])
                for t, v in s["tenant_cache"].items()
            ] or 0),
            ("tenant_prefix_cache_misses_total", "counter", [
                (format_labels(tenant=t), v["misses"])
                for t, v in s["tenant_cache"].items()
            ] or 0),
            ("prefix_cache_hits_total", "counter", pc["hits"]),
            ("prefix_cache_misses_total", "counter", pc["misses"]),
            ("prefix_tokens_saved_total", "counter", pc["prefix_tokens_saved"]),
            ("prefill_tokens_total", "counter", pc["prefill_tokens"]),
            ("kvcache_evictions_total", "counter", pc["evictions"]),
            ("admission_deferrals_total", "counter", pc["deferrals"]),
            ("kvcache_fallbacks_total", "counter", pc["fallbacks"]),
            ("prefix_cache_hit_rate", "gauge", pc["hit_rate"] or 0.0),
            ("kvcache_pool_occupancy", "gauge", pc["pool_occupancy"]),
            ("radix_demotions_total", "counter", pc["tier"]["demotions"]),
            ("radix_promotions_total", "counter", pc["tier"]["promotions"]),
            ("tier_hits_total", "counter", pc["tier"]["hits"]),
            ("tier_occupancy_bytes", "gauge", pc["tier"]["occupancy_bytes"]),
            ("prefill_routed_total", "counter", s["disagg"]["prefill_routed"]),
            ("adopted_slots_total", "counter", s["disagg"]["adopted_slots"]),
            ("prefill_handoffs_published_total", "counter",
             s["disagg"]["handoffs_published"]),
            ("distill_published_total", "counter",
             s["distill"]["published"]),
            ("distill_steps_total", "counter", s["distill"]["steps"]),
            ("distill_records_total", "counter", s["distill"]["records"]),
            ("spec_alpha_window", "gauge", s["distill"]["alpha_window"]),
            ("draft_version", "gauge", s["distill"]["draft_version"]),
            ("draft_refreshes_total", "counter", [
                (format_labels(reason=r), v)
                for r, v in s["distill"]["refreshes"].items()
            ] or 0),
        ])


@jax.jit
def _fold_record_ids(rng, ids):
    """``StreamingGenerator._records_key_data``'s program: ``ids`` [3, N]
    uint32 (the topic's checksum, the partition, the offset) → the raw
    key data [N, W] of ``rng`` folded with each column in turn."""
    def one(topic, partition, offset):
        k = jax.random.fold_in(rng, topic)
        k = jax.random.fold_in(jax.random.fold_in(k, partition), offset)
        return jax.random.key_data(k)

    return jax.vmap(one)(ids[0], ids[1], ids[2])


class _PendingPrefill:
    """One admission's queued chunk-prefill work (paged mode).

    The slot and its blocks are already reserved (table linked, radix
    inserted); ``seq`` is the UNCACHED suffix still to be written —
    ``seq[off:]`` remains — with ``seq[0]`` sitting at logical position
    ``start``. ``resume`` carries a journal warm-resume's emitted
    tokens (activation restores state instead of sampling token 0);
    None for a cold admission."""

    __slots__ = ("slot", "rec", "seq", "off", "start", "key_np", "resume",
                 "enq_tick")

    def __init__(self, slot, rec, seq, start, key_np, resume, enq_tick):
        self.slot = slot
        self.rec = rec
        self.seq = seq
        self.off = 0
        self.start = start
        self.key_np = key_np
        self.resume = resume
        self.enq_tick = enq_tick


@dataclasses.dataclass
class PrefillHandoff:
    """One prompt's filled-KV transfer unit (disaggregated prefill).

    A PREFILL worker (``prefill_role=True``) runs the normal chunked-
    prefill machinery to fill a slot's prompt blocks, samples token 0
    in-dispatch with the standard per-record key discipline, then
    extracts this — record identity + payload CRC (so a handoff can
    never adopt onto a different record), the sampling contract, the
    per-record RNG key, token 0, and the raw per-pool payload bytes of
    the ``prompt_blocks`` blocks covering positions [0, prompt_len) —
    and publishes it on the transfer plane (a broker topic;
    fleet/prefill.py owns the wire encoding). A DECODE replica ADOPTS
    it: payloads scattered into freshly linked pool blocks (radix-
    matched prefix blocks skip the upload — they already hold the
    identical bytes), state merged exactly like a 1-token journal warm
    resume — no prompt pass ever runs on the decode replica, and the
    continuation is bitwise the run a monolithic server would produce
    (the chunk machinery's chunk-width invariance is what makes the
    worker's KV bytes equal the local prefill's).

    ``pools``: one host array per device pool tensor, each sliced to
    the prompt's blocks on axis 1 — 2 arrays on compute-dtype pools,
    4 (payload+scale ×2) on int8 pools. The tier/journal sibling of a
    ``JournalEntry``, generalized from crash recovery to routing."""

    topic: str
    partition: int
    offset: int
    crc: int
    key_data: tuple
    temperature: float
    top_k: int | None
    top_p: float | None
    token0: int
    prompt_blocks: int
    pools: tuple

    @property
    def key(self) -> tuple[str, int, int]:
        return (self.topic, self.partition, self.offset)

    def payload_bytes(self) -> int:
        return sum(a.nbytes for a in self.pools)


class _TxnOutboxProducer:
    """The quarantine's producer in exactly-once mode: dead-letter
    produces are STAGED into the server's transactional outbox (keyed by
    the poison record's identity, parsed from the ``dlq.*`` provenance
    headers the quarantine always writes) instead of sent immediately —
    they are produced inside the commit window's transaction, atomic
    with the offset that retires the record. The returned handle
    resolves immediately: in transactional mode durability IS the
    transaction commit, which the commit discipline already gates before
    any offset becomes durable."""

    def __init__(self, server: "StreamingGenerator") -> None:
        self._server = server

    def send(self, topic, value, *, key=None, partition=None,
             timestamp_ms=None, headers=()):
        from torchkafka_tpu.source.producer import (
            RecordMetadata,
            _ResolvedSend,
        )

        h = {k: v for k, v in headers}
        ident = (
            h["dlq.topic"].decode(),
            int(h["dlq.partition"]),
            int(h["dlq.offset"]),
        )
        self._server._txn_outbox[ident] = dict(
            topic=topic, value=value, key=key, headers=tuple(headers),
        )
        return _ResolvedSend(RecordMetadata(topic, -1, -1))

    def flush(self, timeout_s=None) -> None:
        pass  # staged sends settle at transaction commit

    def close(self) -> None:
        pass


def _record_tenant(record: Record) -> str:
    """Tenant = the record key (the rule fleet/qos.py and obs/trace.py
    admit and label by), for the per-tenant cache-locality counters."""
    if record.key is None:
        return "anon"
    try:
        return record.key.decode("utf-8")
    except UnicodeDecodeError:
        return record.key.hex()


class _ShadowConsumer:
    """The canary shadow generator's consumer-shaped null object: it is
    never a group member, never polls, and owns no partitions — so the
    shadow's commit path is structurally a no-op (an empty assignment
    drops every ledger partition from the snapshot) and nothing a shadow
    decodes can reach a broker. See ``StreamingGenerator.spawn_shadow``."""

    def poll(self, max_records: int = 1, timeout_ms: int = 0) -> list:
        return []

    def assignment(self):
        return frozenset()

    def commit(self, offsets) -> None:
        pass

    def heartbeat(self) -> None:
        pass

    def close(self) -> None:
        pass


def _default_decode_prompt(prompt_len: int) -> Callable[[Record], np.ndarray]:
    def decode(record: Record) -> np.ndarray:
        toks = np.frombuffer(record.value, dtype=np.int32)[:prompt_len]
        if toks.shape[0] < prompt_len:
            toks = np.pad(toks, (0, prompt_len - toks.shape[0]))
        return toks

    return decode


class StreamingGenerator:
    """Continuous-batching server over a Kafka-semantics consumer.

    ``run()`` yields ``(record, tokens)`` in COMPLETION order (not offset
    order); each completion retires its record in the ledger, and offsets
    commit every ``commit_every`` completions plus once at the end — so a
    crash re-delivers exactly the prompts whose generations never finished.
    """

    def __init__(
        self,
        consumer,
        params,
        cfg: TransformerConfig,
        *,
        slots: int = 8,
        prompt_len: int,
        max_new: int,
        eos_id: int | None = None,
        commit_every: int = 32,
        decode_prompt: Callable[[Record], np.ndarray] | None = None,
        max_poll_records: int = 512,
        ticks_per_sync: int = 4,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        rng: jax.Array | None = None,
        output_producer=None,
        output_topic: str | None = None,
        exactly_once: bool = False,
        encode_output: Callable[[Record, np.ndarray], bytes] | None = None,
        max_send_failure_streak: int = 64,
        quarantine=None,
        mesh=None,
        kv_dtype: str | None = None,
        kv_kernel: bool | str = "auto",
        kv_pages: PagedKVConfig | dict | None = None,
        kv_tier: TierConfig | dict | None = None,
        prefill_role: bool = False,
        journal: DecodeJournal | None = None,
        tracer=None,
        trace_replica: int | None = None,
        max_new_of: Callable[[Record], int | None] | None = None,
        model_version: int = 0,
        distill_topic: str | None = None,
        distill_producer=None,
    ) -> None:
        """``ticks_per_sync``: decode ticks chained per device dispatch
        (and per host sync of the done mask). Higher amortises dispatch
        latency; the cost is completed slots idling up to K-1 ticks before
        re-admission. 1 = immediate recycling (lowest latency hardware).

        ``temperature``: 0 = greedy (matches ``generate``'s default);
        > 0 samples categorically per slot from logits/temperature.
        ``rng`` is the BASE of a per-record key schedule: each admitted
        record derives ``fold_in(rng, topic/partition/offset)`` once, and
        token i of that record draws with ``fold_in(record_key, i)`` —
        so a record's sampled continuation is a pure function of (base
        key, record identity), independent of slot placement, tick
        interleaving, admission order, or WHICH replica decodes it. That
        independence is what makes journal-based warm failover
        token-exact (torchkafka_tpu/journal) and same-seed fleet runs
        replayable under chaos. ``top_k``/``top_p`` restrict the sampled
        support (top-k threshold then nucleus mass,
        ``models.generate.sample_logits`` — the SAME definition the
        lockstep path uses, static-shape so the tick stays one compiled
        program; ignored at temperature 0, where the filter cannot
        change the argmax).

        ``output_producer``/``output_topic``: publish each completion to a
        topic (key = the prompt record's key; ``encode_output(record,
        tokens) -> bytes``, default int32 token bytes). Sends are async;
        the producer is FLUSHED before every offset commit, and a failed
        flush SKIPS the commit (fail closed) — outputs are durable before
        the prompts that produced them commit, so a crash regenerates
        instead of losing completions (at-least-once end to end; the
        output topic may see duplicates, keyed by the prompt's key).

        ``exactly_once``: the TRANSACTIONAL output mode — pass a
        ``source.producer.TransactionalProducer`` (or any object with
        its begin/send/send_offsets/commit/abort surface; the kafka
        adapter's ``KafkaTransactionalProducer`` qualifies) as
        ``output_producer`` and every commit window becomes ONE broker
        transaction covering that window's completions AND their source
        offsets, Kafka-KIP-98-style. Consequences, each the upgrade of
        an at-least-once behavior above: completions are invisible to
        ``read_committed`` consumers until the window's offsets commit
        WITH them (no more duplicates-on-replay — a crash before commit
        aborts the transaction and the regenerated outputs are the only
        committed copy); a survivable commit failure (rebalance) aborts
        the whole window and this server re-produces, inside the NEXT
        transaction, exactly the window outputs for partitions it still
        owns (departed partitions' records re-serve on their new owner —
        the only committed copy, again); the quarantine's DLQ produce
        rides the same transaction, so poison retirement (DLQ copy +
        offset) is atomic too; and journal-re-served completions are
        produced inside the new incarnation's transaction while the dead
        incarnation's uncommitted transaction was aborted by the epoch
        fence at ``TransactionalProducer`` construction — never
        double-published. A ``ProducerFencedError`` anywhere on this
        path is terminal fail-stop: another incarnation owns this
        replica's transactional id; serving on would be zombie work.
        ``read_uncommitted`` consumers (the default everywhere) observe
        the output topic exactly as before.

        ``mesh``: model-sharded serving (``jax.sharding.Mesh``) — params
        are committed to the training ``param_specs`` layouts (tp/fsdp,
        quantize-aware), the KV slot pool shards kv heads over ``tp`` and
        slots over ``data``, and XLA inserts the megatron collectives.
        This is what serves anything one chip cannot hold (bf16 8B+, long
        KV budgets). Token-exact vs mesh-less serving
        (differential-tested); the multichip dryrun proves the path.

        ``kv_dtype``: None = the compute dtype (token-exact vs
        ``generate``); ``"int8"`` = quantized slot pool (int8 payload +
        per-(position, head) f32 absmax scale, ≈52% of bf16 pool bytes at
        head_dim 128) — the memory headroom that buys more concurrent
        slots at the 8B-class scales, at the cost of bounded
        quantization error (opt-in precisely because token-exactness is
        given up).

        ``kv_kernel``: the Pallas DYNAMIC-LENGTH int8 decode-attention
        kernel (``ops.kvattn.int8_decode_attention_dynlen``) for the
        pool read: per-slot watermarks are scalar-prefetched and only
        positions [0, pos] are DMA'd per slot, so HBM traffic scales
        with each slot's actual fill instead of the pool size —
        inexpressible in XLA, where every read is pool-shaped, and
        continuous batching lives at partial fills (time a call and
        roofline share: PERF.md §5). ``"auto"`` (default) engages the
        kernel only at int8 pools ≥ 1024 tokens (TPU backend, tiling
        shapes, pool tiling at a ≥ 256 block); else the XLA read.
        Composes with ``mesh``: a Pallas call is opaque to GSPMD, so the
        sharded read runs per (data, tp) shard inside ``shard_map``
        (``ops.kvattn.int8_decode_attention_dynlen_sharded``, the
        ``flash_attention_sharded`` precedent — slots over data, kv
        heads over tp, no collectives), gated by the capability probe on
        the same divisibilities the XLA layouts need. ``True``: REQUIRE
        the kernel at any pool length; raises if shapes/mesh can't honor
        it (so a benchmark never misattributes the XLA read's numbers to
        the kernel; the reason is in the error and on ``metrics``);
        off-TPU it runs in Pallas interpret mode — correct but slow, for
        tests. ``False``: always the XLA read. In kernel mode the pool
        is stored K-major ([L, B, K, M, Dh]) so every head's tile is a
        contiguous slice and the kernel's dots batch over heads with no
        relayout (ops/kvattn.py docstring); note the tick time is then
        FILL-DEPENDENT (the benchmark's ``kvattn.roofline_pct`` reads it).

        ``max_send_failure_streak``: a SYNCHRONOUS send failure leaves its
        record uncommitted (the watermark stalls there, it re-delivers on
        restart) but serving continues — a transient output-broker blip
        should not kill the server. After this many CONSECUTIVE sync
        failures the output path is evidently down and every further
        completion is un-committable replay work, so the server fail-stops
        with ``OutputDeliveryError`` — the same signal the flush/get path
        gives for terminal delivery failures (ADVICE r3).

        ``kv_pages``: a ``kvcache.PagedKVConfig`` (or its dict) — the
        PAGED slot pool with radix-tree prefix reuse. The per-slot dense
        cache is replaced by a shared pool of ``num_blocks`` blocks of
        ``block_size`` tokens plus per-slot block tables; admission
        matches each prompt's longest cached whole-block prefix in a
        host-side radix tree (``kvcache.RadixCache``), links the shared
        physical blocks into the slot's table, and prefills ONLY the
        uncached suffix — prompts sharing a tenant/system prefix stop
        re-prefilling it, and pool bytes follow live tokens instead of
        slots × max_context. Token-comparable with the dense path (same
        ``_attend_cached`` math over a gathered view — the cache-on/off
        differential in tests/test_kvcache.py pins greedy + seeded
        sampling + chaos-replay exactness); eviction is ADVISORY (a miss
        just re-prefills). Pool pressure defers admissions (FIFO
        re-offer once blocks free); a pool too small for even one slot
        falls back to dense cache-off serving with a warning
        (``metrics.cache_fallbacks``). Composes with ``mesh``: the
        block pools shard kv heads over tp and replicate over data
        (shared storage — any slot's table may reference any block),
        per-slot state shards over data, tables
        ride replicated, and the whole admission/radix/chunk machinery
        is mesh-blind host code — token-exact vs single-device serving
        (differential-tested across {data}, {tp}, {data, tp} meshes).
        Not MoE (the paged prefill routes experts densely — decode's
        rule — which would break exactness vs the training-dispatch
        dense prefill; validated with a precise error by
        ``kvcache.resolve_kv_backend``).

        Admission is CHUNKED (``prefill_chunk`` on the config is the
        chunk width): admission reserves the slot + blocks and enqueues
        the uncached suffix host-side; every decode tick then carries a
        bounded, statically-shaped chunk of queued suffix tokens
        ALONGSIDE all decode slots in the SAME jitted program
        (Sarathi-style — prefill rides the weight stream decode already
        pays for). Consequences: admission compiles O(1) programs
        regardless of suffix-length mix, decode inter-token latency
        stays one tick per token under prompt storms (the chunk bounds
        prefill work per tick; the queue drains FIFO), and per-record
        outputs stay bitwise identical to the dense path (each chunk
        query attends exactly [0, position] of its slot's logical view —
        the same math at every chunk width).

        ``kv_dtype="int8"`` composes with ``kv_pages``: the block pools
        store int8 payloads + group-wise absmax scales
        (``models.quant.quant_kv_groups`` — the same (position, head)
        groups as the dense int8 pool, so int8-paged is token-exact vs
        int8-DENSE serving), ~52% of the compute-dtype pool bytes.
        ``kv_kernel`` then selects the Pallas BLOCK-TABLE read for the
        decode ticks (``ops.kvattn.int8_paged_decode_attention`` — the
        dyn-len kernel's watermark-DMA structure reading through
        per-slot block tables, so HBM traffic scales with live tokens
        and no gathered view is materialised); "auto" engages it on TPU
        at pools >= 1024 tokens with tiling shapes, True requires it
        (raises when it cannot be honored), chunk-carrying ticks read
        via the XLA gather either way (the multi-query chunk needs the
        gathered view).

        ``journal``: a ``journal.DecodeJournal`` — record, per in-flight
        slot, the minimal resumable state (record identity + payload CRC,
        sampling params, the per-record RNG key, tokens emitted so far),
        refreshed every ``journal.cadence`` tokens and always at admit
        and finish, written tmp-fsync-rename so a torn write is
        invisible. Paired with ``add_resume_hints`` (the fleet feeds a
        dead replica's journal to survivors): a redelivered prompt with a
        hint is WARM-RESUMED — ``prompt + emitted_tokens`` prefilled in
        one dispatch (a radix hit under ``kv_pages``, a plain longer
        prefill when dense), RNG key and position restored — so the
        continuation is token-exact vs the never-killed run and the
        re-decoded tokens are bounded by the journal cadence; a
        journaled FINISHED completion re-serves with zero re-decode.
        Warm resume of partial generations needs the compute-dtype pool
        (``kv_dtype=None``) and a resume-capable prefill: one device,
        a data-free mesh, or the paged path under any mesh
        (``_resume_supported``); hints are ignored (cold replay, still
        correct) otherwise.

        ``tracer``: an ``obs.RecordTracer`` — per-record lifecycle span
        events (polled → admitted → first token → per-token ticks →
        finished → committed, plus warm-resume/DLQ/deferral branches)
        emitted at every stage boundary this server crosses, keyed by
        the record's (topic, partition, offset) identity;
        ``trace_replica`` tags the events (the fleet sets it per
        replica). None (the default) costs only the per-site ``is not
        None`` guards.

        ``quarantine``: a ``resilience.PoisonQuarantine``. Without it, an
        undecodable prompt is retired immediately as dropped (the
        original policy — no durable copy). With it, each decode failure
        spends the record's retry budget (re-attempted in place — a
        transient external-tokenizer fault heals here), and once the
        budget is gone the prompt is dead-lettered with an ACKNOWLEDGED
        produce before its offset retires (``metrics.quarantined``); a
        failed DLQ produce raises ``OutputDeliveryError`` — fail-stop,
        crash-before-commit, so the committed watermark never covers a
        record that is neither served nor durably quarantined.

        ``distill_topic``: publish each completion as a framed training
        record (``distill.wire.encode_completion`` — prompt ids,
        committed tokens, tenant key, model version) for the online
        draft-distillation loop. The frames follow the SAME durability
        discipline as outputs, commit-gated both ways so the training
        corpus only ever contains COMMITTED tokens: under
        ``exactly_once`` they are staged beside the output outbox and
        produced inside the commit window's transaction (atomic with
        outputs + offsets — an aborted window's frames are invisible,
        a zombie's frames are fenced with its transaction); in
        at-least-once mode they are held host-side and produced only
        AFTER the offset commit that covers them succeeds (a crash
        before commit publishes nothing for the re-delivered records —
        the regenerated completions publish instead). A divergent
        canary or a fenced zombie therefore never trains the draft.
        ``distill_producer`` overrides the producer used for the
        at-least-once publish (default: ``output_producer``); in
        transactional mode the frames always ride the transactional
        producer."""
        if prompt_len + max_new > cfg.max_seq_len:
            raise ValueError("prompt_len + max_new exceeds cfg.max_seq_len")
        for what, asked in (
            ("kv_tier (the radix cache's host tier)", kv_tier is not None),
            ("prefill_role (the disaggregated prefill hand-off)", prefill_role),
            ("a mesh of more than one device",
             mesh is not None and mesh.size > 1),
        ):
            why = asked and _arch_refusal(cfg, what)
            if why:
                raise ValueError(why)
        if max_new < 2:
            raise ValueError("max_new must be >= 2 (prefill emits token 0)")
        if ticks_per_sync < 1:
            raise ValueError("ticks_per_sync must be >= 1")
        self._consumer = consumer
        self._mesh = mesh
        if mesh is not None:
            check_serving_mesh(cfg, mesh, batch=slots)
            params = jax.device_put(params, serving_shardings(cfg, mesh, params))
        self._params = params
        self._cfg = cfg
        self._slots = slots
        self._prompt_len = prompt_len
        self._max_new = max_new
        self._eos_id = eos_id
        self._commit_every = commit_every
        self._decode_prompt = decode_prompt or _default_decode_prompt(prompt_len)
        self._max_poll = max_poll_records
        self._ticks_per_sync = ticks_per_sync
        self._temperature = float(temperature)
        check_sampling_params(top_k, top_p)
        self._top_k = top_k
        self._top_p = top_p
        rng = jax.random.key(0) if rng is None else rng
        if not jax.dtypes.issubdtype(rng.dtype, jax.dtypes.prng_key):
            # Old-style raw uint32 keys: normalize to a typed key so the
            # per-record fold_in/key_data derivation has one spelling.
            rng = jax.random.wrap_key_data(rng)
        self._rng = rng  # per-record key BASE (never split/mutated)
        self._key_width = int(jax.random.key_data(rng).shape[-1])
        if (output_producer is None) != (output_topic is None):
            raise ValueError(
                "output_producer and output_topic must be given together"
            )
        self._output_producer = output_producer
        self._output_topic = output_topic
        if exactly_once:
            if output_producer is None:
                raise ValueError(
                    "exactly_once requires output_producer/output_topic "
                    "(the transaction is the output path)"
                )
            missing = [
                m for m in ("begin", "send_offsets", "commit", "abort")
                if not callable(getattr(output_producer, m, None))
            ]
            if missing:
                raise ValueError(
                    "exactly_once requires a transactional producer "
                    "(source.producer.TransactionalProducer surface); "
                    f"output_producer lacks {missing}"
                )
            if quarantine is not None and (
                getattr(quarantine, "producer", None) is not output_producer
            ):
                raise ValueError(
                    "exactly_once requires the quarantine to share the "
                    "transactional output producer (its DLQ produce must "
                    "ride the same transaction as the offset that retires "
                    "the poison record); build PoisonQuarantine over the "
                    "same TransactionalProducer instance"
                )
        self._txn_mode = exactly_once
        # The transactional OUTBOX: outputs (and DLQ copies) staged by
        # record identity, PRODUCED ONLY AT COMMIT TIME and only for
        # offsets the in-order ledger snapshot covers. Holding sends to
        # the commit point is what makes "outputs + offsets one atomic
        # unit" literally true: an out-of-completion-order output whose
        # offset the watermark cannot yet cover would otherwise commit
        # in one transaction while its record stays redeliverable —
        # the redelivered re-serve then double-publishes. Keyed staging
        # also dedups the eager-rebalance re-serve for free (the second
        # completion overwrites the identical first). Entries survive
        # aborted transactions untouched (the retry re-sends them) and
        # leave only with the committed transaction that covered them.
        self._txn_outbox: dict[tuple[str, int, int], dict] = {}
        # High-water of offsets ALREADY covered by this server's
        # committed transactions. An eager rebalance can hand the server
        # a second copy of a record it fetched before the generation
        # bump (old copy queued, new copy redelivered); if the first
        # copy's window commits before the second copy finishes, the
        # re-serve re-stages the same identity AFTER its covering commit
        # — without this watermark the next window would publish it
        # again. Entries below it are duplicate serves of committed
        # records and are dropped at the commit point.
        self._txn_committed_wm: dict = {}
        if exactly_once and quarantine is not None:
            # Route the DLQ produce into the outbox: the quarantine copy
            # commits atomically WITH the offset that retires the poison
            # record, instead of racing ahead of it.
            quarantine.rebind_producer(_TxnOutboxProducer(self))
        self._encode_output = encode_output or (
            lambda rec, toks: np.asarray(toks, np.int32).tobytes()
        )
        if distill_topic is not None and not exactly_once:
            if distill_producer is None and output_producer is None:
                raise ValueError(
                    "distill_topic requires a producer (distill_producer "
                    "or output_producer) in at-least-once mode"
                )
        self._distill_topic = distill_topic
        self._distill_producer = distill_producer
        # Distill frames staged by record identity. Txn mode: sent inside
        # the commit window's transaction (the outbox discipline). Non-txn
        # mode: held until the offset commit that covers them SUCCEEDS,
        # then produced — commit-gated either way, so the corpus never
        # contains an uncommitted token.
        self._distill_outbox: dict[tuple[str, int, int], bytes] = {}
        if distill_topic is not None:
            from torchkafka_tpu.distill.wire import encode_completion

            self._encode_distill = encode_completion
        else:
            self._encode_distill = None
        if max_send_failure_streak < 1:
            raise ValueError("max_send_failure_streak must be >= 1")
        if kv_pages is not None and isinstance(kv_pages, dict):
            kv_pages = PagedKVConfig(**kv_pages)
        # ``kv_tier``: demote cold radix blocks to a bounded host-RAM
        # store (kvcache/tier.py) instead of freeing them, promote on
        # radix hit — the effective prefix-cache capacity becomes host
        # memory (plus optional disk spill), not pool blocks. Advisory
        # like eviction itself: token-exactness never depends on it.
        if kv_tier is not None and isinstance(kv_tier, dict):
            kv_tier = TierConfig(**kv_tier)
        if kv_tier is not None and kv_pages is None:
            raise ValueError("kv_tier requires kv_pages (it tiers the "
                             "paged radix cache)")
        self._kv_tier_cfg = kv_tier
        self._kv_tier: HostTier | None = None
        # ``prefill_role``: this server is a disaggregated PREFILL
        # worker — it admits prompts through the normal chunked
        # machinery, but the moment a slot's suffix completes (token 0
        # sampled in-dispatch) the slot is HARVESTED into a
        # ``PrefillHandoff`` instead of decoding: the filled prompt
        # blocks' payloads + resume state, for a decode replica to
        # adopt. The record retires in this server's own ledger only
        # when the caller confirms the handoff published
        # (``note_handoff_published``), so a death mid-transfer
        # re-delivers and re-prefills (at-least-once on the handoff
        # plane; the DECODE group's exactly-once story is untouched —
        # it never depends on handoffs existing).
        if prefill_role:
            if kv_pages is None:
                raise ValueError(
                    "prefill_role requires kv_pages (the handoff is cut "
                    "from the chunked-prefill machinery)"
                )
        self._prefill_role = prefill_role
        self._prefilled_ready: list[tuple[Record, PrefillHandoff]] = []
        # Decode-side handoff shelf: installed via add_prefill_handoffs
        # (the fleet's handoff-topic poller), consumed at admission.
        self._prefill_handoffs: dict[tuple[str, int, int], PrefillHandoff] = {}
        self._adopt_upload_jits: dict[int, Callable] = {}
        self._tier_seen = [0, 0, 0]  # demotions/promotions/hits mirrored
        # ONE capability probe for the whole (pages × dtype × kernel ×
        # mesh) space: validates the genuine exclusions eagerly (bad
        # dtype/kernel values, MoE + pages, un-honorable kv_kernel=True)
        # and raises precise errors. The composed axes — sharded paged
        # pools, sharded kernels — are SUPPORTED now;
        # _build/_build_paged re-resolve against the final pool length
        # for the engagement decision and surface it on ``metrics``
        # (kv_backend info + kernel_engaged/kernel_disabled).
        resolve_kv_backend(
            cfg, mesh=mesh, kv_dtype=kv_dtype, kv_kernel=kv_kernel,
            kv_pages=kv_pages, max_len=prompt_len + max_new, slots=slots,
            backend=jax.default_backend(),
        )
        self._kv_pages = kv_pages
        self._paged_deferred: list[Record] = []
        # Chunked-prefill host state (paged mode; see _paged_setup).
        # Defined unconditionally so free_slots/has_active/step are
        # mode-blind: a slot is BUSY while reserved-and-prefilling just
        # as while decoding.
        self._prefilling = np.zeros((slots,), bool)
        self._prefill_queue: list[_PendingPrefill] = []
        self._tick_counter = 0
        self._paged_table_idx = 2  # the table's slot in the state tuple
        self._kv_dtype = kv_dtype
        self._kv_int8 = kv_dtype == "int8"
        self._kv_kernel_opt = kv_kernel
        self._max_send_failure_streak = max_send_failure_streak
        self._send_failure_streak = 0
        self._quarantine = quarantine
        self._pending_outputs: list = []  # send handles since last commit
        self._ledger = OffsetLedger()
        self._max_len = prompt_len + max_new
        self.metrics = ServeMetrics()
        # Slot bookkeeping lives on the instance (not run() locals) so an
        # EXTERNAL admission loop — the serving fleet's QoS scheduler —
        # can drive the server through note_fetched/admit_records/step
        # without the internal poll loop; run() is built on the same
        # surface.
        self._slot_rec: list[Record | None] = [None] * slots
        self._active = np.zeros((slots,), bool)
        self._uncommitted = 0
        self._closed = False
        # Warm failover (torchkafka_tpu/journal): the journal this server
        # WRITES, the hints it may RESUME from, completions re-servable
        # straight from a journal (finished-but-uncommitted), and the
        # host-side per-slot emitted-token mirrors that drive journal
        # cadence and the decoded-token accounting.
        self._journal = journal
        self._tracer = tracer
        self._trace_replica = trace_replica
        # The model version these weights serve as — stamped on every
        # output ("mv" header), every journal entry, and the journal's
        # own meta, so the exactly-once invariant survives a mid-rollout
        # crash: recovery always knows WHICH weights produced what. 0 is
        # the boot checkpoint; swap_params moves it (only between commit
        # windows — see its preconditions).
        self._model_version = int(model_version)
        if journal is not None:
            journal.set_model_version(self._model_version)
        # Per-record output budget: ``max_new_of(record) -> n`` bounds
        # that record's generation to n tokens (clamped to [1, max_new]).
        # ``_slot_budget`` holds it a slot (``max_new`` where there is
        # none), set where a record is attached to its slot
        # (``_attach_record``), and the tick takes it as an operand: a slot
        # latches done at its budget ON THE DEVICE, as at EOS or a full
        # buffer, so no tick decodes a token the host would cut (and the
        # dense int8 kernel fetches nothing for the slot from then on).
        # The host's clamp at the sync (``_retire_block``) stays as the
        # guard for tick programs that do not take the operand (the
        # speculative server's): there a block may overshoot by up to
        # ticks_per_sync - 1 tokens and the overshoot is truncated.
        self._max_new_of = max_new_of
        self._slot_budget = np.full((slots,), max_new, np.int32)
        self._slot_budget_dev = None  # the device's copy; None: stale
        # Whether ``_tick_fn`` / ``_tick_chunk_fn`` take the budget as a
        # trailing operand: _build and _build_paged say so, a subclass
        # that installs tick programs of its own does not.
        self._tick_takes_budget = False
        self._resume_hints: dict[tuple[str, int, int], JournalEntry] = {}
        self._journal_ready: list[tuple[Record, np.ndarray]] = []
        self._slot_emitted = np.zeros((slots,), np.int64)
        self._slot_journaled = np.zeros((slots,), np.int64)
        # Per-slot RECORD keys (raw key data), merged at admit and read by
        # every tick's sampling — deliberately outside the donated state
        # tuple so state-poking tests/tools see the same tuple shapes.
        self._slot_keys = jnp.zeros((slots, self._key_width), jnp.uint32)
        # Rows one trip of the admit program prefills. A program that
        # prefills the whole [slots, prompt] batch whatever its mask (the
        # speculative server's) makes one trip of every slot; the dense
        # build walks the admitted slots in smaller chunks (_build).
        self._admit_chunk_rows = slots
        # Set by _build/_build_paged (the spec subclass's too): the
        # resolved KVBackend this server actually serves with — a paged
        # pool too small for one slot re-resolves as dense here.
        self._kv_backend: KVBackend | None = None
        # The dense build's pool object (kvcache/slot_pool.py); None for the
        # paged build and for a subclass that allocates its own state.
        self._pool = None
        # What the last tick block's routed expert layers counted on the
        # device (_build); None for a config without one.
        self._tick_stats = None
        # What the admissions since the last sync counted of their grouped
        # expert matmul, on the device (_build); fetched with the sync.
        self._admit_stats: list = []
        self._build()
        if self._kv_backend is not None:
            self.metrics.note_backend(self._kv_backend)

    def _build(self) -> None:
        if self._kv_pages is not None and self._paged_setup():
            self._build_paged()
            return
        if self._prefill_role:
            raise ValueError(
                "prefill_role cannot fall back to dense serving — size "
                "kv_pages to hold at least one slot"
            )
        cfg, mesh = self._cfg, self._mesh
        B, P, M = self._slots, self._prompt_len, self._max_len
        # Which pool serves, and whether the Pallas dyn-len read engages, is
        # the capability probe's decision (one rule for dense and paged
        # builds and the metrics); what the layout it names MEANS is the
        # pool object's (kvcache/slot_pool.py): nothing below branches on it.
        self._kv_backend = backend = resolve_kv_backend(
            cfg, mesh=mesh, kv_dtype=self._kv_dtype,
            kv_kernel=self._kv_kernel_opt, kv_pages=None, max_len=M,
            slots=B, backend=jax.default_backend(),
        )
        self._kv_kernel = backend.kernel
        self._pool = pool = make_slot_pool(
            cfg, backend, slots=B, max_len=M, mesh=mesh
        )
        # The slot state's layouts under a mesh, asked once: pinned inside
        # the jitted programs (the donate-and-rebind round trip keeps kv
        # heads on tp and slots on data, not whatever GSPMD first guesses)
        # and where the initial state is placed.
        placed = None if mesh is None else (
            pool.shardings(), slot_sharding(mesh), slot_sharding(mesh),
            slot_sharding(mesh, 2),
        )

        def pin_state(*state):
            if placed is None:
                return state
            return jax.tree.map(lax.with_sharding_constraint, state, placed)

        pick_rows = functools.partial(
            _pick_slots, temperature=self._temperature, top_k=self._top_k,
            top_p=self._top_p,
        )
        # Rows a chunk of the admission prefills, from the static shapes
        # (_ADMIT_CHUNK_TOKENS over the prompt window, the pool's slots at
        # most); under a mesh a whole number of rows a ``data`` shard.
        data = 1 if mesh is None else mesh.shape.get("data", 1)
        R = min(B, -(-max(1, _ADMIT_CHUNK_TOKENS // P) // data) * data)
        self._admit_chunk_rows = R
        # Where a trip's R * P tokens go through the grouped expert matmul
        # (ops/moe.py decides), admit counts what its kernels multiplied.
        grouped = moe.grouped_form(cfg, R * P)
        held = cfg.held_experts  # of a share, the local pairs are counted
        counted = (held[1], held[0] if cfg.moe_partial else None)
        if grouped:
            self.metrics.grouped_matmul = "kernel"
        # A tick's B tokens, by the same rule.
        self.metrics.tick_form = moe.expert_form(cfg, B)

        def admit(params, caches, last_tok, pos, gen, prompts, admit_mask,
                  keys):
            """Prefill the admitted rows of the [B, P] prompt batch, R rows
            a trip, and write each trip's rows into the pool. prompts:
            [B, P] int32; admit_mask: [B] bool; keys: [B, W] uint32
            per-record key data (token 0 draws at index 0). The admitted
            slots come first in ``order`` and the loop makes ceil(admitted
            / R) trips: a slot that is mid-generation costs nothing. The
            pool is the loop's CARRY and every write a dynamic-update-slice
            of one slot's prompt window [0, P); nothing here selects over
            the pool. The last trip's pad rows repeat its last real row."""
            caches, last_tok, pos, gen = pin_state(caches, last_tok, pos, gen)
            order = jnp.argsort(~admit_mask, stable=True)
            count = admit_mask.sum(dtype=jnp.int32)

            @xprof.scope(xprof.SCOPE_KV_WRITE)
            def put(pool_t, rows, slots):
                # rows [L, R, ...] over the window, in the pool's layout.
                shape = pool_t.shape
                if pool.merged_put:
                    # Through a view with a slot's axes merged (its window
                    # is the leading run of its numbers): all of them, or
                    # for the state [H, P, N] all but the minor one, whose
                    # tiles then lie where they lay (the view is free).
                    keep = shape[-1:] if pool_t.ndim > 4 else ()
                    pool_t = pool_t.reshape(*shape[:2], -1, *keep)
                    rows = rows.reshape(*rows.shape[:2], -1, *keep)
                tail = (0,) * (pool_t.ndim - 2)
                for r in range(R):
                    pool_t = lax.dynamic_update_slice(
                        pool_t, rows[:, r:r + 1].astype(pool_t.dtype),
                        (0, slots[r], *tail),
                    )
                return pool_t.reshape(shape)

            def chunk(i, state):
                caches, last_tok, pos, gen, *counts = state
                slots = order[jnp.minimum(i * R + jnp.arange(R), count - 1)]
                logits, fresh, *chosen = prefill(
                    params, cfg, prompts[slots], P, mesh, routing=grouped
                )
                if grouped:  # the routing [L_moe, R, P, top_k]
                    counts = [
                        counts[0] + moe.grouped_counts(chosen[0], *counted)
                    ]
                caches = tuple(
                    put(c, a, slots) for c, a in zip(caches, pool.rows(fresh))
                )
                tok0 = pick_rows(
                    logits, keys[slots], jnp.zeros((R,), jnp.int32)
                )
                first = jnp.zeros((R, self._max_new), gen.dtype)
                return (
                    caches,
                    last_tok.at[slots].set(tok0),
                    pos.at[slots].set(P),
                    gen.at[slots].set(first.at[:, 0].set(tok0)),
                    *counts,
                )

            counts0 = (jnp.zeros((2,), jnp.int32),) if grouped else ()
            return lax.fori_loop(
                0, (count + R - 1) // R, chunk,
                (caches, last_tok, pos, gen, *counts0),
            )

        def tick_block(params, caches, last_tok, pos, gen, active_in, skey,
                       budget=None):
            """K chained decode ticks in ONE dispatch (static K, one host
            sync per K tokens), with a LATCHED done mask: a slot that
            completes at inner tick j is masked out of ticks j+1..K.
            ``skey``: [B, W] uint32 per-slot RECORD keys; tick t of slot b
            draws at fold index ``pos_b - P + 1`` (token 0 was the admit
            draw), so the sampled stream is a pure function of (record,
            index), the warm-failover exactness contract. ``budget``: [B]
            int32, the tokens each slot's answer may have (``max_new``
            where none is passed): a slot completes at it as at a full
            buffer."""
            caches, last_tok, pos, gen = pin_state(caches, last_tok, pos, gen)
            if budget is None:
                budget = jnp.full((B,), self._max_new, jnp.int32)

            def one(carry, _):
                caches, last_tok, pos, gen, done_latch, n_out, stats = carry
                act = active_in & ~done_latch
                with xprof.scope(xprof.SCOPE_EMBED):
                    x = embed_tokens(params, cfg, last_tok)[:, None, :]
                x, caches, stats = pool.tick_layers(
                    params, x, caches, stats, pos, act
                )
                logits = head_logits(params, cfg, x, 0)
                tok = pick_rows(logits, skey, pos - P + 1)
                # A slot that is not live (idle, or latched done) still runs
                # the static tick; what it computes is never read. Through
                # the XLA reads it writes stale kv at its frozen position:
                # safe, re-admission overwrites [0, P) and every later
                # position is rewritten by the tick that reaches it BEFORE
                # the attention that could read it (a jnp.where freezing the
                # caches would copy the pool every token). The dense int8
                # kernel takes ``act`` and neither fetches nor writes for it.
                t = pos - P  # decode ticks completed before this one
                idx = jnp.minimum(t + 1, self._max_new - 1)
                # One-hot select over the tiny [B, max_new] buffer (the
                # POOL is only ever scattered into: slot_pool.py).
                onehot = jnp.arange(self._max_new)[None, :] == idx[:, None]
                gen = jnp.where(onehot & act[:, None], tok[:, None], gen)
                hit_eos = (
                    (tok == self._eos_id) if self._eos_id is not None
                    else jnp.zeros_like(act)
                )
                # Tokens after this tick = t + 2 (prefill's token 0 plus
                # t+1 decode outputs); complete on EOS or at the budget.
                done_now = act & (hit_eos | (t + 2 >= budget))
                pos = jnp.where(act & ~done_now, pos + 1, pos)
                last_tok = jnp.where(act, tok, last_tok)
                n_out = jnp.where(done_now, jnp.minimum(t + 2, budget), n_out)
                done_latch = done_latch | done_now
                return (caches, last_tok, pos, gen, done_latch, n_out, stats), None

            done0 = jnp.zeros((B,), bool)
            n0 = jnp.zeros((B,), jnp.int32)
            # What the routed expert layer did (ServeMetrics.moe_*): experts
            # touched, pairs by expert; fetched with the sync.
            stats0 = (
                jnp.zeros((), jnp.int32),
                jnp.zeros((cfg.held_experts[1],), jnp.int32),
            ) if cfg.routed_moe else ()
            if cfg.moe_partial:
                # Pairs by fate: zero expert, held here, on another chip.
                stats0 += (jnp.zeros((3,), jnp.int32),)
            (caches, last_tok, pos, gen, done, n_out, stats), _ = lax.scan(
                one, (caches, last_tok, pos, gen, done0, n0, stats0), None,
                length=self._ticks_per_sync,
            )
            return (caches, last_tok, pos, gen, done, n_out) + (
                (stats,) if stats else ()
            )

        def resume_admit(params, caches, last_tok, pos, gen, seq, slot,
                         emitted_row, g):
            """Warm-resume ONE slot from a journal hint: prefill ``seq``
            [1, P + g - 1] (prompt + the g journaled tokens minus the last:
            position P+g-1 is rewritten by the next tick anyway) into the
            slot's cache row, and restore the state the no-kill run would
            hold. emitted_row: [max_new], the journaled tokens zero-padded
            as a fresh admit's cleared buffer."""
            caches, last_tok, pos, gen = pin_state(caches, last_tok, pos, gen)
            _logits, fresh = prefill(params, cfg, seq, M, mesh)
            caches = pool.resume_put(caches, fresh, slot)
            last_tok = last_tok.at[slot].set(emitted_row[g - 1])
            pos = pos.at[slot].set(P + g - 1)
            gen = lax.dynamic_update_slice(gen, emitted_row[None, :], (slot, 0))
            return caches, last_tok, pos, gen

        # Donate the pool: the tick and admit write it in place (the carry
        # of their loops), so the output can be the caller's own buffer;
        # the run loop rebinds the returned buffers immediately. Params
        # travel as an ARGUMENT: a closed-over tree lowers as constants,
        # bloats compile memory and ships the weights inside the program.
        _admit = jax.jit(admit, donate_argnums=(1,))
        _tick = jax.jit(tick_block, donate_argnums=(1,))
        self._tick_takes_budget = True
        # The un-jitted tick, for tests that read its jaxpr.
        self._tick_block_raw = tick_block

        def admit_fn(*a):
            out = _admit(self._params, *a)
            self._admit_stats.extend(out[4:])
            return out[:4]

        def tick_fn(*a):
            out = _tick(self._params, *a)
            self._tick_stats = out[6] if len(out) > 6 else None
            return out[:6]

        self._admit_fn, self._tick_fn = admit_fn, tick_fn
        # Hints a pool cannot resume (KVBackend.resumable) are filtered out
        # in _take_hint and replay cold: no resume program is built.
        self._resume_exec = None
        if backend.resumable:
            _resume = jax.jit(resume_admit, donate_argnums=(1,))
            self._resume_exec = lambda *a: _resume(self._params, *a)
        for name, payload in pool.static().items():
            setattr(self.metrics, name, payload)
        if cfg.routed_moe:
            self.metrics.moe_expert_load = np.zeros((held[1],), np.int64)
            self.metrics.experts_held = list(held)
            self.metrics.expert_groups = {
                "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            }
        state = (
            pool.zeros(), jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B, self._max_new), jnp.int32),
        )
        if placed is not None:
            state = jax.device_put(state, placed)
        self._caches, self._last_tok, self._pos, self._gen = state

    # ------------------------------------------------------ paged slot pool
    #
    # kv_pages mode (torchkafka_tpu/kvcache): the dense per-slot cache
    # [L, B, M, K, Dh] becomes a SHARED block pool [L, NB, bs, K, Dh] plus
    # per-slot block tables [B, nblk]. Device shapes stay fully static (the
    # XLA discipline); the dynamic part — which physical block backs which
    # logical position — lives host-side in the allocator/radix pair. The
    # table rides INSIDE the donated state tuple (returned unchanged by the
    # tick) so every dispatch signature matches the dense path and
    # warmup/step need no special plumbing.

    def _paged_setup(self) -> bool:
        """Host-side paging state; False = pool too small for even ONE
        slot's worst case → graceful cache-off fallback (dense build)."""
        pages = self._kv_pages
        nblk = pages.blocks_per_slot(self._max_len)
        if pages.num_blocks - 1 < nblk:
            _logger.warning(
                "kv_pages pool (%d usable blocks of %d tokens) cannot hold "
                "one slot's %d-token worst case (%d blocks); falling back "
                "to dense cache-off serving",
                pages.num_blocks - 1, pages.block_size, self._max_len, nblk,
            )
            self.metrics.cache_fallbacks.add(1)
            self._kv_pages = None
            return False
        self._blocks_per_slot = nblk
        self._kv_alloc = BlockAllocator(pages.num_blocks)
        if self._kv_tier_cfg is not None:
            self._kv_tier = HostTier(self._kv_tier_cfg)
            self._kv_radix = RadixCache(
                self._kv_alloc, pages.block_size, tier=self._kv_tier,
                read_block=self._tier_read_block,
                write_block=self._tier_write_block,
            )
        else:
            self._kv_radix = RadixCache(self._kv_alloc, pages.block_size)
        self._table_np = np.zeros((self._slots, nblk), np.int32)  # all sink
        # The chunk's auto width covers every
        # admission one serving quantum can offer (<= slots records,
        # <= prompt_len uncached tokens each) so default-config
        # admissions complete their prefill in the single next tick —
        # CAPPED at 256 rows: past that the fused pass's per-chunk-row
        # gather dominates the tick (each chunk row materialises its
        # slot's whole logical view per layer), and a long-prompt storm
        # is exactly where bounded per-tick prefill work is the point.
        self._prefill_chunk = pages.prefill_chunk or min(
            self._slots * self._prompt_len, max(256, 2 * pages.block_size)
        )
        self._prefill_queue = []
        self._prefilling = np.zeros((self._slots,), bool)
        self._tick_counter = 0
        return True

    def _build_paged(self) -> None:
        from torchkafka_tpu.ops.kvattn import (
            block_table_attention,
            block_table_attention_q8,
            int8_paged_decode_attention,
            int8_paged_decode_attention_sharded,
            paged_scatter_kmajor,
        )
        from torchkafka_tpu.models.quant import quant_kv_groups

        cfg = self._cfg
        B, P = self._slots, self._prompt_len
        bs = self._kv_pages.block_size
        NB = self._kv_pages.num_blocks
        nblk = self._blocks_per_slot
        nl, kh, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        temp = self._temperature
        mesh = self._mesh
        kv_int8 = self._kv_int8
        self._paged_table_idx = 4 if kv_int8 else 2

        # Pallas BLOCK-TABLE read (ops/kvattn.py): the dyn-len kernel's
        # watermark-DMA structure reading through per-slot block tables,
        # int8 pools only. Decode-only ticks read through it;
        # chunk-carrying ticks use the XLA gather (the multi-query chunk
        # needs the gathered view, and a storm tick is prefill-dominated
        # anyway). The engagement decision is the shared capability
        # probe's ("auto" only on TPU at long pools; True =
        # require-or-raise, validated at construction); under a mesh
        # the read runs per (data, tp) shard inside shard_map
        # (int8_paged_decode_attention_sharded) with the block pools
        # replicated over data and sharded per-block over tp.
        self._kv_backend = resolve_kv_backend(
            cfg, mesh=mesh, kv_dtype="int8" if kv_int8 else None,
            kv_kernel=self._kv_kernel_opt, kv_pages=self._kv_pages,
            max_len=self._max_len, slots=B, backend=jax.default_backend(),
        )
        kv_kernel = self._kv_backend.kernel
        self._kv_kernel = kv_kernel

        pick_rows = functools.partial(
            _pick_slots, temperature=temp, top_k=self._top_k,
            top_p=self._top_p,
        )

        def pull_replicated(x):
            """Constrain a per-slot operand to REPLICATED before the
            chunk tick's concatenation with the (replicated) chunk
            rows — belt to pin_paged's braces (see its docstring for
            why the paged path must keep the data axis out of the
            program on jax 0.4.x)."""
            if mesh is None:
                return x
            from jax.sharding import NamedSharding, PartitionSpec as P

            return lax.with_sharding_constraint(
                x, NamedSharding(mesh, P())
            )

        def pin_paged(pools, last_tok, pos, gen):
            """The paged pin_state: under a mesh, block pools carry kv
            heads over tp and stay REPLICATED over data (shared storage
            — any slot's table may reference any block, so there is no
            slot axis to split), and the per-slot vectors ride
            REPLICATED too. The latter is load-bearing, not a missing
            optimization: on jax 0.4.x, a paged program whose [B]
            state is sharded over data under a multi-axis mesh
            MISCOMPILES at the chunk tick's sharded-with-replicated
            concatenation — wrong VALUES (~O(1) garbage in every chunk
            row's pool write on {data,tp}/{data,fsdp} meshes; exact on
            single-axis meshes; reproduced standalone). Keeping the
            data axis out of the paged program entirely is the
            invariant that is provably exact; tp still shards the kv
            heads and every weight matrix — the actual HBM win — and
            data-parallel serving remains the FLEET's axis (one
            replica per device group). The dense pool keeps its
            slots-over-data layout. Identity on one device."""
            if mesh is None:
                return pools, last_tok, pos, gen
            from jax.sharding import NamedSharding, PartitionSpec as P

            if kv_int8:
                pp = paged_pool_kmajor_sharding(mesh)
                ps = paged_scale_kmajor_sharding(mesh)
            else:
                pp = paged_pool_sharding(mesh)
                ps = None  # compute-dtype pools are all 5D payloads
            rep = NamedSharding(mesh, P())
            return (
                tuple(
                    lax.with_sharding_constraint(c, pp if c.ndim == 5 else ps)
                    for c in pools
                ),
                lax.with_sharding_constraint(last_tok, rep),
                lax.with_sharding_constraint(pos, rep),
                lax.with_sharding_constraint(gen, rep),
            )

        def layer_pass(params, x, positions, tables, pools, *,
                       decode_kernel=False, pos_b=None):
            """All layers' write-then-attend over the paged pool(s) for
            a batch of query rows. x: [Bq, S, D]; positions: [Bq, S];
            tables: [Bq, nblk] PER-ROW block tables — decode rows carry
            the slot table, chunk rows their own freshly-linked rows, so
            one call serves any mix. int8 pools ride as a 4-tuple
            (payload+scale, K-major-per-block); ``decode_kernel`` reads
            through the Pallas block-table kernel at watermarks
            ``pos_b`` (S=1 rows only)."""

            def body(x, inputs):
                layer = inputs[0]
                q, k, v = _project_qkv(x, layer, cfg)
                q = _rope(q, positions, cfg.rope_theta)
                k = _rope(k, positions, cfg.rope_theta)
                if kv_int8:
                    pkq, pks, pvq, pvs = inputs[1:]
                    if decode_kernel:
                        kq, ks = quant_kv_groups(k)
                        vq, vs = quant_kv_groups(v)
                        pkq = paged_scatter_kmajor(pkq, tables, positions, kq)
                        pks = paged_scatter_kmajor(pks, tables, positions, ks)
                        pvq = paged_scatter_kmajor(pvq, tables, positions, vq)
                        pvs = paged_scatter_kmajor(pvs, tables, positions, vs)
                        if mesh is not None:
                            attn = int8_paged_decode_attention_sharded(
                                q, pkq, pks, pvq, pvs, tables, pos_b, mesh
                            )
                        else:
                            attn = int8_paged_decode_attention(
                                q, pkq, pks, pvq, pvs, tables, pos_b
                            )
                        x = _attn_tail(x, attn, layer, cfg)
                    else:
                        x, pkq, pks, pvq, pvs = block_table_attention_q8(
                            x, q, k, v, pkq, pks, pvq, pvs, tables,
                            positions, layer, cfg,
                        )
                    return x, (pkq, pks, pvq, pvs)
                pk, pv = inputs[1:]
                x, pk, pv = block_table_attention(
                    x, q, k, v, pk, pv, tables, positions, layer, cfg
                )
                return x, (pk, pv)

            return lax.scan(body, x, (params["layers"],) + tuple(pools))

        def logits_head(params, x_last):
            return jnp.einsum(
                "bd,dv->bv", x_last, load_weight(params["lm_head"], cfg.dtype),
                preferred_element_type=jnp.float32,
            )

        K = self._ticks_per_sync
        ti = self._paged_table_idx

        def decode_bookkeep(logits, skey, act, last_tok, pos, gen,
                            done_latch, n_out, budget):
            """The decode tick's sampling/EOS/position bookkeeping over
            per-slot logits — identical to the dense tick body's tail
            (see the dense ``tick_block`` on the one-hot gen write and on
            ``budget``)."""
            tok = pick_rows(logits, skey, pos - P + 1)
            t = pos - P  # decode ticks completed before this one
            idx = jnp.minimum(t + 1, self._max_new - 1)
            onehot = jnp.arange(self._max_new)[None, :] == idx[:, None]
            gen = jnp.where(onehot & act[:, None], tok[:, None], gen)
            hit_eos = (
                (tok == self._eos_id) if self._eos_id is not None
                else jnp.zeros_like(act)
            )
            done_now = act & (hit_eos | (t + 2 >= budget))
            pos = jnp.where(act & ~done_now, pos + 1, pos)
            last_tok = jnp.where(act, tok, last_tok)
            n_out = jnp.where(done_now, jnp.minimum(t + 2, budget), n_out)
            done_latch = done_latch | done_now
            return last_tok, pos, gen, done_latch, n_out

        def decode_one(params, pools, table, carry):
            """One decode tick over the paged pool: the dense tick body
            with the block-table scatter/gather (or the Pallas block-
            table read when the kernel is engaged). Inactive slots still
            write their frozen position — their DEVICE table rows point
            at the sink (idle AND still-prefilling slots; see
            _device_table), so the write can never corrupt a block
            another slot holds (pinned by the stale-tail regression in
            tests/test_kvcache.py)."""
            (last_tok, pos, gen, done_latch, n_out, active_in, skey,
             budget) = carry
            act = active_in & ~done_latch
            x = embed_rows(params["embed"], last_tok, cfg.dtype)[:, None, :]
            x, pools = layer_pass(
                params, x, pos[:, None], table, pools,
                decode_kernel=kv_kernel, pos_b=pos,
            )
            x = _rms_norm(x, params["ln_f"])
            logits = logits_head(params, x[:, 0])
            last_tok, pos, gen, done_latch, n_out = decode_bookkeep(
                logits, skey, act, last_tok, pos, gen, done_latch, n_out,
                budget,
            )
            return pools, (
                last_tok, pos, gen, done_latch, n_out, active_in, skey,
                budget,
            )

        def tick_block(params, caches, last_tok, pos, gen, active_in, skey,
                       budget=None):
            """K decode-only ticks in ONE dispatch — the dense
            tick_block's K-chained latched-done structure over the paged
            pool, ``budget`` included. The table passes through the
            donated state unchanged."""
            pools, table = caches[:ti], caches[ti]
            pools, last_tok, pos, gen = pin_paged(pools, last_tok, pos, gen)
            done0 = jnp.zeros((B,), bool)
            n0 = jnp.zeros((B,), jnp.int32)
            if budget is None:
                budget = jnp.full((B,), self._max_new, jnp.int32)

            def one(carry, _):
                pools, rest = carry
                pools, rest = decode_one(params, pools, table, rest)
                return (pools, rest), None

            (pools, rest), _ = lax.scan(
                one,
                (tuple(pools), (last_tok, pos, gen, done0, n0, active_in,
                                skey, budget)),
                None, length=K,
            )
            last_tok, pos, gen, done, n_out = rest[:5]
            return (
                tuple(pools) + (table,) + caches[ti + 1:],
                last_tok, pos, gen, done, n_out,
            )

        C = self._prefill_chunk

        def tick_chunk_block(params, caches, last_tok, pos, gen, active_in,
                             skey, ctok, ctable, cpos, fin_mask, fin_row,
                             budget=None):
            """THE fused tick: one static program carrying a bounded
            prefill chunk alongside all decode slots. The first inner
            tick concatenates the B decode rows with the C chunk rows
            into ONE [B + C]-row layer sweep — every weight tensor is
            read once for both workloads (the Sarathi property: prefill
            rides the stream decode already pays for); the remaining
            K - 1 ticks are decode-only. Each chunk row is one suffix
            token (ctok) of a reserved-but-prefilling slot, writing at
            its logical position (cpos) through its OWN table row
            (ctable — the device-state table masks prefilling slots to
            the sink, so only the chunk rows can touch their freshly
            linked blocks), attending causally over exactly
            [0, position] of its slot's view — bitwise the same math as
            the dense prefill at any chunk width. Padding rows carry
            all-sink tables (writes land harmlessly; their logits are
            ignored host-side). Returns the chunk rows' logits so the
            host can sample token 0 for admissions whose suffix
            completed this tick.

            ACTIVATION rides the same dispatch: ``fin_mask``/``fin_row``
            [B] mark slots whose LAST suffix token sits at chunk row
            ``fin_row[b]`` — after the decode ticks, token 0 is sampled
            from that row's logits (index-0 per-record-key draw, the
            same merge math as the dense admit, so sampling parity is
            bitwise) and the slot's last-token/position/gen state is
            merged in, ready to decode NEXT dispatch. Cold-admission
            activation therefore costs ZERO extra dispatches; only the
            rare journal warm-resume restores state host-side."""
            pools, table = caches[:ti], caches[ti]
            pools, last_tok, pos, gen = pin_paged(pools, last_tok, pos, gen)
            done0 = jnp.zeros((B,), bool)
            n0 = jnp.zeros((B,), jnp.int32)
            if budget is None:
                budget = jnp.full((B,), self._max_new, jnp.int32)
            act = active_in
            toks_all = jnp.concatenate([pull_replicated(last_tok), ctok])
            x = embed_rows(params["embed"], toks_all, cfg.dtype)[:, None, :]
            tables_all = jnp.concatenate(
                [pull_replicated(table), ctable], axis=0
            )
            pos_all = jnp.concatenate([pull_replicated(pos), cpos])
            x, pools = layer_pass(
                params, x, pos_all[:, None], tables_all, tuple(pools)
            )
            x = _rms_norm(x, params["ln_f"])
            logits_all = logits_head(params, x[:, 0])  # [B + C, V]
            chunk_logits = logits_all[B:]
            last_tok, pos, gen, done, n_out = decode_bookkeep(
                logits_all[:B], skey, act, last_tok, pos, gen, done0, n0,
                budget,
            )

            def one(carry, _):
                pools, rest = carry
                pools, rest = decode_one(params, pools, table, rest)
                return (pools, rest), None

            (pools, rest), _ = lax.scan(
                one,
                (tuple(pools), (last_tok, pos, gen, done, n_out, active_in,
                                skey, budget)),
                None, length=K - 1,
            )
            last_tok, pos, gen, done, n_out = rest[:5]
            tok0 = pick_rows(
                chunk_logits[fin_row], skey, jnp.zeros((B,), jnp.int32)
            )
            last_tok = jnp.where(fin_mask, tok0, last_tok)
            pos = jnp.where(fin_mask, P, pos)
            gen = jnp.where(fin_mask[:, None], 0, gen)
            gen = gen.at[:, 0].set(jnp.where(fin_mask, tok0, gen[:, 0]))
            return (
                tuple(pools) + (table,) + caches[ti + 1:],
                last_tok, pos, gen, done, n_out,
            )

        _tick = jax.jit(tick_block, donate_argnums=(1,))
        self._tick_takes_budget = True
        self._tick_jit = _tick
        self._tick_block_raw = tick_block
        self._tick_fn = lambda *a: _tick(self._params, *a)
        _tick_chunk = jax.jit(tick_chunk_block, donate_argnums=(1,))
        self._tick_chunk_jit = _tick_chunk
        self._tick_chunk_fn = lambda *a: _tick_chunk(self._params, *a)
        self._admit_fn = None  # paged admission is host-orchestrated
        self._resume_exec = None  # paged resume rides the chunk path
        # _table_np.copy(): jnp.asarray may ZERO-COPY an aligned host
        # buffer on the CPU backend; admissions mutate _table_np in
        # place, which would rewrite this device table from under the
        # tick (prefilling slots lose their sink mask and idle
        # frozen-pos writes corrupt freshly linked blocks).
        if kv_int8:
            self._caches = (
                jnp.zeros((nl, NB, kh, bs, dh), jnp.int8),
                jnp.zeros((nl, NB, kh, bs), jnp.float32),
                jnp.zeros((nl, NB, kh, bs, dh), jnp.int8),
                jnp.zeros((nl, NB, kh, bs), jnp.float32),
                jnp.asarray(self._table_np.copy()),
            )
        else:
            self._caches = (
                jnp.zeros((nl, NB, bs, kh, dh), cfg.dtype),
                jnp.zeros((nl, NB, bs, kh, dh), cfg.dtype),
                jnp.asarray(self._table_np.copy()),
            )
        self._last_tok = jnp.zeros((B,), jnp.int32)
        self._pos = jnp.zeros((B,), jnp.int32)
        self._gen = jnp.zeros((B, self._max_new), jnp.int32)
        if mesh is not None:
            # Place the initial pools/state in their serving layouts so
            # the first dispatch doesn't start from single-device
            # buffers. Per-slot state is REPLICATED — the paged program
            # must keep the data axis out entirely (pin_paged's
            # docstring; sharding it miscompiles on jax 0.4.x) — and
            # the table stays a replicated host snapshot (rebuilt by
            # every admission/retirement).
            from jax.sharding import NamedSharding, PartitionSpec as PSpec

            if kv_int8:
                pp = paged_pool_kmajor_sharding(mesh)
                ps = paged_scale_kmajor_sharding(mesh)
            else:
                pp = paged_pool_sharding(mesh)
                ps = None
            self._caches = tuple(
                jax.device_put(c, pp if c.ndim == 5 else ps)
                for c in self._caches[:self._paged_table_idx]
            ) + self._caches[self._paged_table_idx:]
            rep = NamedSharding(mesh, PSpec())
            self._last_tok = jax.device_put(self._last_tok, rep)
            self._pos = jax.device_put(self._pos, rep)
            self._gen = jax.device_put(self._gen, rep)

    def _paged_set_table(self, caches, table_dev):
        """Rebind the device block table inside the state tuple (the
        table's slot in the tuple differs by pool mode — after the 2
        compute-dtype pools, the 4 int8 pools, or the spec server's 4
        two-model pools)."""
        i = self._paged_table_idx
        return caches[:i] + (table_dev,) + caches[i + 1:]

    def _device_table(self) -> jax.Array:
        """The block table the DEVICE state carries. The
        rows of reserved-but-still-prefilling slots are masked to the
        sink: an inactive decode row still writes its frozen position
        unconditionally, and that write must never land in the freshly
        linked blocks the chunk rows are filling (the chunk rows carry
        their REAL rows separately, as the ctable operand). A fresh
        array, never a view of ``_table_np``: jnp.asarray may ZERO-COPY
        an aligned host buffer on the CPU backend, and later
        admissions/releases mutate ``_table_np`` in place."""
        t = np.where(
            self._active[:, None], self._table_np, SINK_BLOCK
        ).astype(np.int32)
        return jnp.asarray(t)

    def _release_slot_blocks(self, i: int) -> None:
        """Drop a retired slot's references; its table row falls back to
        the sink so the tick's frozen-position write lands harmlessly."""
        row = [int(b) for b in self._table_np[i] if b != SINK_BLOCK]
        if row:
            self._kv_alloc.decref(row)
        self._table_np[i, :] = SINK_BLOCK

    # ------------------------------------------------ tiered radix cache
    #
    # The host-RAM tier's pool I/O (kv_tier=): RadixCache calls these to
    # DEMOTE an evicted block's payload to host memory and to PROMOTE a
    # tier hit back into a fresh block. One payload = the per-pool
    # tensors at one block index (2 on compute-dtype pools, 4 on int8);
    # the bytes round-trip exactly, so a promotion is bitwise the
    # re-prefill it replaces.

    def _tier_read_block(self, block: int) -> tuple:
        ti = self._paged_table_idx
        return tuple(
            np.asarray(jax.device_get(p[:, block]))
            for p in self._caches[:ti]
        )

    def _tier_write_block(self, block: int, payload) -> None:
        fn = getattr(self, "_tier_write_jit", None)
        if fn is None:
            def write(pools, b, pay):
                return tuple(
                    p.at[:, b].set(q.astype(p.dtype))
                    for p, q in zip(pools, pay)
                )

            fn = jax.jit(write, donate_argnums=(0,))
            self._tier_write_jit = fn
        ti = self._paged_table_idx
        pools = fn(
            self._caches[:ti], jnp.int32(block),
            tuple(jnp.asarray(a) for a in payload),
        )
        self._caches = tuple(pools) + self._caches[ti:]

    def _sync_tier_metrics(self) -> None:
        """Mirror the radix/tier counters onto ServeMetrics (the radix
        owns the source of truth; deltas keep re-syncs idempotent)."""
        if self._kv_tier is None:
            return
        r = self._kv_radix
        sd, sp, sh = self._tier_seen
        if r.demotions > sd:
            self.metrics.radix_demotions.add(r.demotions - sd)
        if r.promotions > sp:
            self.metrics.radix_promotions.add(r.promotions - sp)
        if r.tier_hits > sh:
            self.metrics.tier_hits.add(r.tier_hits - sh)
        self._tier_seen = [r.demotions, r.promotions, r.tier_hits]
        self.metrics.tier_occupancy_bytes.set(
            float(self._kv_tier.occupancy_bytes)
        )

    # --------------------------------------------- disaggregated prefill
    #
    # Prefill side (prefill_role=True): completed suffix prefills are
    # harvested into PrefillHandoff units instead of decoding — the
    # slot's prompt-block payloads + resume state, for the fleet's
    # transfer plane (fleet/prefill.py). Decode side: handoffs installed
    # via add_prefill_handoffs are adopted at admission — payload
    # scattered into fresh blocks, token 0 merged like a 1-token warm
    # resume, no prompt pass.

    def _prompt_block_count(self) -> int:
        """Blocks covering positions [0, prompt_len): the straddling
        final block included (its tail past prompt_len is garbage the
        write-before-attend discipline never reads)."""
        return (self._prompt_len - 1) // self._kv_pages.block_size + 1

    def _extract_prompt_blocks(self, slot: int) -> tuple[int, tuple]:
        nb_p = self._prompt_block_count()
        ids = jnp.asarray(self._table_np[slot, :nb_p].astype(np.int32))
        ti = self._paged_table_idx
        return nb_p, tuple(
            np.asarray(jax.device_get(p[:, ids]))
            for p in self._caches[:ti]
        )

    def _harvest_prefilled(self, finishers) -> None:
        """Prefill-role epilogue of a chunk tick: every slot whose
        suffix completed this tick (token 0 already sampled in-dispatch
        by the fin merge — the standard per-record key draw) is cut
        into a handoff and released; nothing ever decodes here."""
        last = np.asarray(jax.device_get(self._last_tok))
        released = False
        for e, _row_idx in finishers:
            i = e.slot
            rec = self._slot_rec[i]
            if rec is None or not self._active[i]:
                continue
            nb_p, pools = self._extract_prompt_blocks(i)
            hand = PrefillHandoff(
                rec.topic, rec.partition, rec.offset, value_crc(rec.value),
                tuple(int(x) for x in np.asarray(e.key_np).ravel()),
                self._temperature, self._top_k, self._top_p,
                int(last[i]), nb_p, pools,
            )
            self._prefilled_ready.append((rec, hand))
            self._active[i] = False
            self._slot_rec[i] = None
            self._slot_emitted[i] = 0
            self._slot_journaled[i] = 0
            self._release_slot_blocks(i)
            released = True
        if released:
            self._caches = self._paged_set_table(
                self._caches, self._device_table()
            )
            self.metrics.cache_pool_occupancy.set(self._kv_alloc.occupancy())

    def take_prefilled(self) -> list[tuple[Record, PrefillHandoff]]:
        """Pop the harvested handoffs (prefill role). The caller
        publishes each onto the transfer plane and then confirms with
        ``note_handoff_published`` — only that retires the record in
        this worker's ledger, so a death between harvest and publish
        re-delivers the prompt to the next prefill incarnation."""
        ready, self._prefilled_ready = self._prefilled_ready, []
        return ready

    def note_handoff_published(self, rec: Record, blocks: int = 0) -> None:
        """The handoff for ``rec`` is durably on the transfer plane:
        retire the record in the prefill group's ledger."""
        self.metrics.handoffs_published.add(1)
        if self._tracer is not None:
            self._tracer.prefill_handoff(
                rec, blocks, replica=self._trace_replica
            )
        self._ledger.emitted(rec)
        self._uncommitted += 1

    def add_prefill_handoffs(self, entries: dict) -> None:
        """Install decoded ``PrefillHandoff`` units keyed by (topic,
        partition, offset). Consumed when the record is next offered for
        admission; CRC/contract-gated at adoption, so a stale or foreign
        handoff can never corrupt a slot (it just falls back to a local
        prefill)."""
        self._prefill_handoffs.update(entries)

    def has_prefill_handoff(self, key: tuple[str, int, int]) -> bool:
        """Routing probe (fleet/prefill.py's PrefillRouter): is a
        handoff ready for this record identity?"""
        return key in self._prefill_handoffs

    def _take_handoff(self, rec: Record) -> "PrefillHandoff | None":
        """Pop and validate ``rec``'s handoff; None = prefill locally
        (the at-least-once fallback every disaggregated path keeps)."""
        if self._kv_pages is None:
            return None
        hand = self._prefill_handoffs.pop(
            (rec.topic, rec.partition, rec.offset), None
        )
        if hand is None:
            return None
        ti = self._paged_table_idx
        nb_p = self._prompt_block_count()
        ok = (
            hand.crc == value_crc(rec.value)
            and hand.temperature == self._temperature
            and hand.top_k == self._top_k
            and hand.top_p == self._top_p
            and hand.prompt_blocks == nb_p
            and len(hand.pools) == ti
        )
        if ok:
            for a, p in zip(hand.pools, self._caches[:ti]):
                if (
                    tuple(a.shape) != (p.shape[0], nb_p) + tuple(p.shape[2:])
                    or a.dtype != np.dtype(p.dtype)
                ):
                    ok = False
                    break
        if not ok:
            self.metrics.resume_rejected.add(1)
            return None
        return hand

    def _adopt_upload(self, block_ids: list[int], payloads: tuple) -> None:
        """Scatter an adopted handoff's payload blocks into the pool
        (one jit specialisation per upload width, bounded by the prompt
        block count)."""
        n = len(block_ids)
        fn = self._adopt_upload_jits.get(n)
        if fn is None:
            def write(pools, ids, pay):
                return tuple(
                    p.at[:, ids].set(q.astype(p.dtype))
                    for p, q in zip(pools, pay)
                )

            fn = jax.jit(write, donate_argnums=(0,))
            self._adopt_upload_jits[n] = fn
        ti = self._paged_table_idx
        pools = fn(
            self._caches[:ti],
            jnp.asarray(np.asarray(block_ids, np.int32)),
            tuple(jnp.asarray(a) for a in payloads),
        )
        self._caches = tuple(pools) + self._caches[ti:]

    def _pack_chunk(self):
        """Fill the static chunk operands from the FIFO prefill queue:
        up to ``prefill_chunk`` suffix tokens, taken strictly in queue
        order (the ordering the radix-insert-at-admit safety argument
        stands on), each row carrying its token, logical position, and
        its slot's REAL table row. Padding rows point at the sink.
        Returns (ctok, ctable, cpos, fin_mask, fin_row, packed,
        finishers) — finishers are (entry, last_row_index) for
        admissions whose suffix completes in this chunk; cold finishers
        additionally mark ``fin_mask``/``fin_row`` so the fused program
        samples token 0 and merges the activation state IN-DISPATCH
        (journal resumes restore state host-side instead)."""
        C = self._prefill_chunk
        B = self._slots
        nblk = self._blocks_per_slot
        ctok = np.zeros((C,), np.int32)
        cpos = np.zeros((C,), np.int32)
        ctable = np.full((C, nblk), SINK_BLOCK, np.int32)
        fin_mask = np.zeros((B,), bool)
        fin_row = np.zeros((B,), np.int32)
        finishers: list[tuple[_PendingPrefill, int]] = []
        packed = 0
        while packed < C and self._prefill_queue:
            e = self._prefill_queue[0]
            n = min(C - packed, len(e.seq) - e.off)
            if e.off == 0 and self._tracer is not None:
                # First suffix tokens riding a fused tick for this record.
                self._tracer.chunk_scheduled(
                    e.rec, replica=self._trace_replica
                )
            ctok[packed:packed + n] = e.seq[e.off:e.off + n]
            cpos[packed:packed + n] = e.start + e.off + np.arange(n)
            ctable[packed:packed + n] = self._table_np[e.slot]
            e.off += n
            packed += n
            if e.off == len(e.seq):
                finishers.append((e, packed - 1))
                if e.resume is None:
                    fin_mask[e.slot] = True
                    fin_row[e.slot] = packed - 1
                self._prefill_queue.pop(0)
        return ctok, ctable, cpos, fin_mask, fin_row, packed, finishers

    def _activate_chunk_finishers(self, finishers) -> None:
        """Host bookkeeping for slots whose suffix prefill completed
        this tick: flip them active (their first decode tick is the
        NEXT dispatch — the in-program fin merge already sampled token
        0 for cold admissions), restore journal warm-resume state
        (rare; host-side), and push the device table so the newly
        active rows unmask from the sink."""
        B = self._slots
        res_mask = np.zeros((B,), bool)
        res_last = np.zeros((B,), np.int32)
        res_pos = np.zeros((B,), np.int32)
        res_gen = np.zeros((B, self._max_new), np.int32)
        for e, _row_idx in finishers:
            self._prefilling[e.slot] = False
            self._active[e.slot] = True
            if self._tracer is not None:
                # Token 0 was sampled in the activating dispatch (cold) or
                # restored from the journal (warm): TTFT closes here.
                self._tracer.slot_active(
                    e.rec, replica=self._trace_replica,
                    warm=e.resume is not None,
                )
            # Extra ticks spent queued beyond the one-tick minimum — 0
            # when the admission's whole suffix rode the first chunk.
            self.metrics.admission_stall_ticks.add(
                max(0, self._tick_counter - e.enq_tick - 1)
            )
            if e.resume is not None:
                emitted = e.resume
                res_mask[e.slot] = True
                res_last[e.slot] = emitted[-1]
                res_pos[e.slot] = self._prompt_len + len(emitted) - 1
                res_gen[e.slot, : len(emitted)] = emitted
        if res_mask.any():
            m = jnp.asarray(res_mask)
            self._last_tok = jnp.where(
                m, jnp.asarray(res_last), self._last_tok
            )
            self._pos = jnp.where(m, jnp.asarray(res_pos), self._pos)
            self._gen = jnp.where(
                m[:, None], jnp.asarray(res_gen), self._gen
            )
        self._caches = self._paged_set_table(
            self._caches, self._device_table()
        )

    @property
    def pending_admissions(self) -> int:
        """Records accepted by ``admit_records`` but deferred on block-pool
        pressure — they re-offer FIRST (per-partition FIFO) as blocks
        free. Callers subtract this from ``free_slots()`` when sizing new
        offers, and keep calling ``admit_records([])`` while it is
        nonzero so the backlog drains."""
        return len(self._paged_deferred)

    def _admit_records_paged(self, records: list[Record]) -> int:
        """Paged admission: per record — radix longest-prefix match, link
        the shared blocks, allocate private blocks (LRU-evicting
        unreferenced cached prefixes under pressure), then hand the
        uncached suffix to the PREFILL path. Sequential per record so a
        duplicate prompt inside one batch hits its predecessor's freshly
        inserted prefix.

        The slot is reserved and the suffix ENQUEUED — the decode tick's
        fused program processes it a bounded chunk at a time (step →
        _pack_chunk), and the slot activates (token 0 sampled with the
        same per-record-key discipline, or journal state restored) the
        tick its last suffix token lands. Admission itself dispatches
        NOTHING and compiles nothing: O(1) programs across any
        suffix-length mix. The radix insert happens here, at reservation
        time — a later admission matching these still-being-filled
        blocks is safe because the chunk queue is strictly FIFO, so the
        matched positions are always written in an earlier (or the same,
        write-before-attend) dispatch than any query that attends over
        them.

        A record carrying a journal resume hint prefills
        ``prompt + emitted_tokens`` instead (the cached prompt prefix
        still radix-hits) and restores position/RNG state host-side — no
        token 0 to sample; a FINISHED hint consumes no slot at all (the
        completion re-serves from the journal at the next step)."""
        phys_free = [
            i for i in range(self._slots)
            if not self._active[i] and not self._prefilling[i]
        ]
        if len(records) + len(self._paged_deferred) > len(phys_free):
            raise ValueError(
                f"offered {len(records)} records with "
                f"{len(phys_free) - len(self._paged_deferred)} admission "
                "slots (free slots minus deferred admissions)"
            )
        in_flight = self._slots - len(phys_free)
        was_deferred = len(self._paged_deferred)
        queue = self._paged_deferred + list(records)
        self._paged_deferred = []
        bs = self._kv_pages.block_size
        nblk = self._blocks_per_slot
        B, W = self._slots, self._key_width
        keys_np = np.zeros((B, W), np.uint32)
        key_mask = np.zeros((B,), bool)
        adopted: list[tuple[int, np.ndarray]] = []
        reserved = 0  # slots reserved, their prefill enqueued
        journal_dirty = False
        # NOTE: no local alias of self._caches here — tier demotions/
        # promotions inside radix.match/evict rebind self._caches
        # mid-loop, and an alias taken before the loop would clobber
        # them at the end.
        slot_iter = iter(phys_free)
        key_data = self._records_key_data(queue)
        while True:
            nxt = self._next_decodable(queue)
            if nxt is None:
                break
            rec, toks = nxt
            toks = np.asarray(toks, np.int32)
            kd = key_data[(rec.topic, rec.partition, rec.offset)]
            hint = self._take_hint(rec)
            hand = self._take_handoff(rec) if hint is None else None
            if hint is not None and hint.finished:
                out = np.asarray(hint.tokens, np.int32)
                self._journal_ready.append((rec, out))
                self.metrics.journal_served.add(1)
                if self._tracer is not None:
                    self._tracer.journal_served(
                        rec, len(out), replica=self._trace_replica
                    )
                if self._journal is not None:
                    self._journal_record(rec, hint.key_data or kd, out, True)
                    journal_dirty = True
                continue
            i = next(slot_iter, None)
            if i is None:
                # Unreachable under the caller contract (records <= free
                # slots, finished hints consume none) — fail loudly
                # rather than silently dropping a record.
                raise RuntimeError("paged admission ran out of free slots")
            emitted = (
                np.asarray(hint.tokens, np.int32) if hint is not None
                else None
            )
            seq = (
                toks if emitted is None
                else np.concatenate([toks, emitted[:-1]])
            )
            matched = self._kv_radix.match(seq)
            needed = nblk - len(matched)
            short = needed - self._kv_alloc.available()
            if short > 0:
                evicted = self._kv_radix.evict(short)
                if evicted:
                    self.metrics.cache_evictions.add(evicted)
            priv = self._kv_alloc.alloc(needed)
            if priv is None:
                # Every free block is pinned by in-flight slots: DEFER.
                # Blocks free as generations retire; deferred records
                # re-offer first, keeping per-partition FIFO (the
                # replay-free-drain invariant). The one-slot worst case
                # always fits (constructor fallback guards it), so this
                # is pressure, never deadlock. A resume hint goes back on
                # the shelf with its record.
                if matched:
                    self._kv_alloc.decref(matched)
                if hint is not None:
                    self._resume_hints[
                        (rec.topic, rec.partition, rec.offset)
                    ] = hint
                if hand is not None:
                    # Back on the shelf: the deferred re-offer re-adopts.
                    self._prefill_handoffs[
                        (rec.topic, rec.partition, rec.offset)
                    ] = hand
                if self._tracer is not None:
                    self._tracer.deferred(rec, replica=self._trace_replica)
                self._paged_deferred.append(rec)
                self._paged_deferred.extend(queue)
                queue = []
                break
            row = matched + priv
            self._table_np[i, :] = row
            if hand is not None:
                # ADOPTION: the prefill worker already computed this
                # prompt's KV — scatter the uncached blocks' payloads in
                # (radix-matched blocks already hold the identical
                # bytes) and activate with the handoff's token 0, merged
                # exactly like a 1-token journal warm resume. No prompt
                # pass runs on this replica, at any chunk width.
                nb_p = hand.prompt_blocks
                up = row[len(matched):nb_p]
                if up:
                    self._adopt_upload(up, tuple(
                        a[:, len(matched):nb_p] for a in hand.pools
                    ))
                # Payload uploaded, slot not yet active, record not yet
                # in any ledger snapshot: death here re-delivers and
                # re-adopts (or re-prefills) byte-identically.
                crash_hook("decode_adopt_pre_activate")
                cacheable = RadixCache.matchable_blocks(len(toks), bs)
                self._kv_radix.insert(toks, row[:cacheable])
                self._attach_record(i, rec)
                key_np = (
                    np.asarray(hand.key_data, np.uint32)
                    if hand.key_data else kd
                )
                keys_np[i] = key_np
                key_mask[i] = True
                self._active[i] = True
                self._slot_emitted[i] = 1
                self._slot_journaled[i] = 1
                adopted.append((i, np.asarray([hand.token0], np.int32)))
                self.metrics.adopted_slots.add(1)
                if self._tracer is not None:
                    self._tracer.adopted(rec, replica=self._trace_replica)
                if self._journal is not None:
                    self._journal_record(rec, key_np, (hand.token0,), False)
                    journal_dirty = True
                continue
            start = len(matched) * bs
            # Register the PROMPT's matchable whole blocks for reuse
            # (existing nodes are the ones we just matched; new nodes
            # adopt this slot's freshly linked private blocks — still
            # being FILLED, safe by chunk-queue FIFO: see the method
            # docstring). Emitted-token blocks are never
            # cached: offsets are unique, so they could only ever match
            # their own redelivery.
            cacheable = RadixCache.matchable_blocks(len(toks), bs)
            self._kv_radix.insert(toks, row[:cacheable])
            tenant = _record_tenant(rec)
            if matched:
                self.metrics.prefix_hits.add(1)
                self.metrics.tenant_prefix_hits(tenant).add(1)
                self.metrics.prefix_tokens_saved.add(start)
            else:
                self.metrics.prefix_misses.add(1)
                self.metrics.tenant_prefix_misses(tenant).add(1)
            self._attach_record(i, rec)
            key_np = (
                np.asarray(hint.key_data, np.uint32)
                if hint is not None and hint.key_data is not None else kd
            )
            keys_np[i] = key_np
            key_mask[i] = True
            if hint is None:
                self._slot_emitted[i] = 0
                self._slot_journaled[i] = 0
                if self._journal is not None:
                    self._journal_record(rec, kd, (), False)
                    journal_dirty = True
            else:
                self._slot_emitted[i] = len(emitted)
                self._slot_journaled[i] = len(emitted)
                self.metrics.warm_resumes.add(1)
                self.metrics.journal_tokens_restored.add(len(emitted))
                if self._tracer is not None:
                    self._tracer.warm_resumed(
                        rec, len(emitted), replica=self._trace_replica
                    )
                if self._journal is not None:
                    self._journal_record(rec, key_np, emitted, False)
                    journal_dirty = True
            # Reserve, enqueue, dispatch nothing: the tick's fused
            # program prefills this suffix chunk by chunk and the slot
            # activates the tick its last token lands.
            self._prefilling[i] = True
            self._prefill_queue.append(_PendingPrefill(
                i, rec, np.asarray(seq[start:], np.int32), start,
                key_np, emitted, self._tick_counter,
            ))
            if self._tracer is not None:
                self._tracer.prefill_queued(
                    rec, len(seq) - start, replica=self._trace_replica
                )
            reserved += 1
        if queue:  # defensive: slots exhausted with records left
            self._paged_deferred.extend(queue)
        # Count records ENTERING the deferred state, not retry spins: the
        # run/pump loops re-offer the backlog every quantum under
        # pressure, which must not inflate the counter.
        newly_deferred = len(self._paged_deferred) - was_deferred
        if newly_deferred > 0:
            self.metrics.admission_deferrals.add(newly_deferred)
        self.metrics.cache_pool_occupancy.set(self._kv_alloc.occupancy())
        self._sync_tier_metrics()
        filled = len(adopted) + reserved
        # The paged admission prefills row by row: no row is prefilled
        # that was not admitted (an adoption prefills nothing here).
        if reserved:
            self.metrics.admit_calls.add(1)
            self.metrics.admit_rows.add(reserved)
            self.metrics.admit_rows_prefilled.add(reserved)
        if filled:
            if in_flight > 0:
                self.metrics.readmissions.add(filled)
            if adopted:
                # Reservations push nothing: the device table keeps
                # prefilling rows masked to the sink until activation
                # (_device_table). Adopted slots activate NOW — their
                # rows must unmask this push.
                self._caches = self._paged_set_table(
                    self._caches, self._device_table()
                )
            self._slot_keys = jnp.where(
                jnp.asarray(key_mask)[:, None], jnp.asarray(keys_np),
                self._slot_keys,
            )
        if adopted:
            res_mask = np.zeros((B,), bool)
            res_last = np.zeros((B,), np.int32)
            res_pos = np.zeros((B,), np.int32)
            res_gen = np.zeros((B, self._max_new), np.int32)
            for i, emitted in adopted:
                res_mask[i] = True
                res_last[i] = emitted[-1]
                # An adoption restores exactly one emitted token (the
                # handoff's admit draw) — the g=1 warm-resume state.
                res_pos[i] = self._prompt_len + len(emitted) - 1
                res_gen[i, : len(emitted)] = emitted
            m = jnp.asarray(res_mask)
            self._last_tok = jnp.where(
                m, jnp.asarray(res_last), self._last_tok
            )
            self._pos = jnp.where(m, jnp.asarray(res_pos), self._pos)
            self._gen = jnp.where(
                m[:, None], jnp.asarray(res_gen), self._gen
            )
            if self._tracer is not None:
                for i, _emitted in adopted:
                    # Adoption's first token genuinely exists now: TTFT
                    # closes here (not warm — nothing predates the poll).
                    self._tracer.slot_active(
                        self._slot_rec[i], replica=self._trace_replica,
                    )
        if journal_dirty:
            self._journal.flush()
        return filled

    def warmup(self) -> None:
        """Compile the admit and decode programs (no-op inputs) so the
        first real generation doesn't pay XLA compilation (minutes at the
        8B-class scales, not milliseconds). The no-op admit (all-False
        mask) leaves the slot state semantically unchanged."""
        B = self._slots
        none = jnp.zeros((B,), bool)
        # The tick/admit "key" operand is per-slot record-key data
        # ([B, W] uint32); the zero-initialized slot keys are exactly the
        # no-op shape.
        key = self._slot_keys
        if self._kv_pages is not None:
            # Compile every program a paged serve can dispatch: the
            # fused chunk tick (an all-padding chunk — writes land in
            # the sink) and the decode-only tick. Admission compiles
            # NOTHING later — these are the whole program set, whatever
            # suffix-length mix arrives.
            C, nblk = self._prefill_chunk, self._blocks_per_slot
            out = self._tick_chunk_fn(
                self._caches, self._last_tok, self._pos, self._gen,
                none, key, jnp.zeros((C,), jnp.int32),
                jnp.full((C, nblk), SINK_BLOCK, jnp.int32),
                jnp.zeros((C,), jnp.int32), none,
                jnp.zeros((B,), jnp.int32), *self._tick_budget(),
            )
            self._caches, self._last_tok, self._pos, self._gen = out[:4]
            jax.device_get(out[4])
            out = self._tick_fn(
                self._caches, self._last_tok, self._pos, self._gen, none, key,
                *self._tick_budget(),
            )
            self._caches, self._last_tok, self._pos, self._gen = out[:4]
            jax.device_get(out[4])
            return
        self._caches, self._last_tok, self._pos, self._gen = self._admit_fn(
            self._caches, self._last_tok, self._pos, self._gen,
            jnp.zeros((B, self._prompt_len), jnp.int32), none, key,
        )
        out = self._tick_fn(
            self._caches, self._last_tok, self._pos, self._gen, none, key,
            *self._tick_budget(),
        )
        self._caches, self._last_tok, self._pos, self._gen = out[:4]
        jax.device_get(out[4])

    # ---------------------------------------------- live model lifecycle

    @property
    def model_version(self) -> int:
        """The version id of the weights currently serving."""
        return self._model_version

    def swap_params(self, params, version: int) -> None:
        """Hot-swap the serving weights IN PLACE — no recompilation (the
        jitted programs take params as an argument; rebinding the
        closure's source is the whole swap) and no group churn (the
        consumer, lease, and slots are untouched).

        Preconditions make a mixed-version commit window impossible by
        construction: the caller must have QUIESCED (no active or
        prefilling slot — finish in-flight first) and CLOSED the commit
        window (flush_commits) — so every output the old weights
        produced is already committed under the old version tag, and
        everything after this call is produced, journaled, and committed
        under the new one. Durability order is version-journal-first:
        the journal's model_version meta is fsynced BEFORE the in-memory
        rebind, so a SIGKILL between the two restarts on weights that
        match the (empty) journal either way — ``rollout_pre_swap`` dies
        with the OLD version durable, ``swap_mid_apply`` with the NEW;
        the crash matrix kills at both to prove half-old/half-new state
        is unreachable."""
        if self.has_active():
            raise RuntimeError(
                "swap_params requires a quiesced server (drain in-flight "
                "generations first — the warm-drain discipline)"
            )
        if self._uncommitted or (self._txn_mode and self._txn_outbox):
            raise RuntimeError(
                "swap_params requires a closed commit window "
                "(flush_commits first) — a window must never span model "
                "versions"
            )
        version = int(version)
        crash_hook("rollout_pre_swap")
        if self._journal is not None:
            self._journal.set_model_version(version)
            self._journal.sync()
        crash_hook("swap_mid_apply")
        if self._mesh is not None:
            params = jax.device_put(
                params, serving_shardings(self._cfg, self._mesh, params)
            )
        # ONE rebind: the admit/tick lambdas read self._params at call
        # time, so there is no instant where some program sees old and
        # some new weights.
        self._params = params
        self._model_version = version
        if self._tracer is not None:
            self._tracer.swapped(
                version, replica=self._trace_replica
            )

    def spawn_shadow(self, params, version: int) -> "StreamingGenerator":
        """A scratch single-slot generator over CANDIDATE weights for
        canary shadow-serving: same config, prompt decoding, sampling
        contract, and per-record RNG base as this server — so for any
        record its output is byte-for-byte what the candidate version
        WOULD commit — but no consumer group, no producer, no journal:
        nothing a shadow decodes can ever reach the committed view (the
        'divergent canary never publishes' invariant is structural).
        Dense serving path regardless of the incumbent's KV mode (paged/
        dense are differential-tested token-exact)."""
        return StreamingGenerator(
            _ShadowConsumer(), params, self._cfg,
            slots=1,
            prompt_len=self._prompt_len,
            max_new=self._max_new,
            eos_id=self._eos_id,
            commit_every=2**31 - 1,
            decode_prompt=self._decode_prompt,
            ticks_per_sync=1,
            temperature=self._temperature,
            top_k=self._top_k,
            top_p=self._top_p,
            rng=self._rng,
            mesh=self._mesh,
            max_new_of=self._max_new_of,
            model_version=int(version),
        )

    def shadow_decode(self, rec: Record) -> np.ndarray | None:
        """Decode ``rec`` to completion on THIS generator as a shadow
        pass (canary use: call on a ``spawn_shadow`` instance). Returns
        the tokens, or None if the record is undecodable. The record is
        ledger-registered locally but never committed anywhere."""
        self.note_fetched([rec])
        if self.admit_records([rec]) == 0 and not self._journal_ready:
            return None
        out: np.ndarray | None = None
        while self.has_active() or self._journal_ready:
            for done_rec, toks in self.step():
                if done_rec.offset == rec.offset and \
                        done_rec.topic == rec.topic and \
                        done_rec.partition == rec.partition:
                    out = toks
        return out

    # ------------------------------------------- external admission surface
    #
    # run() is a thin loop over four primitives, each usable on its own by
    # an EXTERNAL scheduler (the serving fleet's QoS admission layer,
    # torchkafka_tpu/fleet/): the caller polls its own consumer, decides
    # which records deserve a slot, and drives the device loop tick by
    # tick. The commit/ledger discipline is identical on both paths — the
    # primitives are the same code run() executes.

    @property
    def slots(self) -> int:
        """Size of the decode slot pool."""
        return self._slots

    def free_slots(self) -> int:
        """Slots currently available for admission (a reserved-but-
        still-prefilling chunked admission holds its slot)."""
        return int((~(self._active | self._prefilling)).sum())

    def has_active(self) -> bool:
        """True while any generation is in flight — decoding OR still
        chunk-prefilling (the drain/idle loops must keep ticking until
        queued admissions activate and retire)."""
        return bool(self._active.any() or self._prefilling.any())

    def note_fetched(self, records: list[Record]) -> None:
        """Register polled records with the ledger BEFORE queueing them.

        External admission must call this at poll time, not admit time: a
        record sitting in an admission queue while a LATER record of the
        same partition completes would otherwise be invisible to the
        ledger, and the commit watermark could advance past it — losing it
        on crash. (run() calls this on its own polls.)"""
        # Fetched, not yet registered anywhere durable: death in this
        # window must re-deliver the records verbatim (nothing references
        # them but the broker's uncommitted offsets).
        crash_hook("post_poll")
        self._ledger.fetched_many(records)
        tr = self._tracer
        if tr is not None:
            for r in records:
                tr.polled(r, replica=self._trace_replica)

    def note_partitions_revoked(self, tps) -> None:
        """A rebalance took these partitions away: reset their ledger
        state and drop their internally-deferred admissions. Without
        the reset, records fetched here but served by the NEW owner
        stay 'pending' forever — and if the partition later comes BACK
        (scale-down returning a scale-up's range), the stale entries
        hold the snapshot below the broker's committed watermark and
        the next commit REGRESSES it group-wide (last-write-wins).
        Records already decoding in slots are left alone: their
        completions resolve against the dropped partition as tolerated
        no-ops, and any copy the new owner serves is the ordinary
        at-least-once duplicate."""
        tps = set(tps)
        if not tps:
            return
        self._ledger.drop(tps)
        if self._paged_deferred:
            kept = [r for r in self._paged_deferred if r.tp not in tps]
            dropped = len(self._paged_deferred) - len(kept)
            if dropped:
                self._paged_deferred = kept
                _logger.info(
                    "dropped %d deferred admission(s) for revoked "
                    "partitions", dropped,
                )

    def _next_decodable(self, queue: list[Record]):
        """Pop ``queue`` until a record decodes; returns (record, tokens)
        or None when exhausted. Failures follow the poison policy: with a
        quarantine, each failure spends the record's retry budget (the
        SAME record re-attempts in place — a transient tokenizer fault
        heals here) and an exhausted budget dead-letters it (the record
        is RESOLVED, its offset may retire; a failed DLQ produce raised
        OutputDeliveryError out of note_failure — fail-stop before any
        commit could cover the record). Without one, the record retires
        as dropped (the reference's None-filter analog) — or it would
        re-deliver and crash the server forever on restart."""
        while queue:
            rec = queue.pop(0)
            while True:
                try:
                    return rec, self._decode_prompt(rec)
                except Exception as exc:
                    if self._quarantine is not None:
                        # In exactly_once mode the quarantine's producer
                        # was rebound onto the transactional outbox at
                        # construction: its dead-letter produce stages by
                        # record identity and commits atomically with
                        # the offset that retires the poison record (a
                        # re-quarantine after redelivery overwrites the
                        # identical entry — one committed DLQ copy).
                        try:
                            resolved = self._quarantine.note_failure(rec, exc)
                        except OutputDeliveryError:
                            self.metrics.dlq_delivery_failures.add(1)
                            if self._tracer is not None:
                                self._tracer.dlq_failed(
                                    rec, replica=self._trace_replica
                                )
                            raise
                        if not resolved:
                            continue  # budget left: re-attempt in place
                        self.metrics.quarantined.add(1)
                        if self._tracer is not None:
                            self._tracer.quarantined(
                                rec, replica=self._trace_replica
                            )
                        # DLQ copy acknowledged durable; the offset has
                        # NOT retired yet — the crash window where
                        # redelivery must re-quarantine idempotently.
                        crash_hook("post_dlq_pre_retire")
                    else:
                        _logger.exception(
                            "dropping undecodable prompt %s@%s:%s",
                            rec.topic, rec.partition, rec.offset,
                        )
                        if self._tracer is not None:
                            self._tracer.dropped(
                                rec, replica=self._trace_replica
                            )
                    self._ledger.dropped(rec)
                    self.metrics.dropped.add(1)
                    break  # next record
        return None

    def _records_key_data(self, records: list[Record]) -> dict:
        """Every record's sampling key, by ``(topic, partition, offset)``:
        ``rng`` folded with the record's identity — a pure function of
        (base key, topic, partition, offset), so every replica/process
        derives the SAME key for the same record (the fleet shares
        gen_kwargs). Raw key data, journal- and device-friendly. An
        admission's keys come from ONE dispatch and one fetch: a record
        at a time each key is three eager folds and a blocking fetch, a
        device round trip a record while the device waits for the
        admission (a third of a second for 64 records, two for 384:
        PERF.md §6, PR 41). The ids are padded to the slots (a whole
        number of times), so the program has one shape and compiles with
        the first admission (a warm-up's), never inside a serving window."""
        if not records:
            return {}
        width = -(-len(records) // self._slots) * self._slots
        ids = np.zeros((3, width), np.uint32)
        for j, rec in enumerate(records):
            ids[:, j] = (
                zlib.crc32(rec.topic.encode()) & 0x7FFFFFFF,
                rec.partition & 0x7FFFFFFF, rec.offset & 0x7FFFFFFF,
            )
        data = np.asarray(_fold_record_ids(self._rng, jnp.asarray(ids)))
        return {
            (rec.topic, rec.partition, rec.offset): data[j].astype(np.uint32)
            for j, rec in enumerate(records)
        }

    def add_resume_hints(self, entries: dict) -> None:
        """Install journal entries (``journal.DecodeJournal.load`` of a
        dead replica's file, or a previous incarnation's) keyed by
        (topic, partition, offset). A hint is consumed when its record is
        next offered for admission; unmatched hints sit harmlessly (the
        payload CRC check means a hint can never resume a different
        record)."""
        self._resume_hints.update(entries)

    def _take_hint(self, rec: Record) -> JournalEntry | None:
        """Pop and validate ``rec``'s resume hint. None = admit cold."""
        hint = self._resume_hints.pop(
            (rec.topic, rec.partition, rec.offset), None
        )
        if hint is None:
            return None
        g = len(hint.tokens)
        ok = (
            hint.crc == value_crc(rec.value)
            and hint.temperature == self._temperature
            and hint.top_k == self._top_k
            and hint.top_p == self._top_p
            # A prefix decoded under another model version continued
            # under this one would match NEITHER reference — version-
            # mismatched hints fall back to cold replay (still correct).
            and hint.model_version == self._model_version
            and 1 <= g <= self._max_new
            and (hint.finished or g < self._max_new)
            # Partial-generation resume prefills through this server's
            # cache: possible exactly when the pool keeps the exactness
            # contract and the prefill has a spelling (_resume_supported).
            # Finished hints need no prefill at all.
            and (hint.finished or self._resume_supported())
        )
        if not ok:
            if g >= 1:  # a bare admit-time entry is not a rejection
                self.metrics.resume_rejected.add(1)
            return None
        return hint

    def _resume_supported(self) -> bool:
        """Can a PARTIAL journal hint warm-resume here (``KVBackend.
        resumable``)? Everything else replays cold, which is still correct."""
        return self._kv_backend.resumable

    def _journal_record(self, rec, key_data, tokens, finished) -> None:
        self._journal.record(
            rec, key_data, tokens=tokens, finished=finished,
            temperature=self._temperature, top_k=self._top_k,
            top_p=self._top_p, model_version=self._model_version,
        )

    def _attach_record(self, i: int, rec: Record) -> None:
        """Slot ``i`` serves ``rec`` from here on: the record and its
        answer budget (``max_new_of``, held to [1, max_new]; ``max_new``
        where there is none), which the next tick block takes."""
        self._slot_rec[i] = rec
        budget = self._max_new_of(rec) if self._max_new_of is not None else None
        budget = (
            self._max_new if budget is None
            else max(1, min(int(budget), self._max_new))
        )
        if budget != self._slot_budget[i]:
            self._slot_budget[i] = budget
            self._slot_budget_dev = None

    def _tick_budget(self) -> tuple:
        """The tick programs' trailing operand: the slots' budgets on the
        device, sent again only after an admission changed one (no
        transfer a tick); nothing for tick programs that take none."""
        if not self._tick_takes_budget:
            return ()
        if self._slot_budget_dev is None:
            self._slot_budget_dev = jnp.asarray(self._slot_budget.copy())
        return (self._slot_budget_dev,)

    def _resume_into_slot(self, i: int, rec: Record, prompt_toks,
                          hint: JournalEntry, key_np: np.ndarray) -> None:
        """Dense warm resume: one prefill dispatch of prompt + journaled
        tokens into slot ``i`` (see the in-jit ``resume_admit``)."""
        emitted = np.asarray(hint.tokens, np.int32)
        g = len(emitted)
        seq = np.concatenate(
            [np.asarray(prompt_toks, np.int32), emitted[:-1]]
        )[None, :]
        row = np.zeros((self._max_new,), np.int32)
        row[:g] = emitted
        out = self._resume_exec(
            self._caches, self._last_tok, self._pos, self._gen,
            jnp.asarray(seq), jnp.int32(i), jnp.asarray(row), jnp.int32(g),
        )
        self._caches, self._last_tok, self._pos, self._gen = out
        self._attach_record(i, rec)
        self._active[i] = True
        self._slot_emitted[i] = g
        self._slot_journaled[i] = g
        self.metrics.warm_resumes.add(1)
        self.metrics.journal_tokens_restored.add(g)
        if self._tracer is not None:
            self._tracer.warm_resumed(rec, g, replica=self._trace_replica)
            self._tracer.slot_active(
                rec, replica=self._trace_replica, warm=True
            )
        if self._journal is not None:
            self._journal_record(rec, key_np, emitted, False)

    def admit_records(self, records: list[Record]) -> int:
        """Prefill-admit ``records`` into free slots; returns the number
        of slots filled (cold admissions + journal warm resumes; a
        FINISHED journal hint re-serves from the journal without a slot).
        Undecodable records are retired as dropped/quarantined
        (``_next_decodable``) and do not consume a slot. Records must
        already be ``note_fetched``; the caller must not offer more
        records than ``free_slots()`` (minus ``pending_admissions`` in
        paged mode, where pool pressure can also DEFER records — call
        with an empty list to re-offer the deferred backlog)."""
        if self._kv_pages is not None:
            # Paged admission dispatches no prefill (the fused tick
            # carries it): the whole call is preparation.
            with xprof.span(xprof.SPAN_ADMIT_PREP):
                return self._admit_records_paged(records)
        with xprof.span(xprof.SPAN_ADMIT_PREP):
            free = [i for i in range(self._slots) if not self._active[i]]
            if len(records) > len(free):
                raise ValueError(
                    f"offered {len(records)} records with {len(free)} "
                    "free slots"
                )
            in_flight = self._slots - len(free)
            B, W = self._slots, self._key_width
            prompts = np.zeros((B, self._prompt_len), np.int32)
            admit_mask = np.zeros((B,), bool)
            keys_np = np.zeros((B, W), np.uint32)
            key_mask = np.zeros((B,), bool)
            queue = list(records)
            key_data = self._records_key_data(queue)
            slot_iter = iter(free)
            resumed = 0
            journal_dirty = False
            while True:
                nxt = self._next_decodable(queue)
                if nxt is None:
                    break
                rec, toks = nxt
                kd = key_data[(rec.topic, rec.partition, rec.offset)]
                hint = self._take_hint(rec)
                if hint is not None and hint.finished:
                    # The dead replica finished this completion but never
                    # committed it: re-serve the journaled tokens verbatim at
                    # the next step — zero re-decode, byte-identical output.
                    out = np.asarray(hint.tokens, np.int32)
                    self._journal_ready.append((rec, out))
                    self.metrics.journal_served.add(1)
                    if self._tracer is not None:
                        self._tracer.journal_served(
                            rec, len(out), replica=self._trace_replica
                        )
                    if self._journal is not None:
                        self._journal_record(
                            rec, hint.key_data or kd, out, True
                        )
                        journal_dirty = True
                    continue
                i = next(slot_iter, None)
                if i is None:
                    # Unreachable under the caller contract (records <= free
                    # slots; finished hints consume none).
                    raise RuntimeError("admission ran out of free slots")
                key_np = (
                    np.asarray(hint.key_data, np.uint32)
                    if hint is not None and hint.key_data is not None else kd
                )
                keys_np[i] = key_np
                key_mask[i] = True
                if hint is not None:
                    self._resume_into_slot(i, rec, toks, hint, key_np)
                    resumed += 1
                    journal_dirty = journal_dirty or self._journal is not None
                    continue
                prompts[i] = toks
                self._attach_record(i, rec)
                admit_mask[i] = True
                self._active[i] = True
                self._slot_emitted[i] = 0
                self._slot_journaled[i] = 0
                if self._journal is not None:
                    self._journal_record(rec, kd, (), False)
                    journal_dirty = True
            admitted = int(admit_mask.sum())
            filled = admitted + resumed
            if filled:
                if in_flight > 0:
                    # Slots refilled while other generations were mid-flight:
                    # the observable that distinguishes continuous batching
                    # from lockstep waves.
                    self.metrics.readmissions.add(filled)
                self._slot_keys = jnp.where(
                    jnp.asarray(key_mask)[:, None], jnp.asarray(keys_np),
                    self._slot_keys,
                )
            if admitted:
                operands = (
                    jnp.asarray(prompts), jnp.asarray(admit_mask),
                    jnp.asarray(keys_np),
                )
        if admitted:
            with xprof.span(xprof.SPAN_ADMIT):
                out = self._admit_fn(
                    self._caches, self._last_tok, self._pos, self._gen,
                    *operands,
                )
            # Rebind self state after every dispatch: admit/tick DONATE
            # the pool, so the old self._caches handles are dead buffers —
            # without this, anything reading server state afterwards (a
            # second run, spec_stats) holds deleted arrays.
            self._caches, self._last_tok, self._pos, self._gen = out
            if self._tracer is not None:
                for i in np.nonzero(admit_mask)[0]:
                    self._tracer.slot_active(
                        self._slot_rec[i], replica=self._trace_replica,
                        dispatched=True,
                    )
        if filled:
            # The admit program prefills its admitted rows a chunk at a
            # time, the last chunk padded to its static rows; a warm resume
            # prefills its one row in a dispatch of its own.
            chunks = -(-admitted // self._admit_chunk_rows)
            self.metrics.admit_calls.add(1)
            self.metrics.admit_rows.add(filled)
            self.metrics.admit_rows_prefilled.add(
                chunks * self._admit_chunk_rows + resumed
            )
        if journal_dirty:
            self._journal.flush()
        return filled

    def _txn_abort(self) -> None:
        """Defensive abort of an in-flight transaction (best effort — a
        dead broker just leaves it for the next ``begin`` or the next
        incarnation's epoch fence to abort). The outbox is untouched:
        its entries re-send inside the next window's transaction."""
        try:
            if self._output_producer.abort():
                self.metrics.txn_aborts.add(1)
        except Exception:  # noqa: BLE001 - the broker will abort it
            _logger.debug("defensive transaction abort failed", exc_info=True)

    def _retire_completion(
        self, rec: Record, out: np.ndarray,
        completions: list[tuple[Record, np.ndarray]],
    ) -> None:
        """The single completion exit: metrics, output publish (fail
        closed per record), ledger retirement. Shared by tick-produced
        completions and journal-served ones, so both follow the exact
        same durability discipline."""
        self.metrics.completions.add(1)
        self.metrics.tokens.add(len(out))
        if len(out) < self._max_new:
            self.metrics.truncated.add(1)
        if self._tracer is not None:
            self._tracer.finished(
                rec, len(out), replica=self._trace_replica
            )
        if self._distill_topic is not None:
            # Frame the training-corpus record NOW (tokens in hand) but
            # produce it only WITH the commit that covers its offset
            # (txn: inside the transaction; at-least-once: after the
            # commit succeeds) — the corpus holds committed tokens only.
            # Keyed by record identity: a re-serve overwrites the
            # identical frame (one committed copy, ever).
            self._distill_outbox[(rec.topic, rec.partition, rec.offset)] = (
                self._encode_distill(
                    self._decode_prompt(rec), out,
                    tenant=rec.key, model_version=self._model_version,
                )
            )
        sent_ok = True
        if self._output_producer is not None:
            # Async send; durability is settled in _commit (flush
            # + per-handle get) BEFORE offsets commit. A
            # SYNCHRONOUS send failure (buffer full with the
            # output broker down, closed producer, missing topic)
            # must not kill serving OR let the record commit: skip
            # emitted() so the ledger watermark stalls at exactly
            # this record — it re-delivers and regenerates on
            # restart.
            if self._txn_mode:
                # STAGE, don't send: the outbox entry is produced inside
                # the commit window's transaction — and only once the
                # in-order watermark covers this record's offset, so its
                # output and its offset are one atomic broker decision.
                # Keyed by record identity: an eager-rebalance re-serve
                # of the same record overwrites the identical entry (one
                # committed copy, ever). Nothing here can fail, so the
                # send-failure streak machinery doesn't apply — output
                # path health surfaces at transaction commit instead.
                self._txn_outbox[(rec.topic, rec.partition, rec.offset)] = (
                    dict(
                        topic=self._output_topic,
                        value=self._encode_output(rec, out),
                        key=rec.key,
                        # The version tag: every committed output window
                        # records which weights produced it (swap_params
                        # only lands between windows, so a window is
                        # never mixed-version).
                        headers=(
                            ("mv", str(self._model_version).encode()),
                        ),
                    )
                )
            else:
                try:
                    self._pending_outputs.append(
                        self._output_producer.send(
                            self._output_topic,
                            self._encode_output(rec, out),
                            key=rec.key,
                            headers=(
                                ("mv", str(self._model_version).encode()),
                            ),
                        )
                    )
                    self._send_failure_streak = 0
                except Exception:  # noqa: BLE001 - fail closed per record
                    sent_ok = False
                    self.metrics.output_send_failures.add(1)
                    self._send_failure_streak += 1
                    _logger.exception(
                        "output send failed for %s@%d:%d; leaving "
                        "it uncommitted to re-deliver",
                        rec.topic, rec.partition, rec.offset,
                    )
                if (
                    self._send_failure_streak
                    >= self._max_send_failure_streak
                ):
                    # The output path is down, not blinking: every
                    # further completion would be un-committable
                    # replay work behind a permanently stalled
                    # watermark. Fail-stop like the flush/get path
                    # so the operator gets one signal for "output
                    # lost".
                    raise OutputDeliveryError(
                        f"{self._send_failure_streak} "
                        "consecutive output send failures; "
                        "failing stop so uncommitted prompts "
                        "re-deliver instead of serving into a "
                        "stalled commit watermark"
                    )
        if sent_ok:
            self._ledger.emitted(rec)
            self._uncommitted += 1
        completions.append((rec, out))

    def step(self) -> list[tuple[Record, np.ndarray]]:
        """One decode tick block over the active slots; returns the
        completions it retired (ledger-emitted, output-published, commit
        cadence applied) in completion order — journal-served
        completions (finished entries from a dead replica's journal,
        zero re-decode) first, then the tick's. No-op on an idle pool
        with no journal backlog."""
        completions: list[tuple[Record, np.ndarray]] = []
        if self._journal_ready:
            ready, self._journal_ready = self._journal_ready, []
            with xprof.span(xprof.SPAN_RETIRE):
                for rec, out in ready:
                    self._retire_completion(rec, out, completions)
        run_chunk = bool(self._prefill_queue)
        if self._active.any() or run_chunk:
            self._tick_counter += 1
            tick_t0 = time.perf_counter()
            finishers = None
            if run_chunk:
                # The fused program: a bounded chunk of queued suffix
                # tokens rides this tick's layer sweep alongside every
                # decode slot — admission work never preempts a decode
                # tick, it shares one.
                with xprof.span(xprof.SPAN_CHUNK_PACK):
                    (ctok, ctable, cpos, fin_mask, fin_row, packed,
                     finishers) = self._pack_chunk()
                with xprof.span(xprof.SPAN_TICK):
                    caches, last_tok, pos, gen, done, n_out = (
                        self._tick_chunk_fn(
                            self._caches, self._last_tok, self._pos,
                            self._gen, jnp.asarray(self._active.copy()),
                            self._slot_keys, jnp.asarray(ctok),
                            jnp.asarray(ctable), jnp.asarray(cpos),
                            jnp.asarray(fin_mask), jnp.asarray(fin_row),
                            *self._tick_budget(),
                        )
                    )
                self.metrics.chunk_ticks.add(1)
                self.metrics.prefill_tokens.add(packed)
                self.metrics.chunk_utilization.set(
                    self.metrics.prefill_tokens.count
                    / (self.metrics.chunk_ticks.count * self._prefill_chunk)
                )
            else:
                with xprof.span(xprof.SPAN_TICK):
                    caches, last_tok, pos, gen, done, n_out = self._tick_fn(
                        self._caches, self._last_tok, self._pos, self._gen,
                        jnp.asarray(self._active.copy()), self._slot_keys,
                        *self._tick_budget(),
                    )
            self._caches, self._last_tok, self._pos, self._gen = (
                caches, last_tok, pos, gen
            )
            # ONE host sync per tick block: done/n_out/gen/pos fetched
            # together (separate np.asarray calls are separate round
            # trips).
            with xprof.span(xprof.SPAN_SYNC):
                done_h, n_out_h, gen_h, pos_h, stats_h, admits_h = (
                    jax.device_get((
                        done, n_out, gen, pos, self._tick_stats,
                        self._admit_stats,
                    ))
                )
            self._admit_stats = []
            for rows, tile_rows in admits_h:
                self.metrics.moe_grouped_rows.add(int(rows))
                self.metrics.moe_grouped_tile_rows.add(int(tile_rows))
            if stats_h is not None:
                touched, load, *fates = stats_h
                self.metrics.moe_experts_touched.add(int(touched))
                self.metrics.moe_expert_load += load
                zero, local, absent = (
                    int(n) for n in (fates[0] if fates else (0, load.sum(), 0))
                )
                self.metrics.moe_zero_assignments.add(zero)
                self.metrics.moe_local_assignments.add(local)
                self.metrics.moe_absent_assignments.add(absent)
                self.metrics.moe_assignments.add(zero + local + absent)
            self.metrics.tick_time.observe(time.perf_counter() - tick_t0)
            crash_hook("mid_tick")
            with xprof.span(xprof.SPAN_RETIRE):
                self._retire_block(
                    done_h, n_out_h, gen_h, pos_h, completions, finishers,
                    run_chunk,
                )
        if (
            completions
            and self._uncommitted >= self._commit_every
            and self._commit()
        ):
            self._uncommitted = 0
        return completions

    def _retire_block(self, done_h, n_out_h, gen_h, pos_h, completions,
                      finishers, run_chunk: bool) -> None:
        """What the host does with a tick block's fetched state, from the
        sync's return on: the per-record budget clamp, token accounting
        (metrics, tracer, journal), retirement of finished slots into
        ``completions`` and activation of completed chunk prefills."""
        self.metrics.slot_occupancy.set(float(self._active.mean()))
        if self._max_new_of is not None:
            # device_get may hand back non-writable views; the budget
            # clamp below mutates the done/count mirrors.
            done_h = np.array(done_h)
            n_out_h = np.array(n_out_h)
        # Per-slot emitted-token mirrors: decoded-token accounting
        # (the cold-vs-warm replay differential) and the journal's
        # token cadence both read them. Counted BEFORE retirement so
        # a completing slot's final tokens are journaled while its
        # record is still attached.
        journal_dirty = False
        decoded = 0
        first_tokens = 0  # slots surfacing their admission's own token
        spans = []  # what the served ticks produced, for the pool's meters
        for i in np.nonzero(self._active)[0]:
            cnt = int(
                n_out_h[i] if done_h[i]
                else pos_h[i] - self._prompt_len + 1
            )
            ran = cnt  # tokens of the ticks the device held the slot live
            budget = int(self._slot_budget[i])
            if done_h[i] and cnt == budget:
                # Latched at its budget by the tick: capped unless the
                # budget is the buffer or the last token is the EOS.
                if budget < self._max_new and not (
                    self._eos_id is not None
                    and gen_h[i, cnt - 1] == self._eos_id
                ):
                    self.metrics.output_capped.add(1)
            elif cnt >= budget:
                # The guard for a tick program that does not take the
                # budget: its blocks may overshoot by up to
                # ticks_per_sync - 1 tokens; the overshoot is truncated
                # and the slot force-finished exactly like a device done.
                cnt = budget
                if not done_h[i]:
                    self.metrics.output_capped.add(1)
                done_h[i] = True
                n_out_h[i] = budget
            new_toks = cnt - int(self._slot_emitted[i])
            decoded += new_toks
            first_tokens += int(self._slot_emitted[i] == 0)
            spans.append((max(int(self._slot_emitted[i]), 1), cnt, ran))
            if self._tracer is not None and new_toks > 0:
                self._tracer.tokens(
                    self._slot_rec[i], new_toks,
                    replica=self._trace_replica,
                )
            self._slot_emitted[i] = cnt
            if self._journal is not None:
                rec = self._slot_rec[i]
                if done_h[i]:
                    self._journal.finish(rec, gen_h[i, :cnt])
                    journal_dirty = True
                elif (
                    cnt - int(self._slot_journaled[i])
                    >= self._journal.cadence
                ):
                    self._journal.progress(rec, gen_h[i, :cnt])
                    self._slot_journaled[i] = cnt
                    journal_dirty = True
        if decoded > 0:
            self.metrics.decoded_tokens.add(decoded)
        self.metrics.tokens_per_tick.set(float(decoded))
        self.metrics.slot_ticks_run.add(self._slots * self._ticks_per_sync)
        self.metrics.slot_ticks_served.add(decoded - first_tokens)
        if self._pool is not None:
            self._pool.count_reads(
                self.metrics, spans, self._prompt_len,
                self._slots * self._ticks_per_sync,
            )
        if journal_dirty:
            # Synchronous at the cadence point: the whole point is
            # that a SIGKILL one instruction later finds these tokens
            # on disk.
            self._journal.flush()
        if done_h.any():
            for i in np.nonzero(done_h)[0]:
                rec = self._slot_rec[i]
                assert rec is not None
                self._active[i] = False
                self._slot_rec[i] = None
                self._slot_emitted[i] = 0
                self._slot_journaled[i] = 0
                if self._kv_pages is not None:
                    # Unpin the slot's blocks: uncached ones return to
                    # the free list; cached prefix blocks stay alive on
                    # the radix tree's own reference. The row falls back
                    # to the sink so this slot's frozen-position tick
                    # writes can never touch a re-allocated block.
                    self._release_slot_blocks(i)
                out = gen_h[i, : n_out_h[i]].copy()
                self._retire_completion(rec, out, completions)
            if self._kv_pages is not None:
                self._caches = self._paged_set_table(
                    self._caches, self._device_table()
                )
                self.metrics.cache_pool_occupancy.set(
                    self._kv_alloc.occupancy()
                )
        if finishers:
            # AFTER the done bookkeeping above (which must see the
            # pre-activation active set and its fetched state):
            # completed prefills activate for the NEXT tick.
            self._activate_chunk_finishers(finishers)
            if self._prefill_role:
                # Disaggregated prefill: nothing decodes here — cut
                # the freshly activated slots into handoffs and free
                # them before any decode tick could run.
                self._harvest_prefilled(finishers)
        if run_chunk:
            self.metrics.admission_queue_tokens.set(float(sum(
                len(e.seq) - e.off for e in self._prefill_queue
            )))

    def flush_commits(self) -> bool:
        """Commit anything emitted since the last commit (cadence-pending
        completions). The external-admission caller's end-of-window flush;
        run() calls it on exit. A SURVIVABLE commit failure (rebalance,
        open circuit, broker fault or outage) leaves the cadence counter
        intact, so the completions stay commit-pending and the next
        cadence point or flush retries them — a transient failure at the
        final flush no longer silently strands the tail uncommitted until
        restart. In exactly_once mode a non-empty outbox also forces the
        flush: held out-of-order outputs (e.g. behind a record that
        resolved as DROPPED, which advances no completion counter) must
        still reach a committed transaction.

        Returns False exactly when a survivable failure left work
        pending — the caller's cue to RETRY at its next safe point even
        if no new completions arrive (a fleet replica that went idle
        with a failed flush would otherwise never commit its tail: the
        broker-outage wedge the durable-broker restart drill exposed).
        True means the flush succeeded or nothing needed flushing."""
        if self._uncommitted or (self._txn_mode and self._txn_outbox):
            if self._commit():
                self._uncommitted = 0
                return True
            return False
        return True

    @property
    def cache_tensors(self) -> tuple:
        """The slot pool's tensors as they stand (device arrays; the next
        admit or tick donates them, so read or copy before stepping): for
        tests and for a benchmark's comparison of what is cached with a
        reference. Rows a retired slot wrote stay until its next admission."""
        return self._caches

    @property
    def pending_commit(self) -> int:
        """Completions emitted since the last SUCCESSFUL commit — what a
        flush would cover. Nonzero after serving means a survivable
        commit failure is still unhealed (retry flush_commits once the
        broker recovers, or accept the re-delivery on restart)."""
        return self._uncommitted

    def committable_offsets(self) -> dict:
        """The ledger's committable next-read offsets right now — what the
        next commit would durably record. Fleet observability merges these
        per-replica views (commit.ledger.merged_watermarks)."""
        return self._ledger.snapshot()

    def run(
        self, max_records: int | None = None, idle_timeout_ms: int = 2000
    ) -> Iterator[tuple[Record, np.ndarray]]:
        B = self._slots
        pending: list[Record] = []
        served = 0
        exhausted_at: float | None = None
        self.metrics.reset()
        while True:
            free = self.free_slots()
            in_flight = B - free
            # Admission budget: never take more work than max_records allows
            # (completions already served + generations in flight).
            budget = (
                max(0, max_records - served - in_flight)
                if max_records is not None
                else B
            )
            # Paged-mode deferred admissions hold their future slots (and
            # re-offer first, FIFO); always 0 on the dense path.
            deferred = self.pending_admissions
            take_cap = max(0, min(free - deferred, budget))
            if take_cap and len(pending) < take_cap:
                # Never let an empty topic stall in-flight decode ticks:
                # poll without blocking while anything is generating.
                with xprof.span(xprof.SPAN_POLL):
                    records = self._consumer.poll(
                        max_records=self._max_poll,
                        timeout_ms=0 if in_flight else 50,
                    )
                    if records:
                        self.note_fetched(records)
                if records:
                    pending.extend(records)
                    exhausted_at = None
            if (take_cap and pending) or (free and deferred and budget):
                take = pending[:take_cap]
                del pending[: len(take)]
                self.admit_records(take)
            if not self.has_active() and not self._journal_ready:
                if max_records is not None and served >= max_records:
                    break
                if not pending:
                    if exhausted_at is None:
                        exhausted_at = time.monotonic()
                    elif (time.monotonic() - exhausted_at) * 1000 >= idle_timeout_ms:
                        break
                continue
            for rec, out in self.step():
                served += 1
                yield rec, out
            if max_records is not None and served >= max_records and not self.has_active():
                break
        self.flush_commits()

    def _commit(self) -> bool:
        """Commit the ledger watermark; returns True iff offsets were
        durably committed (callers reset the commit cadence only then, so
        failed cadence commits retry instead of silently skipping).
        Commit failure is survivable (the reference's contract,
        /root/reference/src/kafka_dataset.py:131-135): a rebalance raises
        CommitFailedError and the moved partitions' uncommitted prompts
        simply re-deliver to their new owner.

        With an output topic configured, output durability is settled
        FIRST: flush, then ``get()`` every send handle since the last
        commit (kafka-python's ``flush`` resolves futures but does NOT
        re-raise per-record failures — a terminally failed send would
        otherwise slip through a clean flush). A TRANSIENT flush failure
        skips the commit and keeps the handles (retried next commit); a
        TERMINAL per-record failure raises ``OutputDeliveryError`` —
        fail-stop equals crash-before-commit, so everything since the
        last commit re-delivers and regenerates rather than committing
        past lost output.

        ``commit_latency`` observes the WHOLE commit path — output flush +
        per-handle durability waits + the offset commit — so an
        output-broker stall shows up in the p99 an operator watches.

        With ``exactly_once`` the whole discipline above collapses into
        ONE transaction commit — see ``_commit_txn``."""
        t0 = time.perf_counter()
        if self._txn_mode:
            return self._commit_txn(t0)
        if self._output_producer is not None:
            with xprof.span(xprof.SPAN_OUTPUT_FLUSH):
                try:
                    self._output_producer.flush()
                except Exception:  # noqa: BLE001 - a flush failure fails closed
                    self.metrics.output_flush_failures.add(1)
                    _logger.exception(
                        "output flush failed; SKIPPING offset commit so the "
                        "affected prompts re-deliver and regenerate"
                    )
                    return False
                pending, self._pending_outputs = self._pending_outputs, []
                for handle in pending:
                    try:
                        handle.get(30.0)
                    except Exception as exc:
                        self.metrics.output_flush_failures.add(1)
                        raise OutputDeliveryError(
                            "an output record terminally failed delivery; "
                            "refusing to commit source offsets past lost "
                            "output (restart re-delivers and regenerates)"
                        ) from exc
        snapshot = self._ledger.snapshot()
        # Commit only partitions we still OWN: an eager rebalance (a
        # member joined/left — fleet.scale on the process fleet) can take
        # partitions away with completions still in this ledger. Kafka
        # clients drop those from the commit set — the broker would
        # reject the WHOLE commit as "partitions not owned" otherwise,
        # permanently stalling even the owned partitions' watermark. The
        # new owner re-serves the departed records (at-least-once;
        # duplicates bounded by this replica's uncommitted work), so
        # skipping them here loses nothing. assignment() also syncs the
        # group first, so the commit below carries the POST-rebalance
        # generation instead of burning one doomed attempt.
        try:
            assigned = set(self._consumer.assignment())
        except Exception:  # noqa: BLE001 - transport hiccup: commit as-is
            assigned = None
        if assigned is not None:
            stray = [tp for tp in snapshot if tp not in assigned]
            if stray:
                _logger.info(
                    "dropping %d departed partition(s) from commit after "
                    "rebalance: %s", len(stray), sorted(stray),
                )
                snapshot = {
                    tp: off for tp, off in snapshot.items()
                    if tp in assigned
                }
            if not snapshot:
                return True  # nothing we own has progress to commit
        # Outputs durable, offsets not yet committed: death here must
        # replay (duplicates on the output topic), never lose.
        crash_hook("pre_commit")
        try:
            with xprof.span(xprof.SPAN_COMMIT):
                self._consumer.commit(snapshot)
            self.metrics.commit_latency.observe(time.perf_counter() - t0)
        except CommitFailedError:
            self.metrics.commit_failures.add(1)
            _logger.exception("offset commit failed; prompts will re-deliver")
            return False
        except BrokerUnavailableError:
            # A broker outage that outlived the client's own retry budget
            # (e.g. a broker-process death mid-restart): survivable — the
            # ledger snapshot stays pending, the cadence counter stays
            # intact, and the next flush retries against the recovered
            # broker. Riding the outage here is what lets a WAL-restarted
            # broker pick the fleet back up with zero lost records.
            self.metrics.commit_failures.add(1)
            _logger.warning(
                "broker unavailable at commit; offsets stay pending and "
                "retry at the next flush"
            )
            return False
        if self._tracer is not None:
            # Durably committed: close every covered record's e2e span.
            self._tracer.note_commit(snapshot)
        if self._distill_topic is not None and self._distill_outbox:
            # Commit SUCCEEDED: the frames whose offsets it covers hold
            # committed tokens — publish them now (never before; a crash
            # pre-commit publishes nothing and the re-delivered records'
            # regenerated completions frame the only copy). A send fault
            # keeps the frame for the next commit's retry — losing it to
            # a crash costs one corpus sample, never correctness.
            prod = self._distill_producer or self._output_producer
            if assigned is not None:
                for ident in [
                    i for i in self._distill_outbox
                    if TopicPartition(i[0], i[1]) not in assigned
                ]:
                    del self._distill_outbox[ident]
            covered = [
                i for i in self._distill_outbox
                if i[2] < snapshot.get(TopicPartition(i[0], i[1]), 0)
            ]
            for ident in covered:
                try:
                    prod.send(
                        self._distill_topic, self._distill_outbox[ident]
                    )
                except Exception:  # noqa: BLE001 - retry next commit
                    _logger.warning(
                        "distill frame publish failed; retrying at the "
                        "next commit", exc_info=True,
                    )
                    break
                del self._distill_outbox[ident]
                self.metrics.distill_published.add(1)
        if self._journal is not None:
            # Journal GC at commit flush: entries below the committed
            # watermark are durable history — pruning here is what bounds
            # the journal file by in-flight work.
            self._journal.prune(snapshot)
            self._journal.flush()
        return True

    def _commit_txn(self, t0: float) -> bool:
        """The exactly-once commit: ONE short-lived transaction per
        window — begin, produce every outbox entry the in-order ledger
        snapshot covers (outputs and DLQ copies alike), stage the
        snapshot's offsets with the consumer's CURRENT group metadata,
        commit. Outputs whose offsets the watermark cannot yet cover
        (completions that finished out of order behind a still-pending
        record) are HELD for a later window — publishing them early is
        exactly the committed-output-with-redeliverable-offset hole that
        turns a rebalance into duplicates. Failure classes:

        - ``CommitFailedError`` (rebalance/fencing): SURVIVABLE — the
          broker aborted records + offsets atomically; the outbox is
          untouched, so the next window re-sends whatever this replica
          still owns (the snapshot filter drops departed partitions,
          whose records re-serve on their new owner).
        - ``ProducerFencedError``: TERMINAL — another incarnation owns
          this transactional id; raise (fail-stop, the process fleet
          exits EXIT_FENCED).
        - transport faults: abort defensively and return False — a
          commit whose ack was eaten is answered idempotently by the
          broker on retry.

        ``commit_latency`` observes the whole path — the transaction's
        produces + offset staging + atomic commit — so the measured
        "transaction tax" is honest against the legacy flush-then-commit
        p99."""
        p = self._output_producer
        snapshot = self._ledger.snapshot()
        try:
            assigned = set(self._consumer.assignment())
        except Exception:  # noqa: BLE001 - transport hiccup: commit as-is
            assigned = None
        if assigned is not None:
            stray = [tp for tp in snapshot if tp not in assigned]
            if stray:
                _logger.info(
                    "dropping %d departed partition(s) from txn commit "
                    "after rebalance: %s", len(stray), sorted(stray),
                )
                snapshot = {
                    tp: off for tp, off in snapshot.items()
                    if tp in assigned
                }
            # Outbox entries for departed partitions are STALE: their
            # records either committed under this replica already (never
            # redeliver) or re-serve on the new owner (the only copy).
            # If the partition ever comes back, redelivery re-stages
            # fresh entries; keeping these would double-publish.
            stale = [
                ident for ident in self._txn_outbox
                if TopicPartition(ident[0], ident[1]) not in assigned
            ]
            for ident in stale:
                del self._txn_outbox[ident]
                # The departed record's distill frame is stale with it:
                # its new owner frames the only committed copy.
                self._distill_outbox.pop(ident, None)
        dup_serves = [
            ident for ident in self._txn_outbox
            if ident[2] < self._txn_committed_wm.get(
                TopicPartition(ident[0], ident[1]), 0
            )
        ]
        for ident in dup_serves:
            # A re-serve of a record a previous window already committed
            # (both copies of an eager-rebalance double delivery ran to
            # completion): the committed view has its single copy.
            del self._txn_outbox[ident]
            self._distill_outbox.pop(ident, None)
        if dup_serves:
            _logger.info(
                "dropped %d duplicate re-serve(s) already covered by "
                "committed transactions", len(dup_serves),
            )
        sendable = [
            ident for ident in self._txn_outbox
            if ident[2] < snapshot.get(TopicPartition(ident[0], ident[1]), 0)
        ]
        # Distill frames covered by this window's snapshot ride the SAME
        # transaction as the outputs + offsets: an aborted window's
        # corpus records are invisible, a fenced zombie's are aborted
        # with its transaction — only committed tokens ever train.
        d_sendable = [
            ident for ident in self._distill_outbox
            if ident[2] < snapshot.get(TopicPartition(ident[0], ident[1]), 0)
        ] if self._distill_topic is not None else []
        if not snapshot and not p.in_transaction:
            return True  # nothing resolved, nothing dangling: no-op
        try:
            # begin() also aborts any transaction a lost commit ack left
            # dangling broker-side, so state drift self-heals here.
            p.begin()
            for ident in sendable:
                kw = self._txn_outbox[ident]
                p.send(
                    kw["topic"], kw["value"], key=kw["key"],
                    headers=kw.get("headers", ()),
                )
            for ident in d_sendable:
                # Tenant key rides inside the frame header; no record
                # key needed for the corpus topic.
                p.send(self._distill_topic, self._distill_outbox[ident])
            if snapshot:
                p.send_offsets(
                    getattr(self._consumer, "group_id"), snapshot,
                    member_id=getattr(self._consumer, "member_id", None),
                    generation=getattr(self._consumer, "generation", None),
                )
            p.commit()
        except ProducerFencedError:
            self.metrics.commit_failures.add(1)
            self.metrics.txn_aborts.add(1)
            _logger.exception(
                "transactional producer fenced; failing stop — the "
                "successor incarnation owns this replica's outputs now"
            )
            raise
        except CommitFailedError:
            self.metrics.commit_failures.add(1)
            self.metrics.txn_aborts.add(1)
            self._txn_abort()  # defensive: send_offsets may refuse pre-commit
            _logger.exception(
                "transaction aborted on commit failure; the outbox "
                "re-sends next window, departed records re-serve on "
                "their new owner"
            )
            return False
        except Exception:  # noqa: BLE001 - transport fault: retry later
            self.metrics.commit_failures.add(1)
            self._txn_abort()
            _logger.exception(
                "transaction commit failed in flight; aborted "
                "defensively — the outbox re-sends next flush"
            )
            return False
        for ident in sendable:
            del self._txn_outbox[ident]
        for ident in d_sendable:
            self._distill_outbox.pop(ident, None)
        if d_sendable:
            self.metrics.distill_published.add(len(d_sendable))
        for tp, off in snapshot.items():
            if off > self._txn_committed_wm.get(tp, 0):
                self._txn_committed_wm[tp] = off
        self.metrics.txn_commits.add(1)
        self.metrics.txn_held_outputs.set(float(len(self._txn_outbox)))
        self.metrics.commit_latency.observe(time.perf_counter() - t0)
        if self._tracer is not None:
            self._tracer.note_commit(snapshot)
        if self._journal is not None:
            self._journal.prune(snapshot)
            self._journal.flush()
        return True

    def sync_journal(self) -> None:
        """Flush + fsync the decode journal (no-op without one) — the
        SIGTERM drain path's durability point: whatever is in flight when
        the process exits must be warm-resumable by the next owner."""
        if self._journal is not None:
            self._journal.sync()

    def close(self) -> None:
        """Voluntary shutdown: commit the watermark for everything already
        COMPLETED (abandoning ``run()`` mid-iteration intentionally skips
        this — a crash must re-deliver). In-flight generations stay
        uncommitted and re-deliver on restart, like the stream's close
        contract (/root/reference/src/kafka_dataset.py:89 keeps unfinished
        work uncommitted; finished-and-yielded work is the user's).
        IDEMPOTENT: the drain path can hit this twice (a second SIGTERM
        lands mid-drain) — the second call must not re-commit through a
        consumer the first call's caller already closed."""
        if self._closed:
            return
        self._closed = True
        try:
            self._commit()
        except ConsumerClosedError:
            # A completed drain (Replica.finish_drain) already committed
            # the final watermark and closed the consumer; the close()
            # that a shutdown teardown (or second signal) lands here
            # afterwards must not die re-committing an unchanged
            # watermark through it.
            pass
        finally:
            self.sync_journal()

    def __enter__(self) -> "StreamingGenerator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
