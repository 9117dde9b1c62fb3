"""Record-lifecycle tracing, SLO histograms, and the unified exporter
(torchkafka_tpu/obs).

Pins the subsystem's four contracts:

1. DERIVATION EXACTNESS — under a ManualClock, TTFT / inter-token latency
   / queue wait / e2e are exact arithmetic over the injected timestamps,
   and the ring/JSONL sinks preserve the event stream.
2. TRACE DETERMINISM — the repo's differential style applied to
   observability itself: a same-seed replica-kill chaos replay through a
   2-replica paged fleet yields an IDENTICAL event sequence modulo
   timestamps (and byte-identical including timestamps under a manual
   clock); traced serving is token-exact and commit-ledger-identical vs
   untraced.
3. EXPOSITION CONFORMANCE — one parametrized grammar check across ALL
   render_prometheus implementations (Stream/Serve/Fleet/Resilience +
   the SLO tracer): HELP/TYPE lines for every metric, valid metric
   names, counter naming, label escaping that survives hostile tenant
   keys (tenants come straight from record keys).
4. ENDPOINT — the stdlib HTTP exporter serves every registered source
   from one scrape and survives a broken source.
"""

import re
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchkafka_tpu as tk
from torchkafka_tpu.fleet import ReplicaChaos, ServingFleet
from torchkafka_tpu.fleet.metrics import FleetMetrics
from torchkafka_tpu.models.transformer import TransformerConfig, init_params
from torchkafka_tpu.obs import (
    BurnRateMonitor,
    MetricsExporter,
    ObsConfig,
    RecordTracer,
    SLOHistograms,
    SLOTarget,
    pooled_slo_summary,
)
from torchkafka_tpu.obs.burn import BURNING, OK, SHEDDING, WARNING
from torchkafka_tpu.obs.trace import (
    BURN_STATE, CANARY_STARTED, COMMITTED, FINISHED, JOURNAL_HANDOFF,
    POLLED, QOS_ADMITTED, REPLICA_FENCED, REPLICA_JOINED, ROLLED_BACK,
    ROLLOUT_PHASE, SLOT_ACTIVE, SWAPPED,
)
from torchkafka_tpu.resilience import ManualClock
from torchkafka_tpu.serve import ServeMetrics, StreamingGenerator
from torchkafka_tpu.source.records import Record
from torchkafka_tpu.utils.metrics import (
    LatencyHistogram,
    ResilienceMetrics,
    StreamMetrics,
    escape_label_value,
    format_labels,
)
from torchkafka_tpu.utils.tracing import ingest_lag_ms

P, MAX_NEW, VOCAB = 8, 8, 64
PAGES = {"block_size": 4, "num_blocks": 40}


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=P + MAX_NEW, dtype=jnp.float32,
    )
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


def _rec(offset=0, key=b"tenantA", lane=b"interactive"):
    return Record("t", 0, offset, b"payload", key=key,
                  headers=(("lane", lane),))


# --------------------------------------------------------------------------
# 1. Derivation exactness under a manual clock
# --------------------------------------------------------------------------


class TestTracerDerivations:
    def test_lifecycle_latencies_exact(self):
        mc = ManualClock()
        tr = RecordTracer(ObsConfig(clock=mc.now))
        r = _rec()
        tr.polled(r, replica=3)
        mc.advance(0.010)
        tr.qos_admitted(r, "interactive", 0.010, replica=3)
        mc.advance(0.040)
        tr.slot_active(r, replica=3)
        mc.advance(0.006)
        tr.tokens(r, 3, replica=3)  # 2ms/token at host-sync granularity
        mc.advance(0.004)
        tr.tokens(r, 2, replica=3)
        tr.finished(r, 6, replica=3)
        mc.advance(0.001)
        tr.note_commit({("t", 0): 1})

        view = tr.record_trace("t", 0, 0)
        assert view.stages() == [
            POLLED, QOS_ADMITTED, SLOT_ACTIVE, "tokens", "tokens",
            FINISHED, COMMITTED,
        ]
        assert view.queue_wait_s == pytest.approx(0.010)
        assert view.ttft_s == pytest.approx(0.050)
        assert view.e2e_s == pytest.approx(0.061)
        assert view.itl_s == pytest.approx([0.002] * 3 + [0.002] * 2)

        slo = tr.slo
        assert slo.hist("ttft").count == 1
        assert slo.hist("ttft").percentile(50) == pytest.approx(0.050)
        assert slo.hist("ttft", "tenant", "tenantA").count == 1
        assert slo.hist("ttft", "lane", "interactive").count == 1
        assert slo.hist("ttft", "replica", "3").count == 1
        assert slo.hist("itl").count == 5
        assert slo.hist("itl").percentile(99) == pytest.approx(0.002)
        assert slo.hist("queue_wait").percentile(50) == pytest.approx(0.010)
        assert slo.hist("e2e").percentile(50) == pytest.approx(0.061)
        assert tr.summary()["open_records"] == 0

    def test_commit_covers_only_finished_below_watermark(self):
        mc = ManualClock()
        tr = RecordTracer(ObsConfig(clock=mc.now))
        done, in_flight, other_part = _rec(0), _rec(1), Record("t", 1, 0, b"x")
        for r in (done, in_flight, other_part):
            tr.polled(r)
        tr.slot_active(done)
        tr.finished(done, 4)
        tr.slot_active(in_flight)  # active but not finished
        tr.note_commit({("t", 0): 1})  # covers offset 0 only
        stages = [e.stage for e in tr.events]
        assert stages.count(COMMITTED) == 1
        assert tr.record_trace("t", 0, 0).e2e_s is not None
        assert tr.record_trace("t", 0, 1).e2e_s is None
        assert tr.summary()["open_records"] == 2

    def test_redelivery_restarts_lifecycle(self):
        """A re-polled record (replica death) must time its TTFT from the
        NEW poll, not the dead incarnation's."""
        mc = ManualClock()
        tr = RecordTracer(ObsConfig(clock=mc.now))
        r = _rec()
        tr.polled(r, replica=0)
        mc.advance(5.0)  # first incarnation dies; much later...
        tr.polled(r, replica=1)
        mc.advance(0.020)
        tr.slot_active(r, replica=1)
        assert tr.slo.hist("ttft").percentile(50) == pytest.approx(0.020)

    def test_warm_slot_active_skips_ttft(self):
        """A warm resume's first token was decoded pre-kill; it must not
        fabricate a TTFT sample."""
        tr = RecordTracer(ObsConfig(clock=ManualClock().now))
        r = _rec()
        tr.polled(r)
        tr.warm_resumed(r, 5)
        tr.slot_active(r, warm=True)
        assert tr.slo.hist("ttft").count == 0
        tr.tokens(r, 2)
        assert tr.slo.hist("itl").count == 2  # ITL still measured

    def test_dispatched_admission_closes_ttft_at_the_first_sync(self):
        """An admission that was only dispatched has no token the host
        can see: TTFT closes at the first ``tokens`` event, seconds later
        on a real chip, not at ``slot_active``."""
        mc = ManualClock()
        tr = RecordTracer(ObsConfig(clock=mc.now))
        r = _rec()
        tr.polled(r, replica=3)
        mc.advance(0.050)
        tr.slot_active(r, replica=3, dispatched=True)
        assert tr.slo.hist("ttft").count == 0  # nothing surfaced yet
        mc.advance(2.0)  # the admit program and the first tick block
        tr.tokens(r, 5, replica=3)
        assert tr.slo.hist("ttft").count == 1
        assert tr.slo.hist("ttft").percentile(50) == pytest.approx(2.050)
        assert tr.slo.hist("ttft", "replica", "3").count == 1
        mc.advance(1.0)
        tr.tokens(r, 4, replica=3)  # a later sync closes nothing more
        tr.finished(r, 9, replica=3)
        assert tr.slo.hist("ttft").count == 1
        # The per-record view reads events alone and stops at the stamp.
        assert tr.record_trace("t", 0, 0).ttft_s == pytest.approx(0.050)

    def test_dispatched_flag_leaves_the_event_stream_alone(self):
        def lifecycle(**kw):
            mc = ManualClock()
            tr = RecordTracer(ObsConfig(clock=mc.now))
            r = _rec()
            tr.polled(r)
            mc.advance(0.050)
            tr.slot_active(r, **kw)
            mc.advance(2.0)
            tr.tokens(r, 5)
            tr.finished(r, 5)
            tr.note_commit({("t", 0): 1})
            return tr

        plain, flagged = lifecycle(), lifecycle(dispatched=True)
        assert list(plain.events) == list(flagged.events)  # times too
        assert plain.slo.hist("ttft").percentile(50) == pytest.approx(0.050)
        assert flagged.slo.hist("ttft").percentile(50) == pytest.approx(2.050)

    def test_finished_closes_a_dispatched_ttft_without_token_events(self):
        """A journal-served or tokenless finish still closes the
        interval, and the burn monitor gets the same TTFT the histogram
        did."""
        seen = []

        class Monitor:
            def note_completed(self, lane, tenant, *, ttft_s, **_kw):
                seen.append(ttft_s)

        mc = ManualClock()
        tr = RecordTracer(ObsConfig(clock=mc.now))
        tr.attach_monitor(Monitor())
        r = _rec()
        tr.polled(r)
        tr.slot_active(r, dispatched=True)
        mc.advance(0.700)
        tr.finished(r, 1)
        tr.note_commit({("t", 0): 1})
        assert tr.slo.hist("ttft").percentile(50) == pytest.approx(0.700)
        assert seen == [pytest.approx(0.700)]

    def test_warm_dispatched_admission_still_skips_ttft(self):
        tr = RecordTracer(ObsConfig(clock=ManualClock().now))
        r = _rec()
        tr.polled(r)
        tr.slot_active(r, warm=True, dispatched=True)
        tr.tokens(r, 2)
        assert tr.slo.hist("ttft").count == 0

    def test_ring_bound_and_drop_counter(self):
        tr = RecordTracer(ObsConfig(capacity=8, clock=ManualClock().now))
        for i in range(20):
            tr.polled(_rec(i))
        assert len(tr.events) == 8
        assert tr.dropped_events == 12
        assert tr.emitted == 20
        assert [e.offset for e in tr.events] == list(range(12, 20))

    def test_jsonl_roundtrip_and_streaming_sink(self, tmp_path):
        stream_path = tmp_path / "live.jsonl"
        mc = ManualClock()
        tr = RecordTracer(ObsConfig(clock=mc.now,
                                    jsonl_path=str(stream_path)))
        r = _rec()
        tr.polled(r)
        mc.advance(0.5)
        tr.slot_active(r)
        tr.finished(r, 2)
        tr.close()
        export_path = tmp_path / "ring.jsonl"
        assert tr.export_jsonl(str(export_path)) == 3
        for path in (stream_path, export_path):
            loaded = RecordTracer.load_jsonl(str(path))
            assert [e.signature for e in loaded] == tr.signature()
            assert [e.t for e in loaded] == [e.t for e in tr.events]

    def test_token_events_off_keeps_slo(self):
        mc = ManualClock()
        tr = RecordTracer(ObsConfig(clock=mc.now, token_events=False))
        r = _rec()
        tr.polled(r)
        tr.slot_active(r)
        mc.advance(0.004)
        tr.tokens(r, 2)
        assert all(e.stage != "tokens" for e in tr.events)
        assert tr.slo.hist("itl").count == 2  # derived metric survives

    def test_pooled_slo_summary(self):
        mc = ManualClock()
        a, b = (RecordTracer(ObsConfig(clock=mc.now)) for _ in range(2))
        for tr, t in ((a, 0.010), (b, 0.030)):
            r = _rec()
            tr.polled(r)
            mc.advance(t)
            tr.slot_active(r)
        pooled = pooled_slo_summary([a.slo, b.slo])
        assert pooled["ttft"]["all"]["count"] == 2
        assert pooled["ttft"]["by_tenant"]["tenantA"]["count"] == 2
        assert pooled["ttft"]["all"]["p99_ms"] == pytest.approx(30.0)

    def test_config_validation(self):
        with pytest.raises(ValueError, match="capacity"):
            ObsConfig(capacity=0)
        with pytest.raises(ValueError, match="window_s"):
            ObsConfig(window_s=0)
        with pytest.raises(TypeError):
            MetricsExporter([object()])


# --------------------------------------------------------------------------
# 1b. Sliding-window SLO views — exact under a manual clock
# --------------------------------------------------------------------------


class TestWindowedHistograms:
    def test_windowed_percentiles_exact(self):
        """Samples land in clock-indexed buckets; a horizon covers the
        current partial bucket plus the completed ones intersecting it —
        exact arithmetic under a ManualClock."""
        mc = ManualClock()
        h = LatencyHistogram(window_s=1.0, n_windows=4, clock=mc.now)
        h.observe(0.010)           # bucket 0
        mc.advance(1.0)
        h.observe(0.020)           # bucket 1
        h.observe_many(0.030, 2)   # bucket 1
        mc.advance(1.0)            # now t=2.0, bucket 2 current (empty)
        # Horizon 1s: bucket 2 (empty) + bucket 1.
        w = h.windowed_summary(1.0)
        assert w["count"] == 3
        assert w["p50_ms"] == pytest.approx(30.0)
        # Horizon 2s reaches bucket 0 as well.
        assert h.windowed_summary(2.0)["count"] == 4
        # Cumulative view is untouched.
        assert h.count == 4

    def test_window_roll_evicts_old_buckets(self):
        mc = ManualClock()
        h = LatencyHistogram(window_s=1.0, n_windows=2, clock=mc.now)
        for i in range(5):
            h.observe(0.001 * (i + 1))
            mc.advance(1.0)
        # Ring bound 2: only the last two buckets survive, regardless of
        # the horizon asked for.
        assert len(h.windowed_snapshot(100.0)) == 2
        assert h.count == 5  # cumulative never forgets

    def test_requires_windowing(self):
        h = LatencyHistogram()
        with pytest.raises(ValueError, match="window_s"):
            h.windowed_snapshot()
        with pytest.raises(ValueError, match="window_s"):
            LatencyHistogram(window_s=0.0)
        with pytest.raises(ValueError, match="expose_windows"):
            SLOHistograms(expose_windows=(1.0,))

    def test_slo_windowed_summary_per_label(self):
        mc = ManualClock()
        slo = SLOHistograms(window_s=1.0, clock=mc.now)
        slo.observe("ttft", 0.010, tenant="a", lane="interactive")
        mc.advance(3.0)
        slo.observe("ttft", 0.050, tenant="a", lane="interactive")
        w = slo.windowed_summary(1.0)
        assert w["ttft"]["all"]["count"] == 1
        assert w["ttft"]["by_tenant"]["a"]["p50_ms"] == pytest.approx(50.0)
        cum = slo.summary()
        assert cum["ttft"]["all"]["count"] == 2

    def test_tracer_windowed_view_from_config(self):
        mc = ManualClock()
        tr = RecordTracer(ObsConfig(clock=mc.now, window_s=2.0))
        r = _rec()
        tr.polled(r)
        mc.advance(0.040)
        tr.slot_active(r)
        assert tr.slo.windowed
        assert tr.slo.hist("ttft").windowed_summary(2.0)["count"] == 1
        mc.advance(50.0)
        assert tr.slo.hist("ttft").windowed_summary(2.0)["count"] == 0
        # The exposition grew the *_window_ms families.
        text = tr.render_prometheus()
        assert "torchkafka_slo_ttft_window_ms{" in text


# --------------------------------------------------------------------------
# 1c. Burn-rate monitor: ladder, transitions, goodput
# --------------------------------------------------------------------------


def _burn_fixture(objective=0.9, **kw):
    mc = ManualClock()
    tr = RecordTracer(ObsConfig(clock=mc.now, window_s=0.5))
    target = SLOTarget(
        metric="ttft", threshold_s=0.010, objective=objective,
        fast_window_s=1.0, slow_window_s=4.0, min_samples=2, **kw,
    )
    mon = BurnRateMonitor(tr.slo, [target], tracer=tr)
    tr.attach_monitor(mon)
    return mc, tr, mon


def _observe_ttft(tr, mc, n, seconds, lane="batch", tenant="t"):
    for _ in range(n):
        r = Record("t", 0, _observe_ttft.seq, b"x", key=tenant.encode(),
                   headers=(("lane", lane.encode()),))
        _observe_ttft.seq += 1
        tr.polled(r)
        mc.advance(seconds)
        tr.slot_active(r)


_observe_ttft.seq = 0


class TestBurnRateMonitor:
    def test_state_ladder_and_typed_transitions(self):
        mc, tr, mon = _burn_fixture(objective=0.75)  # budget 0.25
        # All samples violating → fast burn 4.0, slow burn 4.0 → shedding.
        _observe_ttft(tr, mc, 6, 0.050)
        states = mon.evaluate()
        assert states[("ttft", "", "")] == SHEDDING
        assert mon.transitions >= 1
        burn_events = [e for e in tr.events if e.stage == BURN_STATE]
        assert burn_events
        attrs = dict(burn_events[0].attrs)
        assert attrs["from"] == OK and attrs["to"] == SHEDDING
        assert burn_events[0].topic == "slo"
        # Re-evaluating without new samples adds no transitions.
        before = mon.transitions
        mon.evaluate()
        assert mon.transitions == before
        # Fast window drains first: advance past fast, not slow.
        mc.advance(2.0)
        assert mon.evaluate()[("ttft", "", "")] == OK

    def test_warning_needs_only_fast_burn(self):
        mc, tr, mon = _burn_fixture(objective=0.5)  # budget 0.5
        # Half the samples violate → burn 1.0 → warning, not burning.
        _observe_ttft(tr, mc, 3, 0.002)
        _observe_ttft(tr, mc, 3, 0.050)
        assert mon.evaluate()[("ttft", "", "")] == WARNING

    def test_min_samples_guard(self):
        mc, tr, mon = _burn_fixture()
        _observe_ttft(tr, mc, 1, 0.050)  # below min_samples=2
        assert mon.evaluate()[("ttft", "", "")] == OK

    def test_lane_scoped_target(self):
        mc, tr, mon = _burn_fixture(objective=0.75, lane="batch")
        _observe_ttft(tr, mc, 6, 0.050, lane="interactive")
        # The violating lane is interactive; a batch-scoped target must
        # not fire (and only monitors its own scope).
        states = mon.evaluate()
        assert list(states) == [("ttft", "lane", "batch")]
        assert states[("ttft", "lane", "batch")] == OK

    def test_goodput_classification(self):
        mc, tr, mon = _burn_fixture()
        # One within (2ms <= 10ms), one violating (50ms), one warm
        # resume (no TTFT → vacuously within).
        start = _observe_ttft.seq
        _observe_ttft(tr, mc, 1, 0.002, tenant="a")
        _observe_ttft(tr, mc, 1, 0.050, tenant="a")
        warm = Record("t", 0, 10**6, b"x", key=b"a")
        tr.polled(warm)
        tr.slot_active(warm, warm=True)
        for off in range(start, _observe_ttft.seq):
            r = Record("t", 0, off, b"x", key=b"a")
            tr.finished(r, 2)
        tr.finished(warm, 2)
        tr.note_commit({("t", 0): 10**6 + 1})
        g = mon.goodput_summary()
        assert g["tenants"]["a"]["completed"] == 3
        assert g["tenants"]["a"]["within_slo"] == 2
        mon.note_deferred("a", 5)
        mon.note_quarantined("a")
        g = mon.goodput_summary()
        assert g["tenants"]["a"]["deferred"] == 5
        assert g["tenants"]["a"]["quarantined"] == 1

    def test_validation(self):
        with pytest.raises(ValueError, match="objective"):
            SLOTarget(objective=1.0)
        with pytest.raises(ValueError, match="metric"):
            SLOTarget(metric="nope")
        with pytest.raises(ValueError, match="fast_window_s"):
            SLOTarget(fast_window_s=10.0, slow_window_s=5.0)
        with pytest.raises(ValueError, match="warn_burn"):
            SLOTarget(warn_burn=3.0, burning_burn=2.0)
        slo = SLOHistograms()  # not windowed
        with pytest.raises(ValueError, match="window_s"):
            BurnRateMonitor(slo, [SLOTarget()])
        with pytest.raises(ValueError, match="SLOTarget"):
            BurnRateMonitor(SLOHistograms(window_s=1.0), [])
        assert BURNING in ("burning",)  # ladder constant exported


# --------------------------------------------------------------------------
# 2. Trace determinism + traced-vs-untraced exactness
# --------------------------------------------------------------------------


def _topic(broker, prompts, key_fn=None):
    broker.create_topic("p", partitions=2)
    for i in range(prompts.shape[0]):
        broker.produce(
            "p", prompts[i].tobytes(), partition=i % 2,
            key=key_fn(i) if key_fn else None,
        )


def _serve(cfg, params, prompts, tracer=None, **kw):
    broker = tk.InMemoryBroker()
    _topic(broker, prompts, key_fn=lambda i: b"ten%d" % (i % 2))
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    server = StreamingGenerator(
        consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
        commit_every=4, tracer=tracer, **kw,
    )
    out = {}
    for rec, toks in server.run(max_records=prompts.shape[0]):
        out[(rec.partition, rec.offset)] = np.asarray(toks)
    committed = {
        pt: broker.committed("g", tk.TopicPartition("p", pt)) for pt in (0, 1)
    }
    consumer.close()
    return out, committed


def _prompts(n, seed=7):
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, VOCAB, (n, P), dtype=np.int32)
    prompts[:, :5] = np.arange(5, dtype=np.int32)  # shared radix prefix
    return prompts


class TestServedTTFT:
    """The SLO's TTFT on a real server whose clock moves between the
    admission's dispatch and the sync that surfaces its token."""

    ADMIT_S, BLOCK_S = 0.25, 1.0

    def _serve(self, model, **kw):
        cfg, params = model
        mc = ManualClock()
        tr = RecordTracer(ObsConfig(clock=mc.now))
        broker = tk.InMemoryBroker()
        _topic(broker, _prompts(4))
        consumer = tk.MemoryConsumer(broker, "p", group_id="g")
        server = StreamingGenerator(
            consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
            ticks_per_sync=4, tracer=tr, **kw,
        )

        def slow(fn, seconds):
            def call(*a):
                mc.advance(seconds)  # the program's device time
                return fn(*a)
            return call

        if server._admit_fn is not None:
            server._admit_fn = slow(server._admit_fn, self.ADMIT_S)
        server._tick_fn = slow(server._tick_fn, self.BLOCK_S)
        if getattr(server, "_tick_chunk_fn", None) is not None:
            server._tick_chunk_fn = slow(server._tick_chunk_fn, self.BLOCK_S)
        assert sum(1 for _ in server.run(max_records=4)) == 4
        consumer.close()
        return tr

    def test_dense_ttft_spans_the_admission_and_the_first_block(self, model):
        tr = self._serve(model)
        ttft = tr.slo.hist("ttft")
        assert ttft.count == 4
        # Polled at 0; the admit program takes 0.25 s and the first block
        # of ticks 1 s before the host sees a token. The stamp of
        # ``slot_active`` is the dispatch's, 0.25 s in.
        assert ttft.percentile(50) == pytest.approx(1.25)
        assert ttft.percentile(99) == pytest.approx(1.25)
        assert tr.record_trace("p", 0, 0).ttft_s == pytest.approx(0.25)

    def test_chunked_ttft_still_closes_at_slot_active(self, model):
        """The chunked path stamps ``slot_active`` after the sync of the
        tick that sampled token 0: the histogram and the stamp agree."""
        tr = self._serve(model, kv_pages=PAGES)
        ttft = tr.slo.hist("ttft")
        assert ttft.count == 4
        for off in (0, 1):
            view = tr.record_trace("p", 0, off)
            assert view.ttft_s >= self.BLOCK_S
            assert ttft.percentile(99) >= view.ttft_s
        stamps = sorted(
            tr.record_trace("p", part, off).ttft_s
            for part in (0, 1) for off in (0, 1)
        )
        assert ttft.percentile(99) == pytest.approx(stamps[-1])
        assert ttft.percentile(1) == pytest.approx(stamps[0])


class TestTracedServingExactness:
    @pytest.mark.parametrize("kw", [
        {}, {"kv_pages": PAGES},
        {"temperature": 0.8, "top_k": 8, "rng": jax.random.key(3)},
    ], ids=["dense-greedy", "paged-chunked", "dense-sampled"])
    def test_traced_vs_untraced_token_and_ledger_identical(self, model, kw):
        cfg, params = model
        prompts = _prompts(8)
        base, base_committed = _serve(cfg, params, prompts, **kw)
        tr = RecordTracer(ObsConfig(clock=ManualClock().now))
        traced, traced_committed = _serve(
            cfg, params, prompts, tracer=tr, **kw
        )
        assert set(base) == set(traced)
        for k in base:
            np.testing.assert_array_equal(base[k], traced[k], err_msg=str(k))
        assert base_committed == traced_committed
        # The trace is balanced: every record polled, activated,
        # finished, and committed exactly once.
        sig = tr.signature()
        for stage in (POLLED, SLOT_ACTIVE, FINISHED, COMMITTED):
            assert sum(s[0] == stage for s in sig) == 8, stage
        assert tr.summary()["open_records"] == 0


class TestTraceDeterminism:
    """Same-seed chaos replay → identical trace, the kvcache fleet
    differential's fixture shape with the tracer riding along."""

    def _chaos_run(self, cfg, params, obs):
        broker = tk.InMemoryBroker()
        broker.create_topic("t", partitions=4)
        prompts = _prompts(16, seed=21)
        for i in range(16):
            broker.produce(
                "t", prompts[i].tobytes(),
                key=b"tenant-%d" % (i % 2), partition=i % 4,
            )
        fleet = ServingFleet(
            lambda rid: tk.MemoryConsumer(broker, "t", group_id="gc"),
            params, cfg, replicas=2, prompt_len=P, max_new=MAX_NEW,
            slots=2, commit_every=2, gen_kwargs={"kv_pages": dict(PAGES)},
            obs=obs,
        )
        chaos = ReplicaChaos(seed=5, min_completions=2, max_completions=6)
        outputs: dict = {}
        order = []
        for _rid, rec, toks in fleet.serve(idle_timeout_ms=2000, chaos=chaos):
            key = (rec.partition, rec.offset)
            order.append(key)
            outputs.setdefault(key, []).append(np.asarray(toks))
        committed = {
            pt: broker.committed("gc", tk.TopicPartition("t", pt))
            for pt in range(4)
        }
        tracer = fleet.tracer
        fleet.close()
        return outputs, order, committed, chaos.killed, tracer

    def test_same_seed_chaos_trace_identical(self, model):
        cfg, params = model
        # Manual clocks: byte-identical traces INCLUDING timestamps.
        a = self._chaos_run(
            cfg, params, RecordTracer(ObsConfig(clock=ManualClock().now))
        )
        b = self._chaos_run(
            cfg, params, RecordTracer(ObsConfig(clock=ManualClock().now))
        )
        assert a[3] == b[3] and len(a[3]) == 1  # same seeded kill fired
        assert a[1] == b[1]  # same completion order (duplicates included)
        assert a[4].signature() == b[4].signature()  # modulo timestamps
        assert list(a[4].events) == list(b[4].events)  # byte-identical
        # The chaos branches really traced: a redelivered prompt was
        # re-polled, so polled > unique records.
        sig = a[4].signature()
        polled = sum(s[0] == POLLED for s in sig)
        assert polled > 16 or any(len(v) > 1 for v in a[0].values())

    def test_traced_chaos_fleet_matches_untraced(self, model):
        cfg, params = model
        off = self._chaos_run(cfg, params, None)
        on = self._chaos_run(
            cfg, params, RecordTracer(ObsConfig(clock=ManualClock().now))
        )
        assert on[3] == off[3]
        assert on[1] == off[1]
        assert set(on[0]) == set(off[0]) and len(on[0]) == 16
        for key in off[0]:
            for x, y in zip(on[0][key], off[0][key]):
                np.testing.assert_array_equal(x, y, err_msg=str(key))
        assert on[2] == off[2]  # committed watermarks byte-identical


# --------------------------------------------------------------------------
# 3. Exposition conformance across ALL render_prometheus implementations
# --------------------------------------------------------------------------

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\["\\n])*"'
_SAMPLE_RE = re.compile(
    rf"^({_NAME})(\{{{_LABEL}(?:,{_LABEL})*\}})? (\S+)$"
)
EVIL_TENANT = 'ev"il\\ten\nant'  # quote, backslash, newline — all from a key


def _assert_conformant(text: str) -> int:
    """Validate one exposition: every sample parses, carries HELP + TYPE,
    counters end _total, values are floats. Returns the sample count."""
    helped, typed = set(), {}
    samples = 0
    for line in text.strip().split("\n"):
        if line.startswith("# HELP "):
            name, sep, help_text = line[len("# HELP "):].partition(" ")
            assert re.fullmatch(_NAME, name), line
            assert sep and help_text.strip(), f"empty HELP: {line!r}"
            helped.add(name)
            continue
        if line.startswith("# TYPE "):
            name, _, mtype = line[len("# TYPE "):].partition(" ")
            assert mtype in ("counter", "gauge"), line
            typed[name] = mtype
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparsable sample line: {line!r}"
        name, _labels, value = m.group(1), m.group(2), m.group(3)
        float(value)  # must be a number
        assert name in helped, f"sample without HELP: {name}"
        assert name in typed, f"sample without TYPE: {name}"
        if typed[name] == "counter":
            assert name.endswith("_total"), f"counter not _total: {name}"
        samples += 1
    assert samples > 0
    return samples


def _stream_metrics():
    m = StreamMetrics()
    m.records.add(100)
    m.commit_latency.observe(0.01)
    m.ingest_lag_ms.set(12.5)
    return m.render_prometheus()


def _serve_metrics():
    m = ServeMetrics()
    m.completions.add(3)
    m.tokens.add(24)
    m.commit_latency.observe(0.002)
    m.slot_occupancy.set(0.5)
    m.prefix_hits.add(2)
    # PR-8 families: per-tick step time / tokens-per-tick, output caps,
    # per-tenant cache locality (hostile tenant key included).
    m.tick_time.observe(0.004)
    m.tokens_per_tick.set(3.0)
    m.output_capped.add(1)
    m.tenant_prefix_hits(EVIL_TENANT).add(2)
    m.tenant_prefix_misses(EVIL_TENANT).add(1)
    # PR-13 family: the resolved KV backend + kernel engagement pair
    # (reason strings become label values — the escape path matters).
    from torchkafka_tpu.kvcache import KVBackend

    m.note_backend(KVBackend(
        layout="paged", int8=True, kernel=False,
        kernel_disabled_reason='auto: backend="cpu" is not tpu',
        chunked=True, data=2, tp=2,
    ))
    # ISSUE-14 families: tiered radix cache traffic + disaggregated
    # prefill routing/adoption counters.
    m.radix_demotions.add(4)
    m.radix_promotions.add(3)
    m.tier_hits.add(3)
    m.tier_occupancy_bytes.set(8192)
    m.prefill_routed.add(2)
    m.adopted_slots.add(2)
    m.handoffs_published.add(1)
    # ISSUE-19 distill families: corpus/trainer counters, the windowed
    # live-α gauge the controller gates on, the applied draft version,
    # and refresh counters labeled by reason.
    m.distill_published.add(4)
    m.distill_steps.add(2)
    m.distill_records.add(8)
    m.spec_alpha_window.set(0.625)
    m.draft_version.set(3)
    m.draft_refreshes("published").add(1)
    m.draft_refreshes("alpha_drop").add(2)
    text = m.render_prometheus()
    for family in (
        "radix_demotions_total", "radix_promotions_total",
        "tier_hits_total", "tier_occupancy_bytes", "prefill_routed_total",
        "adopted_slots_total", "prefill_handoffs_published_total",
        "distill_published_total", "distill_steps_total",
        "distill_records_total", "spec_alpha_window", "draft_version",
        "draft_refreshes_total",
    ):
        assert f"torchkafka_serve_{family}" in text, family
    assert 'reason="alpha_drop"' in text
    return text


def _fleet_metrics():
    m = FleetMetrics()
    m.completions.add(5)
    m.tenant_admitted(EVIL_TENANT).add(2)
    m.tenant_throttled(EVIL_TENANT).add(1)
    m.tenant_deferred(EVIL_TENANT).add(1)
    m.tenant_queue_depth(EVIL_TENANT).set(3)
    m.lane_wait("interactive").observe(0.004)
    m.replica_occupancy(0).set(0.75)
    m.replica_completions(0).add(5)
    # ISSUE-10 liveness families: joins / fences counters and the
    # per-member lease-age gauge (member ids are operator-chosen strings
    # — hostile ones must escape like tenant keys do).
    m.replica_joins.add(3)
    m.replica_fences.add(1)
    m.member_lease_age("r0i0").set(0.4)
    m.member_lease_age(EVIL_TENANT).set(1.25)
    # ISSUE-15 autoscale families: decision counters labeled
    # {role, direction, reason}, per-role target + phase gauges and the
    # time-in-phase clock (fleet/autoscale.py's controller narration).
    m.autoscale_decision("decode", "up", "burn").add(2)
    m.autoscale_decision("decode", "down", "idle").add(1)
    m.autoscale_decision("prefill", "up", "queue").add(1)
    m.autoscale_target("decode").set(3)
    m.autoscale_target("prefill").set(1)
    m.autoscale_phase("decode").set(1)
    m.autoscale_time_in_phase("decode").set(4.5)
    # ISSUE-18 rollout families: controller phase + target gauges,
    # per-member served-version gauges (member ids escape like tenant
    # keys), canary diff / rollback / checkpoint-reject counters with
    # reason labels.
    m.rollout_phase.set(1)
    m.rollout_target_version.set(3)
    m.canary_token_diffs.add(2)
    m.replica_model_version("r0i0").set(3)
    m.replica_model_version(EVIL_TENANT).set(2)
    m.rollback("canary_divergence").add(1)
    m.checkpoint_reject("wire").add(2)
    # ISSUE-19 distill families: the fleet-applied draft version, the
    # per-replica draft versions (member ids escape like tenant keys),
    # and refresh counters labeled by reason.
    m.draft_version.set(2)
    m.replica_draft_version("r0i0").set(2)
    m.replica_draft_version(EVIL_TENANT).set(1)
    m.draft_refreshes("alpha_drop").add(1)
    m.draft_refreshes("checkpoint_rejected").add(1)
    text = m.render_prometheus(replicas=None)
    for family in (
        "autoscale_decisions_total", "autoscale_target_replicas",
        "autoscale_phase", "autoscale_time_in_phase_seconds",
        "rollout_phase", "rollout_target_version",
        "canary_token_diffs_total", "replica_model_version",
        "rollbacks_total", "checkpoint_rejects_total",
        "draft_applied_version", "draft_version",
        "draft_refreshes_total",
    ):
        assert f"torchkafka_fleet_{family}" in text, family
    assert 'role="decode",direction="up",reason="burn"' in text
    assert 'reason="canary_divergence"' in text
    assert 'reason="checkpoint_rejected"' in text
    assert 'member="r0i0"' in text
    return text


def _burn_monitor():
    mc, tr, mon = _burn_fixture(objective=0.75)
    start = _observe_ttft.seq
    _observe_ttft(tr, mc, 6, 0.050, tenant=EVIL_TENANT)
    mon.evaluate()
    for off in range(start, _observe_ttft.seq):
        r = Record("t", 0, off, b"x", key=EVIL_TENANT.encode())
        tr.finished(r, 2)
    tr.note_commit({("t", 0): 10**6})
    mon.note_deferred(EVIL_TENANT, 2)
    mon.note_quarantined(EVIL_TENANT)
    return mon.render_prometheus()


def _windowed_slo_tracer():
    """A windowed tracer: the *_window_ms families must render on the
    same grammar as everything else."""
    mc = ManualClock()
    tr = RecordTracer(ObsConfig(clock=mc.now, window_s=1.0,
                                expose_windows=(1.0, 4.0)))
    r = Record("t", 0, 0, b"x", key=EVIL_TENANT.encode(),
               headers=(("lane", b"interactive"),))
    tr.polled(r, replica=0)
    mc.advance(0.02)
    tr.qos_admitted(r, "interactive", 0.02, replica=0)
    tr.slot_active(r, replica=0)
    mc.advance(0.001)
    tr.tokens(r, 2, replica=0)
    tr.finished(r, 3, replica=0)
    tr.note_commit({("t", 0): 1})
    return tr.render_prometheus(prefix="torchkafka_wslo")


def _traced_fleet_metrics():
    """FleetMetrics with the full PR-8 attachment set — windowed SLO +
    burn monitor + goodput + step-time aggregation — on ONE exposition,
    rendered under a distinct prefix so the combined scrape stays
    duplicate-free."""
    mc, tr, mon = _burn_fixture(objective=0.75)
    start = _observe_ttft.seq
    _observe_ttft(tr, mc, 4, 0.050, tenant=EVIL_TENANT)
    mon.evaluate()
    for off in range(start, _observe_ttft.seq):
        tr.finished(Record("t", 0, off, b"x",
                           key=EVIL_TENANT.encode()), 2)
    tr.note_commit({("t", 0): 10**6})
    m = FleetMetrics()
    m.attach_slo(tr.slo)
    m.attach_burn(mon)
    m.completions.add(4)
    m.tenant_admitted(EVIL_TENANT).add(4)
    m.tenant_deferred(EVIL_TENANT).add(2)
    return m.render_prometheus(prefix="torchkafka_tfleet", replicas=None)


def _resilience_metrics():
    m = ResilienceMetrics()
    m.retries.add(2)
    m.circuit_opens.add(1)
    m.circuit_state.set(0.5)
    return m.render_prometheus()


def _broker_metrics(tmp_path_factory=None):
    """A durable broker's WAL/recovery exposition, populated by a REAL
    write-and-recover cycle (not hand-set counters): appends + fsyncs
    from traffic, then a second construction replays the log and fills
    the recovery_* families."""
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        b = tk.InMemoryBroker(wal_dir=td, wal_durability="commit")
        b.create_topic("t")
        b.produce("t", b"v1")
        pid, epoch = b.init_producer_id("x")
        b.begin_txn(pid, epoch)
        b.txn_produce(pid, epoch, "t", b"open")
        b.wal.close()  # release the fd; the un-flushed state IS the crash
        r = tk.InMemoryBroker(wal_dir=td, wal_durability="commit")
        r.produce("t", b"post")  # appends on the recovered broker too
        text = r.metrics.render_prometheus()
        r.close()
    return text


def _slo_tracer():
    mc = ManualClock()
    tr = RecordTracer(ObsConfig(clock=mc.now))
    r = Record("t", 0, 0, b"x", key=EVIL_TENANT.encode(),
               headers=(("lane", b"interactive"),))
    tr.polled(r, replica=0)
    mc.advance(0.02)
    tr.qos_admitted(r, "interactive", 0.02, replica=0)
    tr.slot_active(r, replica=0)
    mc.advance(0.001)
    tr.tokens(r, 2, replica=0)
    tr.finished(r, 3, replica=0)
    tr.note_commit({("t", 0): 1})
    return tr.render_prometheus()


@pytest.mark.parametrize("render", [
    _stream_metrics, _serve_metrics, _fleet_metrics, _resilience_metrics,
    _slo_tracer, _burn_monitor, _windowed_slo_tracer, _traced_fleet_metrics,
    _broker_metrics,
], ids=["stream", "serve", "fleet", "resilience", "slo", "burn",
        "windowed-slo", "traced-fleet", "broker"])
def test_exposition_conformance(render):
    """The one grammar every exposition must satisfy — so the shared
    endpoint can't drift per class, and hostile tenant keys (quotes,
    backslashes, newlines) can't break a scrape."""
    text = render()
    _assert_conformant(text)


def test_membership_events_ride_the_trace_stream():
    """ISSUE-10 membership observability: replica_joined /
    replica_fenced / journal_handoff are typed events on the SAME
    stream as record lifecycles (topic "fleet", sequential offsets),
    deterministic under a manual clock, with the fencing reason and
    lease age in the attrs — and they open no record lifecycle."""
    mc = ManualClock()
    tr = RecordTracer(ObsConfig(clock=mc.now))
    tr.replica_joined("r0i0", replica=0)
    mc.advance(1.0)
    tr.replica_fenced("r0i0", reason="lease_expired", lease_age_s=2.5,
                      replica=0)
    tr.journal_handoff("r0i0", entries=3, replica=0)
    evs = list(tr.events)
    assert [e.stage for e in evs] == [
        REPLICA_JOINED, REPLICA_FENCED, JOURNAL_HANDOFF,
    ]
    assert [e.key for e in evs] == [("fleet", 0, 0), ("fleet", 0, 1),
                                    ("fleet", 0, 2)]
    fenced = dict(evs[1].attrs)
    assert fenced["reason"] == "lease_expired"
    assert fenced["lease_age_s"] == 2.5
    assert dict(evs[2].attrs)["entries"] == 3
    assert tr.summary()["open_records"] == 0
    # Same-seed determinism: a replay emits identical signatures.
    tr2 = RecordTracer(ObsConfig(clock=ManualClock().now))
    tr2.replica_joined("r0i0", replica=0)
    tr2.replica_fenced("r0i0", reason="lease_expired", lease_age_s=2.5,
                       replica=0)
    tr2.journal_handoff("r0i0", entries=3, replica=0)
    assert tr2.signature() == tr.signature()


def test_rollout_events_ride_the_trace_stream():
    """ISSUE-18 lifecycle observability: rollout_phase / canary_started
    / swapped / rolled_back are typed events on the SAME stream as
    record lifecycles (topic "fleet", sequential offsets) with the
    phase, member, version, slice and reason in the attrs — they open
    no record lifecycle, and a same-input replay emits identical
    signatures (the byte-auditable narration contract)."""
    mc = ManualClock()
    tr = RecordTracer(ObsConfig(clock=mc.now))
    tr.rollout_phase("canary", 3)
    tr.canary_started("r0i0", 3, slice_n=4)
    mc.advance(0.5)
    tr.swapped(3, member="r0i0", replica=0)
    tr.rollout_phase("rolling", 3)
    tr.rolled_back("canary_divergence", 3)
    evs = list(tr.events)
    assert [e.stage for e in evs] == [
        ROLLOUT_PHASE, CANARY_STARTED, SWAPPED, ROLLOUT_PHASE, ROLLED_BACK,
    ]
    assert [e.key for e in evs] == [
        ("fleet", 0, i) for i in range(5)
    ]
    assert dict(evs[0].attrs) == {"phase": "canary", "version": 3}
    canary = dict(evs[1].attrs)
    assert canary == {"member": "r0i0", "version": 3, "slice_n": 4}
    swapped = dict(evs[2].attrs)
    assert swapped == {"member": "r0i0", "replica": 0, "version": 3}
    assert dict(evs[4].attrs) == {
        "reason": "canary_divergence", "version": 3,
    }
    assert tr.summary()["open_records"] == 0
    # Same-seed determinism: a replay emits identical signatures.
    tr2 = RecordTracer(ObsConfig(clock=ManualClock().now))
    tr2.rollout_phase("canary", 3)
    tr2.canary_started("r0i0", 3, slice_n=4)
    tr2.swapped(3, member="r0i0", replica=0)
    tr2.rollout_phase("rolling", 3)
    tr2.rolled_back("canary_divergence", 3)
    assert tr2.signature() == tr.signature()


def test_in_process_fleet_emits_membership_events(tmp_path):
    """A traced ServingFleet narrates its own membership: joins at
    construction, a fence + journal handoff on kill_replica — and the
    liveness counters ride FleetMetrics.summary()."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=12, dtype=jnp.float32,
    )
    params = init_params(jax.random.key(0), cfg)
    broker = tk.InMemoryBroker()
    broker.create_topic("t", partitions=2)
    rng = np.random.default_rng(0)
    for i in range(4):
        broker.produce("t", rng.integers(0, 64, 4, np.int32).tobytes(),
                       partition=i % 2)
    fleet = ServingFleet(
        lambda rid: tk.MemoryConsumer(broker, "t", group_id="g"),
        params, cfg, replicas=2, prompt_len=4, max_new=4, slots=2,
        journal_dir=tmp_path, journal_cadence=1, obs=True,
    )
    stages = [e.stage for e in fleet.tracer.events]
    assert stages.count(REPLICA_JOINED) == 2
    served = fleet.serve_all(max_records=2, idle_timeout_ms=500)
    assert served
    fleet.kill_replica(0)
    stages = [e.stage for e in fleet.tracer.events]
    assert stages.count(REPLICA_FENCED) == 1
    mem = fleet.metrics.summary(fleet.replicas)["membership"]
    assert mem["joins"] == 2 and mem["fences"] == 1
    fleet.close()


def test_exposition_label_escaping_roundtrip():
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    body = format_labels(tenant=EVIL_TENANT, percentile="p50")
    assert "\n" not in body
    # The fleet's rendered evil-tenant sample must still parse.
    text = _fleet_metrics()
    evil_lines = [
        line for line in text.splitlines()
        if "tenant_admitted_total{" in line
    ]
    assert evil_lines and all(_SAMPLE_RE.match(li) for li in evil_lines)


def test_combined_exposition_has_no_duplicate_metric_families():
    """One scrape of every class must not define the same metric name
    twice (Prometheus rejects duplicate families) — the prefixes keep
    the families disjoint."""
    text = "".join((
        _stream_metrics(), _serve_metrics(), _fleet_metrics(),
        _resilience_metrics(), _slo_tracer(), _burn_monitor(),
        _windowed_slo_tracer(), _traced_fleet_metrics(),
        _broker_metrics(),
    ))
    names = re.findall(r"^# TYPE (\S+)", text, re.M)
    assert len(names) == len(set(names))
    _assert_conformant(text)


# --------------------------------------------------------------------------
# 4. The HTTP endpoint
# --------------------------------------------------------------------------


class TestExporter:
    def test_serves_all_sources_and_survives_broken_one(self):
        m = StreamMetrics()
        m.records.add(7)
        tr = _slo_tracer  # callable source returning exposition text

        def broken():
            raise RuntimeError("scrape me not")

        with MetricsExporter([m, tr, broken]) as exporter:
            with urllib.request.urlopen(exporter.url, timeout=10) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith("text/plain")
                body = resp.read().decode()
        assert "torchkafka_records_total 7" in body
        assert "torchkafka_slo_ttft_ms" in body
        assert "# source error: RuntimeError" in body
        _assert_conformant(
            "\n".join(li for li in body.splitlines()
                      if not li.startswith("# source error")) + "\n"
        )

    def test_404_off_path_and_restartable(self):
        exporter = MetricsExporter([StreamMetrics()]).start()
        try:
            url = f"http://127.0.0.1:{exporter.port}/nope"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(url, timeout=10)
        finally:
            exporter.stop()
        with pytest.raises(RuntimeError, match="not started"):
            _ = exporter.port


# --------------------------------------------------------------------------
# Satellite: ingest lag through the injectable clock
# --------------------------------------------------------------------------


class TestIngestLagClock:
    def test_helper_uses_injected_clock(self):
        mc = ManualClock(start=2.0)  # "epoch" 2s = 2000ms
        assert ingest_lag_ms(500, clock=mc.now) == pytest.approx(1500.0)
        mc.advance(1.0)
        assert ingest_lag_ms(500, clock=mc.now) == pytest.approx(2500.0)
        assert ingest_lag_ms(0, clock=mc.now) == 0.0  # no timestamp
        assert ingest_lag_ms(500, now_ms=700.0) == pytest.approx(200.0)

    def test_stream_lag_gauge_is_exact_under_manual_clock(self):
        broker = tk.InMemoryBroker()
        broker.create_topic("lag", partitions=1)
        for i in range(4):
            # Records appended at t=1.0s on the synthetic timeline.
            broker.produce(
                "lag", np.arange(4, dtype=np.int32).tobytes(),
                partition=0, timestamp_ms=1000 + i,
            )
        mc = ManualClock(start=2.5)  # poll happens at t=2.5s
        consumer = tk.MemoryConsumer(broker, "lag", group_id="glag")
        with tk.KafkaStream(
            consumer, tk.fixed_width(4, np.int32), batch_size=4,
            prefetch=0, to_device=False, idle_timeout_ms=1,
            owns_consumer=True, clock=mc.now,
        ) as stream:
            batch, token = next(iter(stream))
            token.commit()
            # newest record stamped 1003ms, clock reads 2500ms.
            assert stream.metrics.ingest_lag_ms.value == pytest.approx(
                2500.0 - 1003.0
            )
