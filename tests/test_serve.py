"""Continuous-batching generation server (torchkafka_tpu/serve.py).

Pins the three properties that make it a correct streaming server:
token-exact parity with the lockstep ``generate`` path, EOS early-stop with
slot recycling across admission waves, and per-completion offset accounting
(commit covers exactly the finished prompts; unfinished ones re-deliver).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchkafka_tpu as tk
from torchkafka_tpu.models.generate import generate
from torchkafka_tpu.models.transformer import TransformerConfig, init_params
from torchkafka_tpu.serve import StreamingGenerator

P, MAX_NEW, VOCAB = 8, 8, 64


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=P + MAX_NEW, dtype=jnp.float32,
    )
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


def _topic(broker, n):
    broker.create_topic("p", partitions=2)
    rng = np.random.default_rng(7)
    prompts = rng.integers(0, VOCAB, (n, P), dtype=np.int32)
    for i in range(n):
        broker.produce("p", prompts[i].tobytes(), partition=i % 2)
    return prompts


def _expected(cfg, params, prompts, eos_id=None):
    full = np.asarray(generate(params, cfg, jnp.asarray(prompts), MAX_NEW))
    outs = []
    for row in full:
        if eos_id is not None:
            # The server checks EOS only on decode outputs (positions >= 1);
            # prefill's token 0 is emitted unconditionally.
            hits = np.nonzero(row[1:] == eos_id)[0]
            if hits.size:
                outs.append(row[: hits[0] + 2])
                continue
        outs.append(row)
    return outs


class TestStreamingGenerator:
    def test_matches_lockstep_generate(self, model):
        cfg, params = model
        broker = tk.InMemoryBroker()
        prompts = _topic(broker, 10)
        consumer = tk.MemoryConsumer(broker, "p", group_id="g")
        server = StreamingGenerator(
            consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
            commit_every=4,
        )
        expected = _expected(cfg, params, prompts)
        got = {}
        for rec, toks in server.run(max_records=10):
            got[(rec.partition, rec.offset)] = toks
        assert len(got) == 10
        for (part, off), toks in got.items():
            # record at (part, off) is prompt index 2*off + part
            idx = 2 * off + part
            np.testing.assert_array_equal(toks, expected[idx], err_msg=f"prompt {idx}")
        # All 10 completions committed (final flush).
        total = sum(
            broker.committed("g", tk.TopicPartition("p", p)) or 0 for p in (0, 1)
        )
        assert total == 10
        consumer.close()

    def test_eos_truncates_and_recycles_slots(self, model):
        """Pick an EOS id that provably appears mid-generation for at least
        one prompt: those slots must stop early (truncated output) and admit
        the next prompt sooner — more admission waves than slots."""
        cfg, params = model
        probe = _expected(cfg, params, np.asarray(
            np.random.default_rng(7).integers(0, VOCAB, (16, P), dtype=np.int32)
        ))
        # eos = a token some generation emits at a decode position.
        eos_id = None
        for row in probe:
            if len(set(row[1:].tolist())) > 1:
                eos_id = int(row[2])
                break
        assert eos_id is not None
        broker = tk.InMemoryBroker()
        prompts = _topic(broker, 16)
        consumer = tk.MemoryConsumer(broker, "p", group_id="g2")
        server = StreamingGenerator(
            consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
            eos_id=eos_id, commit_every=100,
        )
        expected = _expected(cfg, params, prompts, eos_id=eos_id)
        seen = 0
        some_truncated = False
        for rec, toks in server.run(max_records=16):
            idx = 2 * rec.offset + rec.partition
            np.testing.assert_array_equal(toks, expected[idx], err_msg=f"prompt {idx}")
            if len(toks) < MAX_NEW:
                some_truncated = True
            seen += 1
        assert seen == 16
        assert some_truncated, "chosen eos never fired: test is vacuous"
        consumer.close()

    def test_crash_before_commit_redelivers_unfinished(self, model):
        cfg, params = model
        broker = tk.InMemoryBroker()
        _topic(broker, 8)
        consumer = tk.MemoryConsumer(broker, "p", group_id="g3")
        server = StreamingGenerator(
            consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
            commit_every=2,
        )
        finished = []
        for rec, toks in server.run(max_records=8):
            finished.append(rec)
            if len(finished) == 4:
                break  # crash: no final flush for completions 3-4+
        consumer.close()
        committed = sum(
            broker.committed("g3", tk.TopicPartition("p", p)) or 0 for p in (0, 1)
        )
        # commit_every=2 → at least the first pair durable, never more than
        # the number of finished generations.
        assert 2 <= committed <= len(finished)
        # Restart with the same group: exactly the uncommitted prompts
        # re-deliver.
        consumer2 = tk.MemoryConsumer(broker, "p", group_id="g3")
        redelivered = []
        while True:
            recs = consumer2.poll(max_records=64, timeout_ms=50)
            if not recs:
                break
            redelivered.extend(recs)
        assert len(redelivered) == 8 - committed
        consumer2.close()

    def test_max_records_is_strict(self, model):
        """Admission respects the budget: served + in-flight never exceeds
        max_records, so exactly N completions come out with work pending."""
        cfg, params = model
        broker = tk.InMemoryBroker()
        _topic(broker, 12)
        consumer = tk.MemoryConsumer(broker, "p", group_id="g4")
        server = StreamingGenerator(
            consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW
        )
        out = list(server.run(max_records=3))
        assert len(out) == 3
        consumer.close()

    def test_poison_record_dropped_not_fatal(self, model):
        """An undecodable record is retired as dropped (the reference's
        None-filter analog) instead of crash-looping the partition."""
        cfg, params = model
        broker = tk.InMemoryBroker()
        broker.create_topic("p", partitions=1)
        rng = np.random.default_rng(0)
        broker.produce("p", b"\x01\x02\x03")  # 3 bytes: not an int32 row
        good = rng.integers(0, VOCAB, (2, P), dtype=np.int32)
        for i in range(2):
            broker.produce("p", good[i].tobytes())
        consumer = tk.MemoryConsumer(broker, "p", group_id="g5")

        def strict_decode(rec):
            toks = np.frombuffer(rec.value, dtype=np.int32)
            assert toks.shape[0] == P
            return toks

        server = StreamingGenerator(
            consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
            decode_prompt=strict_decode, commit_every=1,
        )
        served = list(server.run(max_records=2))
        assert len(served) == 2
        # The poison record is inside the committed watermark (dropped), so
        # a restart does NOT re-deliver it.
        assert broker.committed("g5", tk.TopicPartition("p", 0)) == 3
        consumer.close()

    def test_commit_failure_survivable(self, model, caplog):
        """A CommitFailedError during flush (a commit racing a rebalance
        the client has not yet synced — injected here, since _commit now
        pre-syncs the group and drops departed partitions, the Kafka-
        client discipline that closes the deterministic window this test
        once rode) must be logged and survived, not die: uncommitted
        prompts simply re-deliver (the reference's contract,
        kafka_dataset.py:131-135)."""
        import logging

        from torchkafka_tpu.errors import CommitFailedError

        caplog.set_level(logging.ERROR, logger="torchkafka_tpu.serve")
        cfg, params = model
        broker = tk.InMemoryBroker()
        broker.create_topic("p", partitions=1)
        rng = np.random.default_rng(3)
        for _ in range(4):
            broker.produce(
                "p", rng.integers(0, VOCAB, P, dtype=np.int32).tobytes()
            )

        class _RaceyConsumer(tk.MemoryConsumer):
            """First commit races a rebalance: the broker rejects it
            after the sync (exactly what a coordinator that finished a
            rebalance mid-RPC does); later commits land."""

            fail_next = True

            def commit(self, offsets=None):
                if _RaceyConsumer.fail_next:
                    _RaceyConsumer.fail_next = False
                    raise CommitFailedError(
                        "generation bumped mid-commit (injected race)"
                    )
                return super().commit(offsets)

        c1 = _RaceyConsumer(broker, "p", group_id="gr")
        server = StreamingGenerator(
            c1, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
            commit_every=1,
        )
        outs = [
            (rec.partition, rec.offset)
            for rec, _toks in server.run(max_records=4, idle_timeout_ms=500)
        ]
        assert len(outs) == 4  # served past the failed commit without dying
        assert not _RaceyConsumer.fail_next, "the injected race never fired"
        assert any(
            "commit failed" in r.message for r in caplog.records
        ), "the failed commit was never logged"
        assert server.metrics.commit_failures.count == 1
        # The retry discipline healed the watermark: the final flush
        # covered everything (nothing would re-deliver on restart).
        assert c1.committed(tk.TopicPartition("p", 0)) == 4
        c1.close()

    def test_tp_sharded_params(self, model):
        """Serving with tensor-parallel-sharded params: the server's jitted
        admit/decode respect the params' committed shardings (GSPMD inserts
        the collectives) — no server changes needed, outputs token-exact."""
        from torchkafka_tpu.models.transformer import (
            init_params, param_specs, shardings_for_mesh,
        )
        from torchkafka_tpu.parallel import make_mesh

        # n_kv_heads=2 so the kv projections divide over tp=2 (the shared
        # fixture uses 1 kv head, which cannot shard).
        cfg = TransformerConfig(
            vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq_len=P + MAX_NEW, dtype=jnp.float32,
        )
        params = init_params(jax.random.key(0), cfg)
        mesh = make_mesh({"data": 4, "tp": 2})
        shardings = shardings_for_mesh(mesh, param_specs(cfg))
        sharded = jax.device_put(params, shardings)
        broker = tk.InMemoryBroker()
        prompts = _topic(broker, 6)
        consumer = tk.MemoryConsumer(broker, "p", group_id="gtp")
        server = StreamingGenerator(
            consumer, sharded, cfg, slots=2, prompt_len=P, max_new=MAX_NEW
        )
        expected = _expected(cfg, params, prompts)
        seen = 0
        for rec, toks in server.run(max_records=6):
            idx = 2 * rec.offset + rec.partition
            np.testing.assert_array_equal(toks, expected[idx], err_msg=f"prompt {idx}")
            seen += 1
        assert seen == 6
        consumer.close()

    @pytest.mark.parametrize("ticks", [1, 3])
    def test_ticks_per_sync_variants(self, model, rng, ticks):
        """K=1 (immediate recycling) and a K that does NOT divide max_new
        both produce token-exact outputs — completion detection inside a
        partial final block must latch correctly."""
        cfg, params = model
        broker = tk.InMemoryBroker()
        prompts = _topic(broker, 6)
        consumer = tk.MemoryConsumer(broker, "p", group_id=f"gk{ticks}")
        server = StreamingGenerator(
            consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
            ticks_per_sync=ticks,
        )
        expected = _expected(cfg, params, prompts)
        seen = 0
        for rec, toks in server.run(max_records=6):
            idx = 2 * rec.offset + rec.partition
            np.testing.assert_array_equal(toks, expected[idx], err_msg=f"prompt {idx}")
            seen += 1
        assert seen == 6
        consumer.close()

    def test_temperature_sampling(self, model, rng):
        """temperature > 0 samples per slot: the server completes and
        commits, outputs are valid token ids, and two different rng keys
        produce different continuations (same prompts)."""
        cfg, params = model

        def serve_with(key_seed):
            broker = tk.InMemoryBroker()
            _topic(broker, 4)
            consumer = tk.MemoryConsumer(broker, "p", group_id=f"gt{key_seed}")
            server = StreamingGenerator(
                consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
                temperature=1.0, rng=jax.random.key(key_seed),
            )
            outs = {}
            for rec, toks in server.run(max_records=4):
                assert toks.min() >= 0 and toks.max() < VOCAB
                outs[(rec.partition, rec.offset)] = toks
            consumer.close()
            return outs

        a = serve_with(1)
        b = serve_with(2)
        assert len(a) == len(b) == 4
        assert any(
            not np.array_equal(a[k], b[k]) for k in a
        ), "different rng keys produced identical samples"

    def test_moe_serving(self, rng):
        """The decode tail routes through _moe_mlp for MoE configs — the
        slot server must generate and commit with an expert-MLP model."""
        cfg = TransformerConfig(
            vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
            d_ff=64, max_seq_len=P + MAX_NEW, dtype=jnp.float32, n_experts=4,
        )
        params = init_params(jax.random.key(2), cfg)
        broker = tk.InMemoryBroker()
        prompts = _topic(broker, 4)
        consumer = tk.MemoryConsumer(broker, "p", group_id="gmoe")
        server = StreamingGenerator(
            consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW
        )
        expected = _expected(cfg, params, prompts)
        seen = 0
        for rec, toks in server.run(max_records=4):
            idx = 2 * rec.offset + rec.partition
            np.testing.assert_array_equal(toks, expected[idx], err_msg=f"prompt {idx}")
            seen += 1
        assert seen == 4
        consumer.close()

    def test_live_production_while_serving(self, model, rng):
        """Prompts arrive WHILE generations run (a live topic, not a
        pre-filled one): the server's non-blocking poll keeps slots busy,
        admits stragglers as they appear, and serves everything."""
        import threading
        import time as _time

        cfg, params = model
        broker = tk.InMemoryBroker()
        broker.create_topic("p", partitions=2)
        total = 10
        prompts = rng.integers(0, VOCAB, (total, P), dtype=np.int32)

        def produce_slowly():
            for i in range(total):
                broker.produce("p", prompts[i].tobytes(), partition=i % 2)
                _time.sleep(0.05)

        consumer = tk.MemoryConsumer(broker, "p", group_id="glive")
        server = StreamingGenerator(
            consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
            commit_every=3,
        )
        t = threading.Thread(target=produce_slowly)
        t.start()
        expected = _expected(cfg, params, prompts)
        seen = 0
        for rec, toks in server.run(max_records=total, idle_timeout_ms=4000):
            idx = 2 * rec.offset + rec.partition
            np.testing.assert_array_equal(toks, expected[idx], err_msg=f"prompt {idx}")
            seen += 1
        t.join()
        assert seen == total
        committed = sum(
            broker.committed("glive", tk.TopicPartition("p", p)) or 0
            for p in (0, 1)
        )
        assert committed == total
        consumer.close()

    def test_close_commits_completed_work(self, model, rng):
        """Context-manager exit (voluntary shutdown) commits completions
        that the commit cadence hadn't flushed yet; in-flight/undelivered
        prompts stay uncommitted for the next owner."""
        cfg, params = model
        broker = tk.InMemoryBroker()
        _topic(broker, 6)
        consumer = tk.MemoryConsumer(broker, "p", group_id="gclose")
        with StreamingGenerator(
            consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
            commit_every=100,  # cadence never fires: only close() commits
        ) as server:
            done = 0
            for _rec, _toks in server.run(max_records=4):
                done += 1
                if done == 4:
                    break  # voluntary stop with 2 prompts never admitted
        committed = sum(
            broker.committed("gclose", tk.TopicPartition("p", p)) or 0
            for p in (0, 1)
        )
        assert committed == 4  # the 4 completions, not the 2 unserved
        consumer.close()

    def test_metrics_prometheus_render(self, model):
        cfg, params = model
        broker = tk.InMemoryBroker()
        _topic(broker, 4)
        consumer = tk.MemoryConsumer(broker, "p", group_id="gm")
        server = StreamingGenerator(
            consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
        )
        done = sum(1 for _ in server.run(max_records=4))
        assert done == 4
        text = server.metrics.render_prometheus()
        assert "torchkafka_serve_completions_total 4" in text
        assert f"torchkafka_serve_tokens_total {4 * MAX_NEW}" in text
        for line in text.strip().split("\n"):
            if not line.startswith("#"):
                float(line.rsplit(" ", 1)[1])
        consumer.close()

    def test_rejects_bad_config(self, model):
        cfg, params = model
        consumer = object()
        with pytest.raises(ValueError, match="max_seq_len"):
            StreamingGenerator(
                consumer, params, cfg, prompt_len=P, max_new=MAX_NEW + 1
            )
        with pytest.raises(ValueError, match="max_new"):
            StreamingGenerator(consumer, params, cfg, prompt_len=P, max_new=1)

    @pytest.mark.parametrize("bad", [1, 0, "on"])
    def test_rejects_non_bool_kv_kernel(self, model, bad):
        """ADVICE r5 #3: ``in (True, False, 'auto')`` accepted 1/0 via
        bool-int equality and then treated them inconsistently (``is
        True`` guards never fired) — identity validation must reject
        them outright."""
        cfg, params = model
        with pytest.raises(ValueError, match="kv_kernel"):
            StreamingGenerator(
                object(), params, cfg, prompt_len=P, max_new=MAX_NEW,
                kv_dtype="int8", kv_kernel=bad,
            )


class TestOutputTopic:
    def test_completions_published_before_commit(self, model):
        """Every completion lands on the output topic (key preserved) and
        the producer is flushed before offsets commit."""
        cfg, params = model
        broker = tk.InMemoryBroker()
        _topic(broker, 6)
        broker.create_topic("out", partitions=1)
        consumer = tk.MemoryConsumer(broker, "p", group_id="g")
        producer = tk.MemoryProducer(broker)
        server = StreamingGenerator(
            consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
            commit_every=2,
            output_producer=producer, output_topic="out",
        )
        got = list(server.run(max_records=6))
        assert len(got) == 6
        c2 = tk.MemoryConsumer(broker, "out", group_id="g2")
        outs = c2.poll(max_records=100, timeout_ms=200)
        assert len(outs) == 6
        by_val = sorted(o.value for o in outs)
        want = sorted(np.asarray(t, np.int32).tobytes() for _, t in got)
        assert by_val == want
        assert server.metrics.summary()["output_flush_failures"] == 0
        consumer.close()

    def test_failed_output_flush_skips_commit(self, model, caplog):
        """Fail closed: completions that never became durable must leave
        their prompts uncommitted (regenerate, don't lose output)."""
        cfg, params = model
        broker = tk.InMemoryBroker()
        _topic(broker, 4)
        broker.create_topic("out", partitions=1)
        consumer = tk.MemoryConsumer(broker, "p", group_id="g")

        class FlakyProducer(tk.MemoryProducer):
            def flush(self, timeout_s=None):
                raise RuntimeError("output broker gone")

        server = StreamingGenerator(
            consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
            commit_every=2,
            output_producer=FlakyProducer(broker), output_topic="out",
        )
        got = list(server.run(max_records=4))
        assert len(got) == 4  # serving itself continues
        assert server.metrics.summary()["output_flush_failures"] >= 1
        committed = sum(
            broker.committed("g", tk.TopicPartition("p", p)) or 0 for p in (0, 1)
        )
        assert committed == 0  # nothing committed: all prompts re-deliver

    def test_sync_send_failure_stalls_watermark_not_server(self, model):
        """A synchronous send refusal (buffer full / closed / bad topic)
        must neither kill serving nor let the affected prompt commit: the
        ledger watermark stalls at exactly that record."""
        cfg, params = model
        broker = tk.InMemoryBroker()
        _topic(broker, 6)
        broker.create_topic("out", partitions=1)
        consumer = tk.MemoryConsumer(broker, "p", group_id="g")

        class FailOnce(tk.MemoryProducer):
            def __init__(self, broker):
                super().__init__(broker)
                self.fails = 0

            def send(self, topic, value, **kw):
                # Fail exactly the first send (prompt p0:0 or p1:0 —
                # whichever completes first).
                if self.fails == 0:
                    self.fails = 1
                    raise RuntimeError("buffer full")
                return super().send(topic, value, **kw)

        producer = FailOnce(broker)
        server = StreamingGenerator(
            consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
            commit_every=2, output_producer=producer, output_topic="out",
        )
        got = list(server.run(max_records=6))
        assert len(got) == 6  # serving survived
        assert server.metrics.summary()["output_send_failures"] == 1
        committed = sum(
            broker.committed("g", tk.TopicPartition("p", p)) or 0 for p in (0, 1)
        )
        # Exactly one record's watermark is stalled (its partition commits
        # stop just before it); everything else committed.
        assert committed < 6
        c2 = tk.MemoryConsumer(broker, "out", group_id="g2")
        assert len(c2.poll(max_records=100, timeout_ms=200)) == 5

    def test_send_failure_streak_fail_stops(self, model):
        """ADVICE r3: a PERSISTENTLY failing output send must not serve
        forever behind a stalled watermark — after max_send_failure_streak
        consecutive refusals the server raises OutputDeliveryError, the
        same fail-stop signal as terminal async delivery failure, and
        nothing past the stall commits."""
        cfg, params = model
        broker = tk.InMemoryBroker()
        _topic(broker, 6)
        broker.create_topic("out", partitions=1)
        consumer = tk.MemoryConsumer(broker, "p", group_id="g")

        class AlwaysDown(tk.MemoryProducer):
            def send(self, topic, value, **kw):
                raise RuntimeError("broker down")

        server = StreamingGenerator(
            consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
            commit_every=2, output_producer=AlwaysDown(broker),
            output_topic="out", max_send_failure_streak=3,
        )
        with pytest.raises(tk.OutputDeliveryError, match="consecutive"):
            list(server.run(max_records=6))
        assert server.metrics.summary()["output_send_failures"] == 3
        committed = sum(
            broker.committed("g", tk.TopicPartition("p", p)) or 0 for p in (0, 1)
        )
        assert committed == 0  # every completion stayed uncommitted

    def test_terminal_delivery_failure_is_fatal(self, model):
        """A send that FAILED after the flush (async, terminal) must raise
        OutputDeliveryError instead of committing past lost output."""
        cfg, params = model
        broker = tk.InMemoryBroker()
        _topic(broker, 4)
        broker.create_topic("out", partitions=1)
        consumer = tk.MemoryConsumer(broker, "p", group_id="g")

        class DeadHandle:
            def get(self, timeout_s=None):
                raise RuntimeError("retries exhausted")

        class AsyncFail(tk.MemoryProducer):
            def send(self, topic, value, **kw):
                super().send(topic, value, **kw)
                return DeadHandle()

        server = StreamingGenerator(
            consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
            commit_every=2, output_producer=AsyncFail(broker),
            output_topic="out",
        )
        with pytest.raises(tk.OutputDeliveryError):
            list(server.run(max_records=4))
        committed = sum(
            broker.committed("g", tk.TopicPartition("p", p)) or 0 for p in (0, 1)
        )
        assert committed == 0  # nothing committed past the lost outputs

    def test_producer_without_topic_rejected(self, model):
        cfg, params = model
        broker = tk.InMemoryBroker()
        _topic(broker, 2)
        consumer = tk.MemoryConsumer(broker, "p", group_id="g")
        with pytest.raises(ValueError, match="together"):
            StreamingGenerator(
                consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
                output_producer=tk.MemoryProducer(broker),
            )


class TestMeshShardedServing:
    """Explicit-mesh serving (serve.py ``mesh=``): kv heads over tp, slots
    over data, weights tp/fsdp — token-exact vs mesh-less serving, with the
    same per-completion commit accounting."""

    def _run(self, cfg, params, mesh):
        broker = tk.InMemoryBroker()
        prompts = _topic(broker, 10)
        consumer = tk.MemoryConsumer(broker, "p", group_id="gmesh")
        server = StreamingGenerator(
            consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
            mesh=mesh, commit_every=1,
        )
        out = {}
        for rec, toks in server.run(max_records=10):
            out[2 * rec.offset + rec.partition] = np.asarray(toks)
        server.close()
        committed = {
            pt: broker.committed("gmesh", tk.TopicPartition("p", pt))
            for pt in (0, 1)
        }
        consumer.close()
        return prompts, out, committed

    def test_sharded_serving_token_exact_and_commits(self):
        cfg = TransformerConfig(
            vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq_len=P + MAX_NEW, dtype=jnp.float32,
        )
        params = init_params(jax.random.key(0), cfg)
        from torchkafka_tpu.parallel import make_mesh

        prompts, base, committed0 = self._run(cfg, params, None)
        assert committed0 == {0: 5, 1: 5}
        expected = _expected(cfg, params, prompts)
        for idx, toks in base.items():
            np.testing.assert_array_equal(toks, expected[idx])
        for axes in ({"data": 2, "fsdp": 2, "tp": 2}, {"data": 4, "tp": 2}):
            _, out, committed = self._run(cfg, params, make_mesh(axes))
            assert set(out) == set(base)
            for idx in base:
                np.testing.assert_array_equal(
                    out[idx], base[idx], err_msg=f"{axes} prompt {idx}"
                )
            # Every completion committed (commit_every=1): watermarks cover
            # exactly the 5 prompts per partition.
            assert committed == {0: 5, 1: 5}, (axes, committed)


class TestInt8KV:
    """Opt-in int8 slot pool (kv_dtype='int8'): pool bytes ~halve, commits
    stay exact, quantization error is bounded — token-exactness vs the
    bf16 path is deliberately given up (documented)."""

    def test_quant_roundtrip_error_bound(self):
        from torchkafka_tpu.kvcache.slot_pool import _quant_kv

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 32, 2, 16)) * 3.0, jnp.float32)
        q, s = _quant_kv(x)
        assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
        back = np.asarray(q * s[..., None])
        # Symmetric absmax: error <= scale/2 = absmax/254 per group.
        bound = np.asarray(s)[..., None] / 2 + 1e-7
        assert (np.abs(back - np.asarray(x)) <= bound).all()

    def test_serves_and_commits_exactly(self, model):
        cfg, params = model
        broker = tk.InMemoryBroker()
        _topic(broker, 10)
        consumer = tk.MemoryConsumer(broker, "p", group_id="gkv8")
        server = StreamingGenerator(
            consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
            commit_every=1, kv_dtype="int8",
        )
        # Pool layout: int8 payloads + f32 scales, ~ (1 + 4/Dh) bytes per
        # element vs the f32 fixture's 4 (bf16 zoo models: vs 2).
        pool_bytes = sum(int(c.nbytes) for c in server._caches)
        dense_bytes = 2 * cfg.n_layers * 4 * (P + MAX_NEW) * (
            cfg.n_kv_heads * cfg.head_dim
        ) * 4
        assert pool_bytes < dense_bytes / 2, (pool_bytes, dense_bytes)
        served = 0
        for _rec, toks in server.run(max_records=10):
            assert 1 <= len(toks) <= MAX_NEW
            assert (np.asarray(toks) >= 0).all() and (
                np.asarray(toks) < VOCAB
            ).all()
            served += 1
        server.close()
        assert served == 10
        committed = {
            pt: broker.committed("gkv8", tk.TopicPartition("p", pt))
            for pt in (0, 1)
        }
        assert committed == {0: 5, 1: 5}, committed
        consumer.close()

    def test_rejects_bad_kv_dtype(self, model):
        cfg, params = model
        broker = tk.InMemoryBroker()
        broker.create_topic("p", partitions=1)
        consumer = tk.MemoryConsumer(broker, "p", group_id="gbad")
        with pytest.raises(ValueError, match="kv_dtype"):
            StreamingGenerator(
                consumer, params, cfg,
                slots=2, prompt_len=P, max_new=MAX_NEW, kv_dtype="fp8",
            )
        consumer.close()

    def test_mesh_sharded_int8_pool(self):
        """int8 pool + mesh: the 4-tuple (payload, scale, payload, scale)
        survives the donate-and-rebind round trip with payloads sharded
        (kv heads over tp, slots over data — asserted by per-device shard
        extents, not just device membership) and scales on the matching
        4D layout; serves all prompts with exact commits, token-identical
        to single-device int8 (f32 model)."""
        from torchkafka_tpu.parallel import make_mesh

        cfg = TransformerConfig(
            vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq_len=P + MAX_NEW, dtype=jnp.float32,
        )
        params = init_params(jax.random.key(0), cfg)

        def run(mesh):
            broker = tk.InMemoryBroker()
            prompts = _topic(broker, 10)
            consumer = tk.MemoryConsumer(broker, "p", group_id="gkvm")
            server = StreamingGenerator(
                consumer, params, cfg, slots=4, prompt_len=P,
                max_new=MAX_NEW, commit_every=1, kv_dtype="int8", mesh=mesh,
            )
            if mesh is not None:
                assert len(server._caches) == 4
                kq, ks = server._caches[0], server._caches[1]
                # [L, B, M, K, Dh]: B/data=2, K/tp=1 per shard.
                assert kq.addressable_shards[0].data.shape[1] == 4 // 2
                assert kq.addressable_shards[0].data.shape[3] == 2 // 2
                # Scales [L, B, M, K] on the same axes.
                assert ks.addressable_shards[0].data.shape[1] == 4 // 2
                assert ks.addressable_shards[0].data.shape[3] == 2 // 2
            out = {}
            for rec, toks in server.run(max_records=10):
                out[2 * rec.offset + rec.partition] = np.asarray(toks)
            server.close()
            committed = {
                pt: broker.committed("gkvm", tk.TopicPartition("p", pt))
                for pt in (0, 1)
            }
            consumer.close()
            assert committed == {0: 5, 1: 5}, committed
            return out

        base = run(None)
        sharded = run(make_mesh({"data": 2, "tp": 2, "fsdp": 2}))
        assert set(sharded) == set(base)
        for idx in base:
            np.testing.assert_array_equal(sharded[idx], base[idx])


class TestExpertParallelServing:
    """MoE decode on an ep-bearing mesh: expert weights shard over ep
    (serving_shardings strips nothing — param_specs' MoE specs carry the
    axis), the dense-routing combine psums across ep shards, and tokens
    stay exact vs the mesh-less MoE server."""

    def test_ep_sharded_moe_serving_token_exact(self):
        from torchkafka_tpu.parallel import make_mesh

        cfg = TransformerConfig(
            vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq_len=P + MAX_NEW, dtype=jnp.float32, n_experts=4,
        )
        params = init_params(jax.random.key(2), cfg)

        def run(mesh):
            broker = tk.InMemoryBroker()
            _topic(broker, 6)
            consumer = tk.MemoryConsumer(broker, "p", group_id="gep")
            server = StreamingGenerator(
                consumer, params, cfg, slots=2, prompt_len=P,
                max_new=MAX_NEW, commit_every=1, mesh=mesh,
            )
            if mesh is not None:
                # Expert weights actually sharded over ep: per-device
                # shard holds E/ep experts ([L, E, D, F] axis 1).
                wg = server._params["layers"]["w_gate"]
                assert wg.addressable_shards[0].data.shape[1] == 4 // 2, (
                    wg.sharding
                )
            out = {}
            for rec, toks in server.run(max_records=6):
                out[2 * rec.offset + rec.partition] = np.asarray(toks)
            server.close()
            committed = {
                pt: broker.committed("gep", tk.TopicPartition("p", pt))
                for pt in (0, 1)
            }
            consumer.close()
            assert committed == {0: 3, 1: 3}, committed
            return out

        base = run(None)
        sharded = run(make_mesh({"data": 2, "ep": 2, "tp": 2}))
        assert set(sharded) == set(base)
        for idx in base:
            np.testing.assert_array_equal(
                sharded[idx], base[idx], err_msg=f"prompt {idx}"
            )


# ----------------------------------------------------------------------
# The decode tick leaves the KV pool where it lies (PR 25): the pool is the
# layer scan's CARRY. Below, the formulation it replaced — the pool as the
# layer scan's xs and ys, one layer's slab a step — kept as the reference
# the carried tick must equal bit for bit; it lives in this file only.


def _ref_layer_step(x, layer, ck, cv, pos_b, cfg):
    from torchkafka_tpu.models.generate import _attend_cached, _project_qkv
    from torchkafka_tpu.models.transformer import _rope

    q, k, v = _project_qkv(x, layer, cfg)
    q = _rope(q, pos_b[:, None], cfg.rope_theta)
    k = _rope(k, pos_b[:, None], cfg.rope_theta)
    rows = jnp.arange(ck.shape[0])
    ck = ck.at[rows, pos_b].set(k[:, 0].astype(ck.dtype))
    cv = cv.at[rows, pos_b].set(v[:, 0].astype(cv.dtype))
    valid = jnp.arange(ck.shape[1])[None, :] <= pos_b[:, None]
    return _attend_cached(x, q, ck, cv, valid, layer, cfg), (ck, cv)


def _ref_layer_step_q(x, layer, ckq, cks, cvq, cvs, pos_b, cfg, kernel, mesh):
    from torchkafka_tpu.models.generate import (
        _attend_cached, _attn_tail, _project_qkv,
    )
    from torchkafka_tpu.models.transformer import _rope
    from torchkafka_tpu.ops.kvattn import int8_decode_attention_dynlen
    from torchkafka_tpu.kvcache.slot_pool import _quant_kv

    q, k, v = _project_qkv(x, layer, cfg)
    q = _rope(q, pos_b[:, None], cfg.rope_theta)
    k = _rope(k, pos_b[:, None], cfg.rope_theta)
    kq, ks = _quant_kv(k[:, 0])
    vq, vs = _quant_kv(v[:, 0])
    rows = jnp.arange(ckq.shape[0])
    if kernel:  # slab [B, K, M, Dh] / [B, K, M]
        kidx = jnp.arange(ckq.shape[1])[None, :]

        def upd(c, row):
            return c.at[rows[:, None], kidx, pos_b[:, None]].set(row)
    else:  # slab [B, M, K, Dh] / [B, M, K]
        def upd(c, row):
            return c.at[rows, pos_b].set(row)
    slab = (upd(ckq, kq), upd(cks, ks), upd(cvq, vq), upd(cvs, vs))
    if kernel:
        read = int8_decode_attention_dynlen  # one layer's slab, 4-D
        if mesh is not None:
            from jax.sharding import PartitionSpec as PS

            qs, cs = PS("data", None, "tp", None), PS("data", "tp", None, None)
            ss = PS("data", "tp", None)
            read = jax.shard_map(
                read, mesh=mesh, in_specs=(qs, cs, ss, cs, ss, PS("data")),
                out_specs=qs, check_vma=False,
            )
        return _attn_tail(x, read(q, *slab, pos_b), layer, cfg), slab
    valid = jnp.arange(ckq.shape[1])[None, :] <= pos_b[:, None]
    x = _attend_cached(
        x, q, slab[0], slab[2], valid, layer, cfg,
        k_scale=slab[1], v_scale=slab[3],
    )
    return x, slab


def _ref_tick_block(srv, params, caches, last_tok, pos, gen, active_in):
    """``serve.py::_build::tick_block`` as it stood before PR 25, greedy,
    no EOS: the layer scan takes the pool as xs and returns it as ys."""
    from jax import lax

    from torchkafka_tpu.models.quant import embed_rows, load_weight
    from torchkafka_tpu.models.transformer import _rms_norm

    cfg, P_, max_new = srv._cfg, srv._prompt_len, srv._max_new

    def one(carry, _):
        caches, last_tok, pos, gen, done_latch, n_out = carry
        act = active_in & ~done_latch
        x = embed_rows(params["embed"], last_tok, cfg.dtype)[:, None, :]

        def body(x, inputs):
            layer, *slab = inputs
            if srv._kv_int8:
                return _ref_layer_step_q(
                    x, layer, *slab, pos, cfg, srv._kv_kernel, srv._mesh
                )
            return _ref_layer_step(x, layer, *slab, pos, cfg)

        x, caches = lax.scan(body, x, (params["layers"], *caches))
        x = _rms_norm(x, params["ln_f"])
        logits = jnp.einsum(
            "bd,dv->bv", x[:, 0], load_weight(params["lm_head"], cfg.dtype),
            preferred_element_type=jnp.float32,
        )
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t = pos - P_
        idx = jnp.minimum(t + 1, max_new - 1)
        onehot = jnp.arange(max_new)[None, :] == idx[:, None]
        gen = jnp.where(onehot & act[:, None], tok[:, None], gen)
        done_now = act & (t + 2 >= max_new)
        pos = jnp.where(act & ~done_now, pos + 1, pos)
        last_tok = jnp.where(act, tok, last_tok)
        n_out = jnp.where(done_now, jnp.minimum(t + 2, max_new), n_out)
        return (
            tuple(caches), last_tok, pos, gen, done_latch | done_now, n_out
        ), None

    B = last_tok.shape[0]
    init = (caches, last_tok, pos, gen, jnp.zeros((B,), bool),
            jnp.zeros((B,), jnp.int32))
    return lax.scan(one, init, None, length=srv._ticks_per_sync)[0]


_TICK_VARIANTS = {
    # name: (kv_dtype, kv_kernel, mesh axes)
    "bf16": (None, "auto", None),
    "int8": ("int8", False, None),
    "int8-kernel": ("int8", True, None),
    "int8-mesh": ("int8", False, {"data": 2, "tp": 2, "fsdp": 2}),
    "int8-kernel-mesh": ("int8", True, {"data": 2, "tp": 2, "fsdp": 2}),
}


# The latent (MLA) pool with routed experts: its tick has no xs/ys twin to
# be identical to (it was written carried), so it joins the structure test
# and the served-token tests below, not ``_TICK_VARIANTS``.
_LATENT_VARIANTS = {
    "latent": (None, False, None),
    "latent-auto": (None, "auto", None),
}


# The bf16 pool again, sampling: the admission draws token 0 from each
# record's own key, which the chunked admission gathers with its rows.
_SAMPLED_VARIANTS = {"bf16-sampled": (None, "auto", None)}


def _latent_cfg(**over):
    """A leading dense layer and two expert layers over latent attention,
    every width a number of its own."""
    base = dict(
        vocab_size=VOCAB, d_model=32, n_layers=3, n_heads=2, n_kv_heads=2,
        d_ff=48, max_seq_len=P + MAX_NEW, dtype=jnp.float32, kv_lora_rank=16,
        qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, rope_interleave=True,
        first_dense_layers=1, n_experts=8, expert_top_k=2, expert_d_ff=12,
        n_shared_experts=2, router_score="sigmoid", routed_scaling=2.448,
    )
    base.update(over)
    return TransformerConfig(**base)


def _tick_server(variant, ticks=3, latent_over=None, **server_kw):
    """A 2-layer toy server of the variant (heads of 128: the kernel's lane
    width; 4 slots and 3 ticks a sync, numbers no model dimension has)."""
    from torchkafka_tpu.parallel import make_mesh

    kv_dtype, kv_kernel, axes = {
        **_TICK_VARIANTS, **_LATENT_VARIANTS, **_SAMPLED_VARIANTS,
    }[variant]
    if variant in _LATENT_VARIANTS:
        cfg = _latent_cfg(**(latent_over or {}))
    else:
        cfg = TransformerConfig(
            vocab_size=VOCAB, d_model=256, n_layers=2, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq_len=P + MAX_NEW, dtype=jnp.float32,
        )
    params = init_params(jax.random.key(0), cfg)
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    consumer = tk.MemoryConsumer(broker, "p", group_id=f"g-{variant}")
    srv = StreamingGenerator(
        consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
        ticks_per_sync=ticks, kv_dtype=kv_dtype, kv_kernel=kv_kernel,
        mesh=make_mesh(axes) if axes else None, **server_kw,
    )
    assert srv._kv_kernel is (kv_kernel is True)
    return srv, consumer


def _eqns(jaxpr):
    """Every equation under ``jaxpr``, however deeply it is nested."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _scans(jaxpr):
    return (e for e in _eqns(jaxpr) if e.primitive.name == "scan")


@pytest.mark.parametrize(
    "variant", ["bf16", "int8", "int8-kernel", *_LATENT_VARIANTS]
)
def test_layer_scan_carries_the_pool(variant):
    """Structure: in the tick's jaxpr every pool-shaped value of the layer
    scan is a carry. As an input (xs) the pool is sliced a layer at a
    time, as an output (ys) written back a layer at a time into a second
    buffer, and copied whole at the tick's end (PERF.md, PR 25)."""
    from torchkafka_tpu.models.transformer import _layer_groups

    srv, consumer = _tick_server(variant)
    B = 4
    # One layer scan a stacked group: one, or the leading dense layers'
    # and the expert layers' (the pool's layer index runs over both).
    depths = [nl for _key, nl, _e in _layer_groups(srv._cfg)]
    jaxpr = jax.make_jaxpr(srv._tick_block_raw)(
        srv._params, srv._caches, srv._last_tok, srv._pos, srv._gen,
        jnp.ones((B,), bool), srv._slot_keys,
    )
    pool_shapes = {c.shape for c in srv._caches}
    slab_shapes = {s[1:] for s in pool_shapes}
    layer_scans = [
        e for e in _scans(jaxpr.jaxpr)
        if e.params["length"] in depths
        and e.params["length"] != srv._ticks_per_sync
        and any(v.aval.shape in pool_shapes for v in e.invars)
    ]
    assert sorted(e.params["length"] for e in layer_scans) == sorted(depths)
    for scan in layer_scans:
        nc, nk = scan.params["num_consts"], scan.params["num_carry"]
        carry_in = [v.aval.shape for v in scan.invars[nc:nc + nk]]
        carry_out = [v.aval.shape for v in scan.outvars[:nk]]
        xs = [v.aval.shape for v in scan.invars[nc + nk:]]
        ys = [v.aval.shape for v in scan.outvars[nk:]]
        for shape in pool_shapes:
            n = sum(1 for c in srv._caches if c.shape == shape)
            assert carry_in.count(shape) == n and carry_out.count(shape) == n
        moved = pool_shapes | slab_shapes
        assert not [s for s in xs + ys if s in moved], (xs, ys)
        assert not [
            v.aval.shape for v in scan.invars[:nc] if v.aval.shape in moved
        ]
    srv.close()
    consumer.close()


@pytest.mark.parametrize("variant,scatters,kernel_outputs", [
    ("int8", 4, None), ("int8-kernel", 0, 5), ("int8-kernel-mesh", 0, 5),
])
def test_kernel_tick_writes_its_rows_in_the_read(variant, scatters,
                                                 kernel_outputs):
    """Structure: the kernel-mode tick holds no ``scatter`` at all: its one
    Pallas call returns the attention AND the four pool tensors, aliased
    to the four it was given (PR 30). The XLA-mode tick keeps its four
    scatters (one a pool tensor), and the metrics say which way serves."""
    srv, consumer = _tick_server(variant)
    jaxpr = jax.make_jaxpr(srv._tick_block_raw)(
        srv._params, srv._caches, srv._last_tok, srv._pos, srv._gen,
        jnp.ones((4,), bool), srv._slot_keys,
    )
    eqns = list(_eqns(jaxpr.jaxpr))
    assert sum(e.primitive.name.startswith("scatter") for e in eqns) == scatters
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == (0 if kernel_outputs is None else 1)
    for call in calls:
        assert len(call.outvars) == kernel_outputs
        aliases = dict(call.params["input_output_aliases"])
        assert sorted(aliases.values()) == [1, 2, 3, 4]
        for i, o in aliases.items():
            assert call.invars[i].aval.shape == call.outvars[o].aval.shape
            assert call.invars[i].aval.dtype == call.outvars[o].aval.dtype
    row_write = srv.metrics.summary()["kv_backend"]["row_write"]
    assert row_write == ("scatter" if kernel_outputs is None else "kernel")
    srv.close()
    consumer.close()


@pytest.mark.parametrize("variant", list(_TICK_VARIANTS))
def test_carried_tick_equals_xs_ys_reference(variant):
    """Identity: one block of ticks from a ragged state (slots at different
    positions, one of them idle) gives bit-identical gen, pos, done, counts
    and pool to the xs/ys formulation above. The idle slot's pool: the
    reference writes a stale row at its frozen position every tick; the
    dense int8 kernel, which owns its row write, leaves a slot that is not
    live as it was (PR 47)."""
    srv, consumer = _tick_server(variant)
    B = 4
    rng = np.random.default_rng(11)
    prompts = jnp.asarray(rng.integers(0, VOCAB, (B, P)), jnp.int32)
    state = srv._admit_fn(
        srv._caches, srv._last_tok, srv._pos, srv._gen, prompts,
        jnp.ones((B,), bool), srv._slot_keys,
    )
    # A first block with two slots idle leaves the watermarks ragged.
    tick = jax.jit(srv._tick_block_raw)
    state = tick(
        srv._params, *state, jnp.asarray([True, False, True, False]),
        srv._slot_keys,
    )[:4]
    active = jnp.asarray([True, True, False, True])
    got = tick(srv._params, *state, active, srv._slot_keys)
    before = jax.tree.map(np.asarray, state[0])  # the tick donates it
    ref = jax.jit(
        lambda *a: _ref_tick_block(srv, *a)
    )(srv._params, *state, active)
    if srv._kv_kernel:
        idle = jnp.asarray(~np.asarray(active))
        ref = (tuple(
            jnp.where(idle.reshape(1, B, *[1] * (c.ndim - 2)), old, c)
            for c, old in zip(ref[0], before)
        ), *ref[1:])
    assert sorted(np.asarray(state[2]).tolist()) != [P] * B  # ragged indeed
    for name, a, b in zip(
        ("caches", "last_tok", "pos", "gen", "done", "n_out"), got, ref
    ):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y), err_msg=f"{variant}: {name}"
            )
    assert int(np.asarray(got[2]).max()) > P  # the block did decode
    srv.close()
    consumer.close()


# ----------------------------------------------------------------------
# The dense admission prefills only the rows it admits (PR 28): the admitted
# slots in chunks of R rows, each chunk's prompt rows written into the pool
# in place. Below, the formulation it replaced — every slot prefilled, the
# admitted ones merged in by a select over the whole pool — kept as the
# reference the chunked admission must equal bit for bit; it lives in this
# file only.


def _ref_admit(srv, params, caches, last_tok, pos, gen, prompts, admit_mask,
               keys):
    """``serve.py::_build::admit`` as it stood before PR 28."""
    from jax import lax

    from torchkafka_tpu.models.generate import prefill
    from torchkafka_tpu.kvcache.slot_pool import _quant_kv
    from torchkafka_tpu.serve import _pick_slots

    cfg, P_, B = srv._cfg, srv._prompt_len, srv._slots
    logits, fresh = prefill(params, cfg, prompts, srv._max_len, srv._mesh)
    sel = admit_mask[None, :, None, None, None]
    sel4 = admit_mask[None, :, None, None]
    if cfg.is_mla:
        (pool,) = caches
        rows = jnp.where(sel4, fresh, lax.slice_in_dim(pool, 0, P_, axis=2))
        caches = (lax.dynamic_update_slice(pool, rows, (0, 0, 0, 0)),)
    elif srv._kv_int8:
        fkq, fks = _quant_kv(fresh.k)
        fvq, fvs = _quant_kv(fresh.v)
        if srv._kv_kernel:
            fkq, fvq = (jnp.swapaxes(a, 2, 3) for a in (fkq, fvq))
            fks, fvs = (jnp.swapaxes(a, 2, 3) for a in (fks, fvs))
        caches = (
            jnp.where(sel, fkq, caches[0]), jnp.where(sel4, fks, caches[1]),
            jnp.where(sel, fvq, caches[2]), jnp.where(sel4, fvs, caches[3]),
        )
    else:
        caches = (
            jnp.where(sel, fresh.k, caches[0]),
            jnp.where(sel, fresh.v, caches[1]),
        )
    tok0 = _pick_slots(
        logits, keys, jnp.zeros((B,), jnp.int32),
        temperature=srv._temperature, top_k=srv._top_k, top_p=srv._top_p,
    )
    last_tok = jnp.where(admit_mask, tok0, last_tok)
    pos = jnp.where(admit_mask, P_, pos)
    gen = jnp.where(admit_mask[:, None], 0, gen)
    gen = gen.at[:, 0].set(jnp.where(admit_mask, tok0, gen[:, 0]))
    return caches, last_tok, pos, gen


_ADMIT_VARIANTS = [
    "bf16", "bf16-sampled", "int8", "int8-kernel", "latent", "int8-mesh",
]
_ADMIT_MASKS = {
    # admitted rows: the mask (R = 2 of 4 slots: 3 rows pad the last chunk)
    0: [False, False, False, False],
    1: [False, False, True, False],
    3: [True, False, True, True],
    4: [True, True, True, True],
}


@pytest.fixture(scope="module", params=_ADMIT_VARIANTS)
def admit_case(request):
    """A toy server of the variant whose admission walks chunks of 2 rows
    (the chunk constant patched while it is built: toy shapes give it every
    slot otherwise), its pool mid-generation in every slot at ragged
    positions, and the masked merge compiled beside it."""
    from torchkafka_tpu import serve
    from torchkafka_tpu.ops import moe

    variant = request.param
    kw = {"temperature": 0.8, "top_k": 8} if variant in _SAMPLED_VARIANTS else {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serve, "_ADMIT_CHUNK_TOKENS", 2 * P)
        # The routed layer's grouped form for the merge's 4 rows and a
        # chunk's 2 alike (ops/moe.py picks it by the pairs an expert can
        # expect, which toy shapes never reach), as both take it at the
        # sizes that are served: it multiplies row by row, so the two
        # admissions can be held to the bit; the all-experts einsum rounds
        # by its row count on the CPU.
        mp.setattr(moe, "_GROUPED_MIN_PAIRS_PER_EXPERT", 0)
        srv, consumer = _tick_server(variant, latent_over={"n_experts": 4}, **kw)
        assert srv._admit_chunk_rows == 2
        B = 4
        rng = np.random.default_rng(5)
        keys = jnp.asarray(
            rng.integers(0, 2**32, srv._slot_keys.shape, dtype=np.uint32)
        )
        state = srv._admit_fn(
            srv._caches, srv._last_tok, srv._pos, srv._gen,
            jnp.asarray(rng.integers(0, VOCAB, (B, P)), jnp.int32),
            jnp.ones((B,), bool), keys,
        )
        state = jax.jit(srv._tick_block_raw)(
            srv._params, *state, jnp.asarray([True, False, True, True]), keys,
        )[:4]
    shardings = jax.tree.map(lambda a: a.sharding, state)
    before = jax.tree.map(np.asarray, state)
    ref = jax.jit(lambda *a: _ref_admit(srv, *a))
    yield srv, keys, before, shardings, ref
    srv.close()
    consumer.close()


def _assert_admission(srv, got, want, before, mask, exact=True):
    """``got`` against the masked merge's ``want`` from the state ``before``:
    the slot vectors whole, an admitted slot's pool rows [0, P); every
    other row of the pool against what it was."""
    for name, a, b in zip(("last_tok", "pos", "gen"), got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    assert np.asarray(got[2])[mask].tolist() == [P] * int(mask.sum())

    def by_position(a):  # [L, B, M, ...] whatever the pool's layout
        return np.swapaxes(np.asarray(a), 2, 3) if srv._kv_kernel else np.asarray(a)

    for new, old, merged in zip(got[0], before[0], want[0]):
        new, old, merged = by_position(new), by_position(old), by_position(merged)
        if exact:
            np.testing.assert_array_equal(new[:, mask, :P], merged[:, mask, :P])
        else:
            np.testing.assert_allclose(
                new[:, mask, :P], merged[:, mask, :P], rtol=1e-5, atol=1e-6
            )
        np.testing.assert_array_equal(new[:, mask, P:], old[:, mask, P:])
        np.testing.assert_array_equal(new[:, ~mask], old[:, ~mask])
        if mask.any():  # the admission did write rows that differ
            assert (new[:, mask, :P] != old[:, mask, :P]).any()


@pytest.mark.parametrize("admitted", list(_ADMIT_MASKS))
def test_chunked_admission_equals_masked_merge(admit_case, admitted, monkeypatch):
    """Identity: ``last_tok``, ``pos``, ``gen`` and every admitted slot's
    pool rows [0, P) are bit-identical to the masked merge's; every other
    slot's rows, and an admitted slot's rows past its prompt window, are
    what they were.

    The latent pool's compiled rows are held to a rounding, not to the bit:
    XLA's CPU backend contracts the softmax's scale and subtraction in
    ``mla.attend_full`` differently inside a while body than outside one
    (the last bit of a row's attention, whatever the chunk holds). That the
    two admissions are the same arithmetic is held op by op instead, with
    the compiler out of the way, on the mask whose last chunk is padded."""
    from torchkafka_tpu.ops import moe

    # As ``admit_case`` built it: the merge and the op-by-op pass trace here.
    monkeypatch.setattr(moe, "_GROUPED_MIN_PAIRS_PER_EXPERT", 0)
    srv, keys, before, shardings, ref = admit_case
    mask = np.asarray(_ADMIT_MASKS[admitted])
    prompts = jnp.asarray(
        np.random.default_rng(admitted).integers(0, VOCAB, (4, P)), jnp.int32
    )
    assert len(set(before[2].tolist())) > 1  # mid-generation, ragged
    args = (prompts, jnp.asarray(mask), keys)
    want = ref(srv._params, *jax.device_put(before, shardings), *args)
    got = srv._admit_fn(*jax.device_put(before, shardings), *args)
    latent = srv._cfg.is_mla
    _assert_admission(srv, got, want, before, mask, exact=not latent)
    if latent and admitted == 3:
        with jax.disable_jit():
            state = jax.tree.map(jnp.asarray, before)
            want = _ref_admit(srv, srv._params, *state, *args)
            got = srv._admit_fn(*state, *args)
        _assert_admission(srv, got, want, before, mask)


def test_admission_carries_the_pool_and_selects_nothing_of_its_shape(admit_case):
    """Structure: in the admission's jaxpr the pool is the carry of the
    chunk loop, whose trip count is a value of the mask, and of nothing
    else at the top level; no select anywhere takes an operand of the
    pool's shape (the masked merge copied the pool whole, five times)."""
    srv, keys, before, _shardings, ref = admit_case
    args = (
        srv._params, *before, jnp.zeros((4, P), jnp.int32),
        jnp.ones((4,), bool), keys,
    )
    pool_shapes = {c.shape for c in before[0]}

    def pool_selects(fn):
        return [
            e for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "select_n"
            and any(v.aval.shape in pool_shapes for v in e.invars)
        ]

    admit = next(  # the jitted program itself
        c.cell_contents for c in srv._admit_fn.__closure__
        if hasattr(c.cell_contents, "lower")
    )
    jaxpr = jax.make_jaxpr(admit)(*args).jaxpr
    (call,) = [e for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit")]
    top = call.params["jaxpr"].jaxpr
    users = [
        e for e in top.eqns
        if any(getattr(v, "aval", None) is not None
               and v.aval.shape in pool_shapes for v in e.invars)
        and e.primitive.name != "sharding_constraint"
    ]
    # A while, not a scan: the trip count is a value of the mask.
    assert [e.primitive.name for e in users] == ["while"]
    (loop,) = users
    n_pool = len(before[0])
    assert sum(v.aval.shape in pool_shapes for v in loop.invars) == n_pool
    assert sum(v.aval.shape in pool_shapes for v in loop.outvars) == n_pool
    assert not pool_selects(admit)
    if not srv._cfg.is_mla:  # (the latent merge selected over the window)
        assert pool_selects(ref)  # the reference is what it says


# ------------------------------------------------ the latent pool, served


def _greedy_by_full_forward(cfg, params, prompts, n):
    """What a served request must read: each next token the argmax of the
    model's own full forward over the prompt and the tokens so far
    (``generate``'s lockstep decode is not taught latent attention)."""
    from torchkafka_tpu.models.transformer import Transformer

    model = jax.jit(Transformer(cfg).__call__)
    seqs = np.asarray(prompts)
    for _ in range(n):
        nxt = np.asarray(jnp.argmax(model(params, jnp.asarray(seqs))[:, -1], -1))
        seqs = np.concatenate([seqs, nxt[:, None].astype(np.int32)], axis=1)
    return seqs[:, prompts.shape[1]:]


@pytest.mark.parametrize("variant", list(_LATENT_VARIANTS))
def test_latent_model_served_tokens_and_commit_watermark(variant):
    """The tiny MLA + expert model through ``run()`` as any other: every
    served token is the full forward's greedy choice (float32: exact),
    every completion is committed by the last flush, and the expert and
    pool counters count what was served."""
    _dt, kv_kernel, _axes = _LATENT_VARIANTS[variant]
    cfg = _latent_cfg()
    params = init_params(jax.random.key(0), cfg)
    broker = tk.InMemoryBroker()
    prompts = _topic(broker, 10)
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    server = StreamingGenerator(
        consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
        commit_every=4, ticks_per_sync=3, kv_kernel=kv_kernel,
    )
    assert server.metrics.summary()["kv_backend"]["layout"] == "latent"
    expected = _greedy_by_full_forward(cfg, params, prompts, MAX_NEW)
    got = {}
    for rec, toks in server.run(max_records=10):
        got[(rec.partition, rec.offset)] = toks
    assert len(got) == 10
    for (part, off), toks in got.items():
        np.testing.assert_array_equal(toks, expected[2 * off + part])
    for p in (0, 1):
        assert broker.committed("g", tk.TopicPartition("p", p)) == 5
    s = server.metrics.summary()
    served_ticks = s["scheduler"]["slot_ticks_served"]
    assert served_ticks == 10 * (MAX_NEW - 1)
    # Two expert layers, two choices a token a layer.
    assert s["expert_layer"]["moe_assignments"] == served_ticks * 2 * 2
    load = s["expert_layer"]["moe_expert_load"]
    assert len(load) == 8 and sum(load) >= s["expert_layer"]["moe_assignments"]
    assert 0 < s["expert_layer"]["moe_experts_touched"] <= sum(load)
    # The tick of token j reads P + j rows, in each of three layers.
    need = 10 * 3 * sum(P + j for j in range(1, MAX_NEW))
    assert s["latent_pool"]["latent_positions_valid"] == need
    assert s["latent_pool"]["latent_positions_read"] >= need
    consumer.close()


def test_the_admissions_grouped_matmul_is_counted_by_hand():
    """A window of 32 tokens: a trip of 4 rows is 256 pairs over 8 experts,
    32 an expert, so the admission takes the grouped form (its kernels)
    and a tick of 4 rows, one pair an expert, does not. Ten records through
    4 slots: admissions of 4, 4 and 2 rows, each one trip of 4; two expert
    layers, two choices a token. The counts ride the next sync's fetch."""
    from torchkafka_tpu.ops import moe

    window = 32
    cfg = _latent_cfg(max_seq_len=window + MAX_NEW)
    params = init_params(jax.random.key(0), cfg)
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=2)
    rng = np.random.default_rng(7)
    for i in range(10):
        broker.produce(
            "p", rng.integers(0, VOCAB, (window,), dtype=np.int32).tobytes(),
            partition=i % 2,
        )
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    server = StreamingGenerator(
        consumer, params, cfg, slots=4, prompt_len=window, max_new=MAX_NEW,
        commit_every=4, ticks_per_sync=3,
    )
    assert sum(1 for _ in server.run(max_records=10)) == 10
    s = server.metrics.summary()
    assert s["scheduler"]["admit_rows_prefilled"] == 12
    experts = s["expert_layer"]
    assert experts["grouped_matmul"] == "kernel"
    assert experts["moe_grouped_rows"] == 12 * window * 2 * 2
    # Pieces of 128 rows: a trip's 256 pairs a layer are 2 of them, and 8
    # experts' runs touch 9 at the most.
    _tm, ts = moe._gmm_rows(4 * window * 2)
    assert ts == 128 and experts["moe_grouped_tile_rows"] % ts == 0
    assert (
        experts["moe_grouped_rows"] <= experts["moe_grouped_tile_rows"]
        <= 3 * 2 * (2 + 8 - 1) * ts
    )
    assert server._admit_stats == []  # fetched with the last sync
    text = server.metrics.render_prometheus()
    assert f"moe_grouped_rows_total {experts['moe_grouped_rows']}\n" in text
    consumer.close()
    # A window of 8: 64 pairs a trip, 8 an expert. No grouped form in the
    # admit program, no count, no further output.
    small = StreamingGenerator(
        tk.MemoryConsumer(broker, "p", group_id="g2"), params, cfg, slots=4,
        prompt_len=P, max_new=MAX_NEW, ticks_per_sync=3,
    )
    out = small._admit_fn(
        small._caches, small._last_tok, small._pos, small._gen,
        jnp.zeros((4, P), jnp.int32), jnp.ones((4,), bool), small._slot_keys,
    )
    assert len(out) == 4 and small._admit_stats == []
    got = small.metrics.summary()["expert_layer"]
    assert got["grouped_matmul"] is None and got["moe_grouped_rows"] == 0
    small.close()


def test_a_tick_grouped_by_its_own_shapes_serves_the_reference_tokens():
    """Kinds of layer over stacks of every layer's experts, as Mellum2's:
    slots enough that a tick's pairs reach the threshold an expert, so the
    tick sums its experts by the grouped kernels by its own static shapes
    (nothing patched where the server is built or run) and says so. In
    float32 every served token is the greedy choice of the full forward,
    which is traced with the threshold out of reach: its experts go
    through the tile loop and no kernel."""
    from torchkafka_tpu.models.transformer import RopeKind
    from torchkafka_tpu.ops import moe

    experts, top_k = 4, 2
    slots = moe._GROUPED_MIN_PAIRS_OUT_OF_STACKS * experts // top_k
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=48, max_seq_len=P + MAX_NEW, dtype=jnp.float32,
        stated_head_dim=16, sliding_window=4,
        window_pattern=(True, True, True, False), rope_theta=500000.0,
        rope_full=RopeKind(
            500000.0, factor=16.0, original_len=64, attention_factor=1.2773,
        ),
        n_experts=experts, expert_top_k=top_k, expert_d_ff=24,
        router_score="softmax", norm_topk=True,
    )
    params = init_params(jax.random.key(0), cfg)
    broker = tk.InMemoryBroker()
    prompts = _topic(broker, 10)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "_GROUPED_MIN_PAIRS_PER_EXPERT", 10**9)
        mp.setattr(moe, "_GROUPED_MIN_PAIRS_OUT_OF_STACKS", 10**9)
        expected = _greedy_by_full_forward(cfg, params, prompts, MAX_NEW)
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    server = StreamingGenerator(
        consumer, params, cfg, slots=slots, prompt_len=P, max_new=MAX_NEW,
        commit_every=4, ticks_per_sync=3,
    )
    summary = server.metrics.summary()
    assert summary["kv_backend"]["layout"] == "by_kind"
    assert summary["expert_layer"]["tick_form"] == "grouped"
    eqns = _eqns(jax.make_jaxpr(server._tick_block_raw)(
        server._params, server._caches, server._last_tok, server._pos,
        server._gen, jnp.ones((slots,), bool), server._slot_keys,
    ).jaxpr)
    calls = [e.params["name"] for e in eqns if e.primitive.name == "pallas_call"]
    # One scan body holds a period's four layers.
    assert calls == ["tk_gmm_gate_up", "tk_gmm_down"] * 4
    got = dict(
        ((rec.partition, rec.offset), toks)
        for rec, toks in server.run(max_records=10)
    )
    assert len(got) == 10
    for (part, off), toks in got.items():
        np.testing.assert_array_equal(toks, expected[2 * off + part])
    served = server.metrics.summary()["scheduler"]["slot_ticks_served"]
    assert served == 10 * (MAX_NEW - 1)
    consumer.close()
    # One slot fewer: under the threshold, the tile loop, and it says so.
    few = StreamingGenerator(
        tk.MemoryConsumer(broker, "p", group_id="g2"), params, cfg,
        slots=slots - 1, prompt_len=P, max_new=MAX_NEW, ticks_per_sync=3,
    )
    assert few.metrics.summary()["expert_layer"]["tick_form"] == "compacted"
    few.close()


def test_latent_model_crash_before_commit_redelivers_unfinished():
    cfg = _latent_cfg()
    params = init_params(jax.random.key(0), cfg)
    broker = tk.InMemoryBroker()
    _topic(broker, 8)
    consumer = tk.MemoryConsumer(broker, "p", group_id="g3")
    server = StreamingGenerator(
        consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
        commit_every=2,
    )
    finished = []
    for rec, _toks in server.run(max_records=8):
        finished.append((rec.partition, rec.offset))
        if len(finished) == 4:
            break  # crash: nothing flushes what finished since the commit
    consumer.close()
    committed = {
        p: broker.committed("g3", tk.TopicPartition("p", p)) or 0 for p in (0, 1)
    }
    assert 2 <= sum(committed.values()) <= 4
    again = tk.MemoryConsumer(broker, "p", group_id="g3")
    seen = {(r.partition, r.offset) for r in again.poll(max_records=8)}
    assert seen == {
        (p, o) for p in (0, 1) for o in range(committed[p], 4)
    }
    again.close()


# ----------------------------------------------------------------------
# The answer budget on the device (PR 47): a slot latches done at its
# request's budget inside the tick block, as at EOS or a full buffer, so no
# tick decodes a token the host would cut, and the dense int8 kernel
# fetches nothing for the slot from then on. The served tokens do not
# depend on it: the same tokens at any sync cadence, cut at the same budget.

_BUDGET_NEW, _BUDGET_EOS, _BUDGET_RECORDS = 16, 5, 14
_BUDGET_VARIANTS = {
    # name: (kv_dtype, kv_kernel, paged)
    "bf16": (None, "auto", False),
    "int8": ("int8", False, False),
    "int8-kernel": ("int8", True, False),
    "paged": (None, "auto", True),  # _build_paged's programs
}


def _heavy_tailed_budgets(cap=None):
    """Lognormal answer budgets in 1.._BUDGET_NEW (most short, a few the
    whole buffer), one a record; ``cap`` shortens every one."""
    draws = np.random.default_rng(47).lognormal(np.log(4), 0.8, _BUDGET_RECORDS)
    budgets = np.clip(np.rint(draws), 1, _BUDGET_NEW).astype(int)
    budgets[:2] = (1, _BUDGET_NEW)  # the two ends, whatever the draw
    return np.minimum(budgets, cap) if cap else budgets


@functools.lru_cache(maxsize=None)
def _budget_run(variant, ticks, cap=None):
    """Serve the records under their budgets; what was published, what
    the device handed the host at every sync, and the meters."""
    from torchkafka_tpu.workload import header_max_new

    kv_dtype, kv_kernel, paged = _BUDGET_VARIANTS[variant]
    m = P + _BUDGET_NEW
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=256, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq_len=m, dtype=jnp.float32,
    )
    params = init_params(jax.random.key(0), cfg)
    broker = tk.InMemoryBroker()
    broker.create_topic("b", partitions=1)
    rng = np.random.default_rng(3)
    budgets = _heavy_tailed_budgets(cap)
    for budget in budgets:
        broker.produce(
            "b", rng.integers(0, VOCAB, P, dtype=np.int32).tobytes(),
            headers=(("max_new", str(budget).encode()),),
        )
    consumer = tk.MemoryConsumer(broker, "b", group_id=f"g-{variant}-{ticks}")
    kw = {"kv_pages": {"block_size": 4, "num_blocks": 4 * -(-m // 4) + 16}}
    srv = StreamingGenerator(
        consumer, params, cfg, slots=4, prompt_len=P, max_new=_BUDGET_NEW,
        ticks_per_sync=ticks, eos_id=_BUDGET_EOS, kv_dtype=kv_dtype,
        kv_kernel=kv_kernel, max_new_of=header_max_new, commit_every=4,
        **(kw if paged else {}),
    )
    assert (srv._kv_pages is not None) is paged
    handed = []  # (tokens by the device's count, budget) a slot a sync
    retire = srv._retire_block

    def spy(done_h, n_out_h, gen_h, pos_h, *rest):
        for i in np.nonzero(srv._active)[0]:
            count = int(n_out_h[i] if done_h[i] else pos_h[i] - P + 1)
            handed.append((count, bool(done_h[i]), int(srv._slot_budget[i])))
        return retire(done_h, n_out_h, gen_h, pos_h, *rest)

    srv._retire_block = spy
    out = {
        rec.offset: np.asarray(toks)
        for rec, toks in srv.run(max_records=_BUDGET_RECORDS)
    }
    summary = srv.metrics.summary()
    srv.close()
    consumer.close()
    return {
        "out": out, "budgets": budgets, "handed": handed, "summary": summary,
    }


@pytest.mark.parametrize("variant", list(_BUDGET_VARIANTS))
def test_budget_same_tokens_at_any_sync_cadence(variant):
    """Blocks of 8 ticks (a slot may finish seven ticks before its sync)
    and of 1 publish the same tokens for every record, each within its
    budget and cut at it unless an EOS came first."""
    wide, narrow = _budget_run(variant, 8), _budget_run(variant, 1)
    assert sorted(wide["out"]) == list(range(_BUDGET_RECORDS))
    for off, toks in wide["out"].items():
        np.testing.assert_array_equal(toks, narrow["out"][off], err_msg=str(off))
        budget = wide["budgets"][off]
        assert 1 <= len(toks) <= budget
        assert len(toks) == budget or toks[-1] == _BUDGET_EOS


@pytest.mark.parametrize("ticks", [8, 1])
@pytest.mark.parametrize("variant", list(_BUDGET_VARIANTS))
def test_budget_is_latched_on_the_device(variant, ticks):
    """``done`` and ``n_out`` come back at the budget: at no sync does the
    device hand the host more tokens than the slot's budget (the host's
    clamp finds nothing to cut), and a slot that reached it is done."""
    run = _budget_run(variant, ticks)
    assert run["handed"]
    for count, done, budget in run["handed"]:
        assert count <= budget
        assert done or count < budget
    # Capped: finished by a budget under the buffer, and not by an EOS.
    capped = sum(
        len(toks) == run["budgets"][off] < _BUDGET_NEW
        and toks[-1] != _BUDGET_EOS
        for off, toks in run["out"].items()
    )
    assert capped > 0
    assert run["summary"]["output_capped"] == capped
    served = run["summary"]["scheduler"]["slot_ticks_served"]
    assert served == sum(len(t) - 1 for t in run["out"].values())


@pytest.mark.parametrize("variant", ["int8", "int8-kernel"])
def test_dense_int8_pool_counts_rows_needed_and_fetched(variant):
    """``full_positions_valid`` / ``_read`` of the dense int8 pool: the rows
    the served ticks needed and the rows the read fetched for them, a
    layer. Needed never passes fetched, and both fall with the budgets.
    The kernel fetches whole blocks (of 8 here: ``dynlen_block(24)``) for
    live slot-ticks alone; XLA's read the slab of every slot, every tick."""
    long, short = (_budget_run(variant, 8, cap)["summary"] for cap in (None, 3))
    for s in (long, short):
        pool = s["kv_pool"]
        assert pool["read"] == ("kernel" if variant == "int8-kernel" else "xla")
        assert pool["full_layers"] == 2
        assert 0 < pool["full_positions_valid"] <= pool["full_positions_read"]
    read = [s["kv_pool"]["full_positions_read"] for s in (short, long)]
    # As many blocks of 8 ticks either way: XLA's read cannot fall here.
    assert read[0] < read[1] if variant == "int8-kernel" else read[0] == read[1]
    assert short["kv_pool"]["full_positions_valid"] < long["kv_pool"]["full_positions_valid"]
    if variant == "int8-kernel":
        # Every fetched row a whole block's, of a tick that served a token
        # (or latched a budget of one): by the published lengths.
        run = _budget_run(variant, 8)
        block = run["summary"]["kv_pool"]["block"]
        assert block == 8
        rows = sum(
            -(-(P + j) // block) * block
            for toks in run["out"].values()
            for j in range(1, max(len(toks), 2))
        )
        assert run["summary"]["kv_pool"]["full_positions_read"] == 2 * rows
