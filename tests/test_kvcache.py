"""Paged KV-cache pool with radix-tree prefix reuse (torchkafka_tpu/kvcache,
serve.py kv_pages=, ops/kvattn block-table attention).

Pins the subsystem's three contracts:

1. HOST INVARIANTS — allocator refcounts never go negative, blocks are
   conserved (free + live == usable) through random admit/release/evict
   schedules, evicted blocks return to the free list, and the radix match
   equals a brute-force longest-prefix reference (property tests).
2. TOKEN EXACTNESS — cache-on serving (plain and speculative) emits
   byte-identical tokens and a byte-identical commit ledger vs the
   cache-off server, for greedy and seeded sampling, under allocator
   pressure (deferred admissions), and under seeded replica-kill chaos
   through a 2-replica fleet. Eviction is advisory: exactness never
   depends on what the cache holds.
3. STALE-TAIL SAFETY — the serve.py docstring's recycling hazard as an
   asserted invariant: after a slot/block is recycled, every cache
   position that is not yet readable is POISONED with garbage and the
   outputs must not change, on both the dense pool and the paged one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchkafka_tpu as tk
from torchkafka_tpu.kvcache import SINK_BLOCK, BlockAllocator, PagedKVConfig, RadixCache
from torchkafka_tpu.models.generate import generate
from torchkafka_tpu.models.transformer import TransformerConfig, init_params
from torchkafka_tpu.serve import StreamingGenerator
from torchkafka_tpu.serve_spec import SpecStreamingGenerator

P, MAX_NEW, VOCAB, BS = 8, 8, 64, 4
PAGES = {"block_size": BS, "num_blocks": 40}


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=P + MAX_NEW, dtype=jnp.float32,
    )
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


def _prompts(n, shared_prefix_len=5, seed=7):
    """n prompts sharing their first ``shared_prefix_len`` tokens — the
    multi-tenant system-prompt shape the radix tree exists for."""
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, VOCAB, (n, P), dtype=np.int32)
    if shared_prefix_len:
        prompts[:, :shared_prefix_len] = np.arange(
            shared_prefix_len, dtype=np.int32
        )
    return prompts


def _topic(broker, prompts):
    broker.create_topic("p", partitions=2)
    for i in range(prompts.shape[0]):
        broker.produce("p", prompts[i].tobytes(), partition=i % 2)


def _serve(cfg, params, prompts, cls=StreamingGenerator, **kw):
    broker = tk.InMemoryBroker()
    _topic(broker, prompts)
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    server = cls(
        consumer, params, cfg, slots=4, prompt_len=P, max_new=MAX_NEW,
        commit_every=4, **kw,
    )
    out = {}
    for rec, toks in server.run(max_records=prompts.shape[0]):
        out[(rec.partition, rec.offset)] = np.asarray(toks)
    committed = {
        pt: broker.committed("g", tk.TopicPartition("p", pt)) for pt in (0, 1)
    }
    consumer.close()
    return out, committed, server


class TestBlockAllocator:
    def test_alloc_free_conservation(self):
        a = BlockAllocator(9)
        assert a.usable == 8 and a.available() == 8
        got = a.alloc(3)
        assert sorted(got) == [1, 2, 3] and SINK_BLOCK not in got
        assert a.available() == 5 and a.allocated() == 3
        assert a.alloc(6) is None and a.available() == 5  # all-or-nothing
        a.incref(got)
        assert a.decref(got) == []  # still referenced
        assert a.decref(got) == got  # now free
        assert a.available() == 8

    def test_refcount_underflow_raises(self):
        a = BlockAllocator(4)
        (b,) = a.alloc(1)
        a.decref([b])
        with pytest.raises(ValueError, match="decref on free block"):
            a.decref([b])
        with pytest.raises(ValueError, match="sink"):
            a.incref([SINK_BLOCK])

    def test_config_validation(self):
        with pytest.raises(ValueError, match="block_size"):
            PagedKVConfig(block_size=0, num_blocks=8)
        with pytest.raises(ValueError, match="num_blocks"):
            PagedKVConfig(block_size=4, num_blocks=1)
        assert PagedKVConfig(4, 8).blocks_per_slot(10) == 3


class TestRadixCache:
    """Property tests over random admit/release schedules against a
    brute-force reference trie."""

    def _reference_match(self, ref, toks, bs):
        out = []
        cap = RadixCache.matchable_blocks(len(toks), bs)
        for j in range(cap):
            path = tuple(int(t) for t in toks[: (j + 1) * bs])
            if path not in ref:
                break
            out.append(ref[path])
        return out

    def test_match_insert_property(self):
        bs, nblk = 4, P // 4
        alloc = BlockAllocator(256)
        radix = RadixCache(alloc, bs)
        ref: dict[tuple, int] = {}
        rng = np.random.default_rng(1)
        families = _prompts(6, shared_prefix_len=4, seed=3)
        live: list[list[int]] = []
        for _ in range(200):
            if live and rng.random() < 0.4:
                alloc.decref(live.pop(rng.integers(len(live))))
                continue
            toks = families[rng.integers(len(families))].copy()
            if rng.random() < 0.5:  # mutate the tail: partial-prefix hits
                toks[rng.integers(4, P):] = rng.integers(0, VOCAB)
            matched = radix.match(toks)
            assert matched == self._reference_match(ref, toks, bs)
            priv = alloc.alloc(nblk - len(matched))
            assert priv is not None
            row = matched + priv
            cap = RadixCache.matchable_blocks(len(toks), bs)
            radix.insert(toks, row[:cap])
            for j in range(cap):
                ref[tuple(int(t) for t in toks[: (j + 1) * bs])] = row[j]
            live.append(row)
            # Conservation: every usable block is either free or carries
            # at least one reference.
            held = sum(
                1 for b in range(1, alloc.num_blocks) if alloc.refcount(b) > 0
            )
            assert alloc.available() + held == alloc.usable
            # Refcounts equal tree-holds + slot-holds exactly.
            for b in range(1, alloc.num_blocks):
                expect = (b in ref.values()) + sum(r.count(b) for r in live)
                assert alloc.refcount(b) == expect, b

    def test_evict_returns_blocks_and_is_advisory(self):
        bs = 4
        alloc = BlockAllocator(32)
        radix = RadixCache(alloc, bs)
        # Distinct families: each prompt caches its own first block.
        prompts = _prompts(5, shared_prefix_len=0, seed=9)
        for toks in prompts:
            matched = radix.match(toks)
            priv = alloc.alloc(P // bs - len(matched))
            row = matched + priv
            cap = RadixCache.matchable_blocks(len(toks), bs)
            radix.insert(toks, row[:cap])
            alloc.decref(row)  # slot retires immediately
        cached = radix.cached_blocks
        assert cached > 0 and alloc.allocated() == cached
        before = alloc.available()
        freed = radix.evict(2)
        assert freed == 2 and alloc.available() == before + 2
        # Full eviction empties the tree and the pool is whole again.
        radix.evict(alloc.usable)
        assert radix.cached_blocks == 0
        assert alloc.available() == alloc.usable
        # Advisory: a miss after eviction just means no shared blocks.
        assert radix.match(prompts[0]) == []
        assert alloc.alloc(alloc.usable) is not None  # all blocks reusable

    def test_lru_eviction_order(self):
        bs = 4
        alloc = BlockAllocator(32)
        radix = RadixCache(alloc, bs)
        a = np.arange(P, dtype=np.int32)
        b = np.arange(P, dtype=np.int32) + 8
        for toks in (a, b):
            priv = alloc.alloc(1)
            radix.insert(toks, priv)
            alloc.decref(priv)
        blk_a = radix.match(a)
        alloc.decref(blk_a)  # touch a: now b is LRU
        assert radix.evict(1) == 1
        assert radix.match(a) == [blk_a[0]] and radix.match(b) == []
        alloc.decref(blk_a)

    def test_pinned_leaves_never_evict(self):
        bs = 4
        alloc = BlockAllocator(32)
        radix = RadixCache(alloc, bs)
        toks = np.arange(P, dtype=np.int32)
        priv = alloc.alloc(1)
        radix.insert(toks, priv)  # slot ref still held (priv not decref'd)
        assert radix.evict(8) == 0  # pinned by the live slot
        alloc.decref(priv)
        assert radix.evict(8) == 1


class TestPagedServer:
    def test_token_exact_greedy_and_ledger(self, model):
        cfg, params = model
        prompts = _prompts(10)
        base, cb, _ = _serve(cfg, params, prompts)
        paged, cp, sp = _serve(cfg, params, prompts, kv_pages=PAGES)
        assert set(base) == set(paged)
        for k in base:
            np.testing.assert_array_equal(paged[k], base[k], err_msg=str(k))
        assert cp == cb  # commit ledger byte-identical
        pc = sp.metrics.cache_summary()
        assert pc["hits"] > 0 and pc["prefix_tokens_saved"] > 0
        assert pc["prefill_tokens"] < prompts.size  # measured savings

    def test_token_exact_seeded_sampling(self, model):
        cfg, params = model
        prompts = _prompts(8)
        kw = dict(temperature=0.9, top_k=16, rng=jax.random.key(11))
        base, cb, _ = _serve(cfg, params, prompts, **kw)
        paged, cp, _ = _serve(
            cfg, params, prompts, kv_pages=PAGES,
            temperature=0.9, top_k=16, rng=jax.random.key(11),
        )
        assert set(base) == set(paged)
        for k in base:
            np.testing.assert_array_equal(paged[k], base[k], err_msg=str(k))
        assert cp == cb

    def test_identical_prompts_cap_leaves_suffix(self, model):
        """A full-duplicate prompt matches at most prompt_len - 1 tokens
        (the last position must prefill to sample token 0) and still
        serves token-exact."""
        cfg, params = model
        prompts = np.tile(_prompts(1, shared_prefix_len=0), (6, 1))
        base, cb, _ = _serve(cfg, params, prompts)
        paged, cp, sp = _serve(cfg, params, prompts, kv_pages=PAGES)
        for k in base:
            np.testing.assert_array_equal(paged[k], base[k])
        assert cp == cb
        pc = sp.metrics.cache_summary()
        assert pc["hits"] == 5 and pc["misses"] == 1
        # 8-token prompts share (P-1)//BS = 1 whole block; every hit still
        # prefills the remaining P - BS tokens.
        assert pc["prefix_tokens_saved"] == 5 * BS
        assert pc["prefill_tokens"] == P + 5 * (P - BS)

    def test_allocator_exhaustion_defers_then_serves_exactly(self, model):
        """A pool holding ~1.5 slots' worth of blocks: admissions DEFER
        under pressure (never drop, never deadlock) and the output stays
        token-exact with the full commit ledger."""
        cfg, params = model
        prompts = _prompts(8)
        base, cb, _ = _serve(cfg, params, prompts)
        paged, cp, sp = _serve(
            cfg, params, prompts,
            kv_pages={"block_size": BS, "num_blocks": 7},
        )
        assert set(base) == set(paged)
        for k in base:
            np.testing.assert_array_equal(paged[k], base[k], err_msg=str(k))
        assert cp == cb
        assert sp.metrics.admission_deferrals.count > 0
        assert sp.pending_admissions == 0  # backlog fully drained

    def test_pool_too_small_falls_back_cache_off(self, model, caplog):
        """Graceful cache-off fallback: a pool that cannot hold even one
        slot serves DENSE (token-exact, full commits) instead of
        deadlocking, with the fallback counted and logged."""
        import logging

        caplog.set_level(logging.WARNING, logger="torchkafka_tpu.serve")
        cfg, params = model
        prompts = _prompts(6)
        base, cb, _ = _serve(cfg, params, prompts)
        paged, cp, sp = _serve(
            cfg, params, prompts,
            kv_pages={"block_size": BS, "num_blocks": 3},
        )
        for k in base:
            np.testing.assert_array_equal(paged[k], base[k])
        assert cp == cb
        assert sp.metrics.cache_fallbacks.count == 1
        assert sp._kv_pages is None  # dense build took over
        assert any("falling back" in r.message for r in caplog.records)

    def test_eviction_under_pressure_stays_exact(self, model):
        """Distinct prompt families through a pool with little cache
        headroom: cached prefixes get LRU-evicted to make room and the
        outputs stay exact — eviction is advisory."""
        cfg, params = model
        rng = np.random.default_rng(5)
        prompts = rng.integers(0, VOCAB, (10, P), dtype=np.int32)  # no overlap
        base, cb, _ = _serve(cfg, params, prompts)
        paged, cp, sp = _serve(
            cfg, params, prompts,
            # 4 slots x 4 blocks = 16 live worst case; 18 usable blocks
            # leaves 2 blocks of cache headroom -> eviction pressure.
            kv_pages={"block_size": BS, "num_blocks": 19},
        )
        for k in base:
            np.testing.assert_array_equal(paged[k], base[k], err_msg=str(k))
        assert cp == cb
        assert sp.metrics.cache_evictions.count > 0

    def test_spec_paged_token_exact(self, model):
        """Speculative serving over the paged pool: same tokens and
        ledger as the PLAIN dense server (the spec contract composed
        with the paging contract), acceptance counters live, prefix
        hits counted."""
        cfg, params = model
        prompts = _prompts(8)
        base, cb, _ = _serve(cfg, params, prompts)
        spec, cs, ss = _serve(
            cfg, params, prompts, cls=SpecStreamingGenerator, k=2,
            kv_pages={"block_size": BS, "num_blocks": 48},
        )
        assert set(base) == set(spec)
        for k in base:
            np.testing.assert_array_equal(spec[k], base[k], err_msg=str(k))
        assert cs == cb
        st = ss.spec_stats()
        assert st["proposed"] > 0 and st["acceptance"] is not None
        assert ss.metrics.cache_summary()["hits"] > 0

    def test_metrics_exposition_format(self, model):
        cfg, params = model
        prompts = _prompts(6)
        _, _, sp = _serve(cfg, params, prompts, kv_pages=PAGES)
        text = sp.metrics.render_prometheus()
        for name in (
            "prefix_cache_hits_total", "prefix_cache_misses_total",
            "prefix_tokens_saved_total", "prefill_tokens_total",
            "kvcache_evictions_total", "admission_deferrals_total",
            "kvcache_fallbacks_total", "prefix_cache_hit_rate",
            "kvcache_pool_occupancy",
        ):
            assert f"torchkafka_serve_{name}" in text, name
        for line in text.strip().split("\n"):
            if not line.startswith("#"):
                float(line.rsplit(" ", 1)[1])  # every sample parses
        s = sp.metrics.summary()["prefix_cache"]
        assert s["hits"] + s["misses"] == 6


def _chunk_pages(chunk, num_blocks=40):
    return {"block_size": BS, "num_blocks": num_blocks,
            "prefill_chunk": chunk}


class TestChunkedPrefill:
    """PR-6: chunked prefill fused into the decode tick. Admission
    enqueues uncached suffixes; every tick carries a bounded chunk of
    them alongside all decode slots in ONE static jitted program. Pins:

    - token-exactness + commit-ledger identity vs the DENSE server, across
      chunk widths {1 token, half a prompt, auto} and greedy / seeded
      sampling / speculative serving — each chunk query attends exactly
      [0, position] of its slot's view, so the math is bitwise identical
      at any width;
    - admission compiles O(1) programs across 50 mixed-suffix-length
      admissions;
    - the prompt-storm latency bound: 4x-oversubscribed admissions never
      add a single tick to any in-flight slot's inter-token gap, and the
      queue drains FIFO with no deferral starvation."""

    @pytest.fixture(scope="class")
    def runs(self, model):
        cfg, params = model
        prompts = _prompts(10)
        dense = _serve(cfg, params, prompts)
        # The radix work every chunk width must repeat: a fourth width
        # (one prompt a tick), served once.
        _, _, whole = _serve(cfg, params, prompts, kv_pages=_chunk_pages(P))
        return prompts, dense, whole.metrics.cache_summary()

    @pytest.mark.parametrize(
        "chunk", [1, P // 2, None], ids=["1tok", "half", "auto"]
    )
    def test_token_exact_vs_dense(self, model, runs, chunk):
        cfg, params = model
        prompts, (base, cb, _), ws = runs
        got, cg, sg = _serve(
            cfg, params, prompts, kv_pages=_chunk_pages(chunk)
        )
        assert set(got) == set(base)
        for k in base:
            np.testing.assert_array_equal(got[k], base[k], err_msg=str(k))
        assert cg == cb
        # Same radix work and the same total prefilled tokens at every
        # chunk width — only the dispatch structure changes.
        cs = sg.metrics.cache_summary()
        assert cs["prefill_tokens"] == ws["prefill_tokens"]
        assert cs["hits"] == ws["hits"] > 0
        assert sg.metrics.chunk_ticks.count > 0
        assert sg.pending_admissions == 0
        assert not sg._prefill_queue  # chunk queue fully drained

    def test_token_exact_seeded_sampling_chunked(self, model):
        cfg, params = model
        prompts = _prompts(8)
        kw = dict(temperature=0.9, top_k=16)
        base, cb, _ = _serve(cfg, params, prompts, rng=jax.random.key(11),
                             **kw)
        got, cg, _ = _serve(
            cfg, params, prompts, kv_pages=_chunk_pages(3),
            rng=jax.random.key(11), **kw,
        )
        for k in base:
            np.testing.assert_array_equal(got[k], base[k], err_msg=str(k))
        assert cg == cb

    def test_spec_rides_the_chunked_program(self, model):
        """Spec chunked serving: token-exact vs the plain DENSE server
        (the spec contract composed with chunking), admission compiled
        into the tick program."""
        cfg, params = model
        prompts = _prompts(8)
        base, cb, _ = _serve(cfg, params, prompts)
        spec, cs, ss = _serve(
            cfg, params, prompts, cls=SpecStreamingGenerator, k=2,
            kv_pages=_chunk_pages(5, num_blocks=48),
        )
        for k in base:
            np.testing.assert_array_equal(spec[k], base[k], err_msg=str(k))
        assert cs == cb
        assert ss.spec_stats()["proposed"] > 0
        assert ss.metrics.chunk_ticks.count > 0
        assert ss._tick_chunk_jit._cache_size() == 1

    def test_admission_compiles_o1_programs(self, model):
        """50 admissions with MIXED suffix lengths (varying radix match
        depths): the tick set stays at one program per role — the fused
        chunk tick and the decode-only tick — and the server holds no
        other jitted program that an admission could reach."""
        cfg, params = model
        rng = np.random.default_rng(3)
        fams = _prompts(4, shared_prefix_len=0, seed=13)
        rows = []
        for i in range(50):
            t = fams[i % 4].copy()
            cut = int(rng.integers(1, P))
            t[cut:] = rng.integers(0, VOCAB, P - cut, dtype=np.int32)
            rows.append(t)
        prompts = np.stack(rows)
        _, _, s = _serve(
            cfg, params, prompts, kv_pages=_chunk_pages(None, 160)
        )
        assert s._tick_chunk_jit._cache_size() == 1
        assert s._tick_jit._cache_size() <= 1
        assert s._admit_fn is None and not s._adopt_upload_jits

    def test_prompt_storm_decode_latency_bounded_and_fifo(self, model):
        """4x oversubscription with in-flight decode: a 1-block chunk
        width forces the storm to drain over many ticks, and every
        in-flight slot must still emit exactly one token per tick
        (completion_tick - activation_tick == tokens - 1: ZERO decode
        stall), while admissions activate in offer order (FIFO, no
        starvation) and the queue + deferrals drain to empty."""
        cfg, params = model
        n, slots = 16, 4
        prompts = _prompts(n, shared_prefix_len=0, seed=31)
        broker = tk.InMemoryBroker()
        _topic(broker, prompts)
        consumer = tk.MemoryConsumer(broker, "p", group_id="gstorm")

        activation: dict = {}
        act_order: list = []

        class Instrumented(StreamingGenerator):
            def _activate_chunk_finishers(self, finishers):
                for e, _row in finishers:
                    key = (e.rec.partition, e.rec.offset)
                    activation[key] = self._tick_counter
                    act_order.append(key)
                super()._activate_chunk_finishers(finishers)

        server = Instrumented(
            consumer, params, cfg, slots=slots, prompt_len=P,
            max_new=MAX_NEW, commit_every=4, ticks_per_sync=1,
            kv_pages=_chunk_pages(BS, num_blocks=80),
        )
        offered: list = []
        completion: dict = {}
        while len(completion) < n:
            room = server.free_slots() - server.pending_admissions
            recs = (
                consumer.poll(max_records=room, timeout_ms=0) if room else []
            )
            if recs:
                server.note_fetched(recs)
                offered.extend((r.partition, r.offset) for r in recs)
                server.admit_records(recs)
            elif server.pending_admissions and server.free_slots():
                server.admit_records([])
            for rec, toks in server.step():
                completion[(rec.partition, rec.offset)] = (
                    server._tick_counter, len(np.asarray(toks))
                )
        server.flush_commits()
        assert len(completion) == n
        # Decode never stalled: every record's decode span is exactly
        # its token count minus the admit-tick token 0.
        for key, (done_tick, n_toks) in completion.items():
            assert done_tick - activation[key] == n_toks - 1, key
        # FIFO activation, no starvation: offer order IS activation
        # order (deferred/queued admissions re-offer first).
        assert act_order == offered
        m = server.metrics
        assert m.admission_stall_ticks.count > 0  # the storm really queued
        assert not server._prefill_queue and server.pending_admissions == 0
        assert m.chunk_summary()["queue_tokens"] == 0
        consumer.close()

    def test_metrics_exposition_includes_chunk_counters(self, model):
        cfg, params = model
        prompts = _prompts(6)
        _, _, sp = _serve(cfg, params, prompts, kv_pages=_chunk_pages(3))
        text = sp.metrics.render_prometheus()
        for name in (
            "chunk_ticks_total", "admission_stall_ticks_total",
            "admission_queue_tokens", "chunk_utilization",
            "prefill_tokens_per_chunk_tick",
        ):
            assert f"torchkafka_serve_{name}" in text, name
        for line in text.strip().split("\n"):
            if not line.startswith("#"):
                float(line.rsplit(" ", 1)[1])
        cs = sp.metrics.chunk_summary()
        assert cs["chunk_ticks"] > 0 and cs["utilization"] > 0


class TestInt8Paged:
    """The int8 paged pool: block pools store int8 payloads + the SAME
    group-wise (position, head) absmax scales as the dense int8 slot
    pool (models.quant.quant_kv_groups), so int8-paged serving is
    token-exact vs int8-DENSE serving (the int8-vs-bf16 error is the
    opt-in tradeoff, unchanged); the Pallas block-table kernel read
    (ops/kvattn v4) is exact vs the XLA gathered read through the whole
    serving differential."""

    def _run(self, cfg, params, prompts, **kw):
        return _serve(cfg, params, prompts, **kw)

    def test_int8_paged_token_exact_vs_int8_dense(self, model):
        cfg, params = model
        prompts = _prompts(8)
        dense, cd, _ = self._run(cfg, params, prompts, kv_dtype="int8")
        paged, cp, sp = self._run(
            cfg, params, prompts, kv_dtype="int8", kv_pages=PAGES
        )
        assert set(paged) == set(dense)
        for k in dense:
            np.testing.assert_array_equal(paged[k], dense[k], err_msg=str(k))
        assert cp == cd
        assert sp.metrics.cache_summary()["hits"] > 0  # radix still works

    def test_int8_paged_kernel_serving_exact(self, model):
        """kv_kernel=True + kv_pages: the decode ticks read through the
        Pallas block-table kernel (interpret mode off-TPU) and the
        serving output matches the XLA-read int8 paged server and the
        int8 dense server."""
        cfg, params = model
        prompts = _prompts(6)
        dense, cd, _ = self._run(cfg, params, prompts, kv_dtype="int8")
        kern, ck, sk = self._run(
            cfg, params, prompts, kv_dtype="int8", kv_kernel=True,
            kv_pages=PAGES,
        )
        assert sk._kv_kernel is True
        for k in dense:
            np.testing.assert_array_equal(kern[k], dense[k], err_msg=str(k))
        assert ck == cd


class TestStaleTailInvariant:
    """The serve.py docstring hazard as an asserted invariant: a recycled
    slot/block never attends over stale positions. Every cache position
    that is not yet readable (logical position >= the slot's watermark;
    in paged mode also every block the slot does not own) is overwritten
    with garbage mid-serve — outputs must be byte-identical to a fresh
    server's, because each position is written before it first becomes
    attendable."""

    def _drive(self, cfg, params, server, broker, n, poison):
        out = {}
        consumer = server._consumer
        while len(out) < n:
            recs = consumer.poll(max_records=server.free_slots(), timeout_ms=0)
            if recs:
                server.note_fetched(recs)
                server.admit_records(recs)
                poison(server)  # corrupt every not-yet-readable position
            for rec, toks in server.step():
                out[(rec.partition, rec.offset)] = np.asarray(toks)
        server.flush_commits()
        return out

    def _expected(self, cfg, params, prompts):
        return np.asarray(
            generate(params, cfg, jnp.asarray(prompts), MAX_NEW)
        )

    def test_dense_recycled_slot_ignores_stale_tail(self, model):
        cfg, params = model
        prompts = _prompts(6, shared_prefix_len=0)
        broker = tk.InMemoryBroker()
        _topic(broker, prompts)
        consumer = tk.MemoryConsumer(broker, "p", group_id="gs")
        server = StreamingGenerator(
            consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
        )

        def poison(s):
            pos = jnp.asarray(np.asarray(s._pos))
            stale = (
                jnp.arange(s._max_len)[None, :] >= pos[:, None]
            )[None, :, :, None, None]
            s._caches = tuple(
                jnp.where(stale, jnp.float32(1e9), c) for c in s._caches
            )

        got = self._drive(cfg, params, server, broker, 6, poison)
        expected = self._expected(cfg, params, prompts)
        for (part, off), toks in got.items():
            np.testing.assert_array_equal(
                toks, expected[2 * off + part], err_msg=f"{part}:{off}"
            )
        consumer.close()

    def test_paged_recycled_blocks_ignore_stale_tail(self, model):
        """Paged: poison EVERY pool position except the live slots' own
        readable prefix — covering freed blocks re-allocated later, the
        sink block, and each slot's not-yet-written tail."""
        cfg, params = model
        prompts = _prompts(6, shared_prefix_len=0)
        broker = tk.InMemoryBroker()
        _topic(broker, prompts)
        consumer = tk.MemoryConsumer(broker, "p", group_id="gsp")
        server = StreamingGenerator(
            consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
            # No prefix overlap in these prompts: a poisoned CACHED block
            # would break exactness, so keep sharing out of this test
            # (the differential suite covers shared prefixes).
            kv_pages={"block_size": BS, "num_blocks": 12},
        )
        assert server._kv_pages is not None

        def poison(s):
            keep = np.zeros(
                (s._kv_pages.num_blocks, s._kv_pages.block_size), bool
            )
            pos = np.asarray(s._pos)
            for i in range(s._slots):
                if not s._active[i]:
                    continue
                row = s._table_np[i]
                for p in range(int(pos[i])):  # readable: [0, pos)
                    keep[row[p // BS], p % BS] = True
            stale = jnp.asarray(~keep)[None, :, :, None, None]
            pk, pv, table = s._caches
            s._caches = (
                jnp.where(stale, jnp.float32(1e9), pk),
                jnp.where(stale, jnp.float32(1e9), pv),
                table,
            )

        got = self._drive(cfg, params, server, broker, 6, poison)
        expected = self._expected(cfg, params, prompts)
        for (part, off), toks in got.items():
            np.testing.assert_array_equal(
                toks, expected[2 * off + part], err_msg=f"{part}:{off}"
            )
        consumer.close()


def _mesh(axes):
    """A host-device mesh over exactly prod(axes) of the 8 forced CPU
    devices (conftest sets jax_num_cpu_devices)."""
    from torchkafka_tpu.parallel import make_mesh

    n = int(np.prod(list(axes.values())))
    return make_mesh(axes, devices=jax.devices()[:n])


@pytest.fixture(scope="module")
def mesh_model():
    """A tp-divisible serving model (n_kv_heads=2; the module ``model``
    fixture's single kv head cannot shard over tp)."""
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq_len=P + MAX_NEW, dtype=jnp.float32,
    )
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


MESHES = [{"data": 2}, {"tp": 2}, {"data": 2, "tp": 2}]
MESH_IDS = ["data2", "tp2", "data2xtp2"]


class TestShardedPagedServing:
    """PR 13 (ROADMAP item 1): the four KV-backend axes COMPOSE. Paged
    block tables × int8 payloads × the Pallas block-table read ×
    mesh-sharded pools serve together, token-exact and commit-ledger
    byte-identical vs the single-device reference on {data:2}, {tp:2},
    and {data:2, tp:2} host-device meshes. The int8 slices compare
    against int8-DENSE single-device serving (int8-vs-compute-dtype
    error stays the documented opt-in tradeoff; the mesh must add
    nothing on top). One fast smoke runs in tier-1; the full matrix is
    marked slow."""

    def test_sharded_paged_int8_kernel_smoke(self, mesh_model):
        """THE acceptance smoke: StreamingGenerator(mesh=..., kv_pages=
        ..., kv_dtype='int8', kv_kernel=True) constructs and serves —
        the old kv_pages+mesh rejection and kv_kernel mesh hard-disable
        are gone — and is token-exact + ledger-identical vs the
        single-device int8-DENSE server, with the backend decision
        observable on metrics."""
        cfg, params = mesh_model
        prompts = _prompts(8)
        dense, cd, _ = _serve(cfg, params, prompts, kv_dtype="int8")
        got, cg, sg = _serve(
            cfg, params, prompts, mesh=_mesh({"data": 2, "tp": 2}),
            kv_dtype="int8", kv_kernel=True, kv_pages=PAGES,
        )
        assert sg._kv_kernel is True
        assert set(got) == set(dense)
        for k in dense:
            np.testing.assert_array_equal(got[k], dense[k], err_msg=str(k))
        assert cg == cd
        kb = sg.metrics.summary()["kv_backend"]
        assert kb["layout"] == "paged" and kb["kv_dtype"] == "int8"
        assert kb["kernel_engaged"] == 1 and kb["kernel_disabled"] == {}
        assert kb["data"] == 2 and kb["tp"] == 2
        assert kb["row_write"] == "scatter"  # the paged tick's own scatters
        assert sg.metrics.cache_summary()["hits"] > 0  # radix still works

    @pytest.mark.slow
    @pytest.mark.parametrize("axes", MESHES, ids=MESH_IDS)
    def test_mesh_paged_greedy_and_sampled_exact(self, mesh_model, axes):
        cfg, params = mesh_model
        prompts = _prompts(10)
        mesh = _mesh(axes)
        base, cb, _ = _serve(cfg, params, prompts)
        got, cg, _ = _serve(cfg, params, prompts, mesh=mesh, kv_pages=PAGES)
        for k in base:
            np.testing.assert_array_equal(got[k], base[k], err_msg=str(k))
        assert cg == cb
        kw = dict(temperature=0.9, top_k=16)
        sb, csb, _ = _serve(cfg, params, prompts, rng=jax.random.key(11),
                            **kw)
        sg, csg, _ = _serve(
            cfg, params, prompts, mesh=mesh, kv_pages=PAGES,
            rng=jax.random.key(11), **kw,
        )
        for k in sb:
            np.testing.assert_array_equal(sg[k], sb[k], err_msg=str(k))
        assert csg == csb

    @pytest.mark.slow
    @pytest.mark.parametrize("axes", MESHES, ids=MESH_IDS)
    def test_mesh_paged_int8_kernel_exact(self, mesh_model, axes):
        cfg, params = mesh_model
        prompts = _prompts(8)
        dense, cd, _ = _serve(cfg, params, prompts, kv_dtype="int8")
        got, cg, sg = _serve(
            cfg, params, prompts, mesh=_mesh(axes), kv_dtype="int8",
            kv_kernel=True, kv_pages=PAGES,
        )
        assert sg._kv_kernel is True
        for k in dense:
            np.testing.assert_array_equal(got[k], dense[k], err_msg=str(k))
        assert cg == cd

    @pytest.mark.slow
    def test_mesh_spec_paged_exact(self, mesh_model):
        """Spec serving × paged pool × mesh: token-exact vs the plain
        single-device DENSE server (the spec contract composed through
        both axes), speculation provably live."""
        cfg, params = mesh_model
        prompts = _prompts(8)
        base, cb, _ = _serve(cfg, params, prompts)
        spec, cs, ss = _serve(
            cfg, params, prompts, cls=SpecStreamingGenerator, k=2,
            mesh=_mesh({"data": 2, "tp": 2}),
            kv_pages={"block_size": BS, "num_blocks": 48},
        )
        for k in base:
            np.testing.assert_array_equal(spec[k], base[k], err_msg=str(k))
        assert cs == cb
        assert ss.spec_stats()["proposed"] > 0

    @pytest.mark.slow
    def test_mesh_chaos_warm_resume_replay(self, mesh_model, tmp_path):
        """Replica-kill + journal handoff through a 2-replica fleet: the
        MESH-sharded paged run replays byte-identically vs the
        single-device paged run — same completions (duplicates
        included), same order, same committed watermarks — and the
        survivor provably warm-resumed the victim's in-flight prompts
        from its journal (the paged chunked path resumes under a mesh;
        ``_resume_supported``). The kill is deterministic: the replica
        holding active work after the 2nd completion."""
        from torchkafka_tpu.fleet import ServingFleet

        cfg, params = mesh_model

        def run(mesh, jdir):
            broker = tk.InMemoryBroker()
            broker.create_topic("t", partitions=4)
            prompts = _prompts(16, shared_prefix_len=5, seed=21)
            for i in range(16):
                broker.produce(
                    "t", prompts[i].tobytes(),
                    key=b"tenant-%d" % (i % 2), partition=i % 4,
                )
            gen_kwargs = {"kv_pages": PAGES}
            if mesh is not None:
                gen_kwargs["mesh"] = mesh
            fleet = ServingFleet(
                lambda rid: tk.MemoryConsumer(broker, "t", group_id="gm"),
                params, cfg, replicas=2, prompt_len=P, max_new=MAX_NEW,
                slots=2, commit_every=100, gen_kwargs=gen_kwargs,
                journal_dir=jdir, journal_cadence=1,
            )
            outputs: dict = {}
            order = []
            killed = False
            for _rid, rec, toks in fleet.serve(idle_timeout_ms=2000):
                key = (rec.partition, rec.offset)
                order.append(key)
                outputs.setdefault(key, []).append(np.asarray(toks))
                if not killed and len(order) == 2:
                    victim = next(
                        rep.id for rep in fleet.replicas
                        if rep.gen.has_active()
                    )
                    fleet.kill_replica(victim)
                    killed = True
            committed = {
                pt: broker.committed("gm", tk.TopicPartition("t", pt))
                for pt in range(4)
            }
            resumes = sum(
                r.gen.metrics.warm_resumes.count
                + r.gen.metrics.journal_served.count
                for r in fleet.replicas
            )
            fleet.close()
            return outputs, order, committed, killed, resumes

        single = run(None, tmp_path / "single")
        sharded = run(_mesh({"data": 2, "tp": 2}), tmp_path / "mesh")
        assert sharded[3] and single[3]
        assert sharded[1] == single[1]  # order, duplicates included
        assert set(sharded[0]) == set(single[0]) and len(sharded[0]) == 16
        for key in single[0]:
            for a, b in zip(sharded[0][key], single[0][key]):
                np.testing.assert_array_equal(a, b, err_msg=str(key))
        assert sharded[2] == single[2]
        # The journal was provably USED — warm resume works under the
        # mesh, not just cold replay.
        assert sharded[4] > 0 and sharded[4] == single[4]


class TestBackendCapabilityErrors:
    """The capability probe's genuine exclusions: each raises a precise,
    regression-pinned error — everything else composes."""

    @pytest.mark.parametrize(
        "how", ["config", "dict", "dict-int8", "dict-mesh"]
    )
    def test_per_record_admission_is_gone(self, mesh_model, how):
        """``prefill_chunk=0`` is refused with one sentence wherever it
        enters, whatever the backend it is asked of."""
        cfg, params = mesh_model
        kw = {}
        if how == "dict-int8":
            kw["kv_dtype"] = "int8"
        elif how == "dict-mesh":
            kw["mesh"] = _mesh({"data": 2})
        with pytest.raises(
            ValueError, match="per-record admission was removed in PR 29"
        ):
            if how == "config":
                PagedKVConfig(block_size=BS, num_blocks=40, prefill_chunk=0)
            else:
                _serve(
                    cfg, params, _prompts(2), kv_pages=_chunk_pages(0), **kw
                )

    def test_moe_rejects_pages(self):
        cfg = TransformerConfig(
            vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2,
            n_kv_heads=2, d_ff=64, max_seq_len=P + MAX_NEW,
            dtype=jnp.float32, n_experts=4, expert_top_k=2,
        )
        params = init_params(jax.random.key(0), cfg)
        with pytest.raises(ValueError, match="MoE"):
            _serve(cfg, params, _prompts(2), kv_pages=PAGES)

    def test_kernel_true_unhonorable_names_reason(self, mesh_model):
        """kv_kernel=True that cannot be honored raises with the probe's
        reason embedded — never a silent XLA-read fallback. The dense
        pool's tiling gate (head_dim % 128) fails for the toy model."""
        cfg, params = mesh_model
        with pytest.raises(ValueError, match="cannot be honored.*tiling"):
            _serve(cfg, params, _prompts(2), kv_dtype="int8",
                   kv_kernel=True)

    def test_auto_disable_reason_observable(self, model):
        """The kv_kernel='auto' decision lands on metrics: off-TPU the
        kernel never engages and the reason is a labelled counter on
        the exposition, not a silent branch."""
        cfg, params = model
        _, _, s = _serve(
            cfg, params, _prompts(4), kv_dtype="int8", kv_kernel="auto",
            kv_pages=PAGES,
        )
        kb = s.metrics.summary()["kv_backend"]
        assert kb["kernel_engaged"] == 0
        assert any("auto" in r for r in kb["kernel_disabled"])
        assert kb["row_write"] == "scatter"
        text = s.metrics.render_prometheus()
        assert "torchkafka_serve_kv_backend_info{" in text
        info = next(
            ln for ln in text.splitlines()
            if ln.startswith("torchkafka_serve_kv_backend_info{")
        )
        assert 'row_write="scatter"' in info
        assert "torchkafka_serve_kv_kernel_engaged 0" in text
        assert 'torchkafka_serve_kv_kernel_disabled_total{reason="' in text

    def test_resolve_describe_roundtrip(self, mesh_model):
        from torchkafka_tpu.kvcache import resolve_kv_backend

        cfg, _ = mesh_model
        bk = resolve_kv_backend(
            cfg, mesh=_mesh({"data": 2, "tp": 2}), kv_dtype="int8",
            kv_kernel=True, kv_pages=PagedKVConfig(**PAGES),
            max_len=P + MAX_NEW, slots=4, backend="cpu",
        )
        assert bk.paged and bk.int8 and bk.kernel and bk.sharded
        d = bk.describe()
        assert d["layout"] == "paged" and d["data"] == 2 and d["tp"] == 2
        assert d["row_write"] == "scatter"

    @pytest.mark.parametrize("kv_dtype,kv_kernel,row_write", [
        ("int8", True, "kernel"), ("int8", False, "scatter"),
        (None, "auto", "scatter"),
    ])
    def test_row_write_follows_the_dense_kernel(self, kv_dtype, kv_kernel,
                                                row_write):
        """Who writes a tick's new rows: the dense int8 pool's Pallas read
        where it engages (``slot_pool._slot_layer_step_q``'s ``use_kernel``
        branch is this same flag), XLA's scatters everywhere else."""
        from torchkafka_tpu.kvcache import resolve_kv_backend
        from torchkafka_tpu.models import TransformerConfig

        cfg = TransformerConfig(d_model=256, n_heads=2, n_kv_heads=2)
        bk = resolve_kv_backend(
            cfg, kv_dtype=kv_dtype, kv_kernel=kv_kernel, kv_pages=None,
            max_len=32, slots=2, backend="cpu",
        )
        assert bk.kernel is (row_write == "kernel")
        assert bk.row_write == bk.describe()["row_write"] == row_write

    def test_paged_kernel_gate_is_lane_alignment_on_tpu(self):
        """What compiled Mosaic accepted on the v5e (PR 21): the block
        size is the LANE dim of the [NB, K, bs] scale tiles, so 256 and
        384 compile and 264 (% 8, the old gate) is refused. Host-only:
        the resolver decides from the backend string."""
        from torchkafka_tpu.kvcache import resolve_kv_backend
        from torchkafka_tpu.models import TransformerConfig

        cfg = TransformerConfig(d_model=256, n_heads=2, n_kv_heads=2)
        assert cfg.head_dim == 128

        def resolve(bs, kv_kernel):
            return resolve_kv_backend(
                cfg, kv_dtype="int8", kv_kernel=kv_kernel,
                kv_pages=PagedKVConfig(block_size=bs, num_blocks=16),
                max_len=4 * bs, slots=2, backend="tpu",
            )

        for bs in (256, 384):
            assert resolve(bs, True).kernel and resolve(bs, "auto").kernel
        with pytest.raises(ValueError, match=r"block_size=264 % 128"):
            resolve(264, True)
        auto = resolve(264, "auto")
        assert not auto.kernel and "264" in auto.kernel_disabled_reason


class TestFleetChaosDifferential:
    """Cache-on vs cache-off through a 2-replica fleet with a seeded
    mid-generation replica kill: the redelivery/replay path must be
    byte-identical — same completions (duplicates included), same tokens
    per prompt, same committed offsets at every log end."""

    def _run(self, cfg, params, kv_pages):
        from torchkafka_tpu.fleet import ReplicaChaos, ServingFleet

        broker = tk.InMemoryBroker()
        broker.create_topic("t", partitions=4)
        prompts = _prompts(16, shared_prefix_len=5, seed=21)
        for i in range(16):
            broker.produce(
                "t", prompts[i].tobytes(),
                key=b"tenant-%d" % (i % 2), partition=i % 4,
            )
        fleet = ServingFleet(
            lambda rid: tk.MemoryConsumer(broker, "t", group_id="gc"),
            params, cfg, replicas=2, prompt_len=P, max_new=MAX_NEW,
            slots=2, commit_every=2,
            gen_kwargs={"kv_pages": kv_pages} if kv_pages else None,
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
        summary = fleet.metrics.summary(fleet.replicas)
        fleet.close()
        return outputs, order, committed, chaos.killed, summary

    def test_chaos_replay_token_and_ledger_identical(self, model):
        cfg, params = model
        off = self._run(cfg, params, None)
        on = self._run(cfg, params, PAGES)
        assert on[3] == off[3] and len(on[3]) == 1  # same seeded kill
        assert on[1] == off[1]  # same completion order, duplicates included
        assert set(on[0]) == set(off[0]) and len(on[0]) == 16
        for key in off[0]:
            for a, b in zip(on[0][key], off[0][key]):
                np.testing.assert_array_equal(a, b, err_msg=str(key))
        assert on[2] == off[2]  # committed watermarks byte-identical
        # The cache did real work during the chaos run...
        cache = on[4]["prefix_cache"]
        assert cache["hits"] > 0 and cache["hit_rate"] > 0
        # ...and redelivery actually happened (the kill exercised replay).
        assert any(len(v) > 1 for v in on[0].values()) or (
            on[4]["duplicates"] == off[4]["duplicates"]
        )

    def test_fleet_exposition_includes_cache(self, model):
        from torchkafka_tpu.fleet import ServingFleet

        cfg, params = model
        broker = tk.InMemoryBroker()
        broker.create_topic("t", partitions=2)
        prompts = _prompts(6)
        for i in range(6):
            broker.produce("t", prompts[i].tobytes(), partition=i % 2)
        fleet = ServingFleet(
            lambda rid: tk.MemoryConsumer(broker, "t", group_id="gf"),
            params, cfg, replicas=2, prompt_len=P, max_new=MAX_NEW,
            slots=2, commit_every=2, gen_kwargs={"kv_pages": PAGES},
        )
        served = fleet.serve_all(idle_timeout_ms=1500)
        assert len(served) == 6
        text = fleet.metrics.render_prometheus(replicas=fleet.replicas)
        assert "torchkafka_fleet_prefix_cache_hits_total" in text
        assert "torchkafka_fleet_prefix_cache_hit_rate" in text
        for line in text.strip().split("\n"):
            if not line.startswith("#"):
                float(line.rsplit(" ", 1)[1])
        s = fleet.metrics.summary(fleet.replicas)
        assert s["prefix_cache"]["hits"] + s["prefix_cache"]["misses"] == 6
        fleet.close()
