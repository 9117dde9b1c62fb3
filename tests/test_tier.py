"""Tiered radix cache (torchkafka_tpu/kvcache/tier.py + radix tier hooks
+ serve.py kv_tier=): cold prefix blocks demote to a bounded host-RAM
store instead of freeing, and promote back on radix hit — the effective
prefix-cache capacity becomes host memory (plus optional disk spill),
not pool blocks.

Three contract layers, mirroring the radix/allocator property suites:

1. HOST-TIER INVARIANTS — random put/take schedules against a
   brute-force reference model: payload bytes round-trip exactly, RAM
   occupancy never exceeds the configured bound, LRU victims
   spill-or-drop in deterministic op-counter order, disk spill loads
   back bitwise.
2. RADIX × TIER INVARIANTS — random admit/release/evict schedules over
   a simulated pool: every promoted block's bytes equal the pure
   function of its token prefix (i.e. exactly what a re-prefill would
   write), allocator refcounts never go negative, the tier bound holds
   after every op, and the whole schedule replays deterministically.
3. SERVING DIFFERENTIAL — tiered serving is token-exact +
   commit-ledger-byte-identical vs HBM-only serving at a tenant count
   where the HBM-only tree measurably thrashes, with higher hit rate
   and fewer prefill tokens; composes with int8 pools and disk spill;
   metrics ride the conformant exposition.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import torchkafka_tpu as tk
from torchkafka_tpu.kvcache import (
    BlockAllocator,
    HostTier,
    RadixCache,
    TierConfig,
)
from torchkafka_tpu.models.transformer import TransformerConfig, init_params
from torchkafka_tpu.serve import StreamingGenerator

P, MAX_NEW, VOCAB, BS = 8, 8, 64, 4


# --------------------------------------------------------------------------
# 1. HostTier vs a brute-force reference model
# --------------------------------------------------------------------------


class _RefTier:
    """Brute-force model of HostTier's RAM bound + op-counter LRU +
    spill-or-drop policy (no disk: spilled entries are tracked as
    'cold', dropped entries vanish)."""

    def __init__(self, capacity: int, spill: bool) -> None:
        self.capacity = capacity
        self.spill = spill
        self.ram: dict[bytes, tuple[int, int]] = {}  # key -> (bytes, stamp)
        self.cold: set[bytes] = set()
        self.clock = 0

    def put(self, key: bytes, nbytes: int) -> None:
        self.clock += 1
        self.ram.pop(key, None)
        self.cold.discard(key)
        if nbytes > self.capacity:
            if self.spill:
                self.cold.add(key)
            return
        self.ram[key] = (nbytes, self.clock)
        while sum(n for n, _ in self.ram.values()) > self.capacity:
            victim = min(self.ram, key=lambda k: self.ram[k][1])
            del self.ram[victim]
            if self.spill:
                self.cold.add(victim)

    def take(self, key: bytes) -> bool:
        self.clock += 1
        if key in self.ram:
            del self.ram[key]
            return True
        if key in self.cold:
            self.cold.remove(key)
            return True
        return False


class TestHostTier:
    @pytest.mark.parametrize("spill", [False, True], ids=["drop", "spill"])
    def test_put_take_property_vs_reference(self, tmp_path, spill):
        rng = np.random.default_rng(5)
        cap = 4096
        tier = HostTier(TierConfig(
            capacity_bytes=cap,
            spill_dir=str(tmp_path / "spill") if spill else None,
        ))
        ref = _RefTier(cap, spill)
        truth: dict[bytes, tuple] = {}  # key -> payload arrays
        keys = [f"prefix-{i}".encode() for i in range(24)]
        for step in range(400):
            key = keys[rng.integers(len(keys))]
            if rng.random() < 0.55:
                n = int(rng.integers(64, 900))
                payload = (
                    rng.integers(-128, 127, (n,), dtype=np.int8),
                    rng.random((n // 8,), dtype=np.float32),
                )
                tier.put(key, payload)
                ref.put(key, sum(a.nbytes for a in payload))
                truth[key] = tuple(a.copy() for a in payload)
            else:
                got = tier.take(key)
                hit = ref.take(key)
                assert (got is not None) == hit, (step, key)
                if got is not None:
                    # Byte exactness: the promotion IS the demotion.
                    for a, b in zip(got, truth[key]):
                        np.testing.assert_array_equal(a, b)
            # The RAM bound holds after EVERY op.
            assert tier.occupancy_bytes <= cap
            assert set(
                k for k, e in tier._entries.items() if e.arrays is not None
            ) == set(ref.ram)
            if spill:
                assert set(
                    k for k, e in tier._entries.items() if e.arrays is None
                ) == ref.cold
        if spill:
            assert tier.spills > 0 and tier.evictions == 0
        else:
            assert tier.evictions > 0 and tier.spills == 0

    def test_oversized_payload(self, tmp_path):
        tier = HostTier(TierConfig(capacity_bytes=16))
        tier.put(b"big", (np.zeros(64, np.int8),))
        assert tier.take(b"big") is None and tier.rejected == 1
        spilled = HostTier(TierConfig(
            capacity_bytes=16, spill_dir=str(tmp_path),
        ))
        spilled.put(b"big", (np.arange(64, dtype=np.int8),))
        got = spilled.take(b"big")
        np.testing.assert_array_equal(got[0], np.arange(64, dtype=np.int8))
        assert spilled.spill_loads == 1

    def test_config_validation(self):
        with pytest.raises(ValueError, match="capacity_bytes"):
            TierConfig(capacity_bytes=-1)
        with pytest.raises(ValueError, match="read_block"):
            RadixCache(BlockAllocator(8), 4,
                       tier=HostTier(TierConfig(capacity_bytes=1)))


# --------------------------------------------------------------------------
# 2. Radix × tier property schedule over a simulated pool
# --------------------------------------------------------------------------


def _prefix_payload(tokens) -> np.ndarray:
    """The simulated 'KV content' of the block holding ``tokens``' last
    chunk: a pure function of the whole prefix, exactly like real KV."""
    seed = int(np.asarray(tokens, np.int64).sum() * 2654435761 % (2**31))
    return np.random.default_rng(seed).random((BS, 4), dtype=np.float32)


def _run_schedule(seed: int, capacity: int):
    """One random admit/release/evict schedule with a tier; returns the
    observable trace (for determinism) while asserting content/bound
    invariants at every step."""
    rng = np.random.default_rng(seed)
    nb = 17
    pool = np.zeros((nb, BS, 4), np.float32)
    alloc = BlockAllocator(nb)
    tier = HostTier(TierConfig(capacity_bytes=capacity))
    radix = RadixCache(
        alloc, BS, tier=tier,
        read_block=lambda b: (pool[b].copy(),),
        write_block=lambda b, pay: pool.__setitem__(b, pay[0]),
    )
    families = np.random.default_rng(77).integers(
        0, VOCAB, (8, P), dtype=np.int32
    )
    live: list[list[int]] = []
    trace: list = []
    for _ in range(250):
        r = rng.random()
        if live and r < 0.35:
            alloc.decref(live.pop(rng.integers(len(live))))
            trace.append(("release",))
        elif r < 0.45:
            freed = radix.evict(int(rng.integers(1, 4)))
            trace.append(("evict", freed, radix.demotions))
        else:
            toks = families[rng.integers(len(families))]
            matched = radix.match(toks)
            # Content exactness: every matched block's bytes are the pure
            # function of its prefix — promoted and never-evicted blocks
            # are indistinguishable.
            for j, b in enumerate(matched):
                np.testing.assert_array_equal(
                    pool[b], _prefix_payload(toks[: (j + 1) * BS]),
                    err_msg=f"block {b} at depth {j}",
                )
            need = P // BS - len(matched)
            priv = alloc.alloc(need)
            if priv is None:
                alloc.decref(matched) if matched else None
                trace.append(("defer", len(matched)))
                continue
            row = matched + priv
            for j in range(len(matched), P // BS):
                pool[row[j]] = _prefix_payload(toks[: (j + 1) * BS])
            cap_blocks = RadixCache.matchable_blocks(P, BS)
            radix.insert(toks, row[:cap_blocks])
            live.append(row)
            trace.append(("admit", len(matched), radix.promotions))
        # Bound + refcount sanity after every op (decref raises on
        # negative refcounts; conservation pins leaks).
        assert tier.occupancy_bytes <= capacity
        held = sum(1 for b in range(1, nb) if alloc.refcount(b) > 0)
        assert alloc.available() + held == alloc.usable
    trace.append((
        "final", radix.demotions, radix.promotions, radix.tier_hits,
        tier.occupancy_bytes, sorted(tier._entries),
    ))
    return trace


class TestTieredRadixProperty:
    def test_content_refcounts_bound_and_determinism(self):
        for seed in (1, 2, 3):
            t1 = _run_schedule(seed, capacity=6 * BS * 4 * 4)
            t2 = _run_schedule(seed, capacity=6 * BS * 4 * 4)
            assert t1 == t2, f"schedule {seed} replayed differently"
            final = t1[-1]
            assert final[1] > 0, "schedule never demoted"
            assert final[2] > 0, "schedule never promoted"

    def test_promotion_stops_under_pool_pressure(self):
        """Promotion allocates without evicting: an empty free list just
        ends the walk (the prefix re-prefills) — no recursion, no
        deadlock, no refcount motion."""
        nb = 3  # sink + 2 usable
        pool = np.zeros((nb, BS, 4), np.float32)
        alloc = BlockAllocator(nb)
        tier = HostTier(TierConfig(capacity_bytes=1 << 20))
        radix = RadixCache(
            alloc, BS, tier=tier,
            read_block=lambda b: (pool[b].copy(),),
            write_block=lambda b, pay: pool.__setitem__(b, pay[0]),
        )
        toks = np.arange(P, dtype=np.int32)
        (b,) = alloc.alloc(1)
        pool[b] = _prefix_payload(toks[:BS])
        radix.insert(toks, [b])
        alloc.decref([b])
        assert radix.evict(1) == 1 and tier.contains(
            RadixCache._prefix_key([tuple(toks[:BS])])
        )
        pin = alloc.alloc(2)  # exhaust the pool
        assert radix.match(toks) == []  # tier hit exists, no block: miss
        assert radix.promotions == 0
        alloc.decref(pin)
        got = radix.match(toks)
        assert len(got) == 1 and radix.promotions == 1
        np.testing.assert_array_equal(pool[got[0]],
                                      _prefix_payload(toks[:BS]))


# --------------------------------------------------------------------------
# 3. Serving differential: tiered vs HBM-only at a thrashing tenant count
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=P + MAX_NEW, dtype=jnp.float32,
    )
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


def _thrash_prompts(tenants=8, rounds=3, seed=3):
    """More distinct tenant prefixes than a tiny pool can hold, revisited
    round-robin — the workload where an HBM-only tree evicts every
    prefix before its next hit (the hit-by-rank cliff)."""
    rng = np.random.default_rng(seed)
    t = rng.integers(0, VOCAB, (tenants, P), dtype=np.int32)
    return np.stack([t[i % tenants] for i in range(tenants * rounds)])


def _serve(cfg, params, prompts, **kw):
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    for i in range(prompts.shape[0]):
        broker.produce("p", prompts[i].tobytes(), partition=0,
                       key=str(i % 8).encode())
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    server = StreamingGenerator(
        consumer, params, cfg, slots=2, prompt_len=P, max_new=MAX_NEW,
        commit_every=4, kv_pages={"block_size": BS, "num_blocks": 9}, **kw,
    )
    out = {}
    for rec, toks in server.run(max_records=prompts.shape[0]):
        out[rec.offset] = np.asarray(toks)
    committed = broker.committed("g", tk.TopicPartition("p", 0))
    consumer.close()
    return out, committed, server


class TestTieredServing:
    def test_token_exact_and_hit_rate_beats_hbm_only(self, model):
        cfg, params = model
        prompts = _thrash_prompts()
        base, cb, sb = _serve(cfg, params, prompts)
        tier, ct, st = _serve(
            cfg, params, prompts, kv_tier={"capacity_bytes": 1 << 20},
        )
        assert set(base) == set(tier)
        for k in base:
            np.testing.assert_array_equal(tier[k], base[k], err_msg=str(k))
        assert ct == cb  # commit ledger byte-identical
        mb, mt = sb.metrics.cache_summary(), st.metrics.cache_summary()
        # The headline: the HBM-only tree thrashes (every prefix evicted
        # before its revisit); the tier turns those into hits.
        assert mt["hits"] > mb["hits"]
        assert mt["prefill_tokens"] < mb["prefill_tokens"]
        assert mt["tier"]["demotions"] > 0
        assert mt["tier"]["promotions"] > 0
        assert mt["tier"]["hits"] == mt["tier"]["promotions"]
        assert mb["tier"]["demotions"] == 0  # untiered server untouched

    @pytest.mark.slow
    def test_tiered_seeded_sampling_exact(self, model):
        cfg, params = model
        prompts = _thrash_prompts(seed=9)
        kw = dict(temperature=0.8, top_k=8, rng=jax.random.key(5))
        base, cb, _ = _serve(cfg, params, prompts, **kw)
        tier, ct, _ = _serve(
            cfg, params, prompts, kv_tier={"capacity_bytes": 1 << 20}, **kw,
        )
        for k in base:
            np.testing.assert_array_equal(tier[k], base[k], err_msg=str(k))
        assert ct == cb

    @pytest.mark.slow
    def test_tiered_int8_exact(self, model):
        """int8 pools tier too (payload+scale round-trip; exact vs the
        int8 HBM-only server — the opt-in accuracy tradeoff unchanged)."""
        cfg, params = model
        prompts = _thrash_prompts(seed=4)
        base, cb, _ = _serve(cfg, params, prompts, kv_dtype="int8")
        tier, ct, st = _serve(
            cfg, params, prompts, kv_dtype="int8",
            kv_tier={"capacity_bytes": 1 << 20},
        )
        for k in base:
            np.testing.assert_array_equal(tier[k], base[k], err_msg=str(k))
        assert ct == cb
        assert st.metrics.cache_summary()["tier"]["promotions"] > 0

    @pytest.mark.slow
    def test_disk_spill_tier_exact(self, model, tmp_path):
        """A RAM bound too small for even one payload forces every
        demotion through the disk tier — and promotions still land
        byte-identical outputs."""
        cfg, params = model
        prompts = _thrash_prompts(seed=6)
        base, cb, _ = _serve(cfg, params, prompts)
        tier, ct, st = _serve(
            cfg, params, prompts,
            kv_tier={"capacity_bytes": 0, "spill_dir": str(tmp_path)},
        )
        for k in base:
            np.testing.assert_array_equal(tier[k], base[k], err_msg=str(k))
        assert ct == cb
        assert st._kv_tier.spills > 0 and st._kv_tier.spill_loads > 0
        assert st.metrics.cache_summary()["tier"]["promotions"] > 0

    def test_tier_metrics_on_exposition(self, model):
        cfg, params = model
        prompts = _thrash_prompts(seed=2)
        _, _, st = _serve(
            cfg, params, prompts, kv_tier={"capacity_bytes": 1 << 20},
        )
        text = st.metrics.render_prometheus()
        for family in (
            "radix_demotions_total", "radix_promotions_total",
            "tier_hits_total", "tier_occupancy_bytes",
            "prefill_routed_total", "adopted_slots_total",
        ):
            assert f"torchkafka_serve_{family}" in text, family
        assert "radix_demotions_total 0\n" not in text  # non-degenerate
