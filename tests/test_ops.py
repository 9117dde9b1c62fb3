"""Attention ops: ring attention must agree with dense attention exactly.

The reference has no tensor ops (SURVEY.md §2, parallelism table: ring
attention ABSENT) — these tests pin down the net-new sequence-parallel math:
forward and gradient parity between the shard_map ring implementation and
the single-device dense implementation, under causal masking, across mesh
layouts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from torchkafka_tpu.ops import mha, ring_attention
from torchkafka_tpu.parallel import make_mesh


def _qkv(rng, b=4, s=32, h=2, d=8):
    return tuple(
        jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32) for _ in range(3)
    )


class TestDense:
    def test_causality(self, rng):
        """Output at position t must not depend on inputs at positions > t."""
        q, k, v = _qkv(rng)
        base = mha(q, k, v, causal=True)
        k2 = k.at[:, -1].set(99.0)
        v2 = v.at[:, -1].set(99.0)
        poked = mha(q, k2, v2, causal=True)
        np.testing.assert_allclose(base[:, :-1], poked[:, :-1], rtol=1e-6)
        assert not np.allclose(base[:, -1], poked[:, -1])

    def test_matches_softmax_reference(self, rng):
        q, k, v = _qkv(rng, b=2, s=8, h=1, d=4)
        out = mha(q, k, v, causal=False)
        scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(4)
        probs = jax.nn.softmax(jnp.asarray(scores), axis=-1)
        ref = np.einsum("bhqk,bkhd->bqhd", probs, v)
        np.testing.assert_allclose(out, ref, rtol=1e-5)


class TestRing:
    @pytest.mark.parametrize("axes", [{"sp": 8}, {"data": 2, "sp": 4}, {"data": 4, "sp": 2}])
    def test_forward_matches_dense(self, rng, axes):
        mesh = make_mesh(axes)
        q, k, v = _qkv(rng)
        dense = mha(q, k, v, causal=True)
        spec = P(tuple(a for a in ("data",) if a in axes) or None, "sp")
        shard = NamedSharding(mesh, spec)
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
        ring = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh=mesh))(qs, ks, vs)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring), atol=2e-5)

    def test_grad_matches_dense(self, rng):
        mesh = make_mesh({"data": 2, "sp": 4})
        q, k, v = _qkv(rng)
        shard = NamedSharding(mesh, P("data", "sp"))
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
        g_dense = jax.grad(lambda q: mha(q, k, v, causal=True).sum())(q)
        g_ring = jax.grad(
            jax.jit(lambda q: ring_attention(q, ks, vs, mesh=mesh).sum())
        )(qs)
        np.testing.assert_allclose(np.asarray(g_dense), np.asarray(g_ring), atol=2e-5)

    def test_sp1_falls_back_to_dense(self, rng):
        mesh = make_mesh({"data": 8, "sp": 1})
        q, k, v = _qkv(rng)
        out = ring_attention(q, k, v, mesh=mesh)
        np.testing.assert_allclose(out, mha(q, k, v, causal=True), rtol=1e-6)


class TestRingFlash:
    """Ring attention over the Pallas flash kernels: when the local shard
    tiles (Sl a multiple of a flash block) every ring step runs the
    offset-aware flash kernel and the custom VJP circulates dk/dv
    accumulators around the ring. Shard size 128+ here forces that path
    (the tiny-shard tests above cover the dense fallback)."""

    def _sharded(self, rng, mesh, sp, b=2, s=1024, h=2, d=64, dtype=jnp.float32):
        q, k, v = (
            jnp.asarray(rng.normal(size=(b, s, h, d)), dtype) for _ in range(3)
        )
        shard = NamedSharding(mesh, P(None, "sp"))
        return q, k, v, tuple(jax.device_put(x, shard) for x in (q, k, v))

    def test_flash_path_selected(self):
        from torchkafka_tpu.ops.flash import _auto_block

        assert _auto_block(128) == 128 and _auto_block(256) == 256

    @pytest.mark.parametrize("sp", [4, 8])
    def test_forward_matches_dense(self, rng, sp):
        mesh = make_mesh({"data": 8 // sp, "sp": sp})
        q, k, v, (qs, ks, vs) = self._sharded(rng, mesh, sp)
        dense = mha(q, k, v, causal=True)
        ring = jax.jit(
            lambda a, b, c: ring_attention(a, b, c, mesh=mesh, use_flash=True)
        )(qs, ks, vs)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring), atol=5e-5)

    def test_all_grads_match_dense(self, rng):
        """dq is local but dk/dv must travel the ring home — checks the
        rotating-accumulator backward, not just the easy gradient."""
        mesh = make_mesh({"data": 2, "sp": 4})
        q, k, v, (qs, ks, vs) = self._sharded(rng, mesh, 4)
        g_dense = jax.grad(
            lambda q, k, v: (mha(q, k, v, causal=True) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_ring = jax.grad(
            jax.jit(
                lambda q, k, v: (
                    ring_attention(q, k, v, mesh=mesh, use_flash=True) ** 2
                ).sum()
            ),
            argnums=(0, 1, 2),
        )(qs, ks, vs)
        for a, b, name in zip(g_dense, g_ring, "q k v".split()):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, err_msg=f"d{name}"
            )

    def test_non_causal(self, rng):
        mesh = make_mesh({"data": 2, "sp": 4})
        q, k, v, (qs, ks, vs) = self._sharded(rng, mesh, 4)
        dense = mha(q, k, v, causal=False)
        ring = jax.jit(
            lambda a, b, c: ring_attention(
                a, b, c, mesh=mesh, causal=False, use_flash=True
            )
        )(qs, ks, vs)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring), atol=5e-5)

    def test_bf16_matches_dense(self, rng):
        """The production compute dtype through the flash-kernel ring path."""
        mesh = make_mesh({"data": 2, "sp": 4})
        q, k, v, (qs, ks, vs) = self._sharded(rng, mesh, 4, dtype=jnp.bfloat16)
        dense = mha(q, k, v, causal=True).astype(jnp.float32)
        ring = jax.jit(
            lambda a, b, c: ring_attention(a, b, c, mesh=mesh, use_flash=True)
        )(qs, ks, vs).astype(jnp.float32)
        np.testing.assert_allclose(
            np.asarray(dense), np.asarray(ring), atol=0.04
        )


class TestUlysses:
    """All-to-all sequence parallelism: two lax.all_to_all exchanges trade
    the sequence split for a head split, full-sequence attention runs per
    head-shard, and the result is exchanged back. Must agree with dense
    attention exactly — same contract as the ring, different comm shape."""

    @pytest.mark.parametrize("axes", [{"sp": 8}, {"data": 2, "sp": 4}, {"data": 4, "sp": 2}])
    def test_forward_matches_dense(self, rng, axes):
        from torchkafka_tpu.ops import ulysses_attention

        mesh = make_mesh(axes)
        q, k, v = _qkv(rng, h=8)  # heads divisible by every sp size here
        dense = mha(q, k, v, causal=True)
        spec = P(tuple(a for a in ("data",) if a in axes) or None, "sp")
        shard = NamedSharding(mesh, spec)
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
        out = jax.jit(lambda a, b, c: ulysses_attention(a, b, c, mesh=mesh))(
            qs, ks, vs
        )
        np.testing.assert_allclose(np.asarray(dense), np.asarray(out), atol=2e-5)

    def test_all_grads_match_dense(self, rng):
        """The backward differentiates through both all_to_alls (transpose
        rule: the reversed exchange) plus the local attention vjp."""
        from torchkafka_tpu.ops import ulysses_attention

        mesh = make_mesh({"data": 2, "sp": 4})
        q, k, v = _qkv(rng, h=8)
        shard = NamedSharding(mesh, P("data", "sp"))
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
        g_dense = jax.grad(
            lambda q, k, v: (mha(q, k, v, causal=True) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_uly = jax.grad(
            jax.jit(
                lambda q, k, v: (
                    ulysses_attention(q, k, v, mesh=mesh) ** 2
                ).sum()
            ),
            argnums=(0, 1, 2),
        )(qs, ks, vs)
        for a, b, name in zip(g_dense, g_uly, "q k v".split()):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-5, err_msg=f"d{name}"
            )

    def test_non_causal(self, rng):
        from torchkafka_tpu.ops import ulysses_attention

        mesh = make_mesh({"data": 2, "sp": 4})
        q, k, v = _qkv(rng, h=4)
        shard = NamedSharding(mesh, P("data", "sp"))
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
        dense = mha(q, k, v, causal=False)
        out = jax.jit(
            lambda a, b, c: ulysses_attention(a, b, c, mesh=mesh, causal=False)
        )(qs, ks, vs)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(out), atol=2e-5)

    def test_gqa_kv_travels_unrepeated(self, rng):
        """8 q heads, 4 kv heads over sp=4: the all_to_all moves Hkv/n=1 kv
        head per device — no repeat before the exchange — and the local
        attention serves the 2:1 group ratio."""
        from torchkafka_tpu.ops import ulysses_attention

        mesh = make_mesh({"data": 2, "sp": 4})
        q = jnp.asarray(rng.normal(size=(2, 32, 8, 8)), jnp.float32)
        k, v = (
            jnp.asarray(rng.normal(size=(2, 32, 4, 8)), jnp.float32)
            for _ in range(2)
        )
        rep_k, rep_v = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
        dense = mha(q, rep_k, rep_v, causal=True)
        qs = jax.device_put(q, NamedSharding(mesh, P("data", "sp")))
        ks, vs = (
            jax.device_put(x, NamedSharding(mesh, P("data", "sp"))) for x in (k, v)
        )
        out = jax.jit(lambda a, b, c: ulysses_attention(a, b, c, mesh=mesh))(
            qs, ks, vs
        )
        np.testing.assert_allclose(np.asarray(dense), np.asarray(out), atol=2e-5)

    def test_indivisible_heads_raise(self, rng):
        from torchkafka_tpu.ops import ulysses_attention

        mesh = make_mesh({"data": 2, "sp": 4})
        q, k, v = _qkv(rng, h=2)  # 2 heads, sp=4: not divisible
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, k, v, mesh=mesh)

    def test_sp1_falls_back_to_dense(self, rng):
        from torchkafka_tpu.ops import ulysses_attention

        mesh = make_mesh({"data": 8, "sp": 1})
        q, k, v = _qkv(rng)
        out = ulysses_attention(q, k, v, mesh=mesh)
        np.testing.assert_allclose(out, mha(q, k, v, causal=True), rtol=1e-6)

    def test_flash_path_matches_dense(self, rng):
        """Forced flash kernels (interpret mode on CPU) inside the ulysses
        head-shard: the production TPU path."""
        from torchkafka_tpu.ops import ulysses_attention

        mesh = make_mesh({"data": 2, "sp": 4})
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 256, 4, 16)), jnp.float32)
            for _ in range(3)
        )
        shard = NamedSharding(mesh, P(None, "sp"))
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
        dense = mha(q, k, v, causal=True)
        out = jax.jit(
            lambda a, b, c: ulysses_attention(
                a, b, c, mesh=mesh, use_flash=True
            )
        )(qs, ks, vs)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(out), atol=5e-5)


class TestInt8DecodeAttentionKernel:
    """ops/kvattn.py: the dense pool's dynamic-length read and the paged
    pool's block-table read, each held to the scale-folded XLA read."""

    def test_kernel_serving_end_to_end(self):
        """kv_kernel=True serves over the K-major pool (interpret mode on
        CPU): completions count, per-completion commits, and tokens agree
        with the XLA int8 read (f32 model — identical quantized math, the
        only divergence channel is f32 reduction order)."""
        import jax.numpy as jnp

        import torchkafka_tpu as tk
        from torchkafka_tpu.models.transformer import (
            TransformerConfig, init_params,
        )
        from torchkafka_tpu.serve import StreamingGenerator

        cfg = TransformerConfig(
            vocab_size=64, d_model=256, n_layers=2, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq_len=16, dtype=jnp.float32,
        )
        assert cfg.head_dim == 128  # kernel_applicable needs lane-aligned Dh
        params = init_params(jax.random.key(0), cfg)
        rng = np.random.default_rng(7)
        prompts = rng.integers(0, 64, (6, 8), dtype=np.int32)

        def serve(kv_kernel):
            broker = tk.InMemoryBroker()
            broker.create_topic("p", partitions=1)
            for row in prompts:
                broker.produce("p", row.tobytes())
            consumer = tk.MemoryConsumer(broker, "p", group_id="gkm")
            srv = StreamingGenerator(
                consumer, params, cfg, slots=2, prompt_len=8, max_new=8,
                kv_dtype="int8", kv_kernel=kv_kernel, commit_every=1,
            )
            got = {
                rec.offset: np.asarray(toks)
                for rec, toks in srv.run(max_records=len(prompts))
            }
            committed = broker.committed("gkm", tk.TopicPartition("p", 0))
            srv.close()
            consumer.close()
            return got, committed

        got_k, committed_k = serve(True)
        got_x, committed_x = serve(False)
        assert committed_k == committed_x == len(prompts)
        assert len(got_k) == len(got_x) == len(prompts)
        for off in got_x:
            np.testing.assert_array_equal(got_k[off], got_x[off])

    @pytest.mark.parametrize("rep", [1, 4])
    @pytest.mark.parametrize("pos", [
        pytest.param([31, 31, 31, 31], id="full"),
        pytest.param([15, 15, 15, 15], id="half"),
        pytest.param([0, 7, 20, 31], id="mixed-one-at-0"),
        pytest.param([16, 5, 31, 8], id="one-block-past-a-boundary"),
    ])
    def test_dynlen_matches_scale_folded_xla_read(self, monkeypatch, pos, rep):
        """The dynamic-length read (online softmax over M-blocks, only
        [0, pos] fetched) against ``_attend_cached``'s scale-folded read
        of the whole pool masked to each slot's watermark: the fills a
        pool meets, two GQA group sizes, several block sizes."""
        from types import SimpleNamespace

        from torchkafka_tpu.models import generate
        from torchkafka_tpu.ops.kvattn import int8_decode_attention_dynlen
        from torchkafka_tpu.kvcache.slot_pool import _quant_kv

        rng = np.random.default_rng(2)
        B, M, K, Dh = 4, 32, 2, 16
        H = K * rep
        q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, M, K, Dh)) * 2, jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, M, K, Dh)) * 2, jnp.float32)
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        pos = jnp.asarray(pos)
        valid = jnp.arange(M)[None, :] <= pos[:, None]
        # The read alone: the shared tail (wo, MLP) needs layer weights.
        monkeypatch.setattr(
            generate, "_attn_tail", lambda x, attn, layer, cfg: attn
        )
        cfg = SimpleNamespace(dtype=jnp.float32, head_dim=Dh)
        ref = generate._attend_cached(
            None, q, kq, vq, valid, None, cfg, k_scale=ks, v_scale=vs
        )
        kqT, vqT = (jnp.swapaxes(a, 1, 2) for a in (kq, vq))
        ksT, vsT = (jnp.swapaxes(a, 1, 2) for a in (ks, vs))
        for mb in (8, 16, 32):
            out = int8_decode_attention_dynlen(
                q, kqT, ksT, vqT, vsT, pos, block=mb, interpret=True
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
                err_msg=f"block={mb}",
            )

    @staticmethod
    def _stacked_pool(n_layers=3, B=4, M=32, K=2, rep=2, Dh=16, seed=3):
        """A K-major int8 pool [L, B, K, M, Dh] with scales [L, B, K, M],
        a query, and ragged watermarks: one-block, mid-block and
        several-block slots, so that the kernel's buffer parity and its
        prefetch of the NEXT slot's first block (which crosses from one
        slot's rows into the next's) both matter."""
        from torchkafka_tpu.kvcache.slot_pool import _quant_kv

        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(B, 1, K * rep, Dh)), jnp.float32)
        kv = jnp.asarray(
            rng.normal(size=(2, n_layers, B, K, M, Dh)) * 2, jnp.float32
        )
        (kq, ks), (vq, vs) = _quant_kv(kv[0]), _quant_kv(kv[1])
        pos = jnp.asarray([17, 3, 31, 8])
        return q, (kq, ks, vq, vs), pos

    @pytest.mark.parametrize("layer", [0, 1, 2])
    def test_dynlen_layer_indexed_matches_slab_read(self, layer):
        """The read of layer ``l`` out of the stacked pool, taken whole,
        is bit for bit the 4-D read of that layer's slab: same arithmetic,
        only the DMAs' source row moves. As a Python int and traced."""
        from torchkafka_tpu.ops.kvattn import int8_decode_attention_dynlen

        q, pool, pos = self._stacked_pool()
        ref = int8_decode_attention_dynlen(
            q, *(c[layer] for c in pool), pos, block=8, interpret=True
        )
        out = int8_decode_attention_dynlen(
            q, *pool, pos, layer=layer, block=8, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        traced = jax.jit(
            lambda l: int8_decode_attention_dynlen(
                q, *pool, pos, layer=l, block=8, interpret=True
            )
        )(jnp.int32(layer))
        np.testing.assert_array_equal(np.asarray(traced), np.asarray(ref))

    def test_dynlen_layer_needs_the_stacked_pool(self):
        from torchkafka_tpu.ops.kvattn import int8_decode_attention_dynlen

        q, pool, pos = self._stacked_pool()
        with pytest.raises(ValueError, match="stacked pool"):
            int8_decode_attention_dynlen(
                q, *(c[0] for c in pool), pos, layer=0, interpret=True
            )

    @pytest.mark.parametrize(
        "axes", [{"data": 2, "tp": 2, "fsdp": 2}, {"data": 4, "tp": 2}]
    )
    def test_dynlen_sharded_layer_indexed(self, axes):
        """Under a mesh the stacked pool enters the shard_map 5-D and each
        (data, tp) shard merges L with ITS slots: equal to the unsharded
        slab read, which is (slot, head)-parallel."""
        from torchkafka_tpu.models.generate import (
            kv_kmajor_scale_sharding, kv_kmajor_sharding,
        )
        from torchkafka_tpu.ops.kvattn import (
            int8_decode_attention_dynlen,
            int8_decode_attention_dynlen_sharded,
        )

        mesh = make_mesh(axes)
        q, pool, pos = self._stacked_pool()
        placed = tuple(
            jax.device_put(
                c, kv_kmajor_sharding(mesh) if c.ndim == 5
                else kv_kmajor_scale_sharding(mesh)
            )
            for c in pool
        )
        for layer in (0, 2):
            ref = int8_decode_attention_dynlen(
                q, *(c[layer] for c in pool), pos, block=8, interpret=True
            )
            out = jax.jit(
                lambda l: int8_decode_attention_dynlen_sharded(
                    q, *placed, pos, mesh, layer=l, block=8, interpret=True
                )
            )(jnp.int32(layer))
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    # The WRITING form (``rows=``): the tick's four cache scatters inside
    # the read. Geometries (M, block): the row group the kernel sends home
    # is 32 positions of payload and 128 lanes of scales, or the whole
    # block where the block is smaller.
    _WRITE_GEOMETRIES = {
        "block-8": (32, 8),       # groups = the block
        "block-64": (128, 64),    # 32-row payload groups, scales whole
        "block-128": (256, 128),  # 32-row and 128-lane groups, as compiled
    }
    _WRITE_POSITIONS = {
        # name: (M, mb) -> one watermark a slot
        "zero": lambda M, mb: [0, 0, 0, 0],
        "inside-block-0": lambda M, mb: [1, mb // 2, mb - 2, 3],
        "block-edges": lambda M, mb: [mb - 1, mb, mb - 1, mb],
        "multiples-of-32": lambda M, mb: [
            min(32, M - 8), min(32, M - 8) - 1, min(96, M - 8), min(96, M - 8) - 1
        ],
        "last-position": lambda M, mb: [M - 1, M - 1, 0, M - 2],
        "equal": lambda M, mb: [mb + 5] * 4,
        "different": lambda M, mb: [3, mb + 6, M - 1, mb],
    }

    @staticmethod
    def _fresh_rows(B=4, K=2, Dh=16, seed=9):
        from torchkafka_tpu.kvcache.slot_pool import _quant_kv

        rng = np.random.default_rng(seed)
        k, v = jnp.asarray(rng.normal(size=(2, B, K, Dh)) * 3, jnp.float32)
        return (*_quant_kv(k), *_quant_kv(v))

    @staticmethod
    def _scatter(pool, rows, pos, layer):
        """The tick's four scatters as XLA ran them before PR 30."""
        at = (
            layer, jnp.arange(pos.shape[0])[:, None],
            jnp.arange(pool[0].shape[2])[None, :], pos[:, None],
        )
        return tuple(c.at[at].set(r) for c, r in zip(pool, rows))

    @pytest.mark.parametrize("case", list(_WRITE_POSITIONS))
    @pytest.mark.parametrize("geometry", list(_WRITE_GEOMETRIES))
    def test_dynlen_write_equals_scatter_then_read(self, geometry, case):
        """``rows=`` against "scatter the rows, then the read-only form",
        at a non-zero layer of a stacked pool: the four pools bit for bit
        (so every other layer and position is untouched) and the attention
        bit for bit too, since the row is merged into the fetched tile.
        Watermarks inside block 0 are the stale-prefetch case: that block
        was fetched by the slot before, ahead of the write."""
        from torchkafka_tpu.ops.kvattn import int8_decode_attention_dynlen

        M, mb = self._WRITE_GEOMETRIES[geometry]
        q, pool, _ = self._stacked_pool(M=M)
        rows = self._fresh_rows()
        pos = jnp.asarray(self._WRITE_POSITIONS[case](M, mb), jnp.int32)
        layer = 1
        want_pool = self._scatter(pool, rows, pos, layer)
        want = int8_decode_attention_dynlen(
            q, *want_pool, pos, layer=layer, block=mb, interpret=True
        )
        got, *got_pool = jax.jit(
            lambda l: int8_decode_attention_dynlen(
                q, *pool, pos, layer=l, rows=rows, block=mb, interpret=True
            )
        )(jnp.int32(layer))
        for name, g, w, old in zip(
            ("kq", "ks", "vq", "vs"), got_pool, want_pool, pool
        ):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w), err_msg=name
            )
            # Spelled out: B x K rows of layer 1 moved, and nothing else.
            moved = np.asarray(g) != np.asarray(old)
            assert not moved[[0, 2]].any(), name
            at = np.zeros(moved.shape[1:4], bool)  # [B, K, M]
            at[np.arange(4), :, np.asarray(pos)] = True
            assert not moved[1][~at].any(), name
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_dynlen_write_into_a_slab_and_the_clamp(self):
        """Without ``layer`` the write goes into one layer's 4-D slab; a
        watermark past the pool is clamped to its last position (a DMA is
        unchecked; the tick's latch never sends one)."""
        from torchkafka_tpu.ops.kvattn import int8_decode_attention_dynlen

        q, pool, _ = self._stacked_pool()
        slab = tuple(c[2] for c in pool)
        rows = self._fresh_rows()
        M = slab[0].shape[2]
        inside = jnp.asarray([M - 1, 4, M - 1, 9], jnp.int32)
        past = jnp.asarray([M + 5, 4, M, 9], jnp.int32)
        at = (jnp.arange(4)[:, None], jnp.arange(2)[None, :], inside[:, None])
        want_pool = tuple(c.at[at].set(r) for c, r in zip(slab, rows))
        want = int8_decode_attention_dynlen(
            q, *want_pool, inside, block=8, interpret=True
        )
        for pos in (inside, past):
            got, *got_pool = int8_decode_attention_dynlen(
                q, *slab, pos, rows=rows, block=8, interpret=True
            )
            for g, w in zip(got_pool, want_pool):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize(
        "axes", [{"data": 2, "tp": 2, "fsdp": 2}, {"data": 4, "tp": 2}]
    )
    def test_dynlen_sharded_write(self, axes):
        """Under a mesh each (data, tp) shard writes its own slots' and
        heads' rows: pools and attention equal to the unsharded write,
        the pools back under the shardings they came in with."""
        from torchkafka_tpu.models.generate import (
            kv_kmajor_scale_sharding, kv_kmajor_sharding,
        )
        from torchkafka_tpu.ops.kvattn import (
            int8_decode_attention_dynlen,
            int8_decode_attention_dynlen_sharded,
        )

        mesh = make_mesh(axes)
        q, pool, pos = self._stacked_pool()
        rows = self._fresh_rows()
        placed = tuple(
            jax.device_put(
                c, kv_kmajor_sharding(mesh) if c.ndim == 5
                else kv_kmajor_scale_sharding(mesh)
            )
            for c in pool
        )
        want = int8_decode_attention_dynlen(
            q, *self._scatter(pool, rows, pos, 2), pos, layer=2, block=8,
            interpret=True,
        )
        got, *got_pool = jax.jit(
            lambda l: int8_decode_attention_dynlen_sharded(
                q, *placed, pos, mesh, layer=l, rows=rows, block=8,
                interpret=True,
            )
        )(jnp.int32(2))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for g, w, p in zip(got_pool, self._scatter(pool, rows, pos, 2), placed):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            assert g.sharding.is_equivalent_to(p.sharding, g.ndim)

    # The LIVE MASK (``live=``): a slot that is not live fetches nothing,
    # writes nothing and gives zeros; the chain of prefetches and staged
    # row writes runs over the live slots alone.
    _LIVE_MASKS = {
        "first-dead": [0, 1, 1, 1, 1, 1],
        "last-dead": [1, 1, 1, 1, 1, 0],
        "a-run-of-dead-between-live": [1, 0, 0, 0, 1, 1],
        "all-dead": [0, 0, 0, 0, 0, 0],
        "all-live": [1, 1, 1, 1, 1, 1],
    }

    @pytest.mark.parametrize("block", [8, None], ids=["block-8", "block-default"])
    @pytest.mark.parametrize("layered", [False, True], ids=["slab", "layer"])
    @pytest.mark.parametrize("write", [False, True], ids=["read", "rows"])
    @pytest.mark.parametrize("mask", list(_LIVE_MASKS))
    def test_dynlen_live_mask(self, monkeypatch, mask, write, layered, block):
        """Live slots' attention equals the scale-folded XLA read (of the
        pool with the live rows scattered in, where the call writes); dead
        slots' is zero; a live slot's pool row is bit for bit the
        scatter's and a dead slot's pool rows are untouched, whatever its
        frozen watermark says (one lies past the pool)."""
        from types import SimpleNamespace

        from torchkafka_tpu.models import generate
        from torchkafka_tpu.ops.kvattn import (
            dynlen_block, int8_decode_attention_dynlen,
        )

        B, M, K, Dh, layer = 6, 64, 2, 16, 1
        assert dynlen_block(M) == 64
        q, pool, _ = self._stacked_pool(B=B, M=M)
        rows = self._fresh_rows(B=B)
        live = np.asarray(self._LIVE_MASKS[mask], bool)
        pos = jnp.asarray([17, 3, 63, 8, 70, 40], jnp.int32)
        inside = jnp.minimum(pos, M - 1)
        if not layered:
            pool = tuple(c[layer] for c in pool)
        got = jax.jit(
            lambda l, alive: int8_decode_attention_dynlen(
                q, *pool, pos, layer=l if layered else None,
                rows=rows if write else None, live=alive, block=block,
                interpret=True,
            )
        )(jnp.int32(layer), jnp.asarray(live))
        slab = pool if not layered else tuple(c[layer] for c in pool)
        want_slab = slab
        if write:
            got, *got_pool = got
            at = (np.nonzero(live)[0][:, None], jnp.arange(K)[None, :],
                  inside[live][:, None])
            want_slab = tuple(
                c.at[at].set(r[live]) for c, r in zip(slab, rows)
            )
            want_pool = want_slab if not layered else tuple(
                c.at[layer].set(w) for c, w in zip(pool, want_slab)
            )
            for name, g, w in zip(("kq", "ks", "vq", "vs"), got_pool, want_pool):
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(w), err_msg=name
                )
        monkeypatch.setattr(
            generate, "_attn_tail", lambda x, attn, layer, cfg: attn
        )
        kq, ks, vq, vs = want_slab  # K-major: [B, K, M, ·]
        ref = generate._attend_cached(
            None, q, jnp.swapaxes(kq, 1, 2), jnp.swapaxes(vq, 1, 2),
            jnp.arange(M)[None, :] <= inside[:, None], None,
            SimpleNamespace(dtype=jnp.float32, head_dim=Dh),
            k_scale=jnp.swapaxes(ks, 1, 2), v_scale=jnp.swapaxes(vs, 1, 2),
        )
        got = np.asarray(got)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[~live], 0)
        np.testing.assert_allclose(
            got[live], np.asarray(ref)[live], atol=2e-5, rtol=2e-5
        )

    @pytest.mark.parametrize("axes", [{"data": 2, "tp": 2, "fsdp": 2}])
    def test_dynlen_sharded_live_mask(self, axes):
        """Under a mesh ``live`` splits over ``data`` with the slots: each
        shard orders its own, and the result is the unsharded call's."""
        from torchkafka_tpu.models.generate import (
            kv_kmajor_scale_sharding, kv_kmajor_sharding,
        )
        from torchkafka_tpu.ops.kvattn import (
            int8_decode_attention_dynlen,
            int8_decode_attention_dynlen_sharded,
        )

        mesh = make_mesh(axes)
        q, pool, pos = self._stacked_pool()
        rows = self._fresh_rows()
        live = jnp.asarray([False, True, False, False])  # a whole shard dead
        placed = tuple(
            jax.device_put(
                c, kv_kmajor_sharding(mesh) if c.ndim == 5
                else kv_kmajor_scale_sharding(mesh)
            )
            for c in pool
        )
        want, *want_pool = int8_decode_attention_dynlen(
            q, *pool, pos, layer=2, rows=rows, live=live, block=8,
            interpret=True,
        )
        got, *got_pool = jax.jit(
            lambda l: int8_decode_attention_dynlen_sharded(
                q, *placed, pos, mesh, layer=l, rows=rows, live=live, block=8,
                interpret=True,
            )
        )(jnp.int32(2))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for g, w in zip(got_pool, want_pool):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_paged_kernel_matches_gathered_read(self):
        """The block-table read (the dyn-len kernel's watermark-DMA
        structure through per-slot block tables) against the XLA gathered
        scale-folded read, with slots sharing physical prefix blocks,
        watermarks at block edges and mid-block, and free/garbage blocks
        the tables never reference (the kernel must not touch them)."""
        import jax.numpy as jnp

        from torchkafka_tpu.models.generate import _attend_cached
        from torchkafka_tpu.models.quant import quant_kv_groups
        from torchkafka_tpu.ops.kvattn import (
            int8_paged_decode_attention, paged_gather_kmajor,
        )

        rng = np.random.default_rng(5)
        NB, bs, K, rep, Dh = 12, 8, 2, 2, 16
        B, nblk = 4, 4  # logical view 32 positions per slot
        H = K * rep

        class _Cfg:
            dtype = jnp.float32
            head_dim = Dh

        q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
        raw_k = rng.normal(size=(NB, bs, K, Dh)) * 2
        raw_v = rng.normal(size=(NB, bs, K, Dh)) * 2
        # K-major-per-block pools, garbage everywhere (unreferenced
        # blocks included — the gather mask and the kernel's block loop
        # must both ignore them).
        kq, ks = quant_kv_groups(jnp.asarray(raw_k, jnp.float32))
        vq, vs = quant_kv_groups(jnp.asarray(raw_v, jnp.float32))
        kqT, vqT = (jnp.swapaxes(a, 1, 2) for a in (kq, vq))  # [NB, K, bs, Dh]
        ksT, vsT = (jnp.swapaxes(a, 1, 2) for a in (ks, vs))  # [NB, K, bs]
        # Slots 0/1 share block 3 as a cached prefix (the radix shape);
        # block 0 is the sink, blocks 9-11 are free garbage.
        table = jnp.asarray([
            [3, 1, 2, 4], [3, 5, 6, 7], [8, 2, 1, 5], [4, 6, 3, 8],
        ], jnp.int32)
        pos = jnp.asarray([0, 7, 12, 31])  # block edges and mid-block
        # Reference: gathered view + scale-folded _attend_cached. The
        # attention tail needs layer weights; compare pre-tail by using
        # an identity-free spelling — reimplement the fold directly.
        ck = paged_gather_kmajor(kqT, table).astype(jnp.float32)
        cv = paged_gather_kmajor(vqT, table).astype(jnp.float32)
        cks = paged_gather_kmajor(ksT, table)
        cvs = paged_gather_kmajor(vsT, table)
        M = nblk * bs
        qg = q[:, 0].reshape(B, K, rep, Dh)
        scores = jnp.einsum("bkre,bmke->bkrm", qg, ck)
        scores = scores * cks.transpose(0, 2, 1)[:, :, None, :]
        scores = scores / jnp.sqrt(jnp.float32(Dh))
        valid = jnp.arange(M)[None, :] <= pos[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        probs = probs * cvs.transpose(0, 2, 1)[:, :, None, :]
        ref = jnp.einsum("bkrm,bmke->bkre", probs, cv).reshape(B, 1, H, Dh)
        out = int8_paged_decode_attention(
            q, kqT, ksT, vqT, vsT, table, pos, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5
        )

    @pytest.mark.parametrize("pool, block", [
        (4096, 512), (2048, 512),
        (1024, 256),  # four blocks a pool: half of it is no block (PR 47)
        (512, 256), (768, 256), (384, 128),
        (1032, 8),    # tiles, but tiny → TPU-gated
        (1030, 0),    # does not tile at all
    ])
    def test_dynlen_block_of_a_pool(self, pool, block):
        """The rows the dynamic-length read fetches at a time, and what the
        serving probe makes of them on the TPU (a block under 256 there is
        refused: ``_kernel_probe_dense``)."""
        from types import SimpleNamespace

        from torchkafka_tpu.kvcache.backend import _kernel_probe_dense
        from torchkafka_tpu.ops.kvattn import dynlen_block

        assert dynlen_block(pool) == block
        cfg = SimpleNamespace(head_dim=128)
        refused = _kernel_probe_dense(cfg, pool, on_tpu=True) is not None
        assert refused == (block < 256)

    def test_kernel_gates(self):
        """The dyn-len kernel's scratch is block-sized, so LONG pools are
        supported; pools that only tile at tiny blocks are refused on TPU
        but accepted off-TPU (interpret correctness path)."""
        import jax.numpy as jnp

        import torchkafka_tpu as tk
        from torchkafka_tpu.models.transformer import (
            TransformerConfig, init_params,
        )
        from torchkafka_tpu.serve import StreamingGenerator

        # M=4096 is accepted with the explicit kernel (off-TPU it honors
        # via interpret — ctor only, no decode executed here).
        cfg = TransformerConfig(
            vocab_size=64, d_model=1024, n_layers=1, n_heads=8,
            n_kv_heads=8, d_ff=64, max_seq_len=4096, dtype=jnp.float32,
        )
        params = init_params(jax.random.key(0), cfg)
        broker = tk.InMemoryBroker()
        broker.create_topic("p", partitions=1)
        consumer = tk.MemoryConsumer(broker, "p", group_id="gvf")
        srv = StreamingGenerator(
            consumer, params, cfg, slots=2, prompt_len=4064,
            max_new=32, kv_dtype="int8", kv_kernel=True,
        )
        assert srv._kv_kernel is True
        srv.close()
        consumer.close()

    def test_kernel_opt_in_gate(self):
        """kv_kernel requires kv_dtype='int8' and defaults OFF."""
        import jax.numpy as jnp

        import torchkafka_tpu as tk
        from torchkafka_tpu.models.transformer import (
            TransformerConfig, init_params,
        )
        from torchkafka_tpu.serve import StreamingGenerator

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq_len=16, dtype=jnp.float32,
        )
        params = init_params(jax.random.key(0), cfg)
        broker = tk.InMemoryBroker()
        broker.create_topic("p", partitions=1)
        consumer = tk.MemoryConsumer(broker, "p", group_id="gkk")
        with pytest.raises(ValueError, match="kv_kernel requires"):
            StreamingGenerator(
                consumer, params, cfg, slots=2, prompt_len=8, max_new=8,
                kv_kernel=True,
            )
        srv = StreamingGenerator(
            consumer, params, cfg, slots=2, prompt_len=8, max_new=8,
            kv_dtype="int8",
        )
        assert srv._kv_kernel is False  # off by default
        consumer.close()
