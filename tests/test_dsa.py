"""Learned sparse attention (``ops/dsa.py``, ``TransformerConfig.index_*``,
the indexed slot pool): the decode kernels ``tk_dsa_index`` and
``tk_dsa_attend`` under the Pallas interpreter against ``jax.numpy`` forms
at lengths on both sides of the top-k, the admission's selection against
``lax.top_k`` (ties too) and its flash forward against a dense masked
softmax, the configuration's refusals by name, the held share beside
grouped-query attention summed to the uncut layer, and the server: tokens,
rows, index keys and meters through ``StreamingGenerator``."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import torchkafka_tpu as tk
from torchkafka_tpu.kvcache import resolve_kv_backend
from torchkafka_tpu.models.transformer import (
    Transformer, TransformerConfig, init_params,
)
from torchkafka_tpu.ops import dsa
from torchkafka_tpu.ops.moe import routed_moe_mlp
from torchkafka_tpu.serve import StreamingGenerator

TOPK = 8


def sparse_cfg(**kw) -> TransformerConfig:
    base = dict(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32, stated_head_dim=16, window_pattern=(False,),
        n_experts=8, expert_top_k=2, expert_d_ff=32, experts_held=(0, 2),
        router_score="softmax", norm_topk=True, index_heads=4,
        index_head_dim=16, index_topk=TOPK,
    )
    base.update(kw)
    return TransformerConfig(**base)


# ----------------------------------------------------------- the kernels


def pool_of(key, dtype, L=2, B=4, M=32, K=2, Dh=16, Di=16):
    ks = jax.random.split(key, 3)
    k = jax.random.normal(ks[0], (L, B, M, K, Dh), dtype)
    v = jax.random.normal(ks[1], (L, B, M, K, Dh), dtype)
    rows = dsa.pack_rows(k, v)
    rows = rows.reshape(*rows.shape[:3], *dsa.row_tile(rows.shape[-1]))
    keys = jax.random.normal(ks[2], (L, B, Di, M), dtype)
    return k, v, rows, keys


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_row_packs_and_unpacks_to_itself(dtype):
    k, v, rows, _ = pool_of(jax.random.key(0), dtype)
    k2, v2 = dsa.unpack_rows(rows.reshape(*rows.shape[:3], -1), 2, 16, dtype)
    assert (k2 == k).all() and (v2 == v).all()
    assert rows.dtype == (jnp.int32 if dtype == jnp.bfloat16 else dtype)


# A slot that is not live, lengths 1, top-k - 1, top-k, top-k + 1, a full slot.
LENGTHS = (0, 1, TOPK - 1, TOPK, TOPK + 1, 32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_decode_kernels_against_their_plain_forms(dtype):
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5
    )
    _k, _v, rows, keys = pool_of(jax.random.key(1), dtype, B=len(LENGTHS))
    ks = jax.random.split(jax.random.key(2), 3)
    b = len(LENGTHS)
    qi = jax.random.normal(ks[0], (b, 4, 16), dtype)
    w = jax.random.normal(ks[1], (b, 4), jnp.float32)
    q = jax.random.normal(ks[2], (b, 4, 16), dtype)
    n = jnp.asarray(LENGTHS, jnp.int32)
    for layer in (0, 1):
        got = dsa.index_scores(qi, w, keys, layer, n)
        want = dsa.index_scores_dense(
            qi[:, None], keys[layer].swapaxes(1, 2), w[:, None]
        )[:, 0]
        held = jnp.arange(32)[None, :] < n[:, None]
        assert (jnp.isneginf(got) == ~held).all()
        np.testing.assert_allclose(
            np.where(held, got, 0), np.where(held, want, 0), **tol
        )
        _best, idx = lax.top_k(got, TOPK)
        chosen = jnp.minimum(n, TOPK)
        out = dsa.attend_selected(
            q, rows, layer, idx, chosen, n_kv=2, scale=0.25
        )
        ref = dsa.attend_selected_reference(
            q, rows, layer, idx, chosen, n_kv=2, scale=0.25
        )
        assert (out[0] == 0).all()  # the slot that is not live
        np.testing.assert_allclose(
            np.asarray(out[1:], np.float32), np.asarray(ref[1:], np.float32),
            **tol,
        )


CHUNK = dsa.ATTEND_CHUNK
# Selected rows on both sides of every edge of three chunks: no chunk, one
# row, a chunk's tail, a whole chunk, a second chunk's first row (the
# buffer's other half), an even count, the last chunk's tail, the whole list.
COUNTS = (0, 1, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK, 3 * CHUNK - 1, 3 * CHUNK)


def chunked_read(dtype, n, head_dim=128):
    """A call over three chunks of selected rows (rows of whole lanes at
    ``head_dim`` 128: two sublanes a row in bfloat16, four in float32):
    slots of ``n``, none and ``3 * CHUNK - n`` rows, the entries past a
    slot's count out of range. -> (the counts, the kernel's, the
    reference's over the entries that count)."""
    k = 3 * CHUNK
    _k, _v, rows, _keys = pool_of(
        jax.random.key(9), dtype, L=2, B=3, M=k + 40, K=2, Dh=head_dim
    )
    ks = jax.random.split(jax.random.key(10), 4)
    q = jax.random.normal(ks[0], (3, 4, head_dim), dtype)
    idx = jnp.stack([jax.random.permutation(kk, k + 40)[:k] for kk in ks[1:]])
    counts = jnp.asarray([n, 0, k - n], jnp.int32)
    past = jnp.arange(k)[None, :] >= counts[:, None]
    wild = jnp.where(jnp.arange(k)[None, :] % 2 == 0, 2**30, -7)
    got = dsa.attend_selected(
        q, rows, 1, jnp.where(past, wild, idx).astype(jnp.int32), counts,
        n_kv=2, scale=head_dim ** -0.5,
    )
    want = dsa.attend_selected_reference(
        q, rows, 1, idx.astype(jnp.int32), counts, n_kv=2,
        scale=head_dim ** -0.5,
    )
    return counts, got, want


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", COUNTS)
def test_the_selected_read_over_several_chunks(dtype, n):
    """Every chunk's rows arrive before they are multiplied, in either half
    of the buffer; a chunk's tail is masked, an entry past the count is
    neither fetched (it is out of range) nor counted, and the slot that is
    not live between two that are reads zeros."""
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5
    )
    counts, got, want = chunked_read(dtype, n)
    live = np.asarray(counts > 0)
    assert got.dtype == dtype and (np.asarray(got, np.float32)[~live] == 0).all()
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        **tol,
    )


def test_a_chunk_s_one_wait_takes_what_its_starts_gave(monkeypatch):
    """The DMA semaphores are left at zero after an even and an odd count
    of chunks (slots of two, none and one): the kernel run with its
    semaphores read at the end, and the call made twice. Narrow rows: the
    interpreter's semaphore is 16 bits wide, and a chunk must fit it. So
    the byte count of the full-size wait (512 KB a chunk, rows of whole
    lanes) is held by Mosaic's check of the semaphores at the kernel's
    exit, on the chip alone, and by no test here; nor can the wide-row
    cases above tell one wait from a wait a row, the interpreter's DMAs
    being done when they start."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    left = []

    def probed(meta, idx, q, pool, o, sems, buf, sem, *scratch, **kw):
        dsa._attend_kernel(meta, idx, q, pool, o, buf, sem, *scratch, **kw)
        for s in range(2):
            sems[s] = pltpu.semaphore_read(sem.at[s])

    honest = pl.pallas_call

    def with_probe(kernel, *, out_shape, grid_spec, **kw):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=grid_spec.num_scalar_prefetch,
            grid=grid_spec.grid, in_specs=grid_spec.in_specs,
            out_specs=[
                grid_spec.out_specs,
                pl.BlockSpec((None, 2), lambda s, *_: (s, 0),
                             memory_space=pltpu.SMEM),
            ],
            scratch_shapes=grid_spec.scratch_shapes,
        )
        b = out_shape.shape[0]
        call = honest(
            lambda *refs: probed(*refs, **kernel.keywords),
            out_shape=[out_shape, jax.ShapeDtypeStruct((b, 2), jnp.int32)],
            grid_spec=grid_spec, **kw,
        )

        def run(*args):
            out, sems = call(*args)
            left.append(sems)
            return out

        return run

    first = chunked_read(jnp.float32, 2 * CHUNK, 16)[1]
    with monkeypatch.context() as patch:
        patch.setattr(dsa.pl, "pallas_call", with_probe)
        probed_out = chunked_read(jnp.float32, 2 * CHUNK, 16)[1]
    assert len(left) == 1 and (np.asarray(left[0]) == 0).all()
    again = chunked_read(jnp.float32, 2 * CHUNK, 16)[1]
    assert (np.asarray(first) == np.asarray(again)).all()
    assert (np.asarray(first) == np.asarray(probed_out)).all()


@pytest.mark.parametrize("wrong", ["queries", "list", "counts", "pool"])
def test_the_unchecked_read_refuses_shapes_that_do_not_fit(wrong):
    """The kernel is compiled without Mosaic's bounds checks, so what
    holds a program's slot under the pool's is the wrapper, at trace time:
    more queries than the pool has slots, a list or counts of another
    batch, or a pool that is not ``[L, B, M, tile, lanes]`` never reach
    the kernel."""
    _k, _v, rows, _keys = pool_of(jax.random.key(11), jnp.float32)  # B = 4
    b = 5 if wrong == "queries" else 4
    q = jnp.zeros((b, 4, 16))
    idx = jnp.zeros((3 if wrong == "list" else b, 8), jnp.int32)
    n = jnp.zeros((3 if wrong == "counts" else b,), jnp.int32)
    if wrong == "pool":
        rows = rows.reshape(*rows.shape[:3], -1)
    with pytest.raises(AssertionError):
        dsa.attend_selected(q, rows, 0, idx, n, n_kv=2, scale=1.0)


def test_the_read_compiles_for_the_chip_without_mosaic_s_checks():
    """The chip's compiler, no chip attached (every other case here runs
    the interpreter, which has no such flag): at the cell's row (bfloat16,
    a ``(4, 128)`` tile) and three chunks, ``tk_dsa_attend`` carries
    ``disable_bounds_checks`` and compiles; ``tk_dsa_index``, whose
    blocks the pipeline addresses, keeps Mosaic's checks."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    b, m, k = 4, 4096, 3 * CHUNK
    read = jax.jit(lambda q, rows, idx, n: dsa.attend_selected(
        q, rows, 1, idx, n, n_kv=4, scale=1.0, interpret=False,
    )).lower(
        of((b, 32, 128), jnp.bfloat16), of((2, b, m, 4, 128), jnp.int32),
        of((b, k), jnp.int32), of((b,), jnp.int32),
    )
    text = read.as_text()
    assert "tk_dsa_attend" in text and "disable_bounds_checks" in text
    assert "disable_bounds_checks\\22: true" in text
    read.compile()
    scores = jax.jit(lambda qi, w, keys, n: dsa.index_scores(
        qi, w, keys, 1, n, interpret=False,
    )).lower(
        of((b, 16, 64), jnp.bfloat16), of((b, 16), jnp.float32),
        of((2, b, 64, m), jnp.bfloat16), of((b,), jnp.int32),
    ).as_text()
    assert "tk_dsa_index" in scores
    assert "disable_bounds_checks\\22: true" not in scores


def test_a_slot_that_is_not_live_names_the_block_before_it():
    """No fetch for it: the order of slots changes no score."""
    _k, _v, _rows, keys = pool_of(jax.random.key(3), jnp.float32, M=256)
    qi = jax.random.normal(jax.random.key(4), (4, 4, 16))
    w = jnp.ones((4, 4))
    for n in ([0, 0, 200, 0], [130, 0, 0, 256], [0, 0, 0, 0]):
        n = jnp.asarray(n, jnp.int32)
        got = dsa.index_scores(qi, w, keys, 1, n)
        assert (jnp.isneginf(got) == (jnp.arange(256)[None] >= n[:, None])).all()


# --------------------------------------------------- the admission's forms


def indexer_inputs(key, b=2, s=256, hi=4, di=16):
    ks = jax.random.split(key, 3)
    return (
        jax.random.normal(ks[0], (b, s, hi, di)),
        jax.random.normal(ks[1], (b, s, di)),
        jax.random.normal(ks[2], (b, s, hi)),
    )


def top_k_mask(scores, k):
    b, s, _ = scores.shape
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(scores == 0, 0.0, scores)
    _best, idx = lax.top_k(jnp.where(causal[None], scores, -jnp.inf), k)
    return jnp.zeros((b, s, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None], idx
    ].set(True) & causal[None]


def test_the_selection_by_bisection_is_top_k_s():
    qi, ki, w = indexer_inputs(jax.random.key(5))
    got = dsa.select_mask(qi, ki, w, 40, block=64)
    assert got.dtype == jnp.int8
    want = top_k_mask(dsa.index_scores_dense(qi, ki, w), 40)
    assert ((got != 0) == want).all()
    # The rows under the top-k hold their whole past; the others 40.
    assert (got.sum(-1)[:, :40] == jnp.arange(1, 41)[None]).all()
    assert (got.sum(-1)[:, 40:] == 40).all()


def test_ties_go_to_the_lower_position():
    qi, ki, w = indexer_inputs(jax.random.key(6))
    coarse = jnp.round(dsa.index_scores_dense(qi, ki, w) * 2) / 2  # many equal
    got = dsa._select_block(coarse, jnp.arange(256), 40)
    assert ((got != 0) == top_k_mask(coarse, 40)).all()


def test_the_selected_flash_forward_is_the_dense_masked_softmax():
    qi, ki, w = indexer_inputs(jax.random.key(7))
    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 128))
    k = jax.random.normal(ks[1], (2, 256, 2, 128))
    v = jax.random.normal(ks[2], (2, 256, 2, 128))
    kw = dict(topk=40, scale=128 ** -0.5)
    kernel = dsa.sparse_prefill_attention(
        q, k, v, qi, ki, w, use_kernel=True, interpret=True, **kw
    )
    dense = dsa.sparse_prefill_attention(q, k, v, qi, ki, w, use_kernel=False, **kw)
    np.testing.assert_allclose(kernel, dense, rtol=1e-4, atol=1e-4)
    # Under the top-k everywhere the mask is the causal triangle.
    whole = dsa.sparse_prefill_attention(
        q, k, v, qi, ki, w, topk=256, scale=128 ** -0.5, use_kernel=False
    )
    from torchkafka_tpu.ops.attention import mha

    plain = mha(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), causal=True)
    np.testing.assert_allclose(whole, plain, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------ the configuration


def test_the_configuration_is_taken_and_refused_by_name():
    cfg = sparse_cfg()
    assert cfg.is_sparse and cfg.routed_moe and cfg.moe_partial
    params = init_params(jax.random.key(0), cfg)
    layers = params["layers"]
    assert layers["wiq"].shape == (2, 64, 4, 16)
    assert layers["wik"].shape == (2, 64, 16) and layers["wiw"].shape == (2, 64, 4)
    assert layers["router"].shape == (2, 64, 8)  # every output of the router
    assert layers["w_gate"].shape == (2, 2, 64, 32)  # the held share
    for kw in (
        dict(index_topk=0), dict(index_head_dim=15), dict(window_pattern=()),
        dict(window_pattern=(True, False), sliding_window=8),
    ):
        with pytest.raises(ValueError, match="learned sparse attention"):
            sparse_cfg(**kw)
    with pytest.raises(ValueError, match="linear_pattern model alone"):
        sparse_cfg(n_shared_experts=1)
    backend = resolve_kv_backend(cfg, max_len=48, slots=4, backend="cpu")
    assert backend.layout == "indexed" and not backend.resumable
    for kw, why in (
        (dict(kv_dtype="int8"), "compute-dtype only"),
        (dict(kv_kernel=True), "tk_dsa_index"),
        (dict(kv_pages=object()), "dense per-slot pool"),
    ):
        with pytest.raises(ValueError, match=why):
            resolve_kv_backend(cfg, max_len=48, slots=4, backend="cpu", **kw)


def test_what_else_takes_a_cache_refuses_it_by_name():
    from torchkafka_tpu.models.generate import generate
    from torchkafka_tpu.models.quant import quantize_params
    from torchkafka_tpu.models.transformer import make_train_step, param_specs
    from torchkafka_tpu.serve_spec import SpecStreamingGenerator

    cfg = sparse_cfg()
    params = init_params(jax.random.key(0), cfg)
    prompts = jnp.ones((1, 4), jnp.int32)
    for call in (
        lambda: generate(params, cfg, prompts, 2),
        lambda: quantize_params(params, cfg),
        lambda: make_train_step(cfg, None, None),
        lambda: param_specs(cfg),
        lambda: SpecStreamingGenerator(None, params, cfg, draft_layers=1),
    ):
        with pytest.raises(ValueError, match="learned sparse attention"):
            call()


def test_the_shares_of_a_layer_sum_to_the_uncut_layer():
    """One layer's routed part: the 4 shares of 2 of 8 experts, each given
    its own experts' weights, summed, are the layer that holds all 8."""
    whole = sparse_cfg(experts_held=None)
    layer = jax.tree.map(
        lambda t: t[0], init_params(jax.random.key(1), whole)["layers"]
    )
    h = jax.random.normal(jax.random.key(2), (3, 24, 64))
    want, routing = routed_moe_mlp(h, layer, whole)
    total = jnp.zeros_like(want)
    for first in range(0, 8, 2):
        share = dataclasses.replace(whole, experts_held=(first, 2))
        part = {
            n: (w[first: first + 2] if n in ("w_gate", "w_up", "w_down") else w)
            for n, w in layer.items()
        }
        got, chosen = routed_moe_mlp(h, part, share)
        assert (chosen == routing).all()  # the router keeps all its outputs
        total = total + got
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------- the server


def serve(cfg, params, prompts, new, **kw):
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    for row in prompts:
        broker.produce("p", np.asarray(row, np.int32).tobytes())
    server = StreamingGenerator(
        tk.MemoryConsumer(broker, "p", group_id="g"), params, cfg,
        slots=4, prompt_len=prompts.shape[1], max_new=new, ticks_per_sync=4,
        **kw,
    )
    out = {}
    for rec, toks in server.run(max_records=len(prompts), idle_timeout_ms=200):
        out[rec.offset] = np.asarray(toks)
    return server, out


@pytest.mark.parametrize("window", [6, 24])  # under the top-k, and past it
def test_the_served_tokens_are_the_full_forward_s(window):
    cfg = sparse_cfg()
    params = init_params(jax.random.key(3), cfg)
    prompts = np.asarray(
        jax.random.randint(jax.random.key(4), (3, window), 1, 128)
    )
    new = 12
    server, out = serve(cfg, params, prompts, new)
    # Teacher-forced through the full forward: every served token is the
    # first choice at the position before it.
    served = np.stack([out[i] for i in range(len(prompts))])
    logits = Transformer(cfg)(
        params, jnp.asarray(np.concatenate([prompts, served], axis=1))
    )
    first = np.asarray(jnp.argmax(logits[:, window - 1: window + new - 1], -1))
    assert (first == served).all()
    summary = server.metrics.summary()
    assert summary["kv_backend"]["layout"] == "indexed"
    pool = summary["kv_pool"]
    assert pool["topk"] == TOPK and pool["index_layers"] == 2
    assert pool["read"] == "kernel" and pool["bytes_index"] > 0
    # Token j >= 1 of a request holds window + j rows, a layer.
    held = 2 * 3 * sum(window + j for j in range(1, new))
    chosen = 2 * 3 * sum(min(window + j, TOPK) for j in range(1, new))
    assert pool["index_positions_valid"] == held == pool["sparse_positions_valid"]
    assert pool["sparse_positions_selected"] == chosen
    # (the read fetches whole chunks, and the toy's chunk is the top-k)
    assert pool["sparse_positions_read"] == 2 * 3 * TOPK * (new - 1)
    assert pool["index_positions_read"] >= held
    assert summary["expert_layer"]["experts_held"] == [0, 2]
    assert server.cache_tensors[0].shape[:3] == (2, 4, window + new)
    assert server.cache_tensors[1].shape == (2, 4, 16, window + new)
