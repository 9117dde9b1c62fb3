"""``kvcache/slot_pool.py`` held to the server it is behind: for every
layout the dense build has, the pool object made from the resolved
``KVBackend`` alone says the tensors ``StreamingGenerator.cache_tensors``
holds (order, shapes, dtypes), the static payloads ``metrics.summary()``
shows, and whether a journal hint can warm-resume (the PR 45 fault, a
hybrid without latent attention answered True, as a table); under a mesh,
the shardings the state is placed in and pinned to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from test_linear_attn import hybrid_cfg
from test_longcat_layer import double_cfg
from test_mla import latent_cfg
from test_ssd_hybrid import ssd_cfg

import torchkafka_tpu as tk
from torchkafka_tpu.kvcache import resolve_kv_backend, slot_pool
from torchkafka_tpu.models import TransformerConfig
from torchkafka_tpu.models import generate as G
from torchkafka_tpu.models.transformer import RopeKind, init_params
from torchkafka_tpu.ops.kvattn import dynlen_block
from torchkafka_tpu.serve import StreamingGenerator

P, NEW, SLOTS = 8, 8, 4
M = P + NEW


def dense_cfg(**over) -> TransformerConfig:
    """Heads of 128 (the kernel's lane width), two kv heads for tp."""
    base = dict(
        vocab_size=64, d_model=256, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq_len=M, dtype=jnp.float32,
    )
    return TransformerConfig(**{**base, **over})


def kinds_cfg() -> TransformerConfig:
    return dense_cfg(
        d_model=32, n_layers=4, n_heads=4, d_ff=48, stated_head_dim=16,
        sliding_window=4, window_pattern=(True, True, True, False),
        rope_theta=500000.0, rope_full=RopeKind(
            500000.0, factor=16.0, original_len=64, attention_factor=1.2773,
        ),
        n_experts=4, expert_top_k=2, expert_d_ff=24, router_score="softmax",
        norm_topk=True,
    )


def indexed_cfg() -> TransformerConfig:
    return dense_cfg(
        d_model=32, n_layers=2, n_heads=4, d_ff=48, stated_head_dim=16,
        window_pattern=(False,), n_experts=4, expert_top_k=2, expert_d_ff=24,
        router_score="softmax", norm_topk=True, experts_held=(2, 2),
        index_heads=2, index_head_dim=8, index_topk=4,
    )


F32, I8 = jnp.dtype("float32"), jnp.dtype("int8")
KV, KV8, KV8_S = (2, SLOTS, M, 2, 128), (2, SLOTS, 2, M, 128), (2, SLOTS, 2, M)
# name: (config, kv_dtype, kv_kernel, the pool's class, layout, tensors,
# the summary's static payloads, resumable)
LAYOUTS = {
    "dense": (
        dense_cfg, None, "auto", "SlotPool", "dense", [(KV, F32)] * 2, {}, True,
    ),
    "int8-xla": (
        dense_cfg, "int8", False, "Int8Pool", "dense",
        [(KV, I8), (KV[:4], F32)] * 2,
        {"kv_pool": {"full_layers": 2, "read": "xla", "block": M,
                     "bytes_full": 2 * 2 * SLOTS * M * 2 * (128 + 4)}},
        False,
    ),
    "int8-kernel": (
        dense_cfg, "int8", True, "Int8Pool", "dense",
        [(KV8, I8), (KV8_S, F32)] * 2,
        {"kv_pool": {"full_layers": 2, "read": "kernel",
                     "block": dynlen_block(M),
                     "bytes_full": 2 * 2 * SLOTS * M * 2 * (128 + 4)}},
        False,
    ),
    "latent": (
        lambda: latent_cfg(max_seq_len=M), None, "auto", "LatentPool",
        "latent", [((3, SLOTS, M, 20), F32)],
        {"latent_pool": {"attn_blocks": 1}}, False,
    ),
    "latent-two-blocks": (
        lambda: double_cfg(max_seq_len=M), None, "auto", "LatentPool",
        "latent", [((4, SLOTS, M, 20), F32)],
        {"latent_pool": {"attn_blocks": 2}}, False,
    ),
    "by-kind": (
        kinds_cfg, None, "auto", "ByKindPool", "by_kind",
        [((1, SLOTS, M, 32), F32)] * 2 + [((3, SLOTS, 4, 32), F32)] * 2,
        {"kv_pool": {"window": 4, "window_layers": 3, "full_layers": 1,
                     "bytes_window": 2 * 3 * SLOTS * 4 * 32 * 4,
                     "bytes_full": 2 * SLOTS * M * 32 * 4}},
        False,
    ),
    "indexed": (
        indexed_cfg, None, "auto", "IndexedPool", "indexed",
        [((2, SLOTS, M, 1, 64), F32), ((2, SLOTS, 8, M), F32)],
        {"kv_pool": {"full_layers": 2, "read": "kernel", "index_layers": 2,
                     "topk": 4, "bytes_full": 2 * SLOTS * M * 64 * 4,
                     "bytes_index": 2 * SLOTS * 8 * M * 4,
                     "index_positions_valid": 0, "sparse_positions_read": 0}},
        False,
    ),
    "state-kda-latent": (
        lambda: hybrid_cfg(max_seq_len=M), None, "auto", "StatePool", "state",
        [((3, SLOTS, 2, 128, 128), F32), ((3, SLOTS, 3, 768), F32),
         ((1, SLOTS, M, 40), F32)],
        {"linear_state": {"kind": "kda", "layers": 3, "state_dtype": "float32",
                          "bytes_state": 3 * SLOTS * 2 * 128 * 128 * 4,
                          "bytes_conv": 3 * SLOTS * 3 * 768 * 4},
         "kv_pool": {}},
        False,
    ),
    "state-ssd-gqa": (
        lambda: ssd_cfg(max_seq_len=M), None, "auto", "StatePool", "state",
        [((3, SLOTS, 4, 16, 128), F32), ((3, SLOTS, 3 * (64 + 256)), F32),
         ((1, SLOTS, M, 32), F32), ((1, SLOTS, M, 32), F32)],
        {"linear_state": {"kind": "ssd", "layers": 3, "state_dtype": "float32",
                          "bytes_state": 3 * SLOTS * 4 * 16 * 128 * 4},
         "kv_pool": {"full_layers": 1, "read": "xla",
                     "bytes_full": 2 * SLOTS * M * 32 * 4}},
        False,
    ),
}


def _serve(cfg, kv_dtype, kv_kernel, mesh=None):
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    server = StreamingGenerator(
        tk.MemoryConsumer(broker, "p", group_id="g"),
        init_params(jax.random.key(0), cfg), cfg, slots=SLOTS, prompt_len=P,
        max_new=NEW, kv_dtype=kv_dtype, kv_kernel=kv_kernel, mesh=mesh,
    )
    backend = resolve_kv_backend(
        cfg, mesh=mesh, kv_dtype=kv_dtype, kv_kernel=kv_kernel, kv_pages=None,
        max_len=M, slots=SLOTS, backend=jax.default_backend(),
    )
    pool = slot_pool.make_slot_pool(cfg, backend, slots=SLOTS, max_len=M, mesh=mesh)
    return server, backend, pool


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_the_pool_says_what_the_server_holds(name):
    make_cfg, kv_dtype, kv_kernel, kind, layout, tensors, payloads, resumable = (
        LAYOUTS[name]
    )
    server, backend, pool = _serve(make_cfg(), kv_dtype, kv_kernel)
    assert type(pool).__name__ == kind and backend.layout == layout
    assert backend == server._kv_backend and type(server._pool) is type(pool)
    # (a) the tensors, their order, shapes and dtypes
    held = [(c.shape, c.dtype) for c in server.cache_tensors]
    assert held == tensors
    assert [(s, jnp.dtype(d)) for s, d in pool.shapes()] == tensors
    assert [(z.shape, z.dtype) for z in pool.zeros()] == tensors
    # (b) the static payloads: the pool's are the metrics' own, and the
    # summary shows them under the keys it always had
    summary = server.metrics.summary()
    for attr, payload in pool.static().items():
        assert getattr(server.metrics, attr) == payload
    assert set(pool.static()) <= {"kv_pool_static", "linear_state", "attn_blocks"}
    for section, expected in payloads.items():
        got = summary[section]
        assert {k: got[k] for k in expected} == expected, (section, got)
    if "linear_state" not in payloads:
        assert summary["linear_state"] == {}
    if not payloads.get("kv_pool"):
        assert set(summary["kv_pool"]) == {
            f"{k}_positions_{w}" for k in ("window", "full")
            for w in ("valid", "read")
        }
    assert summary["kv_backend"]["layout"] == layout
    assert "partner" not in summary["kv_backend"]
    # (c) warm resume: the backend's answer is the server's, and a resume
    # program exists exactly where it says so
    assert backend.resumable is resumable is server._resume_supported()
    assert (server._resume_exec is not None) is resumable


@pytest.mark.parametrize("name", ["dense", "int8-xla", "int8-kernel"])
def test_a_mesh_places_and_pins_the_pool_s_own_shardings(name):
    """2 x 2 virtual devices, slots over data and kv heads over tp: what
    ``shardings`` says is where the first state lies, and where a round
    trip through the jitted programs (``pin_state``) leaves it."""
    make_cfg, kv_dtype, kv_kernel, *_ = LAYOUTS[name]
    mesh = tk.make_mesh({"data": 2, "tp": 2}, devices=jax.devices()[:4])
    server, backend, pool = _serve(make_cfg(), kv_dtype, kv_kernel, mesh)
    assert (backend.data, backend.tp, backend.resumable) == (2, 2, False)
    kmajor = name == "int8-kernel"
    payload = (G.kv_kmajor_sharding if kmajor else G.kv_sharding)(mesh)
    scale = (G.kv_kmajor_scale_sharding if kmajor else G.kv_scale_sharding)(mesh)
    want = [payload, scale] * 2 if kv_dtype else [payload] * 2
    assert list(pool.shardings()) == want

    def placed():
        return all(
            c.sharding.is_equivalent_to(s, c.ndim)
            for c, s in zip(server.cache_tensors, want, strict=True)
        )

    assert placed()
    for c in server.cache_tensors:  # slots over data, kv heads over tp
        shard = c.addressable_shards[0].data.shape
        assert shard[1] == SLOTS // 2 and shard[2 if kmajor else 3] == 1
    if not kmajor:  # (the interpreted kernel under shard_map: test_serve.py)
        server.warmup()
        assert placed()
        assert server._pos.sharding.is_equivalent_to(G.slot_sharding(mesh), 1)
