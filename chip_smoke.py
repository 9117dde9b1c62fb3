"""Does the system still start on the chip? One process, a few minutes.

    python chip_smoke.py            # on a machine with a TPU; fails without

Drives both loops this repo exists for through the entry points a user
calls, at the full width of models it already supports, with random weights
from a seed, and checks what comes out:

  gate     JAX's first device must be a TPU. No chip, no run — never a CPU
           (and Pallas-interpreter) run under a device's name.
  kernels  the compiled Pallas reads and flash attention against their XLA
           references on a small input at the served head shapes; the
           dense read's writing form against scatter-then-read, bit for bit;
           the grouped expert matmul's two kernels against lax.ragged_dot
           at both admissions' shapes, a row half padding.
  serve    prompt topic -> StreamingGenerator -> per-completion commits on
           the 8b zoo model (Llama-3-8B widths, all 32 layers, int8
           weights), then two short servers at a 1024-token pool so that
           Mosaic compiles what kv_kernel="auto" picks in production: the
           dynamic-length read (dense int8 pool) and the block-table read
           (paged int8 pool).
  train    token topic -> KafkaStream -> make_train_step -> barrier ->
           commit at the 1b zoo widths, flash attention engaged.

On more than one device the serve and train phases run on a
{"data": n/2, "tp": 2} mesh and additionally require every device to hold
data. The last line of standard output is one JSON object naming the
device as JAX reports it; anything that fails raises, and the exit code is
then not 0. This is a bring-up check, not a benchmark cell: it prints
set-up facts (compile seconds, cache warmth, peak memory), no rates.

``--rehearse-cpu N`` runs the same control flow at toy sizes on N virtual
CPU devices with the kernels interpreted — for debugging this script
without a chip. It is never the default and never inferred from a missing
chip, and its last line carries no "ok".
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import importlib.metadata
import json
import sys
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the rehearsal."""

    serve_scale: str | None  # zoo scale; None = the toy rehearsal model
    train_scale: str | None
    slots: int
    prompt_len: int
    max_new: int
    records: int
    pool_prompt_len: int  # the two kernel servers: prompt + new = pool
    pool_max_new: int
    pool_slots: int
    pool_records: int
    block_size: int
    # "auto" is what production passes; it never engages off-TPU, so the
    # rehearsal must require the kernel to walk the same code.
    kv_kernel: bool | str
    train_seq: int
    train_batch: int
    # The grouped expert matmul's shapes: (tokens, top-k, experts, D, F).
    gmm_shapes: tuple


CHIP = Sizes(
    serve_scale="8b", train_scale="1b",
    slots=16, prompt_len=128, max_new=64, records=32,
    pool_prompt_len=768, pool_max_new=256, pool_slots=4, pool_records=6,
    block_size=256, kv_kernel="auto",
    train_seq=512, train_batch=8,
    # An admission trip of mellum2-12b-a2.5b-8l and of kanana-2-30b-a3b-7l,
    # and a decode tick of the former's 128 slots (16 pairs an expert).
    gmm_shapes=(
        (4096, 8, 64, 2304, 896), (3072, 6, 128, 2048, 768),
        (128, 8, 64, 2304, 896),
    ),
)
REHEARSAL = Sizes(
    serve_scale=None, train_scale=None,
    slots=4, prompt_len=8, max_new=4, records=8,
    pool_prompt_len=24, pool_max_new=8, pool_slots=2, pool_records=3,
    block_size=8, kv_kernel=True,
    train_seq=128, train_batch=4,
    gmm_shapes=((64, 2, 8, 32, 12),),
)


TRAIN_STEPS = 4


class CacheCounter:
    """Persistent-compile-cache traffic, from JAX's own monitoring events:
    a hit is an executable read back, a miss is one compiled and written."""

    def __init__(self) -> None:
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        """Counts since the last take, and whether the cache was warm:
        something was read back and nothing had to be compiled anew."""
        out = {
            "cache_hits": self.hits,
            "cache_misses": self.misses,
            "cache_warm": self.hits > 0 and self.misses == 0,
        }
        self.hits = self.misses = 0
        return out


def _peak_bytes() -> list[int | None]:
    """Per device, the allocator's high-water mark since process start
    (None where the backend does not report one, i.e. the CPU)."""
    import jax

    return [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    ]


def _installed(package: str) -> str | None:
    try:
        return importlib.metadata.version(package)
    except importlib.metadata.PackageNotFoundError:
        return None


def _require(ok, why: str) -> None:
    """A check of the smoke (not an ``assert``: those vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {why}")


def _require_every_device_holds_data() -> list[int | None]:
    """Code that has only seen one real device may put everything on the
    first: with several devices, each must report bytes in use."""
    import jax

    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()
    ]
    if len(in_use) > 1 and in_use[0] is not None:
        _require(all(b > 0 for b in in_use), f"a device holds nothing: {in_use}")
    return in_use


def _report(phase: str, facts: dict) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def _toy_config(max_seq_len: int, **kw):
    """Rehearsal model: heads of 128 (the kernels' lane width), two kv
    heads (tp=2 divides them), everything else as small as it goes."""
    import jax.numpy as jnp

    from torchkafka_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=512, d_model=512, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq_len=max_seq_len, dtype=jnp.float32,
        param_dtype=jnp.float32, **kw,
    )


# ------------------------------------------------------------------ kernels


def check_kernels(sz: Sizes) -> dict:
    """The Pallas reads and flash attention against XLA references, on a
    small input at the served head shapes (8 kv heads x 4 q heads of 128).
    On a TPU this is the compiled Mosaic code."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models.quant import quant_kv_groups
    from torchkafka_tpu.ops.attention import mha
    from torchkafka_tpu.ops.flash import flash_attention
    from torchkafka_tpu.ops.kvattn import (
        int8_decode_attention_dynlen,
        int8_paged_decode_attention,
        paged_gather_kmajor,
    )

    rng = np.random.default_rng(0)
    b, n_kv, rep, dh = 4, 8, 4, 128
    # The pool length and block size the two kernel servers will use.
    m, bs = sz.pool_prompt_len + sz.pool_max_new, sz.block_size
    nblk = m // bs
    h = n_kv * rep
    # bf16 on the chip, as served; the tolerance is bf16's 8 bits of
    # mantissa on the outputs and on the probabilities of the second dot.
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    tol = 2e-2 if dt == jnp.bfloat16 else 5e-5
    q = jnp.asarray(rng.normal(size=(b, 1, h, dh)), dt)
    # A dense K-major pool [B, K, M, Dh] IS a paged pool whose slot b owns
    # blocks b*nblk .. b*nblk+nblk-1 in order, so one reference serves
    # both kernels. Watermarks: empty-ish, a block edge, mid-block, full.
    kq, ks = quant_kv_groups(
        jnp.asarray(rng.normal(size=(b * nblk, bs, n_kv, dh)) * 2, jnp.float32)
    )
    vq, vs = quant_kv_groups(
        jnp.asarray(rng.normal(size=(b * nblk, bs, n_kv, dh)) * 2, jnp.float32)
    )
    pool_kq, pool_vq = (jnp.swapaxes(a, 1, 2) for a in (kq, vq))
    pool_ks, pool_vs = (jnp.swapaxes(a, 1, 2) for a in (ks, vs))
    table = jnp.arange(b * nblk, dtype=jnp.int32).reshape(b, nblk)
    pos = jnp.asarray([0, bs - 1, m // 2 + 3, m - 1], jnp.int32)

    @jax.jit
    def reference(q, pool_kq, pool_ks, pool_vq, pool_vs, table, pos):
        ck = paged_gather_kmajor(pool_kq, table).astype(jnp.float32)
        cv = paged_gather_kmajor(pool_vq, table).astype(jnp.float32)
        cks = paged_gather_kmajor(pool_ks, table)
        cvs = paged_gather_kmajor(pool_vs, table)
        qg = q[:, 0].astype(jnp.float32).reshape(b, n_kv, rep, dh)
        s = jnp.einsum("bkre,bmke->bkrm", qg, ck)
        s = s * cks.transpose(0, 2, 1)[:, :, None, :] / np.sqrt(dh)
        valid = jnp.arange(m)[None, :] <= pos[:, None]
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1) * cvs.transpose(0, 2, 1)[:, :, None, :]
        return jnp.einsum("bkrm,bmke->bkre", p, cv).reshape(b, 1, h, dh)

    def dense_view(pool):  # [B*nblk, K, bs, ...] -> [B, K, M, ...]
        v = pool.reshape(b, nblk, *pool.shape[1:])
        return jnp.swapaxes(v, 1, 2).reshape(b, n_kv, m, *pool.shape[3:])

    ref = np.asarray(reference(q, pool_kq, pool_ks, pool_vq, pool_vs, table, pos))
    paged = jax.jit(int8_paged_decode_attention)(
        q, pool_kq, pool_ks, pool_vq, pool_vs, table, pos
    )
    dense = [dense_view(p) for p in (pool_kq, pool_ks, pool_vq, pool_vs)]
    dyn = jax.jit(int8_decode_attention_dynlen)(q, *dense, pos)
    # The same read out of a STACKED pool taken whole, as the serving tick
    # makes it: the slab as layer 1, behind a layer of other bytes.
    dyn_layer = jax.jit(int8_decode_attention_dynlen)(
        q, *(jnp.stack([jnp.roll(v, 1, axis=0), v]) for v in dense), pos,
        layer=jnp.int32(1),
    )
    err = {}

    def close(name, got, want):
        """Max error relative to the reference's largest magnitude."""
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        _require(got.shape == want.shape, f"{name}: {got.shape} != {want.shape}")
        _require(np.isfinite(got).all(), f"{name}: non-finite output")
        err[name] = float(np.abs(got - want).max() / (np.abs(want).max() or 1.0))
        _require(err[name] <= tol, f"{name}: relative err {err[name]} > {tol}")

    close("block_table_read", paged, ref)
    close("dynlen_read", dyn, ref)
    _require(
        np.array_equal(np.asarray(dyn_layer), np.asarray(dyn)),
        "dynlen_read: layer 1 of the stacked pool differs from its slab",
    )
    # The WRITING form, as the serving tick calls it: this tick's rows go
    # into layer 1 of the stacked pool inside the call. Held bit for bit
    # to "scatter the rows, then read": the pools, and the attention too
    # (the row is merged into the fetched tile, so the sums are the same).
    stacked = [jnp.stack([jnp.roll(v, 1, axis=0), v]) for v in dense]
    # A generator of their own: the flash check below keeps its draws.
    row_rng = np.random.default_rng(1)
    fresh = tuple(
        a for _ in "kv" for a in quant_kv_groups(
            jnp.asarray(row_rng.normal(size=(b, n_kv, dh)), jnp.float32)
        )
    )

    @jax.jit
    def scatter_then_read(q, pool, fresh, pos):
        at = (1, jnp.arange(b)[:, None], jnp.arange(n_kv)[None, :], pos[:, None])
        pool = [c.at[at].set(r) for c, r in zip(pool, fresh)]
        return int8_decode_attention_dynlen(q, *pool, pos, layer=1), *pool

    @functools.partial(jax.jit, donate_argnums=(1,))
    def write_in_read(q, pool, fresh, pos, live=None):
        return int8_decode_attention_dynlen(
            q, *pool, pos, layer=jnp.int32(1), rows=fresh, live=live
        )

    want = scatter_then_read(q, stacked, fresh, pos)
    got = write_in_read(q, [jnp.copy(c) for c in stacked], fresh, pos)
    for name, g, w in zip(("attn", "kq", "ks", "vq", "vs"), got, want):
        _require(
            np.array_equal(np.asarray(g), np.asarray(w)),
            f"dynlen_write: {name} differs from scatter-then-read",
        )
    # Under a live mask (the first slot dead, dead ones between live ones)
    # the live slots' attention and rows are those above; a dead slot
    # gives zeros and its pool is untouched.
    live = np.arange(b) % 2 == 1
    masked = write_in_read(
        q, [jnp.copy(c) for c in stacked], fresh, pos, jnp.asarray(live)
    )
    for name, g, w, old in zip(
        ("attn", "kq", "ks", "vq", "vs"), masked, want, [None, *stacked]
    ):
        g, w = np.asarray(g), np.asarray(w)
        if old is None:
            ok = np.array_equal(g[live], w[live]) and not g[~live].any()
        else:
            old = np.asarray(old)
            ok = (
                np.array_equal(g[1][live], w[1][live])
                and np.array_equal(g[1][~live], old[1][~live])
                and np.array_equal(g[0], old[0])
            )
        _require(ok, f"dynlen_live: {name} differs under a live mask")

    # The grouped expert matmul (ops/moe.py: the gated pair's kernel and
    # the down projection's) against ``lax.ragged_dot`` over the same
    # sorted rows, the layer's experts the SECOND half of their stacks. A
    # row that is half padding routes as the cells' do: the padding's
    # tokens all choose the same k experts, the rest k at random.
    from torchkafka_tpu.ops import moe

    for n, k, e, d, f in sz.gmm_shapes:
        gmm_rng = np.random.default_rng(2)
        idx = np.stack([gmm_rng.permutation(e)[:k] for _ in range(n)])
        idx[: n // 2] = gmm_rng.permutation(e)[:k]
        idx = jnp.asarray(idx, jnp.int32)
        x = jnp.asarray(gmm_rng.normal(size=(n, d)), dt)
        w = jnp.full((n, k), 1.0 / k, jnp.float32)
        mats = [
            jnp.asarray(gmm_rng.normal(size=(2 * e, *s)) / np.sqrt(s[0]), dt)
            for s in ((d, f), (d, f), (f, d))
        ]

        @jax.jit
        def by_ragged_dot(x, idx, w, w_gate, w_up, w_down, e=e, k=k, n=n):
            flat = idx.reshape(-1)
            order = jnp.argsort(flat, stable=True)
            sizes = jnp.zeros((2 * e,), jnp.int32).at[e + flat].add(1)
            rows = x[order // k]
            gate = jax.nn.silu(jax.lax.ragged_dot(rows, w_gate, sizes))
            up = jax.lax.ragged_dot(rows, w_up, sizes)
            out = jax.lax.ragged_dot(gate * up, w_down, sizes)
            inverse = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
            out = out[inverse].reshape(n, k, -1).astype(jnp.float32)
            return jnp.einsum("nkd,nk->nd", out, w).astype(x.dtype)

        got = jax.jit(
            lambda x, idx, w, *m, e=e: moe.grouped_experts(
                x, idx, w, *m, (jnp.int32(e), e)
            )
        )(x, idx, w, *mats)
        close(f"grouped_matmul_{n}x{d}x{f}", got, by_ragged_dot(x, idx, w, *mats))
        del mats

    # Flash forward and backward (GQA, causal) against the dense XLA body.
    seq = sz.train_seq
    fq = jnp.asarray(rng.normal(size=(2, seq, 4, dh)), dt)
    fk = jnp.asarray(rng.normal(size=(2, seq, 2, dh)), dt)
    fv = jnp.asarray(rng.normal(size=(2, seq, 2, dh)), dt)

    def dense(q, k, v):
        return mha(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    got = jax.jit(jax.value_and_grad(loss(flash_attention), (0, 1, 2)))(fq, fk, fv)
    want = jax.jit(jax.value_and_grad(loss(dense), (0, 1, 2)))(fq, fk, fv)
    for name, g, w in zip(
        ("loss", "dq", "dk", "dv"), (got[0], *got[1]), (want[0], *want[1])
    ):
        close(f"flash_{name}", g, w)
    return {"dtype": str(jnp.dtype(dt)), "tolerance": tol, "rel_err": err}


# -------------------------------------------------------------------- serve


def _serve_once(
    tk, params, cfg, mesh, *, group: str, slots: int, prompt_len: int,
    max_new: int, records: int, expect_layout: str | None, cache,
    with_summary: bool = False, **kv,
) -> dict:
    """One server over a fresh 2-partition prompt topic: warm up, serve
    ``records`` prompts to completion, check tokens, commits and (for the
    kernel servers) that the Pallas read is what actually served."""
    from torchkafka_tpu.serve import StreamingGenerator

    broker = tk.InMemoryBroker()
    broker.create_topic("prompts", partitions=2)
    rng = np.random.default_rng(1)
    for i in range(records):
        prompt = rng.integers(0, cfg.vocab_size, prompt_len, dtype=np.int32)
        broker.produce("prompts", prompt.tobytes(), partition=i % 2)
    consumer = tk.MemoryConsumer(broker, "prompts", group_id=group)
    server = StreamingGenerator(
        consumer, params, cfg, slots=slots, prompt_len=prompt_len,
        max_new=max_new, commit_every=16, mesh=mesh, **kv,
    )
    t0 = time.perf_counter()
    server.warmup()
    compile_s = time.perf_counter() - t0
    served = 0
    for _rec, toks in server.run(max_records=records):
        toks = np.asarray(toks)
        _require(toks.shape == (max_new,), f"{group}: completion of {toks.shape}")
        _require(
            ((toks >= 0) & (toks < cfg.vocab_size)).all(),
            f"{group}: token outside the vocabulary",
        )
        served += 1
    summary = server.metrics.summary()
    server.close()
    consumer.close()
    _require(served == records, f"{group}: served {served}/{records}")
    _require(
        summary["commit_failures"] == 0,
        f"{group}: {summary['commit_failures']} commit failures",
    )
    committed = {}
    for p in (0, 1):
        tp = tk.TopicPartition("prompts", p)
        committed[p] = broker.committed(group, tp)
        _require(
            committed[p] == broker.end_offset(tp),
            f"{group}: partition {p} committed {committed[p]}, "
            f"end offset {broker.end_offset(tp)}",
        )
    backend = summary["kv_backend"]
    if expect_layout is not None:
        # The paged build quietly rebuilds dense when the pool is small,
        # and "auto" quietly keeps the XLA read: neither may pass here.
        _require(
            backend["kernel"] is True
            and backend["kernel_engaged"] == 1
            and backend["kernel_disabled_reason"] is None
            and backend["layout"] == expect_layout
            and summary["prefix_cache"]["fallbacks"] == 0,
            f"{group}: the Pallas read did not serve: {backend}, "
            f"fallbacks={summary['prefix_cache']['fallbacks']}",
        )
    return {
        "completions": served,
        "tokens": served * max_new,
        "committed": committed,
        "compile_s": round(compile_s, 1),
        **cache.take(),
        "kv_backend": {
            k: backend[k] for k in
            ("layout", "kv_dtype", "kernel", "kernel_engaged",
             "kernel_disabled_reason", "data", "tp")
        },
        "peak_bytes_in_use": _peak_bytes(),
        **({"summary": summary} if with_summary else {}),
    }


def run_serve(tk, sz: Sizes, mesh, cache) -> None:
    import jax

    from torchkafka_tpu.kvcache import PagedKVConfig
    from torchkafka_tpu.models.generate import serving_shardings
    from torchkafka_tpu.models.zoo import (
        params_nbytes,
        random_serving_params,
        zoo_config,
    )

    pool = sz.pool_prompt_len + sz.pool_max_new
    max_seq = max(sz.prompt_len + sz.max_new, pool)
    cfg = (
        zoo_config(sz.serve_scale, max_seq_len=max_seq)
        if sz.serve_scale else _toy_config(max_seq)
    )
    t0 = time.perf_counter()
    # Exactly as harness/scenarios.py:_serving_model builds it.
    params = random_serving_params(jax.random.key(0), cfg, quantized=True)
    if mesh is not None:
        params = jax.device_put(params, serving_shardings(cfg, mesh, params))
    jax.block_until_ready(params)
    _require_every_device_holds_data()
    _report("serve.params", {
        "model": sz.serve_scale or "toy",
        "layers": cfg.n_layers, "d_model": cfg.d_model,
        "heads": [cfg.n_heads, cfg.n_kv_heads, cfg.head_dim],
        "d_ff": cfg.d_ff, "vocab": cfg.vocab_size,
        "params_bytes": params_nbytes(params),
        "build_s": round(time.perf_counter() - t0, 1),
        **cache.take(), "peak_bytes_in_use": _peak_bytes(),
    })

    _report("serve.main", _serve_once(
        tk, params, cfg, mesh, group="smoke-serve", slots=sz.slots,
        prompt_len=sz.prompt_len, max_new=sz.max_new, records=sz.records,
        expect_layout=None, cache=cache,
    ))
    gc.collect()  # one pool at a time beside the weights
    common = dict(
        slots=sz.pool_slots, prompt_len=sz.pool_prompt_len,
        max_new=sz.pool_max_new, records=sz.pool_records, cache=cache,
        kv_dtype="int8", kv_kernel=sz.kv_kernel,
    )
    _report("serve.dense_int8_kernel", _serve_once(
        tk, params, cfg, mesh, group="smoke-dense-int8",
        expect_layout="dense", **common,
    ))
    gc.collect()
    blocks_per_slot = -(-pool // sz.block_size)
    _report("serve.paged_int8_kernel", _serve_once(
        tk, params, cfg, mesh, group="smoke-paged-int8",
        expect_layout="paged",
        kv_pages=PagedKVConfig(
            block_size=sz.block_size,
            # Every slot's worst case, the sink, and one slot's worth of
            # slack for the radix tree to keep a prefix alive.
            num_blocks=(sz.pool_slots + 1) * blocks_per_slot + 1,
        ),
        **common,
    ))


def run_serve_share(tk, sz: Sizes, cache) -> None:
    """The double-layer family on the normal path at toy size (the real
    head widths, 192 and 128, so the chip compiles flash at them): one
    device whatever the mesh, as such a config refuses one."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models import TransformerConfig
    from torchkafka_tpu.models.transformer import init_params

    dtype = jnp.bfloat16 if sz.serve_scale else jnp.float32
    cfg = TransformerConfig(
        vocab_size=512, d_model=256, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=512, max_seq_len=sz.prompt_len + sz.max_new, dtype=dtype,
        param_dtype=dtype, kv_lora_rank=128, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, rope_interleave=True,
        q_lora_rank=128, mla_scale_q_lora=True, mla_scale_kv_lora=True,
        attn_blocks=2, n_experts=16, zero_experts=8, expert_top_k=4,
        expert_d_ff=128, router_score="softmax", norm_topk=False,
        routed_scaling=6.0, experts_held=(4, 4),
    )
    params = init_params(jax.random.key(2), cfg)
    facts = _serve_once(
        tk, params, cfg, None, group="smoke-double-layer-share",
        slots=sz.slots, prompt_len=sz.prompt_len, max_new=sz.max_new,
        records=sz.pool_records, expect_layout=None, cache=cache,
        with_summary=True,
    )
    summary = facts.pop("summary")
    experts, pool = summary["expert_layer"], summary["latent_pool"]
    fates = [experts[f"moe_{f}_assignments"] for f in ("zero", "local", "absent")]
    _require(
        facts["kv_backend"]["layout"] == "latent" and pool["attn_blocks"] == 2
        and experts["experts_held"] == [4, 4],
        f"smoke-double-layer-share: {facts['kv_backend']}, {pool}, {experts}",
    )
    _require(
        min(fates) > 0 and sum(fates) == experts["moe_assignments"],
        f"smoke-double-layer-share: pairs by fate {fates} of "
        f"{experts['moe_assignments']}",
    )
    _report("serve.double_layer_share", {
        **facts, "pairs_zero_local_absent": fates,
        "latent_positions_valid": pool["latent_positions_valid"],
    })


def run_serve_kinds(tk, sz: Sizes, cache) -> None:
    """Kinds of layer on the normal path at toy size (heads of 128, wider
    than ``d_model / n_heads``, so the chip compiles the windowed flash
    call at them): three sliding-window layers to one full YaRN layer over
    the pool by kind, the routed expert layer beside grouped-query
    attention, served until every ring has wrapped more than twice and
    held token for token against the full forward's greedy choices
    (float32 at the highest matmul precision, so a near-tie cannot flip)."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models import Transformer, TransformerConfig
    from torchkafka_tpu.models.transformer import RopeKind, init_params
    from torchkafka_tpu.serve import StreamingGenerator

    window = 16 if sz.serve_scale else 4
    prompt_len, max_new, records = sz.prompt_len, 3 * window + 4, sz.pool_records
    cfg = TransformerConfig(
        vocab_size=512, d_model=256, n_layers=8, n_heads=4, n_kv_heads=2,
        d_ff=512, max_seq_len=prompt_len + max_new, dtype=jnp.float32,
        param_dtype=jnp.float32, rope_theta=500000.0, stated_head_dim=128,
        sliding_window=window, window_pattern=(True, True, True, False),
        rope_full=RopeKind(
            500000.0, factor=16.0, original_len=64, attention_factor=1.2773,
        ),
        n_experts=8, expert_top_k=2, expert_d_ff=128,
    )
    group = "smoke-kinds"
    with jax.default_matmul_precision("highest"):
        params = init_params(jax.random.key(3), cfg)
        broker = tk.InMemoryBroker()
        broker.create_topic("prompts", partitions=2)
        rng = np.random.default_rng(4)
        prompts = rng.integers(1, cfg.vocab_size, (records, prompt_len), dtype=np.int32)
        sent = {}
        for i, prompt in enumerate(prompts):
            r = broker.produce("prompts", prompt.tobytes(), partition=i % 2)
            sent[(r.partition, r.offset)] = i
        consumer = tk.MemoryConsumer(broker, "prompts", group_id=group)
        server = StreamingGenerator(
            consumer, params, cfg, slots=sz.pool_slots, prompt_len=prompt_len,
            max_new=max_new, commit_every=16,
        )
        rows = np.zeros((records, prompt_len + max_new), np.int32)
        rows[:, :prompt_len] = prompts
        for rec, toks in server.run(max_records=records):
            rows[sent[(rec.partition, rec.offset)], prompt_len:] = toks
        summary = server.metrics.summary()
        server.close()
        consumer.close()
        logits = jax.jit(Transformer(cfg).__call__)(params, jnp.asarray(rows))
        greedy = np.asarray(jnp.argmax(logits[:, prompt_len - 1: -1], axis=-1))
    agree = float((greedy == rows[:, prompt_len:]).mean())
    pool, experts = summary["kv_pool"], summary["expert_layer"]
    _require(
        summary["kv_backend"]["layout"] == "by_kind"
        and (pool["window"], pool["window_layers"], pool["full_layers"])
        == (window, 6, 2),
        f"{group}: {summary['kv_backend']}, {pool}",
    )
    _require(
        agree == 1.0,
        f"{group}: {agree:.4f} of the served tokens are the full forward's "
        f"greedy choices after {max_new / window:.1f} wraps of the ring",
    )
    _require(
        experts["moe_assignments"] == sum(experts["moe_expert_load"]) > 0,
        f"{group}: {experts}",
    )
    _report("serve.layer_kinds", {
        "completions": records, "tokens": records * max_new,
        "ring_wraps": round(max_new / window, 1), "greedy_agreement": agree,
        "window_positions_valid": pool["window_positions_valid"],
        "full_positions_valid": pool["full_positions_valid"],
        "moe_assignments": experts["moe_assignments"],
        **cache.take(), "peak_bytes_in_use": _peak_bytes(),
    })


# -------------------------------------------------------------------- train


def run_train(tk, sz: Sizes, mesh, cache) -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from torchkafka_tpu.models import Transformer, make_train_step
    from torchkafka_tpu.models.transformer import count_params
    from torchkafka_tpu.models.zoo import zoo_config

    n_dev = len(jax.devices())
    seq, batch, steps = sz.train_seq, sz.train_batch, TRAIN_STEPS
    if sz.train_scale:
        # Full depth (1b: all 24 layers fit one 16 GB chip). remat: one
        # layer's activations live at a time, so the memory goes to
        # weights + grads + AdamW state, not to saved activations.
        cfg = dataclasses.replace(
            zoo_config(sz.train_scale, max_seq_len=seq), remat=True
        )
    else:
        # attn_impl="flash": "auto" picks flash only on a TPU backend.
        cfg = _toy_config(seq, attn_impl="flash")
    _require(
        Transformer(cfg, mesh)._use_flash,
        f"flash attention did not engage at seq {seq} on mesh "
        f"{dict(mesh.shape)}: the train step would run the dense body",
    )

    parts = 8
    broker = tk.InMemoryBroker()
    broker.create_topic("tokens", partitions=parts)
    rng = np.random.default_rng(2)
    rows = steps * batch
    for i in range(rows):  # exactly what the steps consume, spread evenly
        toks = rng.integers(0, cfg.vocab_size, seq, dtype=np.int32)
        broker.produce("tokens", toks.tobytes(), partition=i % parts)
    consumer = tk.MemoryConsumer(
        broker, "tokens", group_id="smoke-train",
        assignment=tk.partitions_for_process("tokens", parts, 0, 1),
    )

    t0 = time.perf_counter()
    init_fn, step_fn = make_train_step(cfg, mesh, optax.adamw(1e-4))
    params, opt_state = init_fn(jax.random.key(0))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    n_params = count_params(params)

    losses, step_s = [], []
    with tk.KafkaStream(
        consumer, tk.fixed_width(seq, np.int32), batch_size=batch, mesh=mesh,
        idle_timeout_ms=2000, owns_consumer=True,
    ) as stream:
        it = iter(stream)
        for _ in range(steps):
            batch_, token = next(it)
            _require(
                batch_.data.shape == (batch, seq),
                f"batch of {batch_.data.shape}",
            )
            _require(
                len(batch_.data.sharding.device_set) == n_dev,
                f"batch lives on {len(batch_.data.sharding.device_set)} of "
                f"{n_dev} devices",
            )
            mask = jnp.ones((batch, seq), jnp.int32)
            t0 = time.perf_counter()
            params, opt_state, loss = step_fn(
                params, opt_state, batch_.data, mask
            )
            _require(token.commit(wait_for=loss), "offset commit failed")
            step_s.append(time.perf_counter() - t0)
            losses.append(float(loss))
            _require(np.isfinite(losses[-1]), f"non-finite loss {losses}")
    committed = {
        p: broker.committed("smoke-train", tk.TopicPartition("tokens", p))
        for p in range(parts)
    }
    # Watermarks are next-read offsets and consumption is contiguous from
    # 0, so their sum is exactly the rows the steps consumed: commits
    # neither lost records nor ran ahead of the barrier.
    _require(
        sum(o or 0 for o in committed.values()) == rows,
        f"committed watermarks {committed} do not cover exactly {rows} rows",
    )
    in_use = _require_every_device_holds_data()
    _report("train", {
        "model": sz.train_scale or "toy",
        "layers": cfg.n_layers, "d_model": cfg.d_model,
        "heads": [cfg.n_heads, cfg.n_kv_heads, cfg.head_dim],
        "d_ff": cfg.d_ff, "vocab": cfg.vocab_size, "params": n_params,
        "mesh": dict(mesh.shape), "use_flash": True,
        "steps": steps, "tokens": rows * seq,
        "losses": [round(x, 4) for x in losses],
        "committed": committed,
        "init_s": round(init_s, 1),
        # First step = compile + run; the rest say what a step costs here.
        "compile_s": round(step_s[0] - float(np.median(step_s[1:])), 1),
        **cache.take(),
        "bytes_in_use": in_use,
        "peak_bytes_in_use": _peak_bytes(),
    })


# --------------------------------------------------------------------- main


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", type=int, metavar="N", default=None,
        help="debug this script: toy sizes on N virtual CPU devices, "
        "kernels interpreted; prints no 'ok'",
    )
    args = ap.parse_args(argv)

    from torchkafka_tpu.utils.devices import (
        enable_compile_cache,
        force_cpu_devices,
        require_tpu,
    )

    cache_dir = enable_compile_cache()
    if args.rehearse_cpu is not None:
        force_cpu_devices(args.rehearse_cpu)
        sz = REHEARSAL
    else:
        try:
            require_tpu()
        except RuntimeError as e:
            raise SystemExit(f"chip_smoke: {e}") from None
        sz = CHIP

    import jax

    import torchkafka_tpu as tk
    from torchkafka_tpu import native

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    cache = CacheCounter()
    _report("gate", {
        **device,
        **{pkg: _installed(pkg) for pkg in ("jax", "jaxlib", "libtpu")},
        "python": sys.version.split()[0],
        "compile_cache_dir": cache_dir,
        "native_available": native.available(),
    })
    if not native.available():
        raise RuntimeError(
            "the native decoder did not build (g++ missing?): the ingest "
            "path would run its NumPy fallback"
        )

    n_dev = device["count"]
    if n_dev == 1:
        serve_mesh, train_mesh = None, tk.make_mesh({"data": 1})
    else:
        serve_mesh = train_mesh = tk.make_mesh({"data": n_dev // 2, "tp": 2})

    t0 = time.perf_counter()
    _report("kernels", {**check_kernels(sz), **cache.take()})
    run_serve(tk, sz, serve_mesh, cache)
    gc.collect()
    run_serve_share(tk, sz, cache)
    gc.collect()
    run_serve_kinds(tk, sz, cache)
    gc.collect()
    run_train(tk, sz, train_mesh, cache)
    _report("done", {"total_s": round(time.perf_counter() - t0, 1)})

    verdict = {"rehearsal": True} if args.rehearse_cpu is not None else {"ok": True}
    print(json.dumps({**verdict, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
