"""Speculative decoding: draft-model proposal, single-dispatch verify.

A small DRAFT model proposes ``k`` greedy tokens autoregressively (k tiny
decode dispatches on cheap weights); the TARGET model then scores all
k+1 positions in ONE multi-query decode step (one full weight stream for
up to k+1 tokens of progress) and accepts the longest prefix that
matches its own greedy choices, emitting one correction/bonus token from
its own logits. (The draft actually runs k+1 steps — the last only
ingests its k-th proposal so its cache stays contiguous across
full-accept rounds; see the in-body comment.) GREEDY ONLY, which buys
the strong contract: the emitted sequence is EXACTLY the target model's
greedy continuation for ANY draft sharing the vocab — the draft affects
only SPEED (via its acceptance rate), never content (differential-tested
in tests/test_spec.py).

Why this shape on TPU: decode is weight-streaming bound (PERF.md's
serving rooflines), so the unit of cost is "one full read of the target
weights". Plain decode buys 1 token per read; verify buys 1 + (accepted)
tokens per read for the same stream (the extra k query positions ride
the same weight tiles through the MXU), plus k+1 draft reads at
draft/target cost ratio. Expected speedup = E[accepted + 1] /
((k+1)·c + 1 + v) with c = draft/target tick ratio and v the multi-query
overhead — both are to be measured (harness scenario 7 ``--spec``
reports the acceptance), not assumed. Everything is static-shape: the per-round emission count is
dynamic but lives in POSITION BOOKKEEPING (per-row emitted counters and
a one-hot scatter into a padded buffer), not in array shapes, so the
whole loop jits as one ``lax.while_loop`` (guaranteed ≥1 token per
round, so it terminates in ≤ max_new rounds).

Cache discipline (the subtle part): both models' caches are written
SPECULATIVELY — verify writes k/v for all k+1 inputs, the draft for all
its k proposals — and rejected positions simply become STALE entries
beyond the per-row accepted watermark. Correctness holds because (a)
every attention masks by position against the watermark, so stale
entries are never read before (b) the next round's writes overwrite
them, write-before-attend, starting exactly at the watermark. Rollback
is therefore free: it IS the position bookkeeping. Caches are sized
S + max_new + 2k (overshoot margin: a round may start at position
S + emitted - 1 with emitted ≤ max_new + k after its own overshoot).

No reference analog (the reference ships no model code — SURVEY.md §2);
net-new TPU capability extending BASELINE config 5's generate consumer.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from torchkafka_tpu.models.generate import (
    KVCache,
    _attend_cached,
    _project_qkv,
    prefill,
)
from torchkafka_tpu.models.quant import embed_rows, load_weight
from torchkafka_tpu.models.transformer import (
    TransformerConfig,
    _rms_norm,
    _rope,
)


def truncated_draft(params, cfg: TransformerConfig, n_layers: int):
    """(draft_params, draft_cfg): the standard self-speculative cheap
    draft — the target's FIRST ``n_layers`` layers with its own
    embedding/final-norm/lm_head (all shared by reference, no copy).
    For a trained checkpoint this is the classic layer-skip draft
    (early layers carry most next-token signal); with random weights
    its acceptance is chance-level like any other draft — the
    exactness contract holds either way. Layer params are stacked
    [L, ...] leaves, so truncation is a leading-axis slice."""
    if not (1 <= n_layers <= cfg.n_layers):
        raise ValueError(
            f"n_layers must be in [1, {cfg.n_layers}], got {n_layers}"
        )
    draft_params = dict(params)
    draft_params["layers"] = jax.tree_util.tree_map(
        lambda x: x[:n_layers], params["layers"]
    )
    return draft_params, dataclasses.replace(cfg, n_layers=n_layers)


class SpecStats(NamedTuple):
    """Per-run counters (device arrays inside jit; ints after fetch)."""

    rounds: jax.Array     # verify dispatches executed
    accepted: jax.Array   # draft tokens accepted across all rows/rounds
    proposed: jax.Array   # draft tokens proposed across all rows/rounds


def _multi_step(params, cfg, cache: KVCache, tokens, pos_b):
    """S-query decode step at PER-ROW start positions: tokens [B, S]
    (token s sits at sequence position pos_b + s), writes k/v for all S
    inputs at [pos_b, pos_b + S), returns logits [B, S, V] (position
    pos_b + s + 1 predictions) and the updated cache. S=1 is exactly a
    per-row decode tick; S=k+1 is spec decode's verify. Queries mask
    causally per row (query s reads cache [0, pos_b + s]).

    Sibling implementations (update in step if the write/mask discipline
    changes): generate._layer_step (scalar-pos lockstep) and
    slot_pool._slot_layer_step (per-row S=1, the measured serving tick —
    kept separate so spec-decode changes can never shift its published
    numbers)."""
    b, s = tokens.shape
    x = embed_rows(params["embed"], tokens, cfg.dtype)  # [B, S, D]
    positions = pos_b[:, None] + jnp.arange(s)[None, :]  # [B, S]

    rows = jnp.arange(b)[:, None]  # [B, 1] against positions [B, S]

    def body(x, inputs):
        layer, ck, cv = inputs
        q, k, v = _project_qkv(x, layer, cfg)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        # Scatter writes (slot_pool._slot_layer_step's note: the vmapped
        # dynamic_update_slice lowering rewrites the whole pool per
        # layer; the scatter writes S rows per slot — measured +41%
        # tok/s on the 1B serving tick).
        ck = ck.at[rows, positions].set(k.astype(ck.dtype))
        cv = cv.at[rows, positions].set(v.astype(cv.dtype))
        valid = (
            jnp.arange(ck.shape[1])[None, None, :] <= positions[:, :, None]
        )  # [B, S, M] per-query causal masks
        x = _attend_cached(x, q, ck, cv, valid, layer, cfg)
        return x, (ck, cv)

    x, (ck, cv) = lax.scan(body, x, (params["layers"], cache.k, cache.v))
    x = _rms_norm(x, params["ln_f"])
    logits = jnp.einsum(
        "bsd,dv->bsv", x, load_weight(params["lm_head"], cfg.dtype),
        preferred_element_type=jnp.float32,
    )
    return logits, KVCache(ck, cv)


def speculative_generate(
    target_params,
    target_cfg: TransformerConfig,
    draft_params,
    draft_cfg: TransformerConfig,
    prompt: jax.Array,
    max_new: int,
    *,
    k: int = 4,
):
    """prompt [B, S] int32 → (tokens [B, max_new] int32, SpecStats).

    ``tokens`` is EXACTLY ``generate(target_params, target_cfg, prompt,
    max_new)`` (greedy) up to f32 reduction order; the draft model only
    sets the speed. ``k``: draft tokens proposed per verify dispatch.
    Jit-friendly (static prompt length, max_new, k); quantized (QTensor)
    trees serve unchanged on either side.
    """
    if target_cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError(
            f"draft and target must share a vocab: "
            f"{draft_cfg.vocab_size} != {target_cfg.vocab_size}"
        )
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if max_new < 2:
        raise ValueError("max_new must be >= 2 (prefill emits token 0)")
    batch, seq = prompt.shape
    max_len = seq + max_new + 2 * k  # overshoot margin, see module docstring
    buf = max_new + k + 1

    t_logits0, t_cache = prefill(target_params, target_cfg, prompt, max_len)
    _d_logits0, d_cache = prefill(draft_params, draft_cfg, prompt, max_len)
    tok0 = jnp.argmax(t_logits0, axis=-1).astype(jnp.int32)  # [B]

    gen0 = jnp.zeros((batch, buf), jnp.int32)
    gen0 = gen0.at[:, 0].set(tok0)
    emitted0 = jnp.ones((batch,), jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    carry0 = (t_cache, d_cache, tok0, emitted0, gen0, zero, zero, zero)

    def cond(carry):
        _, _, _, emitted, _, _, _, _ = carry
        return jnp.any(emitted < max_new)

    def body(carry):
        t_cache, d_cache, last_tok, emitted, gen, rounds, acc, prop = carry
        act = emitted < max_new  # [B]
        base = seq + emitted - 1  # position of the last emitted token

        def dbody(c, j):
            d_cache, tok = c
            logits, d_cache = _multi_step(
                draft_params, draft_cfg, d_cache, tok[:, None], base + j
            )
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (d_cache, nxt), nxt

        # k+1 draft steps for k proposals: the LAST step only INGESTS
        # d_k (its own output is discarded) so the draft cache stays
        # contiguous after a full-accept round — without it, position
        # base+k (= accepted d_k) would never receive draft k/v and the
        # next round's draft would attend over a stale hole (caught by
        # the perfect-draft test: acceptance collapsed to ~50%).
        (d_cache, _), d_toks = lax.scan(
            dbody, (d_cache, last_tok), jnp.arange(k + 1)
        )
        d = jnp.transpose(d_toks[:k])  # [B, k]

        v_in = jnp.concatenate([last_tok[:, None], d], axis=1)  # [B, k+1]
        t_logits, t_cache = _multi_step(
            target_params, target_cfg, t_cache, v_in, base
        )
        tga = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)  # [B, k+1]

        match = tga[:, :k] == d
        n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        corr = jnp.take_along_axis(tga, n_acc[:, None], axis=1)[:, 0]  # [B]

        # Emit d[:, :n_acc] then the correction/bonus — a static loop of
        # one-hot row writes over the tiny [B, buf] buffer (measured at
        # parity with scatter on buffers this size — serve.py's gen
        # write; the POOL writes above use scatters, where it matters),
        # masked per row by j <= n_acc and activity.
        idx = jnp.arange(buf)[None, :]
        for j in range(k + 1):
            tok_j = d[:, j] if j < k else corr
            tok_j = jnp.where(j < n_acc, tok_j, corr)
            write = act & (j <= n_acc)
            sel = (idx == (emitted + j)[:, None]) & write[:, None]
            gen = jnp.where(sel, tok_j[:, None], gen)

        last_tok = jnp.where(act, corr, last_tok)
        n_act = jnp.sum(act.astype(jnp.int32))
        emitted = emitted + jnp.where(act, n_acc + 1, 0)
        rounds = rounds + (n_act > 0).astype(jnp.int32)
        acc = acc + jnp.sum(jnp.where(act, n_acc, 0))
        prop = prop + k * n_act
        return (t_cache, d_cache, last_tok, emitted, gen, rounds, acc, prop)

    (_, _, _, _, gen, rounds, acc, prop) = lax.while_loop(cond, body, carry0)
    return gen[:, :max_new], SpecStats(rounds, acc, prop)
