"""Speculative continuous-batching serving: spec decode inside the slot server.

Composes the repo's two flagship inference features, which had never met:
``models/spec_decode.py`` (draft k tokens, verify all k+1 positions in ONE
multi-query target dispatch, accept the longest matching prefix) and
``serve.py``'s ``StreamingGenerator`` (fixed slot pool over a Kafka prompt
topic, per-completion offset retirement through the interval ledger). The
result is the combination every production server runs — continuous
batching + speculation — as a drop-in server: ``SpecStreamingGenerator``
replaces one class name and everything else (admission loop, commit
cadence, output topic, chaos behavior, metrics) is inherited UNCHANGED.

How the composition works: ``StreamingGenerator.run()`` treats the slot
state as an OPAQUE tuple threaded through ``self._admit_fn`` /
``self._tick_fn``. This subclass only overrides ``_build`` to install a
speculative admit/tick pair whose state tuple carries (target pool, draft
pool, acceptance counters); the run loop cannot tell the difference. One
"tick" becomes one SPECULATIVE ROUND per active slot:

1. the draft proposes k greedy tokens autoregressively (k+1 cheap
   single-query steps — the last only ingests proposal k so the draft
   cache stays contiguous across full-accept rounds, spec_decode's rule);
2. the target scores all k+1 positions in one ``_multi_step`` verify
   (per-row start positions — exactly the serving tick generalised to
   S = k+1 queries);
3. per slot, the longest draft prefix matching the target's own argmax is
   accepted and the target's correction/bonus token appended — every
   emitted token is the TARGET's greedy choice, so the server is
   token-exact vs the plain ``StreamingGenerator`` (greedy) and the draft
   sets only the speed (differential-tested in tests/test_serve_spec.py).

Static shapes throughout, the serving discipline: the round emits a
DYNAMIC per-slot count (1..k+1) but it lives in position bookkeeping —
``pos`` advances by the per-slot accepted length, the gen buffer takes a
static k+1-step masked one-hot write, EOS stops emission mid-round via a
static cumulative mask. Rollback is free exactly as in spec_decode: both
pools are written speculatively and rejected positions become stale
entries beyond the per-slot watermark, overwritten write-before-attend by
the next round (the pool carries a k-position overshoot margin).

Commit semantics are untouched BY CONSTRUCTION: completions retire
offsets through the same ledger calls in the inherited ``run()``, so
at-least-once-per-prompt and commit-watermark exactness hold under
speculation — including under injected commit failures (chaos-tested:
speculation never changes which offsets commit).

Greedy-only (temperature=0): the exactness contract is what makes the
draft a pure speed knob. Compute-dtype KV only (int8 pools and the
int8-only Pallas read are validated out with a clear error — both give
up or bypass the exactness contract speculation is built on), but the
MESH composes: both models' params commit to their serving layouts,
the verify/draft multi-query math is plain XLA, and GSPMD shards it
from the layouts alone — token-exact vs single-device spec serving
(differential-tested), dense and paged pools alike.

Measured acceptance is a first-class output: the state tuple carries
device-side (rounds, proposed, accepted) counters and ``spec_stats()``
reports them, so harness scenario 7 ``--spec`` publishes the MEASURED α
of a real checkpoint, not a hypothetical point on the i.i.d. curve.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from torchkafka_tpu.models.generate import KVCache, _project_qkv, prefill
from torchkafka_tpu.models.quant import embed_rows, load_weight
from torchkafka_tpu.models.spec_decode import _multi_step, truncated_draft
from torchkafka_tpu.models.transformer import _rms_norm, _rope
from torchkafka_tpu.resilience.crashpoint import crash_hook
from torchkafka_tpu.serve import StreamingGenerator


class SpecStreamingGenerator(StreamingGenerator):
    """Continuous-batching server that decodes speculatively per slot.

    ``draft_params``/``draft_cfg``: any same-vocab draft model (given
    together), or omit both to build the self-speculative layer-skip
    draft — ``truncated_draft(params, cfg, draft_layers)`` — from the
    target itself (``draft_layers`` defaults to half the target's
    layers). ``k``: draft tokens proposed per verify dispatch.
    ``ticks_per_sync`` now counts speculative ROUNDS per device dispatch
    (each round advances an active slot by 1..k+1 tokens, vs exactly 1
    for a plain tick).
    """

    def __init__(
        self,
        consumer,
        params,
        cfg,
        *,
        draft_params=None,
        draft_cfg=None,
        draft_layers: int | None = None,
        k: int = 4,
        **kwargs,
    ) -> None:
        from torchkafka_tpu.models.transformer import _arch_refusal

        why = _arch_refusal(cfg, "speculative serving")
        if why:
            raise ValueError(why)
        if kwargs.get("temperature", 0.0) != 0.0:
            raise ValueError(
                "speculative serving is greedy-only: the accept rule "
                "compares the draft against the target's argmax, which is "
                "what buys token-exactness vs plain serving (sampled "
                "speculation needs the rejection-sampling rule — not "
                "implemented)"
            )
        if kwargs.get("kv_dtype") is not None:
            raise ValueError(
                "speculative serving keeps the compute-dtype slot pool: "
                "int8 KV gives up token-exactness, the one contract "
                "speculation is built on"
            )
        if kwargs.get("kv_kernel", "auto") is True:
            raise ValueError(
                "kv_kernel=True cannot be honored: the Pallas decode "
                "kernel reads one query per slot, not the k+1-query verify"
            )
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if (draft_params is None) != (draft_cfg is None):
            raise ValueError(
                "draft_params and draft_cfg must be given together "
                "(or neither, for the layer-truncated self-draft)"
            )
        if draft_params is None:
            if draft_layers is None:
                draft_layers = max(1, cfg.n_layers // 2)
            draft_params, draft_cfg = truncated_draft(params, cfg, draft_layers)
        elif draft_layers is not None:
            raise ValueError(
                "draft_layers applies to the self-truncated draft only — "
                "an explicit draft_params/draft_cfg pair already fixes "
                "the draft's depth"
            )
        if draft_cfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft and target must share a vocab: "
                f"{draft_cfg.vocab_size} != {cfg.vocab_size}"
            )
        if kwargs.get("mesh") is not None:
            # Model-sharded spec serving: the DRAFT commits to the same
            # serving layouts as the target (the base __init__ places
            # the target tree); the verify/draft multi-query math is
            # plain XLA, so GSPMD shards it from the layouts alone —
            # exactly the dense server's design rule. Both models must
            # satisfy the mesh divisibilities.
            from torchkafka_tpu.models.generate import (
                check_serving_mesh,
                serving_shardings,
            )

            mesh = kwargs["mesh"]
            check_serving_mesh(
                draft_cfg, mesh, batch=kwargs.get("slots", 8)
            )
            draft_params = jax.device_put(
                draft_params, serving_shardings(draft_cfg, mesh, draft_params)
            )
        self._k = int(k)
        self._draft_params = draft_params
        self._draft_cfg = draft_cfg
        super().__init__(consumer, params, cfg, **kwargs)

    def _build(self) -> None:
        cfg, dcfg, k = self._cfg, self._draft_cfg, self._k
        B, P = self._slots, self._prompt_len
        max_new = self._max_new
        eos_id = self._eos_id
        # Overshoot margin: a round starting at the per-slot watermark
        # ``pos`` (<= P + max_new - 2 for a slot still active) writes
        # verify k/v at [pos, pos + k] — stale beyond the accepted length,
        # overwritten write-before-attend next round, but the pool must
        # hold them. (RoPE beyond cfg.max_seq_len is extrapolation only
        # for those never-attended stale tails.)
        self._max_len = M = P + max_new + k
        self._kv_kernel = False  # the base flag; never engaged here
        # The resolved backend for metrics (spec pools are compute-dtype
        # by validation, so the kernel never engages; pages and mesh
        # compose — the probe validates the same exclusions as the base).
        from torchkafka_tpu.kvcache import resolve_kv_backend

        self._kv_backend = resolve_kv_backend(
            cfg, mesh=self._mesh, kv_dtype=None,
            kv_kernel=self._kv_kernel_opt, kv_pages=self._kv_pages,
            max_len=M, slots=B, backend=jax.default_backend(),
        )
        mesh = self._mesh
        if self._kv_pages is not None and self._paged_setup():
            # Paged pools for BOTH models under ONE block table (same
            # block ids address target and draft tensors), so a radix
            # prefix hit reuses both models' cached prompt k/v.
            self._build_paged()
            return

        def admit(params_pair, state, last_tok, pos, gen, prompts,
                  admit_mask, key):
            """Prefill BOTH models on the full [B, P] batch; merge admitted
            rows into both pools. Token 0 comes from the TARGET's logits
            (greedy) — identical to the plain server's admit, so the two
            servers' completions start from the same token."""
            tparams, dparams = params_pair
            t_k, t_v, d_k, d_v, acc, prop, rounds = state
            t_logits, t_fresh = prefill(tparams, cfg, prompts, M, mesh)
            _d_logits, d_fresh = prefill(dparams, dcfg, prompts, M, mesh)
            sel = admit_mask[None, :, None, None, None]
            t_k = jnp.where(sel, t_fresh.k, t_k)
            t_v = jnp.where(sel, t_fresh.v, t_v)
            d_k = jnp.where(sel, d_fresh.k, d_k)
            d_v = jnp.where(sel, d_fresh.v, d_v)
            tok0 = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
            last_tok = jnp.where(admit_mask, tok0, last_tok)
            pos = jnp.where(admit_mask, P, pos)
            gen = jnp.where(admit_mask[:, None], 0, gen)
            gen = gen.at[:, 0].set(jnp.where(admit_mask, tok0, gen[:, 0]))
            return (t_k, t_v, d_k, d_v, acc, prop, rounds), last_tok, pos, gen

        K = self._ticks_per_sync

        def tick_block(params_pair, state, last_tok, pos, gen, active_in, key):
            """K speculative rounds in one dispatch, done mask latched like
            the plain tick block. Invariant per slot: ``pos`` is the
            sequence position of ``last_tok`` (whose k/v is written by the
            NEXT verify), and gen[0 .. pos - P] holds the emitted tokens."""
            tparams, dparams = params_pair

            def one(carry, _):
                state, last_tok, pos, gen, done_latch, n_out = carry
                t_k, t_v, d_k, d_v, acc, prop, rounds = state
                act = active_in & ~done_latch

                # k+1 draft steps for k proposals — the last step only
                # INGESTS proposal k so the draft cache has an entry at
                # every accepted position after a full-accept round
                # (spec_decode's contiguity rule; see its body comment).
                def dbody(c, j):
                    dc, tok = c
                    logits, dc = _multi_step(
                        dparams, dcfg, dc, tok[:, None], pos + j
                    )
                    nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                    return (dc, nxt), nxt

                (dc, _), d_toks = lax.scan(
                    dbody, (KVCache(d_k, d_v), last_tok), jnp.arange(k + 1)
                )
                d_k, d_v = dc.k, dc.v
                d = jnp.transpose(d_toks[:k])  # [B, k]

                # One multi-query verify at per-slot start positions: the
                # serving tick generalised to S = k+1 (same write/mask
                # discipline — spec_decode._multi_step IS the sibling the
                # serve docstrings point at).
                v_in = jnp.concatenate([last_tok[:, None], d], axis=1)
                t_logits, tc = _multi_step(
                    tparams, cfg, KVCache(t_k, t_v), v_in, pos
                )
                t_k, t_v = tc.k, tc.v
                tga = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)

                match = tga[:, :k] == d
                n_acc = jnp.sum(
                    jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1
                )
                corr = jnp.take_along_axis(tga, n_acc[:, None], axis=1)[:, 0]

                # Emit accepted drafts then the correction/bonus — static
                # k+1-step masked one-hot writes over [B, max_new], like
                # the plain tick's gen write. Three static stop rules per
                # candidate j: past the accepted length (j > n_acc), past
                # the buffer (j >= rem), or after an earlier EOS in this
                # round (alive latch). Every candidate is a TARGET-greedy
                # token, so emission order equals plain serving's.
                emitted_before = pos - P + 1
                rem = max_new - emitted_before
                idxbuf = jnp.arange(max_new)[None, :]
                alive = act
                n_emit = jnp.zeros_like(pos)
                new_last = last_tok
                eos_hit = jnp.zeros_like(act)
                for j in range(k + 1):
                    tok_j = d[:, j] if j < k else corr
                    tok_j = jnp.where(j < n_acc, tok_j, corr)
                    emit = alive & (j <= n_acc) & (j < rem)
                    sel = (
                        idxbuf == (emitted_before + j)[:, None]
                    ) & emit[:, None]
                    gen = jnp.where(sel, tok_j[:, None], gen)
                    n_emit = n_emit + emit.astype(jnp.int32)
                    new_last = jnp.where(emit, tok_j, new_last)
                    if eos_id is not None:
                        # Same rule as the plain server: EOS counts on
                        # decode outputs only (gen index >= 1 — always
                        # true here since emitted_before >= 1), and the
                        # EOS token itself is emitted.
                        hit = emit & (tok_j == eos_id)
                        eos_hit = eos_hit | hit
                        alive = alive & ~hit
                emitted_after = emitted_before + n_emit
                done_now = act & (eos_hit | (emitted_after >= max_new))
                n_out = jnp.where(done_now, emitted_after, n_out)
                pos = jnp.where(act & ~done_now, pos + n_emit, pos)
                last_tok = jnp.where(act, new_last, last_tok)

                # Acceptance counters (device-side; spec_stats() fetches):
                # α = accepted / proposed over every live round — the
                # measured number PERF.md's speedup row is built on.
                n_act = jnp.sum(act.astype(jnp.int32))
                acc = acc + jnp.sum(jnp.where(act, n_acc, 0))
                prop = prop + k * n_act
                rounds = rounds + (n_act > 0).astype(jnp.int32)
                done_latch = done_latch | done_now
                state = (t_k, t_v, d_k, d_v, acc, prop, rounds)
                return (state, last_tok, pos, gen, done_latch, n_out), None

            done0 = jnp.zeros((B,), bool)
            n0 = jnp.zeros((B,), jnp.int32)
            (state, last_tok, pos, gen, done, n_out), _ = lax.scan(
                one, (state, last_tok, pos, gen, done0, n0), None, length=K
            )
            return state, last_tok, pos, gen, done, n_out

        def resume_admit(params_pair, state, last_tok, pos, gen, seq, slot,
                         emitted_row, g):
            """Journal warm resume, spec flavor: BOTH models' cache rows
            prefilled with prompt + journaled tokens in one dispatch (the
            base class's resume_admit over the two-pool state). The
            restored position invariant is the spec one unchanged: pos is
            last_tok's sequence position, whose k/v the NEXT verify
            writes."""
            tparams, dparams = params_pair
            t_k, t_v, d_k, d_v, acc, prop, rounds = state
            _tl, t_fresh = prefill(tparams, cfg, seq, M, mesh)
            _dl, d_fresh = prefill(dparams, dcfg, seq, M, mesh)
            t_k = lax.dynamic_update_slice(
                t_k, t_fresh.k.astype(t_k.dtype), (0, slot, 0, 0, 0)
            )
            t_v = lax.dynamic_update_slice(
                t_v, t_fresh.v.astype(t_v.dtype), (0, slot, 0, 0, 0)
            )
            d_k = lax.dynamic_update_slice(
                d_k, d_fresh.k.astype(d_k.dtype), (0, slot, 0, 0, 0)
            )
            d_v = lax.dynamic_update_slice(
                d_v, d_fresh.v.astype(d_v.dtype), (0, slot, 0, 0, 0)
            )
            last_tok = last_tok.at[slot].set(emitted_row[g - 1])
            pos = pos.at[slot].set(P + g - 1)
            gen = lax.dynamic_update_slice(
                gen, emitted_row[None, :], (slot, 0)
            )
            return (
                (t_k, t_v, d_k, d_v, acc, prop, rounds), last_tok, pos, gen
            )

        # Same dispatch shape as the base: donate the state tuple, pass
        # BOTH param trees as arguments (a closed-over tree lowers as
        # jaxpr constants — the base _build's note).
        _admit = jax.jit(admit, donate_argnums=(1,))
        _tick = jax.jit(tick_block, donate_argnums=(1,))
        _resume = jax.jit(resume_admit, donate_argnums=(1,))
        self._admit_fn = lambda *a: _admit(
            (self._params, self._draft_params), *a
        )
        self._tick_fn = lambda *a: _tick(
            (self._params, self._draft_params), *a
        )
        self._resume_exec = lambda *a: _resume(
            (self._params, self._draft_params), *a
        )
        # The un-jitted tick, for tests that read its jaxpr (the base's
        # hook): it takes only the target tree, so close over the draft.
        self._tick_block_raw = (
            lambda params, *a: tick_block((params, self._draft_params), *a)
        )

        nl, kh, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        dl, dkh, ddh = dcfg.n_layers, dcfg.n_kv_heads, dcfg.head_dim
        self._caches = (
            jnp.zeros((nl, B, M, kh, dh), cfg.dtype),
            jnp.zeros((nl, B, M, kh, dh), cfg.dtype),
            jnp.zeros((dl, B, M, dkh, ddh), dcfg.dtype),
            jnp.zeros((dl, B, M, dkh, ddh), dcfg.dtype),
            # accepted / proposed / rounds — three DISTINCT buffers (the
            # state tuple is donated; one buffer donated thrice is an
            # XLA error).
            jnp.zeros((), jnp.int32).copy(),
            jnp.zeros((), jnp.int32).copy(),
            jnp.zeros((), jnp.int32).copy(),
        )
        self._last_tok = jnp.zeros((B,), jnp.int32)
        self._pos = jnp.zeros((B,), jnp.int32)
        self._gen = jnp.zeros((B, max_new), jnp.int32)

    def _build_paged(self) -> None:
        """Speculative serving over the paged pool (``kv_pages=``).

        Same speculative round as the dense build — k+1 draft steps, one
        multi-query verify, target-argmax accept — but both models' slot
        caches are block pools ``[L, NB, bs, K, Dh]`` addressed through
        ONE per-slot block table, and admission goes through the base
        class's radix match → link → chunk-queue path (both pools
        prefilled by the chunked tick; a prefix hit skips BOTH models'
        prompt re-prefill). Verify/rollback respect block boundaries by
        construction: the verify's [pos, pos + k] writes scatter through
        the table (a span may straddle blocks — each position resolves
        its own (block, offset)), the slot's table covers the full
        P + max_new + k overshoot from admission, and rollback stays pure
        position bookkeeping — rejected positions become stale entries in
        blocks the slot still owns, overwritten write-before-attend next
        round, never blocks another slot could hold. Token-exact vs the
        dense spec server AND the plain servers (greedy), differential-
        tested in tests/test_kvcache.py."""
        from torchkafka_tpu.ops.kvattn import block_table_attention

        cfg, dcfg, k = self._cfg, self._draft_cfg, self._k
        B, P = self._slots, self._prompt_len
        max_new = self._max_new
        eos_id = self._eos_id
        bs = self._kv_pages.block_size
        NB = self._kv_pages.num_blocks

        def multi_step_paged(params, mcfg, pool_k, pool_v, table, tokens,
                             pos_b):
            """``spec_decode._multi_step`` over a paged pool: S queries at
            per-row start positions, write-before-attend through the
            block table, per-query causal masks to the live length."""
            b, s = tokens.shape
            x = embed_rows(params["embed"], tokens, mcfg.dtype)
            positions = pos_b[:, None] + jnp.arange(s)[None, :]  # [B, S]

            def body(x, inputs):
                layer, pk, pv = inputs
                q, kk, vv = _project_qkv(x, layer, mcfg)
                q = _rope(q, positions, mcfg.rope_theta)
                kk = _rope(kk, positions, mcfg.rope_theta)
                x, pk, pv = block_table_attention(
                    x, q, kk, vv, pk, pv, table, positions, layer, mcfg
                )
                return x, (pk, pv)

            x, (pool_k, pool_v) = lax.scan(
                body, x, (params["layers"], pool_k, pool_v)
            )
            x = _rms_norm(x, params["ln_f"])
            logits = jnp.einsum(
                "bsd,dv->bsv", x, load_weight(params["lm_head"], mcfg.dtype),
                preferred_element_type=jnp.float32,
            )
            return logits, pool_k, pool_v

        K = self._ticks_per_sync

        def tick_block(params_pair, caches, last_tok, pos, gen, active_in,
                       key):
            """The dense spec tick over paged pools (same round structure
            and accept/emit bookkeeping — see the dense body's comments);
            the table rides through the donated state unchanged."""
            tparams, dparams = params_pair
            t_k, t_v, d_k, d_v, table, acc, prop, rounds = caches

            def one(carry, _):
                (t_k, t_v, d_k, d_v, acc, prop, rounds, last_tok, pos, gen,
                 done_latch, n_out) = carry
                act = active_in & ~done_latch

                def dbody(c, j):
                    (dk, dv), tok = c
                    logits, dk, dv = multi_step_paged(
                        dparams, dcfg, dk, dv, table, tok[:, None], pos + j
                    )
                    nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                    return ((dk, dv), nxt), nxt

                ((d_k, d_v), _), d_toks = lax.scan(
                    dbody, ((d_k, d_v), last_tok), jnp.arange(k + 1)
                )
                d = jnp.transpose(d_toks[:k])  # [B, k]

                v_in = jnp.concatenate([last_tok[:, None], d], axis=1)
                t_logits, t_k, t_v = multi_step_paged(
                    tparams, cfg, t_k, t_v, table, v_in, pos
                )
                tga = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)

                match = tga[:, :k] == d
                n_acc = jnp.sum(
                    jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1
                )
                corr = jnp.take_along_axis(tga, n_acc[:, None], axis=1)[:, 0]

                emitted_before = pos - P + 1
                rem = max_new - emitted_before
                idxbuf = jnp.arange(max_new)[None, :]
                alive = act
                n_emit = jnp.zeros_like(pos)
                new_last = last_tok
                eos_hit = jnp.zeros_like(act)
                for j in range(k + 1):
                    tok_j = d[:, j] if j < k else corr
                    tok_j = jnp.where(j < n_acc, tok_j, corr)
                    emit = alive & (j <= n_acc) & (j < rem)
                    sel = (
                        idxbuf == (emitted_before + j)[:, None]
                    ) & emit[:, None]
                    gen = jnp.where(sel, tok_j[:, None], gen)
                    n_emit = n_emit + emit.astype(jnp.int32)
                    new_last = jnp.where(emit, tok_j, new_last)
                    if eos_id is not None:
                        hit = emit & (tok_j == eos_id)
                        eos_hit = eos_hit | hit
                        alive = alive & ~hit
                emitted_after = emitted_before + n_emit
                done_now = act & (eos_hit | (emitted_after >= max_new))
                n_out = jnp.where(done_now, emitted_after, n_out)
                pos = jnp.where(act & ~done_now, pos + n_emit, pos)
                last_tok = jnp.where(act, new_last, last_tok)

                n_act = jnp.sum(act.astype(jnp.int32))
                acc = acc + jnp.sum(jnp.where(act, n_acc, 0))
                prop = prop + k * n_act
                rounds = rounds + (n_act > 0).astype(jnp.int32)
                done_latch = done_latch | done_now
                return (
                    t_k, t_v, d_k, d_v, acc, prop, rounds, last_tok, pos,
                    gen, done_latch, n_out,
                ), None

            done0 = jnp.zeros((B,), bool)
            n0 = jnp.zeros((B,), jnp.int32)
            (t_k, t_v, d_k, d_v, acc, prop, rounds, last_tok, pos, gen,
             done, n_out), _ = lax.scan(
                one,
                (t_k, t_v, d_k, d_v, acc, prop, rounds, last_tok, pos, gen,
                 done0, n0),
                None, length=K,
            )
            return (
                (t_k, t_v, d_k, d_v, table, acc, prop, rounds),
                last_tok, pos, gen, done, n_out,
            )

        def tick_chunk_block(params_pair, caches, last_tok, pos, gen,
                             active_in, key, ctok, ctable, cpos,
                             fin_mask, fin_row):
            """The chunked tick, spec flavor: the SAME jitted program
            first pushes this tick's prefill chunk through BOTH models'
            block pools (each chunk row one suffix token of a
            reserved-but-prefilling slot, writing through its own table
            row — ``multi_step_paged`` with S=1 rows IS the chunk
            stage), then runs the K speculative rounds over the active
            slots. One dispatch per tick, O(1) compiled programs across
            any suffix-length mix. Unlike the plain server's fused
            pass the chunk stage is a separate layer sweep per model
            (the verify's multi-query structure doesn't concatenate with
            S=1 chunk rows); the dispatch-count win is identical, the
            weight-stream sharing is plain-mode only. Activation rides
            the dispatch too: ``fin_mask``/``fin_row`` mark slots whose
            last suffix token landed this tick — token 0 is the
            TARGET's argmax at that chunk row (greedy, like every spec
            admission) and the slot state merges in, ready to join the
            NEXT dispatch's rounds."""
            tparams, dparams = params_pair
            t_k, t_v, d_k, d_v, table, acc, prop, rounds = caches
            t_logits_c, t_k, t_v = multi_step_paged(
                tparams, cfg, t_k, t_v, ctable, ctok[:, None], cpos
            )
            _dl, d_k, d_v = multi_step_paged(
                dparams, dcfg, d_k, d_v, ctable, ctok[:, None], cpos
            )
            chunk_logits = t_logits_c[:, -1]  # [C, V]
            caches, last_tok, pos, gen, done, n_out = tick_block(
                params_pair,
                (t_k, t_v, d_k, d_v, table, acc, prop, rounds),
                last_tok, pos, gen, active_in, key,
            )
            tok0 = jnp.argmax(chunk_logits[fin_row], axis=-1).astype(
                jnp.int32
            )
            last_tok = jnp.where(fin_mask, tok0, last_tok)
            pos = jnp.where(fin_mask, P, pos)
            gen = jnp.where(fin_mask[:, None], 0, gen)
            gen = gen.at[:, 0].set(jnp.where(fin_mask, tok0, gen[:, 0]))
            return caches, last_tok, pos, gen, done, n_out

        _tick = jax.jit(tick_block, donate_argnums=(1,))
        self._tick_jit = _tick
        self._tick_fn = lambda *a: _tick(
            (self._params, self._draft_params), *a
        )
        _tick_chunk = jax.jit(tick_chunk_block, donate_argnums=(1,))
        self._tick_chunk_jit = _tick_chunk
        self._tick_chunk_fn = lambda *a: _tick_chunk(
            (self._params, self._draft_params), *a
        )
        self._tick_block_raw = (
            lambda params, *a: tick_block((params, self._draft_params), *a)
        )
        self._admit_fn = None  # paged admission is host-orchestrated
        self._resume_exec = None  # paged resume rides the chunk path
        self._paged_table_idx = 4

        nl, kh, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        dl, dkh, ddh = dcfg.n_layers, dcfg.n_kv_heads, dcfg.head_dim
        self._caches = (
            jnp.zeros((nl, NB, bs, kh, dh), cfg.dtype),
            jnp.zeros((nl, NB, bs, kh, dh), cfg.dtype),
            jnp.zeros((dl, NB, bs, dkh, ddh), dcfg.dtype),
            jnp.zeros((dl, NB, bs, dkh, ddh), dcfg.dtype),
            # .copy(): jnp.asarray may zero-copy an aligned host buffer
            # (CPU backend) and _table_np is mutated in place at
            # admission — snapshot, never a live view.
            jnp.asarray(self._table_np.copy()),
            # accepted / proposed / rounds — distinct buffers (donated
            # tuple; one buffer donated thrice is an XLA error).
            jnp.zeros((), jnp.int32).copy(),
            jnp.zeros((), jnp.int32).copy(),
            jnp.zeros((), jnp.int32).copy(),
        )
        self._last_tok = jnp.zeros((B,), jnp.int32)
        self._pos = jnp.zeros((B,), jnp.int32)
        self._gen = jnp.zeros((B, max_new), jnp.int32)

    def swap_draft_params(self, draft_params, draft_cfg=None) -> None:
        """Hot-swap the DRAFT weights in place — the rollout plane's
        delivery path for continuously-distilled drafts (ROADMAP item 1).
        Cheaper contract than ``swap_params``: the draft only PROPOSES —
        verification against the target is what commits tokens — so a
        draft refresh never changes committed output, only the realized
        acceptance α. It can therefore land between ticks without
        quiescing. The jitted programs close over ``self._draft_params``
        at call time; same structure/shapes required (the compiled
        programs are shape-specialized), which ``draft_cfg`` (when given)
        and the tree check enforce."""
        if draft_cfg is not None and (
            draft_cfg.n_layers != self._draft_cfg.n_layers
            or draft_cfg.vocab_size != self._draft_cfg.vocab_size
            or draft_cfg.d_model != self._draft_cfg.d_model
        ):
            raise ValueError(
                "swap_draft_params requires a structurally identical "
                "draft (the compiled rounds are shape-specialized); "
                "rebuild the generator for a different draft geometry"
            )
        old = jax.tree_util.tree_structure(self._draft_params)
        new = jax.tree_util.tree_structure(draft_params)
        if old != new:
            raise ValueError(
                f"draft tree structure mismatch: {new} != {old}"
            )
        if self._mesh is not None:
            from torchkafka_tpu.models.generate import serving_shardings

            draft_params = jax.device_put(
                draft_params,
                serving_shardings(self._draft_cfg, self._mesh, draft_params),
            )
        # Death HERE (candidate fetched + validated, not yet bound) must
        # be invisible in committed output: the incumbent draft still
        # proposes on restart, and either draft yields the target's
        # greedy tokens — the crash matrix pins exactly that.
        crash_hook("draft_swap_pre_apply")
        self._draft_params = draft_params

    def spec_stats(self) -> dict:
        """Measured speculation counters since construction (one device
        fetch). ``acceptance`` is the realized α — the workload-dependent
        number the i.i.d. speedup curve must be evaluated at. Warmup's
        all-inactive rounds don't count (no active slot → no proposals)."""
        # Counters are the state tuple's TAIL in both layouts (dense:
        # pools + 3 counters; paged: pools + table + 3 counters).
        acc, prop, rounds = (
            int(jax.device_get(x)) for x in self._caches[-3:]
        )
        return {
            "rounds": rounds,
            "proposed": prop,
            "accepted": acc,
            "acceptance": round(acc / prop, 4) if prop else None,
            "k": self._k,
            "draft_layers": self._draft_cfg.n_layers,
        }
