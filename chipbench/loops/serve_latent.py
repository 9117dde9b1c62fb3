"""The serving loop of a latent-attention configuration: ``loops/serve.py``
whole, then one more comparison with the plain reference, on what the
program CACHES.

Why a second comparison. ``serve.py`` compares the served tokens: the
widest gap by which one lies below the reference's best logit. For a
random model with discrete routing that number is set by the rare token
whose top-k near-tie fell the other way in bfloat16, not by the precision
of the layers: the reference with every attention and expert matmul in
8-bit floating point reads the same as a sound run there (PERF.md, PR
27). The cached rows do tell them apart. After the window a probe server
of the deployment's own shapes (the same admit and tick programs) serves
the sampled prompts again for ``check.probe_new`` tokens; its pool then
holds ``concat(norm(c), rope(k_r))`` of every layer at every position:
the prompt window's rows written by the admission (flash at unequal
widths, the grouped expert matmul), the later rows by decode ticks (the
absorbed read of the pool, the all-experts form, the scatter). Each row
is held against the reference's, teacher-forced on what the probe served:
the relative error of a row, its median over positions (a flipped
routing moves a minority of rows by much; a lower precision moves every
row), the worst layer. ``check.max_latent_row_err`` lies between the
sound runs' reading and the control's (``control``).
"""

from __future__ import annotations

import gc

import numpy as np

from chipbench import common


def _serve(ctx):
    return common.load_named("loops", "serve", ctx.root)


# ``tests/chipbench/toy.py`` cuts the deployments of the loops it knows by
# name (``serve``, ``train``) to sizes a CPU runs, and a file that is there
# is not edited for a new loop: a rehearsal of this loop makes the serving
# toy's cuts itself.
REHEARSAL = {
    "deployment": {"slots": 4, "prompt_window": 16, "max_new": 16,
                   "ticks_per_sync": 4, "commit_every": 3},
    "traffic": {"records": 40, "deck": 16, "block": 4, "prompt_median": 6,
                "prompt_sigma": 0.8, "prompt_max": 16, "answer_median": 5,
                "answer_sigma": 0.8, "answer_min": 2, "answer_max": 16},
    "check": {"sample": 24},
}


def run(ctx) -> dict:
    serve = _serve(ctx)
    if ctx.rehearsal:
        ctx.conf["deployment"].update(REHEARSAL["deployment"])
        ctx.mix["traffic"].update(REHEARSAL["traffic"])
        ctx.mix["check"].update(REHEARSAL["check"])
    out = serve.run(ctx)
    if "sample" in out:
        with ctx.phase("cached_rows"):
            out["rows"] = compare_cached_rows(ctx, serve, out)
    return out


def probe(ctx, serve, prompts: np.ndarray, new: int):
    """Serve ``prompts`` [S, window] for ``new`` tokens each through a
    server built as the cell's (same slots and pool: the same programs),
    and read its pool back: (tokens [S, window + new], every slot's rows
    [L, slots, window + new - 1, C] on the host). The server and its
    weights are freed before the reference needs the device."""
    import torchkafka_tpu as tk

    conf, dep = ctx.conf, ctx.conf["deployment"]
    window = prompts.shape[1]
    cfg = ctx.model.program_config(conf, window + dep["max_new"])
    params = ctx.model.serving_params(conf, ctx.seed)
    broker = tk.InMemoryBroker()
    broker.create_topic(serve.PROMPTS, partitions=dep["prompt_partitions"])
    broker.create_topic(serve.OUTPUT, partitions=1)
    consumer = tk.MemoryConsumer(broker, serve.PROMPTS, group_id=serve.GROUP)
    server = serve.build_server(
        ctx, tk, params, cfg, consumer, tk.MemoryProducer(broker), None
    )
    sent = {}
    for i, row in enumerate(prompts):
        r = serve._produce(broker, {
            "tokens": row, "key": b"probe-%d" % i, "max_new": new,
            "partition": i % dep["prompt_partitions"],
        })
        sent[(r.partition, r.offset)] = i
    tokens = np.zeros((len(prompts), window + new), np.int32)
    tokens[:, :window] = prompts
    for rec, toks in server.run(max_records=len(prompts), idle_timeout_ms=200):
        tokens[sent[(rec.partition, rec.offset)], window:] = toks
    (pool,) = server.cache_tensors  # [L, slots, M, C]
    held = np.asarray(pool[:, :, : window + new - 1])
    server.close()
    consumer.close()
    del server, params, pool
    gc.collect()
    return tokens, held


def rows_of(held: np.ndarray, want: np.ndarray, window: int) -> np.ndarray:
    """The rows of the slots that served ``want``'s prompts, in their
    order. The first layer's row at a position depends on that position's
    token alone, so a slot's first-layer rows over the prompt window name
    its prompt."""
    first = held[0, :, :window].astype(np.float32)
    slots = [
        int(np.argmin(((first - w[None]) ** 2).sum((1, 2))))
        for w in want[0, :, :window]
    ]
    if len(set(slots)) != len(slots):
        raise common.Refused(f"the probe's prompts share a slot: {slots}")
    return held[:, slots].astype(np.float32)


def row_err(rows: np.ndarray, want: np.ndarray, positions: slice) -> float:
    """Relative error of a row [.., C] against the reference's, its
    median over the rows of ``positions``, the worst layer."""
    a, b = rows[:, :, positions], want[:, :, positions]
    err = np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)
    return float(np.max(np.median(err.reshape(err.shape[0], -1), axis=1)))


def compare_cached_rows(ctx, serve, out: dict) -> dict:
    check, window = ctx.mix["check"], out["prompt_window"]
    new = min(int(check["probe_new"]), out["max_new"])
    prompts = out["sample"]["toks"][: out["slots"], :window]
    tokens, held = probe(ctx, serve, prompts, new)
    want = ctx.reference.cached_rows(ctx.seed, out["dims"], tokens)[
        :, :, : window + new - 1
    ]
    rows = rows_of(held, want, window)
    limit = float(check["max_latent_row_err"])
    read = {
        "prefill": row_err(rows, want, slice(0, window)),
        "decode": row_err(rows, want, slice(window, None)),
    }
    ctx.say("cached_rows", {"prompts": len(prompts), "new": new, **read})
    for region, value in read.items():
        ctx.checks.at_most(f"latent_row_err.{region}", value, limit)
    return {"tokens": tokens, "want": want, "window": window, **read}


def control(ctx, out: dict) -> dict:
    """``serve.control`` (the reference with every matmul in 8-bit
    floating point, put in the program's place, by the served tokens'
    gap), and the controls of the cached rows: the reference with its
    attention and expert matmuls in 8 bits (``layers``), its expert
    matmuls alone, its products with the cached positions alone, each put
    in the program's place. The served tokens' limit is also read against
    a stream displaced by one position, what a pool indexed one row off
    would serve."""
    serve = _serve(ctx)
    readings = serve.control(ctx, out)
    sample, window = out["sample"], out["prompt_window"]
    toks = sample["toks"]
    displaced = np.roll(toks[:, window: window + out["max_new"]], 1, axis=1)
    gap, _top = ctx.reference.served_logit_gaps(
        ctx.seed, out["dims"], toks, window - 1, out["max_new"],
        probe=displaced,
    )
    readings["served_logit_gap"]["displaced_stream"] = float(
        np.max(np.where(sample["valid"], np.asarray(gap), 0.0))
    )
    rows, limit = out["rows"], float(ctx.mix["check"]["max_latent_row_err"])
    readings["latent_row_err"] = {
        "program": {k: rows[k] for k in ("prefill", "decode")}, "limit": limit,
    }
    for part in ("layers", "experts", "read"):
        low = ctx.reference.cached_rows(
            ctx.seed, out["dims"], rows["tokens"], lowp=part
        )[:, :, : rows["want"].shape[2]]
        readings["latent_row_err"][f"control_{part}"] = {
            "prefill": row_err(low, rows["want"], slice(0, rows["window"])),
            "decode": row_err(low, rows["want"], slice(rows["window"], None)),
        }
    return readings
