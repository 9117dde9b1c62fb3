"""The serving loop of a configuration whose expert layer holds ONE CHIP'S
SHARE of the experts behind a router with zero-compute experts:
``loops/serve.py`` whole (the window, the served tokens against the
reference), then the probe server of ``loops/serve_latent.py`` and TWO
comparisons on what its compiled admit and tick left in the pool.

The first is ``serve_latent``'s: every cached row against the reference's,
teacher-forced, the median over positions, the worst block
(``check.max_latent_row_err``).

Why a second. Of a token's top-k pairs a few meet an expert held here
(0.25 of 12 in the cell); a fault in the share (the held range one expert
off, a local pair dropped by the compaction, another layer's expert
indexed out of the stack) moves one token in fifty by a few per cent. The
rows' MEDIAN over positions and the served tokens' widest gap see neither.
But the row that the first block of layer ``l + 1`` caches at a position
is a function of the stream at that position alone, and the reference
knows how the held experts' part of layer ``l`` shows in it (the row less
what it would be had the part been left out: the part's IMPRINT, zero
where the token chose no held expert). So for every token with a local
pair the program's row is projected on that direction:

    missing = - <row_program - row_reference, imprint> / <imprint, imprint>

reads 0 where the program added the part the reference added, 1 where it
added nothing, or another expert's output (a direction of its own).
Everything else that moves a row (bfloat16 against float32, a near-tie of
the router that fell the other way on a zero expert) is spread over the
row's 576 numbers and projects to a few hundredths. A token whose OWN
local pair was such a near-tie reads 1 in a sound run, so the number
compared is, for each layer but the last and each held expert, the MEDIAN
over the tokens the reference routed to it, and of those the one farthest
from 0, for the rows an admission wrote and the rows ticks wrote
(``check.max_held_pair_missing``, between the sound runs' reading and 1).
The last layer's experts show in no cached row: its branch is the same
compiled scan body as the layers before it, and the served tokens hold
its sum.
"""

from __future__ import annotations

import numpy as np

from chipbench import common

REGIONS = ("prefill", "decode")


def _latent(ctx):
    return common.load_named("loops", "serve_latent", ctx.root)


def run(ctx) -> dict:
    latent = _latent(ctx)
    serve = latent._serve(ctx)
    if ctx.rehearsal:
        ctx.conf["deployment"].update(latent.REHEARSAL["deployment"])
        ctx.mix["traffic"].update(latent.REHEARSAL["traffic"])
        ctx.mix["check"].update(latent.REHEARSAL["check"])
    out = serve.run(ctx)
    if "sample" in out:
        with ctx.phase("cached_rows"):
            out["rows"] = compare_cached_rows(ctx, latent, serve, out)
    return out


def held_pair_missing(rows, want, imprint, chosen, held, positions) -> float:
    """The share of a held expert's part that ``rows`` [2L, S, T, C] lack
    (module docstring) at ``positions``: ``imprint`` [L - 1, S, T, C],
    ``chosen`` [L, S, T, K], ``held`` (first, count). The median over an
    expert's tokens, the (layer, expert) farthest from 0."""
    first, count = held
    worst = 0.0
    for layer, d in enumerate(imprint):
        nxt = 2 * (layer + 1)
        d = d[:, positions]
        r = (rows[nxt] - want[nxt])[:, positions]
        dd = (d * d).sum(-1)
        missing = -(r * d).sum(-1) / np.where(dd > 0, dd, 1.0)
        took = chosen[layer][:, positions]
        for expert in range(first, first + count):
            of = (took == expert).any(-1) & (dd > 0)
            if of.any():
                worst = max(worst, abs(float(np.median(missing[of]))))
    return worst


def readings(latent, rows, ref: dict, held) -> dict:
    """Both numbers of ``rows`` [2L, S, T, C] against the reference's, by
    region."""
    window = ref["window"]
    where = {"prefill": slice(0, window), "decode": slice(window, None)}
    return {
        "latent_row_err": {
            r: latent.row_err(rows, ref["want"], where[r]) for r in REGIONS
        },
        "held_pair_missing": {
            r: held_pair_missing(
                rows, ref["want"], ref["imprint"], ref["chosen"], held, where[r]
            ) for r in REGIONS
        },
    }


def compare_cached_rows(ctx, latent, serve, out: dict) -> dict:
    check, window = ctx.mix["check"], out["prompt_window"]
    new = min(int(check["probe_new"]), out["max_new"])
    prompts = out["sample"]["toks"][: out["slots"], :window]
    tokens, pool = latent.probe(ctx, serve, prompts, new)
    # A finished slot ticks on until the sync, its position held: the row
    # of its last position ends as its final token's, not the one the
    # reference is forced with. That row is left out.
    cut = window + new - 2
    want, imprint, chosen = (
        a[:, :, :cut] for a in ctx.reference.share_rows(
            ctx.seed, out["dims"], tokens
        )
    )
    ref = {"tokens": tokens, "want": want, "window": window,
           "imprint": imprint, "chosen": chosen}
    held = tuple(ctx.conf["deployment"]["experts_held"])
    rows = latent.rows_of(pool, want, window)[:, :, :cut]
    read = readings(latent, rows, ref, held)
    local = (imprint != 0).any(-1)
    ctx.say("cached_rows", {
        "prompts": len(prompts), "new": new,
        "tokens_with_a_local_pair": {
            "prefill": int(local[:, :, :window].sum()),
            "decode": int(local[:, :, window:].sum()),
        }, **read,
    })
    limits = {"latent_row_err": float(check["max_latent_row_err"]),
              "held_pair_missing": float(check["max_held_pair_missing"])}
    for name, by_region in read.items():
        for region, value in by_region.items():
            ctx.checks.at_most(f"{name}.{region}", value, limits[name])
    return {**ref, **read["latent_row_err"], "read": read, "held": held}


def control(ctx, out: dict) -> dict:
    """``serve_latent.control`` (8-bit operands by part, a displaced
    stream), and the faults of the mechanisms this family adds
    (``reference.FAULTS``), each put in the program's place: what both
    numbers of the cached rows then read."""
    latent = _latent(ctx)
    found = latent.control(ctx, out)
    ref = out["rows"]
    found["held_pair_missing"] = {
        "program": ref["read"]["held_pair_missing"],
        "limit": float(ctx.mix["check"]["max_held_pair_missing"]),
    }
    for fault in ctx.reference.FAULTS:
        low = ctx.reference.cached_rows(
            ctx.seed, out["dims"], ref["tokens"], lowp=fault
        )[:, :, : ref["want"].shape[2]]
        for name, by_region in readings(latent, low, ref, ref["held"]).items():
            found[name][f"control_{fault}"] = by_region
    return found
