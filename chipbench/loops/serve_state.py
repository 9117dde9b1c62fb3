"""The serving loop of a configuration whose slots keep a RECURRENT STATE
(linear-attention layers beside latent-attention ones, one chip's share of
experts chosen by group): ``loops/serve.py`` whole (the window, the served
tokens against the plain reference), then a probe server of the
deployment's own programs and four comparisons of what its compiled
admit and tick left in the slots, and of what the last layer adds, which
no slot keeps.

Why more than the served tokens. For a random model with discrete routing
the widest served-logit gap is set by the rare token whose top-k near-tie
fell the other way in bfloat16, not by the precision of the layers
(PERF.md, PR 27): a state kept in bfloat16 serves the same tokens. What a
slot HOLDS does tell. After the window the probe serves
``check.probe_slots`` of the run's prompts at once for
``check.probe_new`` tokens; the reference, teacher-forced on what the
probe served, walks its recurrence token by token, and then:

(a) **the state and the conv tail** of every linear layer. A head's state
    ``[128, 128]`` by its relative error (Frobenius), the median over
    heads and slots, a layer. The first linear layer reads the embedding
    itself, so no layer's rounding stands before it and its state tells
    float32 from bfloat16 accumulation (``check.max_state_err_first``);
    the worst layer carries every earlier layer's rounding and is held to
    a wider limit that a wrong decay (one a head, the bound dropped)
    still passes by far (``check.max_state_err``). The conv tail, three
    rows of bfloat16 a layer, by its relative error, the median over
    slots, the worst layer (``check.max_tail_err``: a tail one token
    early reads 1.4).
(b) **the latent rows** as ``loops/serve_latent.py`` holds them: the
    median over positions of a row's relative error, the rows an
    admission wrote and the rows ticks wrote (``check.
    max_latent_row_err``).
(c) **the held experts' part**, as ``loops/serve_share.py``: the latent
    layer's row at a position is a function of the stream there alone,
    and the reference knows how the held experts' part of the layer
    before shows in it (the IMPRINT, zero where the token chose no held
    expert). The program's row is projected on it: 0 where the program
    added what the reference added, 1 where it added nothing or another
    expert's output; the MEDIAN over the tokens with a local pair, as the
    share cell takes it (``check.max_held_pair_missing``, between the
    sound runs' reading and what the held range one expert off reads). A
    token whose own selection was a near-tie reads 1 in a sound run, and
    the server's padding (token 0 up to the window, over a state that has
    settled) is ONE such token many times over: ``near_ties`` prints
    their number and the reference's selection margin at them, and a mean
    would count them by their number (PERF.md §6, PR 41).
(d) **the last layer's parts.** What the layer that closes the cut adds
    reaches no slot: the served tokens alone carry it, and their widest
    gap is the statistic (a) to (c) were built to do without. So the
    program's own forward over what the probe's slots consumed (the
    forward an admission runs, ``model.final_stream``) gives the stream
    after the last layer, and its difference from the reference's is
    projected, as in (c), on each PART of what the last layer adds
    (``reference.LAST_PARTS``: its held experts' sum, and what its output
    gate changes of its attention): 0 where the program has the part, 1
    where it lacks it, the median over tokens (``check.
    max_last_layer_missing``). This holds the forward of an admission, not
    the compiled tick: the tick's last layer the served tokens hold.

Limits lie between the sound runs' readings and the controls'
(``control``; PERF.md gives both).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import common

REGIONS = ("prefill", "decode")
# ``tests/chipbench/toy.py`` cuts widths and depth of every configuration
# and the deployments of the loops it knows by name; a rehearsal of this
# loop makes its own cuts: the leading dense layer and ONE whole period,
# 2 groups of 4 experts of which one group is held.
REHEARSAL = {
    "config": {
        "num_hidden_layers": 7, "first_k_dense_replace": 1,
        "layer_group_size": 6, "num_experts": 4, "published_num_experts": 8,
        "n_group": 2, "topk_group": 1, "num_experts_per_tok": 2,
        "moe_intermediate_size": 64,
        "moe_shared_expert_intermediate_size": 64,
    },
    "deployment": {"slots": 4, "prompt_window": 16, "max_new": 16,
                   "ticks_per_sync": 4, "commit_every": 3,
                   "experts_held": [0, 4]},
    "traffic": {"records": 40, "deck": 16, "block": 4, "prompt_median": 6,
                "prompt_sigma": 0.8, "prompt_max": 16, "answer_median": 5,
                "answer_sigma": 0.8, "answer_min": 2, "answer_max": 16},
    # Float32 on both sides: the rehearsal's limits are float32's.
    "check": {"sample": 24, "probe_new": 10, "probe_slots": 4,
              "max_logit_gap": 0.05,
              "max_state_err_first": 1e-4, "max_state_err": 1e-3,
              "max_tail_err": 1e-3, "max_latent_row_err": 1e-3,
              "max_held_pair_missing": 0.05,
              "max_last_layer_missing": 0.05},
}


def _latent(ctx):
    return common.load_named("loops", "serve_latent", ctx.root)


def run(ctx) -> dict:
    latent = _latent(ctx)
    serve = latent._serve(ctx)
    if ctx.rehearsal:
        ctx.conf.update(REHEARSAL["config"])
        ctx.conf["deployment"].update(REHEARSAL["deployment"])
        ctx.mix["traffic"].update(REHEARSAL["traffic"])
        ctx.mix["check"].update(REHEARSAL["check"])
    out = serve.run(ctx)
    say_cycles(ctx, out)
    if "sample" in out:
        with ctx.phase("slot_memory"):
            out["memory"] = compare_slot_memory(ctx, latent, serve, out)
    return out


def say_cycles(ctx, out: dict) -> None:
    """For the reader of the log: when each host sync of the window came
    (seconds from its opening) and the tokens it surfaced. A window of
    this cell holds six or seven cycles of one admission and 128 ticks
    behind a first admission of every slot, so a run's tokens a second
    hang on where its last sync falls."""
    by_sync: dict[float, int] = {}
    for r in out["requests"]:
        for t, n in r["syncs"]:
            if ctx.t0 <= t <= ctx.t_close:
                by_sync[t] = by_sync.get(t, 0) + n
    # One sync stamps its slots within milliseconds of one another.
    cycles: list[list[float]] = []
    for t in sorted(by_sync):
        if cycles and t - cycles[-1][0] < 1.0:
            cycles[-1][1] += by_sync[t]
        else:
            cycles.append([t, by_sync[t]])
    ctx.say("cycles", {
        "window_s": ctx.t_close - ctx.t0,
        "syncs_at_s": [round(t - ctx.t0, 3) for t, _n in cycles],
        "tokens": [n for _t, n in cycles],
    })


def probe_prompts(ctx, out: dict, count: int) -> np.ndarray:
    """``count`` distinct prompts [count, window], each padded to the
    window as the server pads it: the sampled requests', then others of
    the run's, drawn from the seed."""
    window = out["prompt_window"]
    rows = [np.asarray(r, np.int32) for r in out["sample"]["toks"][:, :window]]
    seen = {r.tobytes() for r in rows}
    rng = np.random.default_rng([int(ctx.seed), 0x5107])
    for i in rng.permutation(len(out["requests"])):
        if len(rows) >= count:
            break
        r = out["requests"][i]
        row = np.zeros((window,), np.int32)
        row[: r["prompt_len"]] = r["prompt"]
        if row.tobytes() not in seen:
            seen.add(row.tobytes())
            rows.append(row)
    return np.stack(rows[:count])


def probe(ctx, serve, prompts: np.ndarray, new: int):
    """Serve ``prompts`` [S, window] for ``new`` tokens each through a
    server built as the cell's (same slots and slot memory: the same
    programs) → (tokens [S, window + new]; the slots that served them;
    what those slots hold, float32 on the host: the states [L_lin, S, H,
    E, E], the conv tails, the latent rows [L_lat, S, window + new - 2,
    C], and with them the program's stream after its last layer over what
    the slots consumed [S, window + new - 1, D], which no slot keeps).
    The states of every slot are gigabytes, those of the probe's a few
    MB: the slots are found on the device, by the latent rows of the
    program's own forward over the prompts, and the server, its weights
    and its slot memory are freed before the reference needs the device."""
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
    states, tails, pool = server.cache_tensors
    server.close()
    consumer.close()
    del server
    # What the slots consumed: the window and all but the last token. A
    # finished slot ticks on until the sync, its position held: the latent
    # row of its last position ends as its final token's, not the one the
    # reference is forced with, and is left out (the state and the conv
    # tail of a slot that is not active are kept as they are).
    stream, rows = ctx.model.final_stream(
        cfg, params, tokens[:, : window + new - 1]
    )
    slots = slots_of(pool, rows, window)
    at = np.asarray(slots)
    memory = tuple(
        np.asarray(a).astype(np.float32)
        for a in (states[:, at], tails[:, at], pool[:, at, : window + new - 2])
    ) + (stream,)
    del params, states, tails, pool
    gc.collect()
    return tokens, slots, memory


def probe_length(asked: int, max_new: int, ticks_per_sync: int) -> int:
    """The probe's answer length: ``asked`` rounded up to one more than a
    whole number of tick blocks (the admission's token, then whole
    blocks), the most that ``max_new`` holds if that is less. The device
    does not know a record's budget: a slot past it ticks on until the
    host's next sync clamps it, which a pool of rows forgets and a STATE
    does not. An answer that ends on a sync leaves the state after exactly
    the tokens the host was given."""
    blocks = max(1, -(-(asked - 1) // ticks_per_sync))
    blocks = min(blocks, (max_new - 1) // ticks_per_sync)
    if blocks < 1:
        raise common.Refused(
            f"max_new={max_new} holds no whole block of {ticks_per_sync} ticks"
        )
    return 1 + blocks * ticks_per_sync


def slots_of(pool, want: np.ndarray, window: int) -> list[int]:
    """The slot that served each prompt: of the slots that hold anything,
    the one whose latent rows over the prompt window lie nearest ``want``
    [L_lat, S, T, C], the rows of a forward over the prompts."""
    live = np.flatnonzero(
        np.abs(np.asarray(pool[0, :, 0]).astype(np.float32)).sum(-1) > 0
    )
    first = np.asarray(pool[0, live, :window]).astype(np.float32)
    slots = [
        int(live[np.argmin(((first - w[None]) ** 2).sum((1, 2)))])
        for w in want[0, :, :window]
    ]
    if len(set(slots)) != len(slots):
        raise common.Refused(f"the probe's prompts share a slot: {slots}")
    return slots


def _rel(a: np.ndarray, b: np.ndarray, axes) -> np.ndarray:
    num = np.sqrt(((a - b) ** 2).sum(axes))
    return num / np.maximum(np.sqrt((b ** 2).sum(axes)), 1e-30)


def state_err(states: np.ndarray, want: np.ndarray) -> np.ndarray:
    """[L]: a head's state by its relative error, the median over slots
    and heads, a layer. states, want [L, S, H, E, E]."""
    err = _rel(states, want, (-2, -1))  # [L, S, H]
    return np.median(err.reshape(err.shape[0], -1), axis=1)


def tail_err(tails: np.ndarray, want: np.ndarray) -> float:
    """A slot's conv tail [taps - 1, C] by its relative error, the median
    over slots, the worst layer."""
    return float(np.max(np.median(_rel(tails, want, (-2, -1)), axis=1)))


def part_missing(diff: np.ndarray, part: np.ndarray):
    """(the share of ``part`` [.., C] that ``diff`` = program - reference
    lacks, a token: 0 where the program added what the reference added, 1
    where it added nothing or something else; which tokens have a part),
    both [..]."""
    pp = (part * part).sum(-1)
    has = pp > 0
    return -(diff * part).sum(-1) / np.where(has, pp, 1.0), has


def held_pair_missing(rows, want, imprint, positions) -> float:
    """The share of the held experts' part that ``rows`` [L_lat, S, T, C]
    lack at ``positions`` (module docstring): the median over the tokens
    with a local pair, the latent layer farthest from 0."""
    worst = 0.0
    for r, w, d in zip(rows, want, imprint):
        missing, has = part_missing((r - w)[:, positions], d[:, positions])
        if has.any():
            worst = max(worst, abs(float(np.median(missing[has]))))
    return worst


def last_layer_missing(stream, want, part) -> float:
    """The share of a part of what the LAST layer adds that ``stream`` [S,
    T, D] lacks: the median over the tokens that have the part."""
    missing, has = part_missing(stream - want, part)
    return abs(float(np.median(missing[has]))) if has.any() else 0.0


def readings(latent, memory, ref: dict, last_parts) -> dict:
    """Every number of ``memory`` = (states, tails, rows, stream) of the
    probe's slots against the reference's."""
    states, tails, rows, stream = memory
    window = ref["window"]
    where = {"prefill": slice(0, window), "decode": slice(window, None)}
    by_layer = state_err(states, ref["states"])
    return {
        "state_err": {
            "first_layer": float(by_layer[0]),
            "worst_layer": float(by_layer.max()),
        },
        "conv_tail_err": {"worst_layer": tail_err(tails, ref["tails"])},
        "latent_row_err": {
            r: latent.row_err(rows, ref["rows"], where[r]) for r in REGIONS
        },
        "held_pair_missing": {
            r: held_pair_missing(rows, ref["rows"], ref["imprint"], where[r])
            for r in REGIONS
        },
        "last_layer_missing": {
            name: last_layer_missing(stream, ref["hidden"], part)
            for name, part in zip(last_parts, ref["last_parts"])
        },
    }


def limits(check: dict, last_parts) -> dict:
    return {
        "state_err": {
            "first_layer": float(check["max_state_err_first"]),
            "worst_layer": float(check["max_state_err"]),
        },
        "conv_tail_err": {"worst_layer": float(check["max_tail_err"])},
        "latent_row_err": dict.fromkeys(
            REGIONS, float(check["max_latent_row_err"])
        ),
        "held_pair_missing": dict.fromkeys(
            REGIONS, float(check["max_held_pair_missing"])
        ),
        "last_layer_missing": dict.fromkeys(
            last_parts, float(check["max_last_layer_missing"])
        ),
    }


def near_ties(rows, ref: dict, prompts: np.ndarray) -> dict:
    """For the reader of the log, why ``held_pair_missing`` is a median:
    of the window's tokens with a local pair, how many are the server's
    PADDING (token 0 after the prompt's last: identical tokens over a
    state that has settled, so their routing is one near-tie repeated)
    and how many read over a half (served with another expert than the
    reference chose), the mean beside the median, and the reference's
    selection margin at the tokens over a half beside every token's."""
    window = ref["window"]
    at = slice(0, window)
    missing, has = part_missing(
        (rows[-1] - ref["rows"][-1])[:, at], ref["imprint"][-1][:, at]
    )
    last = window - np.argmax(prompts[:, ::-1] != 0, axis=1)  # [S]
    padding = np.arange(window)[None, :] >= last[:, None]
    over = has & (np.abs(missing) > 0.5)
    margin = ref["margin"][-1][:, at]

    def med(mask):
        return float(np.median(margin[mask])) if mask.any() else None

    return {
        "tokens": int(has.sum()), "padding": int((has & padding).sum()),
        "over_half": int(over.sum()),
        "over_half_padding": int((over & padding).sum()),
        "slots_with_one_over_half": int(over.any(-1).sum()),
        "mean": float(np.mean(missing[has])) if has.any() else 0.0,
        "median": float(np.median(missing[has])) if has.any() else 0.0,
        "margin_median": {"every": med(has), "over_half": med(over)},
    }


def cut_rows(ref: dict, cut: int) -> dict:
    """The reference's rows without the last position's (``probe``)."""
    return {
        **ref, "rows": ref["rows"][:, :, :cut],
        "imprint": ref["imprint"][:, :, :cut],
        "margin": ref["margin"][:, :, :cut],
    }


def compare_slot_memory(ctx, latent, serve, out: dict) -> dict:
    check, window = ctx.mix["check"], out["prompt_window"]
    new = probe_length(
        int(check["probe_new"]), out["max_new"],
        int(ctx.conf["deployment"]["ticks_per_sync"]),
    )
    prompts = probe_prompts(
        ctx, out, min(int(check["probe_slots"]), out["slots"])
    )
    t_probe = time.perf_counter()
    tokens, slots, memory = probe(ctx, serve, prompts, new)
    consumed = tokens[:, : window + new - 1]
    cut = window + new - 2
    t_reference = time.perf_counter()
    ref = cut_rows({
        **ctx.reference.slot_memory(ctx.seed, out["dims"], consumed),
        "window": window,
    }, cut)
    t_read = time.perf_counter()
    last_parts = ctx.reference.LAST_PARTS
    read = readings(latent, memory, ref, last_parts)
    local = (ref["imprint"] != 0).any(-1)
    ctx.say("slot_memory", {
        "prompts": len(prompts), "new": new, "slots": slots,
        "seconds": {"probe": t_reference - t_probe,
                    "reference": t_read - t_reference},
        "tokens_with_a_local_pair": {
            "prefill": int(local[:, :, :window].sum()),
            "decode": int(local[:, :, window:].sum()),
        }, **read,
        "near_ties.prefill": near_ties(memory[2], ref, prompts),
    })
    for name, by in limits(check, last_parts).items():
        for part, limit in by.items():
            ctx.checks.at_most(f"{name}.{part}", read[name][part], limit)
    return {"ref": ref, "consumed": consumed, "cut": cut, "read": read}


# The controls are read on the probe's first slots: a reference's pass over
# every slot a control is the comparison's cost nine times.
CONTROL_SLOTS = 8


def control(ctx, out: dict) -> dict:
    """Each of the reference's ``CONTROLS`` put in the program's place:
    what every number of the slot memory then reads (over the probe's
    first ``CONTROL_SLOTS`` slots); and for the served tokens' widest gap
    its two controls, as the other latent loops read them: the token that
    8-bit operands put first, and a stream displaced by one position.
    ``fails`` names, for each control, the comparisons it does not pass:
    each must fail at least one."""
    latent = _latent(ctx)
    sample, dims = out["sample"], out["dims"]
    window, max_new = out["prompt_window"], out["max_new"]
    memory = out["memory"]
    last_parts = ctx.reference.LAST_PARTS
    lim = {
        "served_logit_gap": float(ctx.mix["check"]["max_logit_gap"]),
        **limits(ctx.mix["check"], last_parts),
    }
    some = slice(0, CONTROL_SLOTS)
    ref = {
        n: v if n == "window" else v[some] if n == "hidden" else v[:, some]
        for n, v in memory["ref"].items()
    }
    found = {"limits": lim, "program": {
        "served_logit_gap": sample["widest"], **memory["read"],
    }, "controls": {}, "fails": {}}

    def widest(probe):
        gap, _top = ctx.reference.served_logit_gaps(
            ctx.seed, dims, sample["toks"], window - 1, max_new, probe=probe
        )
        return float(np.max(np.where(sample["valid"], np.asarray(gap), 0.0)))

    for which in ctx.reference.CONTROLS:
        name = "e4m3" if which is True else which
        low = cut_rows(ctx.reference.slot_memory(
            ctx.seed, dims, memory["consumed"][some], lowp=which
        ), memory["cut"])
        read = readings(latent, (
            low["states"], low["tails"], low["rows"], low["hidden"]
        ), ref, last_parts)
        if which is True:
            _gap, top = ctx.reference.served_logit_gaps(
                ctx.seed, dims, sample["toks"], window - 1, max_new, lowp=True
            )
            read["served_logit_gap"] = widest(np.asarray(top))
        found["controls"][name] = read
    served = sample["toks"][:, window: window + max_new]
    found["controls"]["displaced_stream"] = {
        "served_logit_gap": widest(np.roll(served, 1, axis=1)),
    }
    for name, read in found["controls"].items():
        found["fails"][name] = [
            f"{check}.{part}" if part else check
            for check, by in read.items()
            for part, value in (
                by.items() if isinstance(by, dict) else (("", by),)
            )
            if not value <= (lim[check][part] if part else lim[check])
        ]
    return found
