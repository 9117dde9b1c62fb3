"""The serving loop of a configuration with LEARNED SPARSE ATTENTION (an
indexer scores every cached position, a query reads the rows it selected;
one chip's share of softmax-routed experts beside it): ``loops/serve.py``
whole (the window, the served tokens against the plain reference), then a
probe server of the deployment's own programs and the comparisons of what
its compiled admit and ticks left in the slots, and of what the last layer
adds, which no slot keeps. The statistics are ``loops/serve_state.py``'s
(a row by its relative error, a part's missing share by projection, each a
median: that file says why).

After the window the probe serves ``check.probe_slots`` of the run's
prompts at once, each padded to the window as the server pads it (8,192
positions: four times the top-k, or the indexer would not be in the
comparison at all), for ``check.probe_new`` tokens. Its slot memory is
read through the server's own entry points after the last tick; a row at
a position under the window is the compiled admit's, a row past it a
compiled tick's (regions ``prefill`` and ``decode``). The reference,
teacher-forced on what the probe served, gives the same rows, and then:

(a) **the K and V rows** (a position's K row beside its V row, unpacked
    from the pool's words) **and the index-key rows**, every layer's: the
    median over positions of a row's relative error, the worst layer
    (``check.max_kv_row_err``, ``check.max_index_row_err``).
(b) **the selection.** What a layer's attention adds to the stream is a
    thousandth of the stream (a mean over 2,048 values), under the rows'
    own rounding; but the reference knows WHICH WAY layer 1's rows would
    move had layer 0 selected otherwise (``reference.SHIFTS``: every
    valid position attended; the best half of the top-k), and the
    program's rows are projected on each of those directions: 0 where
    the program selected as the reference, 1 where it selected as the
    broken form; the median over the positions past the top-k
    (``check.max_selection_shift``).
(c) **the held experts' part**: how the held experts' part of the layer
    before shows in a layer's rows (the IMPRINT), and the share of it the
    program's rows lack: 0 where the program added what the reference
    added, 1 where it added nothing or another expert's output; the median
    over the tokens with a local pair (``check.max_held_pair_missing``).
    Among sixteen held experts a range moved by one changes a sixteenth
    of the local pairs and no median over them, so the parts of layer 0's
    FIRST and LAST held expert are read alone as well, each over the
    tokens with a pair on it (``held_edge_missing``, the same limit).
(d) **the last layer's parts.** What the layer that closes the cut adds
    reaches no slot. The program's own forward over what the probe's
    slots consumed (``model.final_stream``, the forward an admission runs)
    gives the stream after the last layer, and its difference from the
    reference's is projected on the last layer's attention part and on its
    held experts' part (``check.max_last_layer_missing``).

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
# loop makes its own cuts: 2 layers, 4 index heads, a top-k of 8 under
# windows of 16, 8 experts of which 2 are held, top-2.
REHEARSAL = {
    "config": {
        "num_hidden_layers": 2, "num_experts": 2, "num_local_experts": 2,
        "published_num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 64,
        "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 8},
    },
    "deployment": {"slots": 4, "prompt_window": 16, "max_new": 16,
                   "ticks_per_sync": 4, "commit_every": 3,
                   "experts_held": [0, 2]},
    "traffic": {"records": 40, "deck": 16, "block": 4, "prompt_median": 6,
                "prompt_sigma": 0.8, "prompt_max": 16, "answer_median": 5,
                "answer_sigma": 0.8, "answer_min": 2, "answer_max": 16},
    # Float32 on both sides: the rehearsal's limits are float32's.
    "check": {"sample": 24, "probe_new": 9, "probe_slots": 4,
              "max_logit_gap": 1e-4, "max_kv_row_err": 1e-3,
              "max_index_row_err": 1e-3, "max_selection_shift": 0.05,
              "max_held_pair_missing": 0.05,
              "max_last_layer_missing": 0.05},
}


def _state(ctx):
    return common.load_named("loops", "serve_state", ctx.root)


def run(ctx) -> dict:
    state = _state(ctx)
    serve = state._latent(ctx)._serve(ctx)
    if ctx.rehearsal:
        ctx.conf.update(REHEARSAL["config"])
        ctx.conf["deployment"].update(REHEARSAL["deployment"])
        ctx.mix["traffic"].update(REHEARSAL["traffic"])
        ctx.mix["check"].update(REHEARSAL["check"])
    out = serve.run(ctx)
    state.say_cycles(ctx, out)
    if "sample" in out:
        with ctx.phase("slot_memory"):
            out["memory"] = compare_slot_memory(ctx, state, serve, out)
    return out


def unpack(words: np.ndarray, kv_heads: int, head_dim: int) -> np.ndarray:
    """The pool's rows [.., R, C] -> a position's K row beside its V row
    [.., 2 K E] float32, in the reference's order of columns. int32 words
    hold two bfloat16 each: of the K part's (then the V part's) ``K * E /
    2`` words, word ``g * E/2 + c`` holds head g's channel ``c`` in its low
    half and its channel ``c + E/2`` in its high half. A float32 pool
    (the rehearsal's) holds the numbers as they are."""
    flat = words.reshape(*words.shape[:-2], -1)
    if flat.dtype != np.int32:
        return flat.astype(np.float32)
    bits = flat.view(np.uint32)
    lo = (bits << np.uint32(16)).view(np.float32)
    hi = (bits & np.uint32(0xFFFF0000)).view(np.float32)
    half = head_dim // 2

    def part(a):  # [.., K * E/2] -> [.., K, E/2]
        return a.reshape(*a.shape[:-1], kv_heads, half)

    n = flat.shape[-1] // 2
    return np.concatenate([
        np.concatenate([part(lo[..., s]), part(hi[..., s])], axis=-1)
        .reshape(*flat.shape[:-1], -1)
        for s in (slice(0, n), slice(n, None))
    ], axis=-1)


def probe(ctx, serve, prompts: np.ndarray, new: int):
    """Serve ``prompts`` [S, window] for ``new`` tokens each through a
    server built as the cell's (same slots and slot memory: the same
    programs), by its own entry points (``admit_records``: the compiled
    admit; ``run``: the compiled tick blocks) -> (tokens [S, window +
    new]; the live slots' K|V rows [L, S, cut, 2 K E] and index keys [L,
    S, cut, Di] in slot order, float32 on the host; the program's stream
    after its last layer over what the slots consumed [S, window + new -
    1, D], IN THE PROMPTS' ORDER). The server, its weights and its slot
    memory are freed before the reference needs the device."""
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
    records = []
    while len(records) < len(prompts):
        got = consumer.poll(max_records=len(prompts), timeout_ms=200)
        if not got:
            raise common.Refused("the probe's topic ran dry before its end")
        records.extend(got)
    server.note_fetched(records)
    if server.admit_records(records) != len(prompts):
        raise common.Refused("the probe's prompts were not all admitted")
    # Which slots hold a prompt, read before any tick (a slot that is idle
    # ticks on at position 0 and leaves a row there).
    live = np.flatnonzero(
        (np.asarray(server.cache_tensors[0][0, :, 0]) != 0).any(axis=(-2, -1))
    )
    if len(live) != len(prompts):
        raise common.Refused(
            f"{len(live)} slots hold something after {len(prompts)} admissions"
        )
    tokens = np.zeros((len(prompts), window + new), np.int32)
    tokens[:, :window] = prompts
    for rec, toks in server.run(max_records=len(prompts), idle_timeout_ms=200):
        tokens[sent[(rec.partition, rec.offset)], window:] = toks
    pool_rows, pool_keys = server.cache_tensors
    # What the slots consumed: the window and all but the last token. A
    # finished slot's last position ends as its final token's row, not the
    # one the reference is forced with, and is left out.
    cut = window + new - 2
    rows = unpack(
        np.asarray(pool_rows[:, live, :cut]),
        int(conf["num_key_value_heads"]), int(conf["head_dim"]),
    )
    keys = np.asarray(
        pool_keys[:, live, :, :cut]
    ).astype(np.float32).swapaxes(-1, -2)
    server.close()
    consumer.close()
    del server, pool_rows, pool_keys
    gc.collect()
    stream = np.asarray(ctx.model.final_stream(
        cfg, params, tokens[:, : window + new - 1]
    ))
    del params
    gc.collect()
    return tokens, rows, keys, stream


def slots_of(rows: np.ndarray, want: np.ndarray, window: int) -> list[int]:
    """For each prompt, which of the live slots served it: the one whose
    first layer's rows over the prompt window lie nearest the
    reference's."""
    at = [
        int(np.argmin(((rows[0, :, :window] - w[None]) ** 2).sum((1, 2))))
        for w in want[0, :, :window]
    ]
    if len(set(at)) != len(at):
        raise common.Refused(f"the probe's prompts share a slot: {at}")
    return at


def row_err(state, rows, want, positions: slice) -> float:
    """Relative error of a row [.., C] against the reference's, its
    median over the rows of ``positions``, the worst layer."""
    err = state._rel(rows[:, :, positions], want[:, :, positions], -1)
    return float(np.max(np.median(err.reshape(err.shape[0], -1), axis=1)))


def selection_shift(state, rows, want, broken, positions) -> float:
    """How far layer 1's rows lie from the reference's TOWARDS the rows a
    broken selection in layer 0 would have given: 0 the reference's
    selection, 1 the broken one; the median over ``positions``."""
    shift, has = state.part_missing(
        (rows - want)[:, positions], (want - broken)[:, positions]
    )
    return abs(float(np.median(shift[has]))) if has.any() else 0.0


def regions(window: int, topk: int) -> dict:
    """Where each region's rows lie, and where in it the selection bites
    (a query under the top-k selects every position)."""
    return {
        "prefill": (slice(0, window), slice(min(topk, window), window)),
        "decode": (slice(window, None), slice(max(window, topk), None)),
    }


def readings(state, memory, ref: dict, names) -> dict:
    """Every number of ``memory`` = (rows, index keys, stream) of the
    probe's slots, in the prompts' order, against the reference's."""
    rows, keys, stream = memory
    where = regions(ref["window"], ref["topk"])
    shifts, edges, last_parts = names
    return {
        "kv_row_err": {
            r: row_err(state, rows, ref["rows"], where[r][0]) for r in REGIONS
        },
        "index_row_err": {
            r: row_err(state, keys, ref["index"], where[r][0]) for r in REGIONS
        },
        "selection_shift": {
            f"{name}.{r}": selection_shift(
                state, rows[1], ref["rows"][1], ref["shifts"][name],
                where[r][1],
            ) for name in shifts for r in REGIONS
        },
        "held_pair_missing": {
            r: state.held_pair_missing(
                rows, ref["rows"], ref["imprint"], where[r][0]
            ) for r in REGIONS
        },
        "held_edge_missing": {
            name: state.last_layer_missing(
                rows[1], ref["rows"][1], ref["edges"][name]
            ) for name in edges
        },
        "last_layer_missing": {
            name: state.last_layer_missing(stream, ref["hidden"], part)
            for name, part in zip(last_parts, ref["last_parts"])
        },
    }


def limits(check: dict, names) -> dict:
    shifts, edges, last_parts = names
    return {
        "kv_row_err": dict.fromkeys(REGIONS, float(check["max_kv_row_err"])),
        "index_row_err": dict.fromkeys(
            REGIONS, float(check["max_index_row_err"])
        ),
        "selection_shift": dict.fromkeys(
            (f"{name}.{r}" for name in shifts for r in REGIONS),
            float(check["max_selection_shift"]),
        ),
        "held_pair_missing": dict.fromkeys(
            REGIONS, float(check["max_held_pair_missing"])
        ),
        "held_edge_missing": dict.fromkeys(
            edges, float(check["max_held_pair_missing"])
        ),
        "last_layer_missing": dict.fromkeys(
            last_parts, float(check["max_last_layer_missing"])
        ),
    }


def _names(ctx):
    r = ctx.reference
    return r.SHIFTS, r.EDGES, r.LAST_PARTS


def cut_rows(ref: dict, cut: int) -> dict:
    """The reference's rows without the last position's (``probe``)."""
    return {
        **ref, "rows": ref["rows"][:, :, :cut],
        "index": ref["index"][:, :, :cut],
        "imprint": ref["imprint"][:, :, :cut],
        "shifts": {n: v[:, :cut] for n, v in ref["shifts"].items()},
        "edges": {n: v[:, :cut] for n, v in ref["edges"].items()},
    }


def compare_slot_memory(ctx, state, serve, out: dict) -> dict:
    check, window = ctx.mix["check"], out["prompt_window"]
    new = state.probe_length(
        int(check["probe_new"]), out["max_new"],
        int(ctx.conf["deployment"]["ticks_per_sync"]),
    )
    prompts = state.probe_prompts(
        ctx, out, min(int(check["probe_slots"]), out["slots"])
    )
    t_probe = time.perf_counter()
    tokens, rows, keys, stream = probe(ctx, serve, prompts, new)
    consumed = tokens[:, : window + new - 1]
    cut = window + new - 2
    t_reference = time.perf_counter()
    ref = cut_rows({
        **ctx.reference.slot_memory(ctx.seed, out["dims"], consumed),
        "window": window, "topk": int(ctx.conf["sa_config"]["topk"]),
    }, cut)
    t_read = time.perf_counter()
    at = slots_of(rows, ref["rows"], window)
    memory = (rows[:, at], keys[:, at], stream)
    names = _names(ctx)
    read = readings(state, memory, ref, names)
    local = (ref["imprint"] != 0).any(-1)
    ctx.say("slot_memory", {
        "prompts": len(prompts), "new": new,
        "seconds": {"probe": t_reference - t_probe,
                    "reference": t_read - t_reference},
        "tokens_with_a_local_pair": {
            "prefill": int(local[:, :, :window].sum()),
            "decode": int(local[:, :, window:].sum()),
        }, **read,
    })
    for name, by in limits(check, names).items():
        for part, limit in by.items():
            ctx.checks.at_most(f"{name}.{part}", read[name][part], limit)
    return {"ref": ref, "consumed": consumed, "cut": cut, "read": read}


# The controls are read on the probe's first slots: a reference's pass over
# every slot a control is the comparison's cost four times.
CONTROL_SLOTS = 2


def control(ctx, out: dict) -> dict:
    """Each of the reference's ``CONTROLS`` put in the program's place:
    what every number of the slot memory then reads (over the probe's
    first ``CONTROL_SLOTS`` slots); and for the served tokens' widest gap
    its two controls, as the other loops read them: the token that 8-bit
    operands put first, and a stream displaced by one position. ``fails``
    names, for each control, the comparisons it does not pass: each must
    fail at least one."""
    state = _state(ctx)
    sample, dims = out["sample"], out["dims"]
    window, max_new = out["prompt_window"], out["max_new"]
    memory = out["memory"]
    names = _names(ctx)
    lim = {
        "served_logit_gap": float(ctx.mix["check"]["max_logit_gap"]),
        **limits(ctx.mix["check"], names),
    }
    some = slice(0, CONTROL_SLOTS)
    full = memory["ref"]
    ref = {
        **full, "rows": full["rows"][:, some], "index": full["index"][:, some],
        "imprint": full["imprint"][:, some], "hidden": full["hidden"][some],
        "shifts": {n: v[some] for n, v in full["shifts"].items()},
        "edges": {n: v[some] for n, v in full["edges"].items()},
        "last_parts": tuple(p[some] for p in full["last_parts"]),
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
        low = ctx.reference.slot_memory(
            ctx.seed, dims, memory["consumed"][some], lowp=which
        )
        cut = memory["cut"]
        read = readings(state, (
            low["rows"][:, :, :cut], low["index"][:, :, :cut], low["hidden"],
        ), ref, names)
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
