"""The serving loop of a configuration whose linear layers are GATED SHORT
CONVOLUTIONS that keep no state (two rows of a conv tail a slot a layer)
beside grouped-query layers with a norm a head over K and V rows, and a
sigmoid-routed expert layer held whole: ``loops/serve.py`` whole (the
window, the served tokens against the plain reference), then a probe
server of the deployment's own programs and the comparisons of what its
compiled admit and ticks left in the slots, of what the last layer adds,
which no slot keeps, and of the router's own arithmetic. The statistics
are ``loops/serve_state.py``'s and ``loops/serve_ssd.py``'s (a tail and a
row by their relative error, a part's share by projection, each a median:
those files say why); what differs is what a slot holds.

The topic must not run dry: ``backlog_at_close`` counts the records no
slot had taken when the window closed, and ``correct`` wants one at least.

After the window the probe serves ``check.probe_slots`` of the run's
prompts at once for ``check.probe_new`` tokens. Its slot memory is read
TWICE, through the server's own entry points (``admit_records``, then
``run``): after the admission, when every tail holds the last two rows of
the prompt window (``tk_gconv_seq`` wrote them), and after the last tick
(``tk_gconv_step`` rolled them). The reference, teacher-forced on what the
probe served, gives:

(a) **the conv tail** of every convolution layer at both points:
    ``conv_tail_err.admit``, ``.worst_layer`` (``check.max_tail_err``). A
    tail is ``u = B . X`` of the layer's input: the first layer's holds
    the in-projection and the thirds' order, every later one the layers
    before it whole (the outer gate C, the taps' order, the experts).
(b) **both attention layers' K and V rows**, a position's normed and
    rotated K row beside its V row: the median over positions of a row's
    relative error, the rows the admission wrote and the rows ticks wrote
    (``check.max_kv_row_err``). They hold the norm a head and the rotation.
(c) **the last layer's parts.** What the layer that closes the cut adds
    reaches no slot. The program's own forward over what the probe's
    slots consumed (``model.final_stream``, the forward an admission runs)
    gives the stream after the last layer; its difference from the
    reference's is projected on the last layer's mixer's output and on its
    experts' sum (the share of each that the program LACKS,
    ``check.max_last_layer_missing``).
(d) **the router's arithmetic.** Float32 is stated for it and the bias is
    stated to move the selection alone; what bfloat16 there does (a gate
    off by a four-hundredth, one selection in thirty another) and what the
    bias in the gates does (a gate off by a six-hundredth) lie under the
    rounding of the bfloat16 stream that every other number here is read
    through (a projection of the stream on the shift the bias would add
    read 0.7 to 1.6 in SOUND runs on the chip, PERF.md: the window's
    padding rows are one row many times). So the program's OWN router
    function (``ops/moe.py::route``, the one an admission and a tick
    trace) is run on the reference's normed rows of the last layer,
    rounded to the compute dtype, with the served model's router and bias,
    against the reference's on the same rows: ``router.gate_err`` (the
    largest gate difference over the tokens that chose alike,
    ``check.max_router_gate_err``) and ``router.flips`` (the share of
    tokens with a margin over ``ROUTER_MARGIN`` that chose otherwise,
    ``check.max_router_flips``).

Limits lie between the sound runs' readings and the controls'
(``control``; PERF.md gives both).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import common

REGIONS = ("prefill", "decode")
# A selection is compared where the reference's margin is over this: the
# two sides' float32 scores of the same rows differ by up to 1e-4 on the
# chip (two fusions of the sigmoid), the median margin is 0.019.
ROUTER_MARGIN = 1e-3
# The router is read on the probe's first slots' tokens, and the controls
# too: a reference's pass over every slot a control is the comparison's
# cost six times.
ROUTED_SLOTS = 8
CONTROL_SLOTS = 8
# ``tests/chipbench/toy.py`` cuts widths and depth of every configuration
# and the deployments of the loops it knows by name; a rehearsal of this
# loop makes its own cuts: both leading dense layers and ONE period of
# four (c c | a c c c), 8 experts, top-2; and more records than half a
# second drains, so that the backlog is seen not to run dry.
REHEARSAL = {
    "config": {
        "num_hidden_layers": 6, "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 64, "published_num_hidden_layers": 6,
    },
    "deployment": {"slots": 4, "prompt_window": 16, "max_new": 16,
                   "ticks_per_sync": 4, "commit_every": 3},
    "traffic": {"records": 400, "deck": 16, "block": 4, "prompt_median": 6,
                "prompt_sigma": 0.8, "prompt_max": 16, "answer_median": 5,
                "answer_sigma": 0.8, "answer_min": 2, "answer_max": 16},
    # Float32 on both sides: the rehearsal's limits are float32's.
    "check": {"sample": 8, "probe_new": 9, "probe_slots": 4,
              "max_logit_gap": 1e-4, "max_tail_err": 1e-4,
              "max_kv_row_err": 1e-4, "max_last_layer_missing": 0.01,
              "max_router_gate_err": 1e-5,
              "max_router_flips": 0.0},
}


def _state(ctx):
    return common.load_named("loops", "serve_state", ctx.root)


def _ssd(ctx):
    return common.load_named("loops", "serve_ssd", ctx.root)


def run(ctx) -> dict:
    state = _state(ctx)
    serve = state._latent(ctx)._serve(ctx)
    if ctx.rehearsal:
        ctx.conf.update(REHEARSAL["config"])
        ctx.conf["deployment"].update(REHEARSAL["deployment"])
        ctx.mix["traffic"].update(REHEARSAL["traffic"])
        ctx.mix["check"].update(REHEARSAL["check"])
    # (``weights.Dims`` reads the norm's eps by the dense families' name)
    ctx.conf.update(ctx.model.dims_conf(ctx.conf))
    out = serve.run(ctx)
    state.say_cycles(ctx, out)
    say_load(ctx, out)
    ctx.checks.at_least("backlog_at_close", sum(
        1 for r in out["requests"] if r["active"] is None
    ), 1)
    if "sample" in out:
        with ctx.phase("slot_memory"):
            out["memory"] = compare_slot_memory(ctx, state, serve, out)
    return out


def say_load(ctx, out: dict) -> None:
    """For the reader of the log, from the program's own counters over the
    window: the pairs an expert met a tick (``moe_assignments`` counts the
    slots the device held active; 64 at 512 slots all active) and the
    share of the K/V rows every tick fetched that a served tick needed."""
    first, last = out["counters"][0], out["counters"][-1]

    def moved(section, name):
        return last[section][name] - first[section].get(name, 0)

    conf = ctx.conf
    ticks = moved("scheduler", "slot_ticks_run") / out["slots"]
    layers = sum(not d for d in _dense(conf))
    ctx.say("load", {
        "ticks": ticks,
        "pairs_an_expert_a_tick": moved("expert_layer", "moe_assignments") / max(
            ticks * layers * int(conf["num_experts"]), 1
        ),
        "slot_tick_use": moved("scheduler", "slot_ticks_served") / max(
            moved("scheduler", "slot_ticks_run"), 1
        ),
        "kv_rows_valid_of_read": moved("kv_pool", "full_positions_valid") / max(
            moved("kv_pool", "full_positions_read"), 1
        ),
    })


def _tails(server, ssd, live, taps: int):
    """The conv tails of the ``live`` slots as rows [L, S, taps - 1, D]
    (the program keeps a slot's in one row)."""
    (tails,) = ssd._fetch(server.cache_tensors[:1], live)
    return tails.reshape(*tails.shape[:2], taps - 1, -1)


def probe(ctx, serve, ssd, prompts: np.ndarray, new: int):
    """Serve ``prompts`` [S, window] for ``new`` tokens each through a
    server built as the cell's (same slots and slot memory: the same
    programs), by its own entry points: the records polled and handed to
    ``admit_records`` (the compiled admit), the tails read, then ``run``
    to the end (the compiled tick blocks), the slot memory read again →
    (tokens [S, window + new]; ``live``, the S slots that hold anything, in
    slot order; their tails after the admission [L_lin, S, taps - 1, D];
    after the ticks the tails and the K|V rows [L_att, S, window + new -
    2, 2 * K * Dh], float32 on the host; the program's stream after its
    last layer over what the slots consumed [S, window + new - 1, D],
    which no slot keeps, IN THE PROMPTS' ORDER; and the program's config
    with the last expert layer's router and bias, all its router needs).
    The server, its weights and its slot memory are freed before the
    reference needs the device."""
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
    live = ssd._live(server.cache_tensors[1])
    if len(live) != len(prompts):
        raise common.Refused(
            f"{len(live)} slots hold something after {len(prompts)} admissions"
        )
    taps = int(conf["conv_L_cache"])
    admitted = _tails(server, ssd, live, taps)
    tokens = np.zeros((len(prompts), window + new), np.int32)
    tokens[:, :window] = prompts
    for rec, toks in server.run(max_records=len(prompts), idle_timeout_ms=200):
        tokens[sent[(rec.partition, rec.offset)], window:] = toks
    _tail, pool_k, pool_v = server.cache_tensors
    # What the slots consumed: the window and all but the last token. A
    # finished slot ticks on until the sync, its position held: the row of
    # its last position ends as its final token's, not the one the
    # reference is forced with, and is left out (the tail of a slot that
    # is not active is kept as it is).
    cut = window + new - 2
    rows = np.concatenate(ssd._fetch((pool_k, pool_v), live, cut), axis=-1)
    ticked = (_tails(server, ssd, live, taps), rows)
    server.close()
    consumer.close()
    del server, _tail, pool_k, pool_v
    gc.collect()
    stream = ctx.model.final_stream(
        cfg, params, tokens[:, : window + new - 1]
    )[0]
    last = sum(not d for d in _dense(conf)) - 1  # among the expert layers
    router = (cfg, ctx.model.router_of(params, last))
    del params
    gc.collect()
    return tokens, live, admitted, ticked, stream, router


def _dense(conf) -> list[bool]:
    return [
        l < int(conf["num_dense_layers"])
        for l in range(int(conf["num_hidden_layers"]))
    ]


def router_readings(got, want) -> dict:
    """The program's (chosen, gates) on some rows against the reference's
    (chosen, gates, margin) on the same rows."""
    idx, gates = (np.asarray(a) for a in got)
    ref_idx, ref_gates, margin = (np.asarray(a) for a in want)
    order, ref_order = np.argsort(idx, -1), np.argsort(ref_idx, -1)
    alike = (
        np.take_along_axis(idx, order, -1)
        == np.take_along_axis(ref_idx, ref_order, -1)
    ).all(-1)
    err = np.abs(
        np.take_along_axis(gates, order, -1)
        - np.take_along_axis(ref_gates, ref_order, -1)
    ).max(-1)
    clear = margin > ROUTER_MARGIN
    return {
        "gate_err": float(err[alike].max()) if alike.any() else 1.0,
        "flips": float((~alike & clear).sum() / max(int(clear.sum()), 1)),
    }


def readings(ctx, state, ssd, memory, ref: dict, routed) -> dict:
    """Every number of ``memory`` = (tails after the admission or None,
    tails, rows, stream) of the probe's slots, in the prompts' order,
    against the reference's; ``routed`` = (the program's router's output,
    the reference's) on the same rows."""
    admitted, tails, rows, stream = memory
    window = ref["window"]
    where = {"prefill": slice(0, window), "decode": slice(window, None)}
    parts = zip(ctx.reference.LAST_PARTS, ref["last_parts"])
    read = {
        "conv_tail_err": {"worst_layer": state.tail_err(tails, ref["tails"])},
        "kv_row_err": {
            r: ssd.row_err(rows, ref["rows"], where[r]) for r in REGIONS
        },
        "last_layer_missing": {
            name: state.last_layer_missing(stream, ref["hidden"], part)
            for name, part in parts
        },
        "router": router_readings(*routed),
    }
    if admitted is not None:
        read["conv_tail_err"]["admit"] = state.tail_err(
            admitted, ref["tails_at"]
        )
    return read


def limits(ctx) -> dict:
    check = ctx.mix["check"]
    return {
        "conv_tail_err": dict.fromkeys(
            ("worst_layer", "admit"), float(check["max_tail_err"])
        ),
        "kv_row_err": dict.fromkeys(REGIONS, float(check["max_kv_row_err"])),
        "last_layer_missing": dict.fromkeys(
            ctx.reference.LAST_PARTS, float(check["max_last_layer_missing"])
        ),
        "router": {
            "gate_err": float(check["max_router_gate_err"]),
            "flips": float(check["max_router_flips"]),
        },
    }


def flat(readings: dict) -> dict:
    """``{"check": {"part": v}, "other": w}`` as ``{"check.part": v,
    "other": w}``: the names the comparisons are reported under."""
    return {
        f"{check}.{part}" if part else check: value
        for check, by in readings.items()
        for part, value in (by.items() if isinstance(by, dict) else (("", by),))
    }


def failing(read: dict, lim: dict) -> list[str]:
    """The comparisons of ``read`` that do not pass ``lim``."""
    lim = flat(lim)
    return [n for n, value in flat(read).items() if not value <= lim[n]]


def compare_slot_memory(ctx, state, serve, out: dict) -> dict:
    check, window = ctx.mix["check"], out["prompt_window"]
    ssd = _ssd(ctx)
    new = state.probe_length(
        int(check["probe_new"]), out["max_new"],
        int(ctx.conf["deployment"]["ticks_per_sync"]),
    )
    prompts = state.probe_prompts(
        ctx, out, min(int(check["probe_slots"]), out["slots"])
    )
    t_probe = time.perf_counter()
    tokens, live, admitted, ticked, stream, router = probe(
        ctx, serve, ssd, prompts, new
    )
    consumed = tokens[:, : window + new - 1]
    cut = window + new - 2
    t_reference = time.perf_counter()
    ref = {**ctx.reference.slot_memory(
        ctx.seed, out["dims"], consumed, snap_at=window
    ), "window": window}
    ref["rows"] = ref["rows"][:, :, :cut]
    # The router on the reference's rows of the last layer, as the
    # compute dtype holds them: the program's function and the reference's.
    rows = np.asarray(ref["router_in"][:ROUTED_SLOTS]).reshape(
        -1, ref["router_in"].shape[-1]
    ).astype(ctx.model.dtype_of(ctx.conf["deployment"]["compute_dtype"]))
    layer = len(_dense(ctx.conf)) - 1
    routed = (ctx.model.route_rows(*router, rows), ctx.reference.routed(
        ctx.seed, out["dims"], layer, rows
    ))
    t_read = time.perf_counter()
    at = ssd.slots_of(ticked[1], ref["rows"], window)
    memory = (admitted[:, at], *(a[:, at] for a in ticked), stream)
    read = readings(ctx, state, ssd, memory, ref, routed)
    ctx.say("slot_memory", {
        "prompts": len(prompts), "new": new,
        "slots": [int(live[i]) for i in at],
        "seconds": {"probe": t_reference - t_probe,
                    "reference": t_read - t_reference},
        "routed_rows": len(rows), **read,
    })
    got = flat(read)
    for name, limit in flat(limits(ctx)).items():
        ctx.checks.at_most(name, got[name], limit)
    return {"ref": ref, "consumed": consumed, "cut": cut, "read": read,
            "rows": rows, "routed": routed[0], "layer": layer}



def control(ctx, out: dict) -> dict:
    """Each of the reference's ``CONTROLS`` put in the program's place:
    what every number of the slot memory then reads (over the probe's
    first ``CONTROL_SLOTS`` slots; the router's against the sound
    reference's on the same rows); and for the served tokens' widest gap
    its two controls, as the other loops read them: the token that 8-bit
    operands put first, and a stream displaced by one position. ``fails``
    names, for each control, the comparisons it does not pass: each must
    fail at least one."""
    state, ssd = _state(ctx), _ssd(ctx)
    sample, dims = out["sample"], out["dims"]
    window, max_new = out["prompt_window"], out["max_new"]
    memory = out["memory"]
    lim = {
        "served_logit_gap": float(ctx.mix["check"]["max_logit_gap"]),
        **limits(ctx),
    }
    some = slice(0, CONTROL_SLOTS)
    ref = {
        n: v if n == "window" else v[some] if n in ("hidden", "router_in")
        else v[:, some] for n, v in memory["ref"].items()
    }
    sound = ctx.reference.routed(ctx.seed, dims, memory["layer"], memory["rows"])
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
            ctx.seed, dims, memory["consumed"][some], lowp=which,
            snap_at=window,
        )
        routed = ctx.reference.routed(
            ctx.seed, dims, memory["layer"], memory["rows"], which
        )
        read = readings(ctx, state, ssd, (
            low["tails_at"], low["tails"], low["rows"][:, :, : memory["cut"]],
            low["hidden"],
        ), ref, (routed[:2], sound))
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
        found["fails"][name] = failing(read, lim)
    return found
