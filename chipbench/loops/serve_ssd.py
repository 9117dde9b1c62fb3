"""The serving loop of a configuration whose slots keep a STATE-SPACE
state (Mamba-2 mixers beside a grouped-query attention layer over K and V
rows, one chip's share of softmax-routed experts): ``loops/serve.py``
whole (the window, the served tokens against the plain reference), then a
probe server of the deployment's own programs and the comparisons of what
its compiled admit and ticks left in the slots, and of what the last layer
adds, which no slot keeps. The statistics are ``loops/serve_state.py``'s
(a head's state by its relative error, a row by its, a part's missing
share by projection, each a median: that file says why); what differs is
what a slot holds and when it is read.

After the window the probe serves ``check.probe_slots`` of the run's
prompts at once for ``check.probe_new`` tokens. Its slot memory is read
TWICE, through the server's own entry points (``admit_records``, then
``run``): after the admission, when every slot holds exactly the prompt
window, and after the last tick. The median head of this model forgets a
window within some hundred tokens (``exp(dt A)`` to the 256th), so the
state after the ticks says nothing of the admission's chunked scan: the
first reading holds ``ssd_chunk``, the second ``tk_ssd_step``. The
reference, teacher-forced on what the probe served, walks its recurrence
token by token, and then:

(a) **the state and the conv tail** of every Mamba-2 layer, after the
    window and after the ticks: ``state_err.admit_first_layer``,
    ``.admit_worst_layer``, ``.first_layer``, ``.worst_layer`` and
    ``conv_tail_err.admit``, ``.worst_layer``. The first layer reads the
    embedding itself, so no layer's rounding stands before it and its
    state tells float32 from bfloat16 accumulation
    (``check.max_state_err_first``); the worst layer carries every
    earlier layer's rounding (``check.max_state_err``).
(b) **the attention layer's K and V rows**, a position's K row beside its
    V row: the median over positions of a row's relative error, the rows
    the admission wrote and the rows ticks wrote
    (``check.max_kv_row_err``).
(c) **the held experts' part**: the attention layer rotates nothing, so
    its rows at a position are a function of the stream there alone, and
    the reference knows how the held experts' part of the layer before
    shows in them (the IMPRINT). The program's rows are projected on it:
    0 where the program added what the reference added, 1 where it added
    nothing or another expert's output; the median over the tokens with a
    local pair (``check.max_held_pair_missing``).
(d) **the last layer's part.** What the layer that closes the cut adds
    reaches no slot. The program's own forward over what the probe's
    slots consumed (``model.final_stream``, the forward an admission runs)
    gives the stream after the last layer, and its difference from the
    reference's is projected on the last layer's held experts' part
    (``check.max_last_layer_missing``).

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
# loop makes its own cuts: ONE period of four layers that keeps both kinds
# (M M A M), 8 experts of which 4 are held, top-2.
REHEARSAL = {
    "config": {
        "num_hidden_layers": 4,
        "layer_types": ["mamba", "mamba", "attention", "mamba"],
        "mamba_n_heads": 8, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_chunk_size": 8, "num_local_experts": 4,
        "published_num_local_experts": 8, "num_experts_per_tok": 2,
        "intermediate_size": 64, "shared_intermediate_size": 128,
    },
    "deployment": {"slots": 4, "prompt_window": 16, "max_new": 16,
                   "ticks_per_sync": 4, "commit_every": 3,
                   "experts_held": [0, 4]},
    "traffic": {"records": 40, "deck": 16, "block": 4, "prompt_median": 6,
                "prompt_sigma": 0.8, "prompt_max": 16, "answer_median": 5,
                "answer_sigma": 0.8, "answer_min": 2, "answer_max": 16},
    # Float32 on both sides: the rehearsal's limits are float32's.
    "check": {"sample": 24, "probe_new": 10, "probe_slots": 4,
              "max_logit_gap": 1e-4,
              "max_state_err_first": 1e-4, "max_state_err": 1e-3,
              "max_tail_err": 1e-3, "max_kv_row_err": 1e-3,
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


def _live(pool_k):
    """The slots of a fresh server that hold anything: those an admission
    wrote a K row for at position 0."""
    import jax.numpy as jnp

    filled = jnp.abs(pool_k[0, :, 0].astype(jnp.float32)).sum(-1) > 0
    return np.flatnonzero(np.asarray(filled))


def _fetch(arrays, slots, upto=None):
    """``arrays`` [L, slots, ...] at ``slots``, float32 on the host."""
    return tuple(
        np.asarray(a[:, slots] if upto is None else a[:, slots, :upto])
        .astype(np.float32) for a in arrays
    )


def _memory(server, live, taps: int):
    """The state and the conv tails of the ``live`` slots; the tails as
    rows [L, S, taps - 1, C] (the program keeps a slot's in one row)."""
    states, tails = _fetch(server.cache_tensors[:2], live)
    return states, tails.reshape(*tails.shape[:2], taps - 1, -1)


def probe(ctx, serve, prompts: np.ndarray, new: int):
    """Serve ``prompts`` [S, window] for ``new`` tokens each through a
    server built as the cell's (same slots and slot memory: the same
    programs), by its own entry points: the records polled and handed to
    ``admit_records`` (the compiled admit), the slot memory read, then
    ``run`` to the end (the compiled tick blocks), the slot memory read
    again → (tokens [S, window + new]; ``live``, the S slots that hold
    anything, in slot order; their memory after the admission ``(states
    [L_lin, S, H, P, N], tails)`` and after the ticks ``(states, tails,
    K|V rows [L_att, S, window + new - 2, 2 * K * Dh])``, float32 on the
    host; and the program's stream after its last layer over what the
    slots consumed [S, window + new - 1, D], which no slot keeps, IN THE
    PROMPTS' ORDER). The server, its weights and its slot memory are
    freed before the reference needs the device."""
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
    live = _live(server.cache_tensors[2])
    if len(live) != len(prompts):
        raise common.Refused(
            f"{len(live)} slots hold something after {len(prompts)} admissions"
        )
    taps = int(conf["mamba_d_conv"])
    admitted = _memory(server, live, taps)
    tokens = np.zeros((len(prompts), window + new), np.int32)
    tokens[:, :window] = prompts
    for rec, toks in server.run(max_records=len(prompts), idle_timeout_ms=200):
        tokens[sent[(rec.partition, rec.offset)], window:] = toks
    _states, _tails, pool_k, pool_v = server.cache_tensors
    # What the slots consumed: the window and all but the last token. A
    # finished slot ticks on until the sync, its position held: the row of
    # its last position ends as its final token's, not the one the
    # reference is forced with, and is left out (the state and the conv
    # tail of a slot that is not active are kept as they are).
    cut = window + new - 2
    rows = np.concatenate(_fetch((pool_k, pool_v), live, cut), axis=-1)
    ticked = _memory(server, live, taps) + (rows,)
    server.close()
    consumer.close()
    del server, _states, _tails, pool_k, pool_v
    gc.collect()
    stream = ctx.model.final_stream(
        cfg, params, tokens[:, : window + new - 1]
    )[0]
    del params
    gc.collect()
    return tokens, live, admitted, ticked, stream


def slots_of(rows: np.ndarray, want: np.ndarray, window: int) -> list[int]:
    """For each prompt, which of the live slots served it: the one whose K
    rows over the prompt window lie nearest the reference's. rows [L_att,
    S, T, C] in slot order, want likewise in the prompts' order."""
    first = rows[0, :, :window]
    at = [
        int(np.argmin(((first - w[None]) ** 2).sum((1, 2))))
        for w in want[0, :, :window]
    ]
    if len(set(at)) != len(at):
        raise common.Refused(f"the probe's prompts share a slot: {at}")
    return at


def row_err(rows: np.ndarray, want: np.ndarray, positions: slice) -> float:
    """Relative error of a row [.., C] against the reference's, its
    median over the rows of ``positions``, the worst layer."""
    a, b = rows[:, :, positions], want[:, :, positions]
    err = np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)
    return float(np.max(np.median(err.reshape(err.shape[0], -1), axis=1)))


def readings(state, memory, ref: dict, last_parts) -> dict:
    """Every number of ``memory`` = (states and tails after the admission
    or None, states, tails, rows, stream) of the probe's slots, in the
    prompts' order, against the reference's."""
    admitted, states, tails, rows, stream = memory
    window = ref["window"]
    where = {"prefill": slice(0, window), "decode": slice(window, None)}
    by_layer = state.state_err(states, ref["states"])
    read = {
        "state_err": {
            "first_layer": float(by_layer[0]),
            "worst_layer": float(by_layer.max()),
        },
        "conv_tail_err": {
            "worst_layer": state.tail_err(tails, ref["tails"]),
        },
        "kv_row_err": {
            r: row_err(rows, ref["rows"], where[r]) for r in REGIONS
        },
        "held_pair_missing": {
            r: state.held_pair_missing(
                rows, ref["rows"], ref["imprint"], where[r]
            ) for r in REGIONS
        },
        "last_layer_missing": {
            name: state.last_layer_missing(stream, ref["hidden"], part)
            for name, part in zip(last_parts, ref["last_parts"])
        },
    }
    if admitted is not None:
        early = state.state_err(admitted[0], ref["states_at"])
        read["state_err"].update(
            admit_first_layer=float(early[0]),
            admit_worst_layer=float(early.max()),
        )
        read["conv_tail_err"]["admit"] = state.tail_err(
            admitted[1], ref["tails_at"]
        )
    return read


def limits(check: dict, last_parts) -> dict:
    first, worst = (
        float(check[k]) for k in ("max_state_err_first", "max_state_err")
    )
    return {
        "state_err": {
            "first_layer": first, "worst_layer": worst,
            "admit_first_layer": first, "admit_worst_layer": worst,
        },
        "conv_tail_err": dict.fromkeys(
            ("worst_layer", "admit"), float(check["max_tail_err"])
        ),
        "kv_row_err": dict.fromkeys(REGIONS, float(check["max_kv_row_err"])),
        "held_pair_missing": dict.fromkeys(
            REGIONS, float(check["max_held_pair_missing"])
        ),
        "last_layer_missing": dict.fromkeys(
            last_parts, float(check["max_last_layer_missing"])
        ),
    }


def cut_rows(ref: dict, cut: int) -> dict:
    """The reference's rows without the last position's (``probe``)."""
    return {
        **ref, "rows": ref["rows"][:, :, :cut],
        "imprint": ref["imprint"][:, :, :cut],
        "margin": ref["margin"][:, :, :cut],
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
    tokens, live, admitted, ticked, stream = probe(ctx, serve, prompts, new)
    consumed = tokens[:, : window + new - 1]
    cut = window + new - 2
    t_reference = time.perf_counter()
    ref = cut_rows({
        **ctx.reference.slot_memory(
            ctx.seed, out["dims"], consumed, snap_at=window
        ), "window": window,
    }, cut)
    t_read = time.perf_counter()
    at = slots_of(ticked[2], ref["rows"], window)
    memory = (
        tuple(a[:, at] for a in admitted), *(a[:, at] for a in ticked), stream,
    )
    last_parts = ctx.reference.LAST_PARTS
    read = readings(state, memory, ref, last_parts)
    local = (ref["imprint"] != 0).any(-1)
    ctx.say("slot_memory", {
        "prompts": len(prompts), "new": new,
        "slots": [int(live[i]) for i in at],
        "seconds": {"probe": t_reference - t_probe,
                    "reference": t_read - t_reference},
        "tokens_with_a_local_pair": {
            "prefill": int(local[:, :, :window].sum()),
            "decode": int(local[:, :, window:].sum()),
        }, **read,
    })
    for name, by in limits(check, last_parts).items():
        for part, limit in by.items():
            ctx.checks.at_most(f"{name}.{part}", read[name][part], limit)
    return {"ref": ref, "consumed": consumed, "cut": cut, "read": read}


# The controls are read on the probe's first slots: a reference's pass over
# every slot a control is the comparison's cost eight times.
CONTROL_SLOTS = 8


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
            ctx.seed, dims, memory["consumed"][some], lowp=which,
            snap_at=window,
        ), memory["cut"])
        read = readings(state, (
            (low["states_at"], low["tails_at"]), low["states"], low["tails"],
            low["rows"], low["hidden"],
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
