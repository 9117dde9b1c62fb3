"""The serving loop of a configuration with kinds of layer (sliding-window
and full layers over a pool allocated by kind): ``loops/serve.py`` whole
and as it is (the served tokens of the sampled requests, teacher-forced
through the plain reference; the commit cadence; the guarantees), with
two things a file that is there cannot be edited for:

- the rehearsal's cuts. ``tests/chipbench/toy.py`` cuts the deployments of
  the loops it knows by name to sizes a CPU runs, and gives ``serve`` the
  int8 pool's Pallas read, which a pool by kind refuses; a rehearsal of
  this loop makes its own cuts, down to ONE period of the layer pattern
  and a window the toy's answers wrap;
- the controls. ``control`` puts each of the reference's ``CONTROLS`` in
  the program's place (``loops/serve.py::control`` knows the lower
  precision alone): ``chipbench/control.py --control 1`` prints them all.
"""

from __future__ import annotations

import numpy as np

from chipbench import common

REHEARSAL = {
    "config": {"num_hidden_layers": 4, "sliding_window": 8, "num_experts": 8,
               "num_experts_per_tok": 2, "moe_intermediate_size": 64},
    "deployment": {"slots": 4, "prompt_window": 16, "max_new": 16,
                   "ticks_per_sync": 4, "commit_every": 3},
    "traffic": {"records": 40, "deck": 16, "block": 4, "prompt_median": 6,
                "prompt_sigma": 0.8, "prompt_max": 16, "answer_median": 5,
                "answer_sigma": 0.8, "answer_min": 2, "answer_max": 16},
    "check": {"sample": 24},
}


def _serve(ctx):
    return common.load_named("loops", "serve", ctx.root)


def run(ctx) -> dict:
    if ctx.rehearsal:
        ctx.conf.update(REHEARSAL["config"])
        ctx.conf["deployment"].update(REHEARSAL["deployment"])
        ctx.mix["traffic"].update(REHEARSAL["traffic"])
        ctx.mix["check"].update(REHEARSAL["check"])
    return _serve(ctx).run(ctx)


def control(ctx, run) -> dict:
    """For each control: at each position of the same prompts and served
    tokens, the gap (by the sound reference) of the token the control puts
    first; the widest over the served positions."""
    sample, dims = run["sample"], run["dims"]
    window, max_new = run["prompt_window"], run["max_new"]
    gaps = ctx.reference.served_logit_gaps
    out = {}
    for which in ctx.reference.CONTROLS:
        _gap, top = gaps(
            ctx.seed, dims, sample["toks"], window - 1, max_new, lowp=which
        )
        gap, _top = gaps(
            ctx.seed, dims, sample["toks"], window - 1, max_new,
            probe=np.asarray(top),
        )
        name = "e4m3" if which is True else which
        out[name] = float(
            np.max(np.where(sample["valid"], np.asarray(gap), 0.0))
        )
    return {"served_logit_gap": {
        "program": sample["widest"], "controls": out,
        "limit": float(ctx.mix["check"]["max_logit_gap"]),
    }}
