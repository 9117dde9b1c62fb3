"""Shared by the readers of the routed expert layer's and the latent read's
device time. These are XLA operations, not Pallas kernels, and
``xplane.reduce`` keeps an operation's text (its operand shapes) for
kernels alone; so these readers go back to the profiler's file of the run
and tell the operations by the program that ran them and the shapes they
read. A program without such operations (or a run without a trace) gives
nothing to read."""

from __future__ import annotations

import re
from pathlib import Path

from chipbench import common, xplane

_PARSED: dict = {}


def tick_ops(run) -> list[tuple[str, float]] | None:
    """(text, seconds) of every operation that ran inside a tick program
    in the traced part of the window, on device 0."""
    if not run.get("trace"):
        return None
    trace_dir = (
        Path(run["root"]) / ".chipbench_trace"
        / f"{run['cell']['name']}-{run['seed']}"
    )
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    if not files:
        return None
    path = str(files[-1])
    if path not in _PARSED:
        from jax.profiler import ProfileData

        ops, mods = [], []
        for plane in ProfileData.from_file(path).planes:
            if xplane.DEVICE_PLANE.match(plane.name) and not ops:
                lines = {ln.name: ln for ln in plane.lines}
                if xplane.OPS_LINE in lines:
                    ops = sorted(xplane._events(lines[xplane.OPS_LINE]),
                                 key=lambda e: e[1])
                    mods = list(xplane._events(lines[xplane.MODULES_LINE]))
        owners = xplane._owner(ops, mods)
        _PARSED[path] = [
            (text, d) for (text, _s, d), owner in zip(ops, owners)
            if re.search(r"tick", owner)
            and xplane.opcode(text) not in xplane.CONTAINERS
        ]
    return _PARSED[path]


_CALL = re.compile(r"[\]\}\)] [a-z][a-z\-]*\(")


def split(text: str) -> tuple[str, str]:
    """An operation's text, ``%name = RESULT opcode(OPERANDS), ...``, as
    (result types, operands and what follows them)."""
    _name, _, rest = text.partition(" = ")
    m = _CALL.search(rest)
    return (rest[: m.start() + 1], rest[m.end():]) if m else (rest, "")


def seconds(run, reads: str, result_not: str | None = None) -> float | None:
    """Device seconds of the tick operations that read an operand
    matching ``reads`` and whose result does not match ``result_not``."""
    ops = tick_ops(run)
    if ops is None:
        return None
    total = 0.0
    for text, d in ops:
        result, operands = split(text)
        if re.search(reads, operands) and not (
            result_not and re.search(result_not, result)
        ):
            total += d
    return total or None


def ticks_traced(run) -> float:
    """Decode ticks the traced part of the window ran."""
    from chipbench.layer_metrics import _programs

    _s, runs = _programs.total(run, r"tick")
    return runs * run["conf"]["deployment"]["ticks_per_sync"]


def ticks_in_window(run) -> float:
    ran = section_delta(run, "scheduler", "slot_ticks_run")
    return ran / run["slots"] if ran else 0.0


def section_delta(run, section: str, name: str):
    """How far ``ServeMetrics.summary()[section][name]`` moved over the
    window; None for a program that does not count it."""
    first, last = (
        c.get(section, {}).get(name)
        for c in (run["counters"][0], run["counters"][-1])
    )
    if first is None or last is None:
        return None
    if isinstance(last, list):
        first = first or [0] * len(last)
        return [b - a for a, b in zip(first, last)]
    return last - first


def kernels(run, name: str):
    return common.load_named("kernels", name, run["root"])
