"""Shared by the readers of what the program names itself: its Pallas
kernels (``pl.pallas_call(name="tk_...")`` in ``ops/``) and its host spans
(``utils/tracing.py``), both as ``xplane.reduce`` keeps them. A program
that lacks a name gives nothing to read, and the reader returns None."""

import re

from chipbench import stats, xplane


def kernel_total(run, name: str, program: str = ""):
    """Seconds and calls, on a chip, of the Pallas kernels called ``name``
    (the trace numbers its operations: ``tk_flash_fwd.3``) inside the
    programs whose name matches ``program``."""
    tr = run["trace"]
    if not tr:
        return 0.0, 0.0
    seconds = calls = 0.0
    for key, t in tr["kernels"].items():
        if xplane.op_family(key.rpartition("/")[2]) == name and re.search(
            program, t["program"]
        ):
            seconds += t["total_s"]
            calls += t["count"]
    return seconds, calls


def durations(run, name: str) -> list[float]:
    """Seconds of each span called ``name`` in the traced part of the
    window."""
    tr = run["trace"]
    return [d for _s, d in tr["host_spans"].get(name, ())] if tr else []


def median_ms(run, name: str):
    ds = durations(run, name)
    return 1e3 * stats.median(ds) if ds else None


def ms_per_sync(run, names) -> float | None:
    """Host time a sync costs in the ``tk_serve:<name>`` spans: their
    durations in the traced part of the window, summed, over its syncs.
    None for a program that opens no ``tk_serve:retire`` (the parent of
    the PR that brought these spans opens none of them, and a sum of
    nothing would read as a loop that costs nothing)."""
    syncs = len(durations(run, "tk_serve:sync"))
    if not syncs or not durations(run, "tk_serve:retire"):
        return None
    host = sum(sum(durations(run, f"tk_serve:{name}")) for name in names)
    return 1e3 * host / syncs


def counter_delta(run, name: str):
    """How far the scheduler's cumulative count ``name`` moved between the
    window's first and last reading of ``ServeMetrics.summary()``."""
    first, last = (
        c.get("scheduler", {}).get(name)
        for c in (run["counters"][0], run["counters"][-1])
    )
    return None if first is None or last is None else last - first
