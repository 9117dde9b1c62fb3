"""Shared by the readers of a pool by layer kind: a kind's read of a
decode tick is the tick program's operations that read the kind's stacked
K or V tensor and do not write it (the row's scatter has the pool as its
result), told by operand shape (``chipbench/kernels/kv_kinds.py``). A
configuration without kinds of layer, a program that counts no such pool
or a run without a trace gives nothing to read."""

from __future__ import annotations

from chipbench.layer_metrics import _latent_ops as L


def kinds(run):
    """The kernel counts, or None where the configuration has no kinds."""
    if not run.get("trace") or "layer_types" not in run["conf"]:
        return None
    return L.kernels(run, "kv_kinds")


def read_seconds(run, window: bool):
    """(seconds of the kind's reads in the traced ticks, those ticks)."""
    k = kinds(run)
    if k is None or not k.layers(run["conf"], window):
        return None, 0
    pool = k.pool_pattern(run["conf"], window)
    s = L.seconds(run, pool, result_not=pool)
    return s, (L.ticks_traced(run) if s else 0)


def call_us(run, window: bool):
    """Device time of one layer's read of one tick, microseconds."""
    s, ticks = read_seconds(run, window)
    if not s or not ticks:
        return None
    return 1e6 * s / ticks / kinds(run).layers(run["conf"], window)


def roofline_pct(run, window: bool):
    """The bytes the VALID positions need a tick (the program's own count
    over the window) over the reads' time a tick and the HBM peak."""
    s, ticks = read_seconds(run, window)
    name = ("window" if window else "full") + "_positions_valid"
    valid = L.section_delta(run, "kv_pool", name)
    in_window = L.ticks_in_window(run)
    if not s or not ticks or not valid or not in_window:
        return None
    need = kinds(run).read_bytes(run["conf"], valid / in_window)
    return 100.0 * need / (s / ticks * run["peaks"]["hbm_bytes_s"])
