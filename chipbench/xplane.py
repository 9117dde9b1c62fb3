"""From the profiler's ``.xplane.pb`` to what the metric readers use.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane
(``/device:TPU:<n>``) carries a line of whole programs (``XLA Modules``)
and a line of the operations inside them (``XLA Ops``); host planes carry
the threads' spans, among them the program's ``tk_serve:*`` annotations
and the benchmark's own ``bench:*``. All share one clock.

``reduce`` gives, averaged over the devices used:

  window_s    from the first device operation's start to the last one's
              end (the steady part that was traced)
  busy_s      the union of the intervals in which an operation ran
  programs    name -> {count, total_s}: whole jitted programs
  ops         name -> {count, total_s, opcode, program}: single
              operations, each under the program that ran it
  kernels     name -> {count, total_s, program, text}: the operations
              that are Pallas kernels (``tpu_custom_call``), with the
              operation's text as the trace prints it, operand shapes and
              all: the kernels carry no stable name yet, so a reader
              tells them apart by program and by operand types
  host_spans  name -> [(start_s, dur_s)]: ``tk_*`` and ``bench:*`` spans
  idle_gaps   the longest idle stretches of device 0, each charged to
              the host span that covers most of it
"""

from __future__ import annotations

import re
from collections import defaultdict

from chipbench import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ASYNC_LINE = "Async XLA Ops"  # from an asynchronous operation's start to its done
HOST_SPAN = re.compile(r"^(tk_|bench[:_])")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
# Operations that only hold others (their time is their children's).
CONTAINERS = {"while", "call", "conditional"}
PALLAS = 'custom_call_target="tpu_custom_call"'
_OPCODE = re.compile(r"[\]\}\)] ([a-z][a-z\-]*)\(")


def program_name(name: str) -> str:
    """``jit_tick_block(1234567)`` -> ``jit_tick_block``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_name(name: str) -> str:
    """``%fusion.123 = ...`` or ``fusion.123`` -> ``fusion.123``."""
    name = name.split(" = ")[0].strip()
    return name[1:] if name.startswith("%") else name


def op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: the breakdown groups by family,
    because one program holds hundreds of numbered fusions."""
    return re.sub(r"[.\-_]?\d+$", "", op_name(name))


def opcode(text: str) -> str:
    """The HLO opcode of an operation's text: ``%while.5 = (s32[], ...)
    while(...)`` -> ``while``. Without a text (a bare name), the family."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return op_family(head)
    m = _OPCODE.search(rest)
    return m.group(1) if m else op_family(head)


def _owner(events, modules):
    """For each operation, the program whose run covers its start."""
    owners, j = [], 0
    mods = sorted(modules, key=lambda m: m[1])
    for _name, start, _dur in events:
        while j < len(mods) and mods[j][1] + mods[j][2] < start:
            j += 1
        inside = j < len(mods) and mods[j][1] <= start
        owners.append(program_name(mods[j][0]) if inside else "")
    return owners


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9


def reduce_file(path, n_devices: int | None = None) -> dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(str(path)), n_devices)


def reduce(profile, n_devices: int | None = None) -> dict:
    devices, host_spans = {}, defaultdict(list)
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                devices[int(m.group(1))] = lines
            continue
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, start, dur in _events(line):
                    if HOST_SPAN.match(name):
                        host_spans[name].append((start, dur))
    if not devices:
        raise ValueError("the trace holds no TPU device plane with XLA Ops")
    ids = sorted(devices)[: n_devices or len(devices)]

    per_dev = []
    for i in ids:
        ops = [(n, s, d) for n, s, d in _events(devices[i][OPS_LINE])]
        mods = (
            [(n, s, d) for n, s, d in _events(devices[i][MODULES_LINE])]
            if MODULES_LINE in devices[i] else []
        )
        per_dev.append((ops, mods))

    lo = min(s for ops, _ in per_dev for _, s, _ in ops)
    hi = max(s + d for ops, _ in per_dev for _, s, d in ops)
    busy = [
        stats.union_seconds((s, s + d) for _, s, d in ops) for ops, _ in per_dev
    ]

    n = len(per_dev)
    programs = defaultdict(lambda: {"count": 0.0, "total_s": 0.0})
    ops_t, kernels = {}, {}
    for ops, mods in per_dev:
        for name, _s, d in mods:
            row = programs[program_name(name)]
            row["count"] += 1.0 / n
            row["total_s"] += d / n
        ops = sorted(ops, key=lambda e: e[1])
        for (text, _s, d), owner in zip(ops, _owner(ops, mods)):
            row = ops_t.setdefault((owner, op_name(text)), {
                "count": 0.0, "total_s": 0.0, "opcode": opcode(text),
                "program": owner,
            })
            row["count"] += 1.0 / n
            row["total_s"] += d / n
            if PALLAS in text:
                k = kernels.setdefault((owner, op_name(text)), {
                    "count": 0.0, "total_s": 0.0, "program": owner,
                    "text": text,
                })
                k["count"] += 1.0 / n
                k["total_s"] += d / n

    ops0 = sorted(per_dev[0][0], key=lambda e: e[1])
    leaves = [e for e in ops0 if opcode(e[0]) not in CONTAINERS]
    # Collectives of device 0, and the part of each with no other
    # operation beside it (exposed, not hidden behind compute).
    coll = [(s, s + d) for t, s, d in leaves if COLLECTIVE.match(opcode(t))]
    if ASYNC_LINE in devices[ids[0]]:
        coll += [
            (s, s + d) for t, s, d in _events(devices[ids[0]][ASYNC_LINE])
            if COLLECTIVE.match(opcode(t))
        ]
    other = [(s, s + d) for t, s, d in leaves
             if not COLLECTIVE.match(opcode(t))]
    coll_s = stats.union_seconds(coll)
    exposed_s = stats.union_seconds(coll + other) - stats.union_seconds(other)

    idle = stats.gaps([(s, s + d) for _, s, d in ops0], lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    flat_spans = [
        (name, s, s + d) for name, evs in host_spans.items() for s, d in evs
        if not name.endswith("_loop")
    ]
    charged = defaultdict(float)
    for g0, g1 in idle:
        best, cover = "no span", 0.0
        for name, s, e in flat_spans:
            c = min(e, g1) - max(s, g0)
            if c > cover:
                best, cover = name, c
        charged[best] += g1 - g0
    families = defaultdict(float)
    for text, _s, d in leaves:
        kind = "pallas_kernel" if PALLAS in text else opcode(text)
        families[f"{kind}:{op_family(text)}"] += d

    return {
        "devices": len(ids),
        "window_s": hi - lo,
        "t_lo": lo, "t_hi": hi,
        "busy_s": sum(busy) / n,
        "programs": {k: dict(v) for k, v in programs.items()},
        "ops": {f"{o}/{k}": v for (o, k), v in ops_t.items()},
        "kernels": {f"{o}/{k}": v for (o, k), v in kernels.items()},
        "host_spans": dict(host_spans),
        "collective_s": coll_s,
        "collective_exposed_s": exposed_s,
        "idle_gaps": sorted(charged.items(), key=lambda kv: -kv[1])[:10],
        "device_ops": sorted(families.items(), key=lambda kv: -kv[1])[:10],
        "longest_gap_s": (idle[0][1] - idle[0][0]) if idle else 0.0,
    }
