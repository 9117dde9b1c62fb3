"""Shared by the readers that split a device program's time by the scopes
the program names itself (``jax.named_scope("tk_...")``, the constants
``SCOPE_*`` of ``torchkafka_tpu/utils/tracing.py``).

The profiler's operation line carries no scope. The same ``.xplane.pb``
holds a ``/host:metadata`` plane, though, whose event metadata carry the
``HloProto`` of every module that ran, and there each instruction has its
``metadata.op_name``: the scope path (``jit(tick_block)/while/body/
tk_kv_read/dot_general``). ``jax.profiler.ProfileData`` does not show that
plane (it has no lines), so this file walks the protobuf wire format
itself, with the standard library alone, and joins the instruction names
with the operations ``xplane.reduce`` keeps as ``tr["ops"]``. A file
without that plane, or a program without scopes, gives nothing to read.

    XSpace.planes(1) -> XPlane.name(2), .event_metadata(4) -> value(2)
    -> XEventMetadata.name(2), .stats(5) -> XStat.bytes_value(6)
    -> HloProto.hlo_module(1) -> .name(1), .computations(3)
    -> .id(5), .instructions(2) -> .name(1), .opcode(2),
    .metadata(7).op_name(2), .called_computation_ids(38)
"""

from __future__ import annotations

import re
from collections import Counter
from pathlib import Path

from chipbench import xplane
from chipbench.layer_metrics import _latent_ops, _programs

METADATA_PLANE = "/host:metadata"
UNSCOPED = "unscoped"
# The program's vocabulary (``utils/tracing.py``'s ``SCOPE_*``; a test
# holds the two lists equal). A ``tk_...`` that is none of these is a
# Pallas kernel's own name (``pallas_call(name=)`` opens a scope too) or
# the remat policy's, and names no layer.
SCOPES = frozenset(
    "tk_" + name for name in (
        "embed", "attn_proj", "kv_write", "kv_read", "kv_read_window",
        "kv_read_full", "kv_read_latent", "attn_flash", "ffn", "moe_route",
        "moe_dispatch", "moe_experts", "head", "loss", "optimizer",
    )
)
_SCOPE = re.compile(r"tk_[a-z_]+")
_PARSED: dict = {}


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: a varint as an int, a
    length-delimited field as a memoryview (not copied, so a device
    plane's lines are skipped by their length), fixed ones as None."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def scope_of(op_name: str) -> str | None:
    """The innermost scope of a path such as ``jit(_step)/transpose(jvp(
    tk_attn_proj))/while/body/tk_kv_read/tk_kvattn_dynlen/...``."""
    found = [name for name in _SCOPE.findall(op_name) if name in SCOPES]
    return found[-1] if found else None


def _instruction(buf) -> tuple[str, str | None, list[int]]:
    name, scope, called = "", None, []
    for no, v in fields(buf):
        if no == 1:
            name = _text(v)
        elif no == 7:
            for mno, mv in fields(v):
                if mno == 2:
                    scope = scope_of(_text(mv))
        elif no == 38:
            # repeated int64: packed, or one varint a field
            if isinstance(v, int):
                called.append(v)
            else:
                j = 0
                while j < len(v):
                    c, j = _varint(v, j)
                    called.append(c)
    return name, scope, called


def module_scopes(module) -> tuple[str, dict[str, str]]:
    """An ``HloModuleProto`` as (name, {instruction: scope}). An
    instruction's scope is the innermost ``tk_...`` of its own op_name;
    for one without (a fusion the compiler made of several) the scope most
    of the instructions it calls name; else ``unscoped``."""
    name, comps = "", {}
    for no, v in fields(module):
        if no == 1:
            name = _text(v)
        elif no == 3:
            cid, instrs = 0, []
            for cno, cv in fields(v):
                if cno == 5:
                    cid = cv
                elif cno == 2:
                    instrs.append(_instruction(cv))
            comps[cid] = instrs

    def majority(cid, seen=()) -> Counter:
        votes = Counter()
        for _n, scope, called in comps.get(cid, ()):
            if scope:
                votes[scope] += 1
            else:
                for c in called:
                    if c not in seen:
                        votes += majority(c, (*seen, cid))
        return votes

    out = {}
    for instrs in comps.values():
        for iname, scope, called in instrs:
            if not scope and called:
                votes = Counter()
                for c in called:
                    votes += majority(c)
                scope = votes.most_common(1)[0][0] if votes else None
            out[iname] = scope or UNSCOPED
    return name, out


def read_file(path) -> tuple[dict[str, dict[str, dict[str, str]]], set[str]]:
    """({program name: {the module's name in the file, ``jit_admit(123)``:
    {instruction name: scope}}} of every module the file's metadata plane
    holds; the names of what the device planes' lines may name). The
    number in a module's name is what the trace's ``XLA Modules`` line
    calls a run of it (on the TPU a fingerprint, not the HLO module's id)."""
    buf = memoryview(Path(path).read_bytes())
    programs: dict = {}
    ran: set[str] = set()
    for no, plane in fields(buf):
        if no != 1:
            continue
        parts = list(fields(plane))
        pname = next((_text(v) for n, v in parts if n == 2), "")
        in_metadata = pname == METADATA_PLANE
        if not in_metadata and not xplane.DEVICE_PLANE.match(pname):
            continue
        for n, v in parts:
            if n != 4:
                continue  # lines (3) are skipped whole
            meta = next((mv for mn, mv in fields(v) if mn == 2), None)
            if meta is None:
                continue
            entry = list(fields(meta))
            called = next((_text(mv) for mn, mv in entry if mn == 2), "")
            if not in_metadata:
                ran.add(called)
                continue
            for mn, mv in entry:
                if mn != 5:
                    continue
                for sn, sv in fields(mv):
                    if sn != 6:
                        continue
                    try:
                        for hn, hv in fields(sv):
                            if hn == 1:
                                name, scopes = module_scopes(hv)
                                programs.setdefault(name, {})[called] = scopes
                    except (ValueError, IndexError, TypeError):
                        pass  # a bytes statistic that is no HloProto
    return programs, ran


def scopes_by_program(path) -> dict[str, dict[str, str]]:
    """{program name: {instruction name: scope}}, memoised by path. Of
    two modules of one name, the one a device plane names (the trace's
    ``XLA Modules`` line ran it) is taken, else the last in the file."""
    path = str(path)
    if path not in _PARSED:
        try:
            programs, ran = read_file(path)
        except (ValueError, IndexError, TypeError, OSError):
            programs, ran = {}, set()  # not a file of this format
        _PARSED[path] = {
            name: modules[([m for m in modules if m in ran] or list(modules))[-1]]
            for name, modules in programs.items()
        }
    return _PARSED[path]


def trace_file(run) -> Path | None:
    """The profiler's file of this run (``common.RunContext`` wrote it)."""
    trace_dir = (
        Path(run["root"]) / ".chipbench_trace"
        / f"{run['cell']['name']}-{run['seed']}"
    )
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    return files[-1] if files else None


def by_scope(run, program: str) -> dict[str, float] | None:
    """Seconds of the leaf operations of the programs whose name matches
    ``program``, by scope (``unscoped`` among them); None without a trace,
    without the metadata plane, or where no such program names a scope."""
    tr = run.get("trace")
    if not tr:
        return None
    path = trace_file(run)
    if path is None:
        return None
    scopes = scopes_by_program(path)
    out: Counter = Counter()
    for name, t in tr["ops"].items():
        owner, _, instruction = name.partition("/")
        if t["opcode"] not in xplane.CONTAINERS and re.search(program, owner):
            scope = scopes.get(owner, {}).get(instruction, UNSCOPED)
            out[scope] += t["total_s"]
    return dict(out) if set(out) - {UNSCOPED} else None


def seconds(run, program: str, scopes) -> float | None:
    """Seconds of ``program``'s leaf operations under the scopes whose
    name matches the pattern ``scopes``."""
    split = by_scope(run, program)
    if split is None:
        return None
    return sum(s for name, s in split.items() if re.fullmatch(scopes, name))


def unscoped_pct(run, program: str) -> float | None:
    split = by_scope(run, program)
    if split is None:
        return None
    return 100.0 * split.get(UNSCOPED, 0.0) / sum(split.values())


def _ms_over(run, program: str, scopes: str, units: float) -> float | None:
    total = seconds(run, program, scopes)
    return 1e3 * total / units if total is not None and units else None


def tick_ms(run, scopes: str) -> float | None:
    """ms a decode tick, over the ticks the traced part ran."""
    return _ms_over(run, r"tick", scopes, _latent_ops.ticks_traced(run))


def admit_ms(run, scopes: str) -> float | None:
    """ms a ``jit_admit`` call, as ``prefill_ms.tput`` counts them."""
    return _ms_over(run, r"admit", scopes, _programs.total(run, r"admit")[1])


def step_ms(run, scopes: str) -> float | None:
    """ms a training step."""
    return _ms_over(run, r"_step", scopes, _programs.total(run, r"_step")[1])
