"""Shared by the readers of the gated short convolution's own part of a
tick and of an admission: gates, taps and tail, which the program leaves
to XLA and names as a PATH ELEMENT of its operations
(``jax.named_scope("tk_gconv_step")`` / ``("tk_gconv_seq")`` in
``torchkafka_tpu/ops/gconv.py``, inside ``tk_attn_proj`` and
``tk_attn_flash``): no kernel's name and no ``SCOPE_*`` constant, so
neither ``_named`` nor ``_scopes.by_scope`` sees it. The operations are
found as ``_scopes`` finds a scope's: each instruction's ``op_name`` from
the HLO in the profile's ``/host:metadata`` plane, joined with the
operation line by instruction name; a fusion without a name of its own
takes the side most of the instructions it calls are on. A program without
the name (the parent of the PR that brought it, or a configuration without
such layers) or a run without a trace gives nothing to read."""

from __future__ import annotations

import re
from pathlib import Path

from chipbench import xplane
from chipbench.layer_metrics import _scopes

STEP, SEQ = "tk_gconv_step", "tk_gconv_seq"
_PARSED: dict = {}


def _instruction(buf) -> tuple[str, str, list[int]]:
    """(name, op_name, called computations) of one ``HloInstructionProto``."""
    name, op_name, called = "", "", []
    for no, v in _scopes.fields(buf):
        if no == 1:
            name = _scopes._text(v)
        elif no == 7:
            for mno, mv in _scopes.fields(v):
                if mno == 2:
                    op_name = _scopes._text(mv)
        elif no == 38:
            if isinstance(v, int):
                called.append(v)
            else:
                j = 0
                while j < len(v):
                    c, j = _scopes._varint(v, j)
                    called.append(c)
    return name, op_name, called


def module_named(module, needle: str) -> tuple[str, set[str]]:
    """An ``HloModuleProto`` as (name, the instructions whose ``op_name``
    holds ``needle``: their own, or most of those they call where they
    have none)."""
    name, comps = "", {}
    for no, v in _scopes.fields(module):
        if no == 1:
            name = _scopes._text(v)
        elif no == 3:
            cid, instrs = 0, []
            for cno, cv in _scopes.fields(v):
                if cno == 5:
                    cid = cv
                elif cno == 2:
                    instrs.append(_instruction(cv))
            comps[cid] = instrs

    def votes(cid, seen=()) -> tuple[int, int]:
        yes = no = 0
        for _n, op_name, called in comps.get(cid, ()):
            if op_name:
                yes, no = yes + (needle in op_name), no + (needle not in op_name)
            else:
                for c in called:
                    if c not in seen:
                        y, n = votes(c, (*seen, cid))
                        yes, no = yes + y, no + n
        return yes, no

    out = set()
    for instrs in comps.values():
        for iname, op_name, called in instrs:
            if op_name:
                held = needle in op_name
            else:
                yes = no = 0
                for c in called:
                    y, n = votes(c)
                    yes, no = yes + y, no + n
                held = yes > no
            if held:
                out.add(iname)
    return name, out


def named_by_program(path, needle: str) -> dict[str, set[str]]:
    """{program name: the instructions that hold ``needle``}, memoised;
    of two modules of one name the one a device plane names, else the
    last (as ``_scopes.scopes_by_program``)."""
    key = (str(path), needle)
    if key in _PARSED:
        return _PARSED[key]
    programs: dict = {}
    ran: set[str] = set()
    try:
        buf = memoryview(Path(path).read_bytes())
        for no, plane in _scopes.fields(buf):
            if no != 1:
                continue
            parts = list(_scopes.fields(plane))
            pname = next((_scopes._text(v) for n, v in parts if n == 2), "")
            in_metadata = pname == _scopes.METADATA_PLANE
            if not in_metadata and not xplane.DEVICE_PLANE.match(pname):
                continue
            for n, v in parts:
                if n != 4:
                    continue
                meta = next(
                    (mv for mn, mv in _scopes.fields(v) if mn == 2), None
                )
                if meta is None:
                    continue
                entry = list(_scopes.fields(meta))
                called = next(
                    (_scopes._text(mv) for mn, mv in entry if mn == 2), ""
                )
                if not in_metadata:
                    ran.add(called)
                    continue
                for mn, mv in entry:
                    if mn != 5:
                        continue
                    for sn, sv in _scopes.fields(mv):
                        if sn != 6:
                            continue
                        try:
                            for hn, hv in _scopes.fields(sv):
                                if hn == 1:
                                    name, held = module_named(hv, needle)
                                    programs.setdefault(name, {})[called] = held
                        except (ValueError, IndexError, TypeError):
                            pass  # a bytes statistic that is no HloProto
    except (ValueError, IndexError, TypeError, OSError):
        programs = {}  # not a file of this format
    _PARSED[key] = {
        name: modules[([m for m in modules if m in ran] or list(modules))[-1]]
        for name, modules in programs.items()
    }
    return _PARSED[key]


def seconds(run, program: str, needle: str) -> float | None:
    """Seconds of the leaf operations of the programs whose name matches
    ``program`` and whose ``op_name`` holds ``needle``; None without a
    trace, or where no such program holds the name."""
    tr = run.get("trace")
    if not tr or "conv_L_cache" not in run["conf"]:
        return None
    path = _scopes.trace_file(run)
    if path is None:
        return None
    named = named_by_program(path, needle)
    total, found = 0.0, False
    for name, t in tr["ops"].items():
        owner, _, instruction = name.partition("/")
        if t["opcode"] in xplane.CONTAINERS or not re.search(program, owner):
            continue
        if instruction in named.get(owner, ()):
            total, found = total + t["total_s"], True
    return total if found else None


def conv_layers(run) -> int:
    conf = run["conf"]
    return sum(
        t == "conv" for t in conf["layer_types"][: conf["num_hidden_layers"]]
    )
