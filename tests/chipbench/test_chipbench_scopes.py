"""The scope readers (PR 38): the wire reader of the profiler's file
against a CPU trace of a toy with two scopes and against a file written by
hand; the join with ``tr["ops"]`` and its three rules (the innermost
scope, a fusion's majority, ``unscoped``); the fourteen metrics on that
hand-made run, value by value; their entries in ``BENCHMARK.json``; and a
traced rehearsal that reads through them without raising."""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from toy import make_toy_root  # noqa: E402

from chipbench import common  # noqa: E402
from chipbench.layer_metrics import _scopes as S  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
DRAINS = [
    "mistral7b.backlog-drain", "kanana2.longform-drain",
    "longcat.reasoning-drain", "mellum2.repo-context-drain",
]
TRAIN = ["internlm2-1.8b.pretrain-4k-1chip", "internlm2-1.8b.pretrain-4k-2x2"]
# name -> (unit, cells, the end-to-end metric it moves), in ISSUE 38's order.
FOURTEEN = {
    "tick.kv_read_ms.tput": ("ms", DRAINS, "serve"),
    "tick.experts_ms.tput": ("ms", DRAINS[1:], "serve"),
    "tick.dense_ms.tput": ("ms", DRAINS, "serve"),
    "tick.unscoped_pct": ("%", DRAINS, "serve"),
    "admit.attn_ms.tput": ("ms", DRAINS, "serve"),
    "admit.experts_ms.tput": ("ms", DRAINS[1:], "serve"),
    "admit.expert_dispatch_ms.tput": ("ms", DRAINS[1:], "serve"),
    "admit.dense_ms.tput": ("ms", DRAINS, "serve"),
    "admit.unscoped_pct": ("%", DRAINS, "serve"),
    "step.attn_ms.train": ("ms", TRAIN, "train"),
    "step.ffn_ms.train": ("ms", TRAIN, "train"),
    "step.loss_ms.train": ("ms", TRAIN, "train"),
    "step.optimizer_ms.train": ("ms", TRAIN, "train"),
    "step.unscoped_pct": ("%", TRAIN, "train"),
}


# ------------------------------------------ a file written by hand


def vint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def ld(field: int, payload) -> bytes:
    """A length-delimited field (a string, bytes or a nested message)."""
    payload = payload.encode() if isinstance(payload, str) else payload
    return vint(field << 3 | 2) + vint(len(payload)) + payload


def num(field: int, value: int) -> bytes:
    return vint(field << 3) + vint(value)


def instruction(name, opcode, op_name=None, calls=(), packed=True) -> bytes:
    out = ld(1, name) + ld(2, opcode)
    if op_name is not None:
        out += ld(7, ld(1, "op_type") + ld(2, op_name))
    if calls and packed:
        out += ld(38, b"".join(vint(c) for c in calls))
    for c in () if packed else calls:
        out += num(38, c)
    return out


def computation(ident: int, name: str, instructions) -> bytes:
    return ld(1, name) + b"".join(ld(2, i) for i in instructions) + num(5, ident)


def module(name: str, ident: int, computations) -> bytes:
    return ld(1, name) + b"".join(ld(3, c) for c in computations) + num(5, ident)


def plane(name: str, metadata=(), lines=()) -> bytes:
    """An XPlane: ``metadata`` [(name, HloProto bytes | None)]."""
    out = ld(2, name) + b"".join(ld(3, ln) for ln in lines)
    for i, (mname, hlo) in enumerate(metadata, 1):
        meta = num(1, i) + ld(2, mname)
        if hlo is not None:
            meta += ld(5, num(1, 1) + ld(6, ld(1, hlo)))
        out += ld(4, num(1, i) + ld(2, meta))
    return out


TICK = module("jit_tick_block", 5, [
    computation(7, "fused_computation.2", [
        instruction("dot.1", "dot", "jit(tick_block)/while/body/tk_attn_proj/dot_general"),
        instruction("mul.2", "multiply", "jit(tick_block)/while/body/tk_attn_proj/tk_attn_proj/mul"),
        instruction("exp.3", "exponential", "jit(tick_block)/while/body/tk_ffn/exp"),
        instruction("param_0", "parameter"),
    ]),
    computation(8, "fused_computation.3", [
        instruction("copy.9", "copy"),
        instruction("slice.9", "dynamic-slice", "jit(tick_block)/while/body/dynamic_slice"),
    ]),
    computation(9, "main", [
        instruction("fusion.1", "fusion", "jit(tick_block)/while/body/tk_ffn/tk_moe_experts/dot_general", [7]),
        instruction("fusion.2", "fusion", None, [7]),
        instruction("fusion.3", "fusion", None, [8], packed=False),
        instruction("tk_kvattn_dynlen.4", "custom-call", "jit(tick_block)/while/body/tk_kv_read/tk_kvattn_dynlen"),
        instruction("copy.5", "copy"),
        instruction("while.6", "while", "jit(tick_block)/tk_head/while", [9]),
        instruction("fusion.7", "fusion", "jit(_step)/transpose(jvp(tk_ffn))/tk_flash_out/mul", [8]),
        instruction("fusion.8", "fusion", "jit(tick_block)/tk_kv_read_window/reduce_max", [8]),
        instruction("fusion.9", "fusion", "jit(tick_block)/tk_head/argmax", [8]),
    ]),
])
# What the trace's lines call a run of TICK: on the TPU a fingerprint, not the
# HLO module's id (5).
TICK_RAN = "jit_tick_block(16364831953335209216)"
# The same name compiled earlier with another id: the device plane did not run it.
STALE = module("jit_tick_block", 3, [computation(1, "main", [
    instruction("fusion.1", "fusion", "jit(tick_block)/tk_embed/gather"),
])])
ADMIT = module("jit_admit", 11, [computation(1, "main", [
    instruction("fusion.1", "fusion", "jit(admit)/while/body/tk_attn_flash/mul"),
    instruction("tk_gmm_down.2", "custom-call", "jit(admit)/while/body/tk_moe_experts/tk_gmm_down"),
    instruction("gather.3", "gather", "jit(admit)/while/body/tk_moe_dispatch/gather"),
    instruction("sort.4", "sort", "jit(admit)/while/body/tk_moe_route/sort"),
    instruction("fusion.5", "fusion", "jit(admit)/while/body/tk_ffn/dot_general"),
    instruction("dynamic-slice_bitcast_fusion", "fusion", "jit(admit)/while/body/dynamic_slice"),
])])
STEP = module("jit__step", 13, [computation(1, "main", [
    instruction("tk_flash_bwd_dq.1", "custom-call", "jit(_step)/transpose(jvp(tk_attn_flash))/tk_flash_bwd_dq"),
    instruction("fusion.2", "fusion", "jit(_step)/jvp(tk_attn_proj)/dot_general"),
    instruction("fusion.3", "fusion", "jit(_step)/transpose(jvp(tk_ffn))/checkpoint/rematted_computation/tk_ffn/dot_general"),
    instruction("fusion.4", "fusion", "jit(_step)/jvp(tk_loss)/while/body/reduce_max"),
    instruction("fusion.5", "fusion", "jit(_step)/jvp(tk_head)/mul"),
    instruction("fusion.6", "fusion", "jit(_step)/tk_optimizer/add"),
    instruction("all-reduce.7", "all-reduce"),
    instruction("fusion.8", "fusion", "jit(_step)/transpose(jvp(tk_embed))/scatter-add"),
])])
SPACE = b"".join(ld(1, p) for p in (
    plane("/device:TPU:0", [(TICK_RAN, None), ("%fusion.1", None)],
          lines=[b"\x00" * 4096]),
    plane("/host:metadata", [
        ("jit_tick_block(3)", STALE), (TICK_RAN, TICK),
        ("jit_admit(11)", ADMIT), ("jit__step(13)", STEP),
    ]),
    plane("/host:CPU"),
))
CELL, SEED = "toy.cell", 7


def op(seconds: float, opcode: str = "fusion") -> dict:
    return {"count": 1.0, "total_s": seconds, "opcode": opcode}


def ops_of(program: str, rows: dict) -> dict:
    return {f"{program}/{name}": {**t, "program": program} for name, t in rows.items()}


OPS = {
    **ops_of("jit_tick_block", {
        "fusion.1": op(0.010), "fusion.2": op(0.020), "fusion.3": op(0.003),
        "tk_kvattn_dynlen.4": op(0.040, "custom-call"), "copy.5": op(0.001, "copy"),
        "while.6": op(0.500, "while"), "fusion.7": op(0.002),
        "fusion.8": op(0.006), "fusion.9": op(0.004),
        "not-in-the-module.1": op(0.002),
    }),
    **ops_of("jit_admit", {
        "fusion.1": op(0.030), "tk_gmm_down.2": op(0.050, "custom-call"),
        "gather.3": op(0.020, "gather"), "sort.4": op(0.005, "sort"),
        "fusion.5": op(0.015), "dynamic-slice_bitcast_fusion": op(0.008),
    }),
    **ops_of("jit__step", {
        "tk_flash_bwd_dq.1": op(0.100, "custom-call"), "fusion.2": op(0.050),
        "fusion.3": op(0.200), "fusion.4": op(0.080), "fusion.5": op(0.010),
        "fusion.6": op(0.030), "all-reduce.7": op(0.030, "all-reduce"),
        "fusion.8": op(0.004),
    }),
    "jit_other/fusion.1": {**op(9.0), "program": "jit_other"},
}


@pytest.fixture()
def run(tmp_path):
    """A traced run by hand: two tick blocks of 4 ticks, two admissions,
    four steps; the profiler's file where ``RunContext`` writes it."""
    S._PARSED.clear()
    where = tmp_path / ".chipbench_trace" / f"{CELL}-{SEED}" / "plugins" / "p"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(SPACE)
    return {
        "root": tmp_path, "cell": {"name": CELL}, "seed": SEED,
        "conf": {"deployment": {"ticks_per_sync": 4}},
        "trace": {"ops": OPS, "programs": {
            "jit_tick_block": {"count": 2.0, "total_s": 0.6},
            "jit_admit": {"count": 2.0, "total_s": 0.2},
            "jit__step": {"count": 4.0, "total_s": 0.5},
        }},
    }


@pytest.mark.parametrize("instruction_name,scope", [
    ("fusion.1", "tk_moe_experts"),  # the innermost of its own op_name
    ("fusion.2", "tk_attn_proj"),  # no op_name: what most of its fused name
    ("fusion.3", S.UNSCOPED),  # no op_name, and none inside
    ("tk_kvattn_dynlen.4", "tk_kv_read"),  # a kernel's own name is no scope
    ("copy.5", S.UNSCOPED),
    ("fusion.7", "tk_ffn"),  # through transpose(jvp()); the remat name is no scope
    ("fusion.8", "tk_kv_read_window"),  # the longer name, not its prefix
    ("dot.1", "tk_attn_proj"),  # fused instructions are listed too
])
def test_an_instruction_s_scope_by_the_three_rules(run, instruction_name, scope):
    scopes = S.scopes_by_program(S.trace_file(run))
    # Of the two modules of this name, the one the device plane ran.
    assert scopes["jit_tick_block"][instruction_name] == scope
    assert set(scopes) == {"jit_tick_block", "jit_admit", "jit__step"}


def test_a_module_named_once_is_taken_whether_or_not_the_plane_names_it(run):
    assert S.scopes_by_program(S.trace_file(run))["jit_admit"]["sort.4"] == (
        "tk_moe_route"
    )
    programs, ran = S.read_file(S.trace_file(run))
    assert set(programs["jit_tick_block"]) == {"jit_tick_block(3)", TICK_RAN}
    assert programs["jit_tick_block"]["jit_tick_block(3)"] == {"fusion.1": "tk_embed"}
    assert TICK_RAN in ran


@pytest.mark.parametrize("program,split", [
    ("tick", {
        "tk_moe_experts": 0.010, "tk_attn_proj": 0.020, "tk_kv_read": 0.040,
        "tk_ffn": 0.002, "tk_kv_read_window": 0.006, "tk_head": 0.004,
        # fusion.3, copy.5, and an operation the module does not list
        S.UNSCOPED: 0.003 + 0.001 + 0.002,
    }),
    ("admit", {
        "tk_attn_flash": 0.030, "tk_moe_experts": 0.050, "tk_moe_dispatch": 0.020,
        "tk_moe_route": 0.005, "tk_ffn": 0.015, S.UNSCOPED: 0.008,
    }),
    ("_step", {
        "tk_attn_flash": 0.100, "tk_attn_proj": 0.050, "tk_ffn": 0.200,
        "tk_loss": 0.080, "tk_head": 0.010, "tk_optimizer": 0.030,
        "tk_embed": 0.004, S.UNSCOPED: 0.030,
    }),
])
def test_the_parts_and_the_unscoped_time_sum_to_the_leaf_time(run, program, split):
    got = S.by_scope(run, program)
    assert got == pytest.approx(split)
    leaves = sum(
        t["total_s"] for name, t in OPS.items()
        if program in name.split("/")[0] and t["opcode"] != "while"
    )
    assert sum(got.values()) == pytest.approx(leaves)


@pytest.mark.parametrize("name,value", [
    ("tick.kv_read_ms.tput", 1e3 * (0.040 + 0.006) / 8),
    ("tick.experts_ms.tput", 1e3 * 0.010 / 8),
    ("tick.dense_ms.tput", 1e3 * (0.020 + 0.002 + 0.004) / 8),
    ("tick.unscoped_pct", 100 * 0.006 / 0.088),
    ("admit.attn_ms.tput", 1e3 * 0.030 / 2),
    ("admit.experts_ms.tput", 1e3 * 0.050 / 2),
    ("admit.expert_dispatch_ms.tput", 1e3 * 0.025 / 2),
    ("admit.dense_ms.tput", 1e3 * 0.015 / 2),
    ("admit.unscoped_pct", 100 * 0.008 / 0.128),
    ("step.attn_ms.train", 1e3 * 0.150 / 4),
    ("step.ffn_ms.train", 1e3 * 0.200 / 4),
    ("step.loss_ms.train", 1e3 * 0.094 / 4),
    ("step.optimizer_ms.train", 1e3 * 0.030 / 4),
    ("step.unscoped_pct", 100 * 0.030 / 0.504),
])
def test_each_of_the_fourteen_on_the_hand_made_run(run, name, value):
    read = common.load_named("layer_metrics", name, REPO).read
    assert read(run) == pytest.approx(value)
    # Nothing to read gives None and does not raise: no trace (a
    # rehearsal), no file, a file without the plane, a program that
    # names no scope (the parent of PR 38).
    assert read({**run, "trace": None}) is None
    assert read({**run, "seed": SEED + 1}) is None
    bare = plane("/device:TPU:0", [(TICK_RAN, None)])
    S.trace_file(run).write_bytes(ld(1, bare))
    S._PARSED.clear()
    assert read(run) is None
    # A file cut short, and a bytes statistic that is no HloProto.
    S.trace_file(run).write_bytes(SPACE[: len(SPACE) // 2])
    S._PARSED.clear()
    assert read(run) is None
    junk = plane("/host:metadata", [(TICK_RAN, b"\xff\xff\xff")])
    S.trace_file(run).write_bytes(ld(1, junk))
    S._PARSED.clear()
    assert read(run) is None


# program -> (its name in a trace, the scopes the six cells' programs of that
# kind carry, compiled for the chip: PERF.md §5)
CARRIES = {
    "tick": ("jit_tick_block", sorted(S.SCOPES - {
        "tk_attn_flash", "tk_loss", "tk_optimizer"})),
    "admit": ("jit_admit", sorted(S.SCOPES - {"tk_loss", "tk_optimizer"})),
    "step": ("jit__step", [
        "tk_embed", "tk_attn_proj", "tk_attn_flash", "tk_ffn", "tk_head",
        "tk_loss", "tk_optimizer"]),
}


@pytest.mark.parametrize("kind", CARRIES)
def test_a_program_s_metrics_take_each_scope_once(run, kind):
    """The parts and the unscoped time sum to the program's leaf time:
    every scope a program of this kind carries is read by ONE of its ms
    metrics."""
    program, carried = CARRIES[kind]
    rows = [instruction(f"fusion.{i}", "fusion", f"jit(f)/{s}/mul")
            for i, s in enumerate(carried)] + [instruction("copy.99", "copy")]
    hlo = module(program, 21, [computation(1, "main", rows)])
    S.trace_file(run).write_bytes(ld(1, plane("/host:metadata", [(f"{program}(21)", hlo)])))
    S._PARSED.clear()
    ops = ops_of(program, {
        **{f"fusion.{i}": op(0.001) for i in range(len(carried))},
        "copy.99": op(0.002, "copy"),
    })
    run = {**run, "trace": {**run["trace"], "ops": ops}}
    units = {"tick": 8, "admit": 2, "step": 4}[kind]
    mine = [n for n in FOURTEEN if n.startswith(kind + ".")]
    read = {n: common.load_named("layer_metrics", n, REPO).read(run) for n in mine}
    parts = sum(v for n, v in read.items() if not n.endswith("unscoped_pct"))
    leaf = 0.001 * len(carried) + 0.002
    assert parts * units / 1e3 == pytest.approx(0.001 * len(carried))
    assert read[f"{kind}.unscoped_pct"] == pytest.approx(100 * 0.002 / leaf)


@pytest.mark.parametrize("name", FOURTEEN)
def test_each_of_the_fourteen_has_its_entry_at_the_end(name):
    unit, cells, moves = FOURTEEN[name]
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-14:] == list(FOURTEEN)
    assert BENCH["per_layer"][names.index(name)] == {
        "name": name, "unit": unit, "better": "lower", "source": "device_trace",
        "layer": "device programs", "moves": f"{moves}.tokens_per_s",
        "workloads": cells,
    }
    assert (REPO / "chipbench/layer_metrics" / f"{name}.py").is_file()
    perf = (REPO / "PERF.md").read_text()
    assert f"`{name}`" in perf, f"PERF.md does not name {name}"


def test_the_benchmark_file_stays_inside_its_limits():
    assert len((REPO / "BENCHMARK.json").read_bytes()) < 64 * 1024
    assert len(BENCH["per_layer"]) <= 128
    assert "device programs" in {m["layer"] for m in BENCH["per_layer"][:-14]}


def test_the_vocabulary_is_the_program_s():
    from torchkafka_tpu.utils import tracing

    program = {v for k, v in vars(tracing).items() if k.startswith("SCOPE_")}
    assert program == set(S.SCOPES) and len(program) == 15


# ---------------------------------------------- a trace made on the CPU


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """ISSUE 38's listing: a jitted function with two scopes, traced."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def two_scopes(x, w):
        with jax.named_scope("tk_attn_proj"):
            a = jnp.tanh(x @ w)
        with jax.named_scope("tk_ffn"):
            return jnp.exp(a)

    x = jnp.ones((64, 64), jnp.float32)
    two_scopes(x, x).block_until_ready()
    logdir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(logdir))
    two_scopes(x, x).block_until_ready()
    jax.profiler.stop_trace()
    files = sorted(logdir.rglob("*.xplane.pb"))
    assert files
    return S.read_file(files[-1])[0]


@pytest.mark.parametrize("prefix,scope", [
    ("dot", "tk_attn_proj"), ("tanh.", "tk_attn_proj"), ("exp.", "tk_ffn"),
    # The fusion that spans both scopes: its own op_name is its root's.
    ("tanh_exp", "tk_ffn"),
])
def test_the_wire_reader_against_a_cpu_trace(cpu_trace, prefix, scope):
    (called, scopes), = cpu_trace["jit_two_scopes"].items()
    assert re.fullmatch(r"jit_two_scopes\(\d+\)", called)
    found = {s for name, s in scopes.items() if name.startswith(prefix)}
    assert found == {scope}, scopes


# ------------------------------------------------- a traced rehearsal


@pytest.mark.parametrize("cell", [
    "mellum2.repo-context-drain", "internlm2-1.8b.pretrain-4k-1chip",
])
def test_a_traced_rehearsal_reads_through_the_fourteen(tmp_path, capsys, cell):
    """A rehearsal has no trace: each reader of the cell is run, gives
    nothing, and none raises."""
    from chipbench import run as runner

    root = make_toy_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    mine = [
        m["name"] for m in bench["per_layer"]
        if m["name"] in FOURTEEN and cell in m["workloads"]
    ]
    assert len(mine) == (9 if "drain" in cell else 5)
    rc = runner.main(
        ["--workload", cell, "--seed", "5", "--seconds", "0.5", "--trace", "1"],
        root=root, rehearsal=True,
    )
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rc == 0 and last["checks_passed"] is True
    assert not set(mine) & set(last["metric_names"])
