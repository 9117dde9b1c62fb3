"""The configuration ``lfm2-8b-a1b-10l`` (the first pipeline stage of
LFM2-8B-A1B: both leading dense layers and two periods of one grouped-query
layer with a norm a head to three gated short convolutions, all 32
sigmoid-routed experts, the whole tied vocabulary) and its cell:
BENCHMARK.json's entries (the files, the lists, order and membership), the
file against the catalog's row and ISSUE 52's arithmetic, the plain
reference against the program on seeded weights at a size that keeps every
mechanism (a convolution layer token by token through the tail, the ten
layers' forward, what a slot keeps; the router), every named fault, and
the new readers on a hand-made trace. The cell end to end as a rehearsal,
probe and controls' statistics and all, is a case of
``test_chipbench_rehearsal.py`` (every cell of BENCHMARK.json is); the
compile for a described v5e is ``test_chipbench_lfm2_compile``."""

from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from toy import TOY_KEYS  # noqa: E402

from chipbench import common  # noqa: E402
from chipbench import weights as W  # noqa: E402
from chipbench import run as runner  # noqa: E402
from chipbench.models import lfm2_decoder as family  # noqa: E402
from chipbench.reference import lfm2_decoder as reference  # noqa: E402

CELL, CONFIG = "lfm2.record-enrichment-drain", "lfm2-8b-a1b-10l"
CONF = json.loads((REPO / "chipbench/configs" / f"{CONFIG}.json").read_text())
MIX = json.loads((REPO / "chipbench/workloads" / f"{CELL}.json").read_text())
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PARENT_CONFIGS = (
    "mistral-7b-v0.3-w8", "internlm2-1.8b-1chip", "internlm2-1.8b",
    "kanana-2-30b-a3b-7l", "longcat-flash-omni-4l-ep32",
    "mellum2-12b-a2.5b-8l", "ling-3.0-flash-7l-ep8",
    "granite-4.0-h-small-10l-ep4", "keye-vl-2.0-30b-a3b-8l-ep8",
)
PARENT_CELLS = (
    "mistral7b.backlog-drain", "internlm2-1.8b.pretrain-4k-1chip",
    "internlm2-1.8b.pretrain-4k-2x2", "kanana2.longform-drain",
    "longcat.reasoning-drain", "mellum2.repo-context-drain",
    "ling3.long-decode-drain", "granite4h.multi-session-drain",
    "keye2.long-document-drain",
)
BEFORE = PARENT_CELLS[-1]
NEW_METRICS = ("gconv.step_us.tput", "gconv.seq_ms.tput")
LOOP = common.load_named("loops", "serve_conv", REPO)


def toy_conf(**kw) -> dict:
    """The toy's widths in float32 at the file's TEN layers (c c | a c c c
    | a c c c), 8 experts, top-2."""
    conf = copy.deepcopy(CONF)
    conf.update(TOY_KEYS)
    conf.update(LOOP.REHEARSAL["config"])
    conf.update(num_hidden_layers=10, published_num_hidden_layers=24)
    conf["deployment"].update(LOOP.REHEARSAL["deployment"])
    conf["deployment"].update(compute_dtype="float32", param_dtype="float32")
    conf.update(kw)
    return conf


# ------------------------------------- BENCHMARK.json's entries


def test_benchmark_json_names_the_configuration_and_the_cell():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    )
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "backlog", 1,
    )
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert "64 pairs an expert" in cell["why"] and "2.75x" in cell["why"]
    # After every entry that was there: one put first reads as a change.
    # Order and membership, not lastness: a later PR appends behind these.
    configs = [c["name"] for c in BENCH["configs"]]
    cells = [w["name"] for w in BENCH["workloads"]]
    assert configs.index(CONFIG) == cells.index(CELL) == 9
    assert tuple(configs[:9]) == PARENT_CONFIGS
    assert tuple(cells[:9]) == PARENT_CELLS
    assert sum(w["chips"] == 4 for w in BENCH["workloads"][:10]) == 1
    bench, cell2, conf, mix = runner.load_cell(REPO, CELL)
    assert cell2 == cell and conf == CONF and mix == MIX

    def reports(name):
        return {
            m["name"] for sec in ("end_to_end", "per_layer") for m in bench[sec]
            if name in m.get("workloads", ())
        }

    # serve.tokens_per_s, the sixteen seven-cell lists and the three
    # six-cell expert lists: what the cell before it reports outside its
    # own kernels' metrics, and the two this PR brings.
    before = {
        n for n in reports(BEFORE) if not n.startswith(("dsa.", "flash."))
    }
    assert len(before) == 20 and reports(CELL) == before | set(NEW_METRICS)
    assert not {
        n for n in reports(CELL)
        if n.startswith(("kvattn.", "kda.", "ssd.", "dsa."))
    }
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", ())
        if CELL in listed and m["name"] not in NEW_METRICS:
            # Appended behind the cell before it, nothing else moved.
            assert listed.index(CELL) == listed.index(BEFORE) + 1
    names = [m["name"] for m in bench["per_layer"]]
    assert all("workloads" in m for m in bench["per_layer"])
    for name in NEW_METRICS:
        m = bench["per_layer"][names.index(name)]
        assert m["workloads"][0] == CELL and m["moves"] == "serve.tokens_per_s"
        assert (m["layer"], m["source"]) == ("kernels", "device_trace")
        assert (REPO / "chipbench/layer_metrics" / f"{name}.py").is_file()
        assert names.index(name) > names.index("flash.sel_roofline_pct")
    assert "gconv.step_roofline_pct" not in names  # (no kernel: no roofline)
    for name in PARENT_CELLS:
        runner.load_cell(REPO, name)


def test_the_traffic_is_the_issue_s_letter_for_letter():
    assert MIX["loop"] == "serve_conv"
    assert MIX["traffic"] == {
        "kind": "backlog", "records": 8000, "deck": 64, "block": 16,
        "prompt_median": 256, "prompt_sigma": 0.8, "prompt_max": 512,
        "answer_median": 512, "answer_sigma": 0.6, "answer_min": 2,
        "answer_max": 1536, "tenants": 8, "tenant_zipf": 1.1,
        "pairing_seed": 52,
    }
    assert MIX["warmup_records"] == 3
    dep = CONF["deployment"]
    assert (dep["slots"], dep["prompt_window"], dep["max_new"]) == (512, 512, 1536)
    assert (dep["ticks_per_sync"], dep["commit_every"]) == (128, 32)
    assert dep["prompt_partitions"] == 2 and dep["kv_kernel"] is False
    assert dep["kv_dtype"] is None and dep["compute_dtype"] == "bfloat16"
    assert dep["mesh"] is None and dep["delivery"] == "at-least-once"
    assert (dep["chips_sharing_a_layer"], dep["experts_held"]) == (1, None)
    assert (dep["pipeline_stages"], dep["stage"]) == (3, 1)
    assert dep["layers_of_the_stages"] == [[0, 9], [10, 17], [18, 23]]
    # 64 pairs an expert a tick, a deployment's own.
    assert dep["slots"] * CONF["num_experts_per_tok"] / CONF["num_experts"] == 64
    for name in (
        "thirds", "taps", "norm_a_head", "tied_embedding", "router",
        "gate_eps", "router_bias", "weights", "broker", "deployment",
    ):
        assert name in CONF["assumed"], name


def test_the_file_is_the_catalog_s_row_but_for_the_cut():
    if not CATALOG.is_file():
        pytest.skip("no catalog here")
    row = next(
        r for r in map(json.loads, CATALOG.read_text().splitlines())
        if r["name"] == "LFM2-8B-A1B"
    )
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if CONF.get(k) != v}
    assert differ == {"num_hidden_layers"} == set(entry["reduced"])
    assert (CONF["num_hidden_layers"], CONF["published_num_hidden_layers"]) == (
        10, row["config"]["num_hidden_layers"],
    )
    assert set(CONF["changed_from_source"]) == differ
    assert len(CONF["layer_types"]) == 24  # all 24 names; the first 10 run
    assert "".join(t[0] for t in CONF["layer_types"][:10]) == "ccfcccfccc"


def test_the_cut_by_hand():
    """ISSUE 52's arithmetic, term for term."""
    a = family.Arch.from_conf(CONF)
    assert a.mixer_params(True) == 2048 * 6144 + 2048 * 2048 + 3 * 2048 == 16_783_360
    assert a.mixer_params(False) == (
        2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    ) == 10_485_888
    assert a.expert_params == 3 * 2048 * 1792 == 11_010_048
    assert 32 * a.expert_params == 352_321_536
    assert a.layer_params(0) == 16_783_360 + 4_096 + 3 * 2048 * 7168
    assert a.layer_params(2) == 10_485_888 + 4_096 + 352_321_536 + 65_536 + 32
    assert a.params == (
        8 * 16_783_360 + 2 * 10_485_888 + 40_960 + 2 * 44_040_192
        + 8 * (352_321_536 + 65_568) + 134_217_728 + 2_048
    ) == 3_196_676_608
    assert a.pattern == (False, True, True, True) and a.dense_layers == 2
    assert a.kinds.count(True) == 8 and a.kv_row == 512
    # The whole model so reckoned: the published 8.3B with 1.5B active.
    whole = family.Arch.from_conf({**CONF, "num_hidden_layers": 24})
    assert round(whole.params / 1e9, 2) == 8.34
    active = whole.params - 22 * 28 * whole.expert_params
    assert round(active / 1e9, 2) == 1.56
    # The slot memory ISSUE 52 reckons: 4,096 B a position, 8 KB a layer.
    dep = CONF["deployment"]
    positions = dep["prompt_window"] + dep["max_new"]
    assert 2 * 2 * a.kv_row * 2 == 4096
    assert 2 * 2 * dep["slots"] * positions * a.kv_row * 2 == 4_294_967_296
    assert (a.taps - 1) * a.hidden * 2 == 8192


# --------------------------------- the reference against the program


@pytest.fixture(scope="module")
def toy():
    import jax
    import jax.numpy as jnp

    conf = toy_conf()
    cfg = family.program_config(conf, 32)
    params = family.serving_params(conf, 7)
    dims = W.Dims.from_conf(family.dims_conf(conf))
    tokens = jax.random.randint(jax.random.key(1), (2, 24), 1, 512)
    assert cfg.linear_kind == "conv" and cfg.qk_norm and cfg.tie_embeddings
    assert (cfg.first_dense_layers, cfg.linear_pattern) == (
        2, (False, True, True, True),
    )
    assert cfg.dtype == jnp.float32
    return conf, cfg, params, dims, np.asarray(tokens)


def test_the_ten_layers_forward_gives_the_reference_s_logits(toy):
    import jax

    from torchkafka_tpu.models import Transformer

    _conf, cfg, params, dims, tokens = toy
    got = np.asarray(jax.jit(Transformer(cfg).__call__)(params, tokens))
    want = np.asarray(reference.logits(7, dims, tokens))
    # float32 on both sides: accumulation order alone (1e-6 of a logit of
    # size one); bfloat16 anywhere reads a thousand times this.
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert np.abs(want).max() > 0.5


def test_what_a_slot_keeps_is_the_reference_s(toy):
    """The program's admission forward: the tails are the last two ``u``
    rows of every convolution layer, the K rows normed and rotated, the
    stream after the last layer; and a convolution layer token by token
    through the tail (the tick's step) ends on the same tail."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models import linear_attn

    conf, cfg, params, dims, tokens = toy
    stream, k_rows, tails = family.final_stream(cfg, params, tokens)
    ref = reference.slot_memory(7, dims, tokens, snap_at=16)
    assert ref["tails"].shape == tails.shape == (8, 2, 2, 256)
    np.testing.assert_allclose(tails, ref["tails"], atol=2e-5)
    np.testing.assert_allclose(
        k_rows, ref["rows"][..., : k_rows.shape[-1]], atol=2e-5
    )
    np.testing.assert_allclose(stream, ref["hidden"], atol=2e-5)
    assert ref["tails_at"].shape == ref["tails"].shape
    # Layer 0, a token at a time through ``attend_step``: its tail after
    # 24 tokens and after 16, and the mixer's output at every token.
    layer = {n: v[0] for n, v in params["dense_layers"].items()}
    x = jnp.asarray(params["embed"])[tokens]
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps)
    whole, _state, _tail = linear_attn.attend_sequence(h, layer, cfg)
    held = (jnp.zeros((1, 2, 512)),)
    step = jax.jit(
        lambda h_t, held: linear_attn.attend_step(h_t, layer, cfg, held, 0, None)
    )
    for t in range(24):
        y, held = step(h[:, t:t + 1], held)
        np.testing.assert_allclose(y[:, 0], whole[:, t], atol=1e-6)
        if t + 1 in (16, 24):
            want = ref["tails_at" if t + 1 == 16 else "tails"][0]
            np.testing.assert_allclose(
                held[0][0].reshape(2, 2, 256), want, atol=2e-6
            )


def test_the_program_s_router_is_the_reference_s_on_the_same_rows(toy):
    """``route_rows`` (the program's ``ops/moe.py::route``) against
    ``routed`` on the reference's own rows: the same experts, gates to
    float32's grain; the statistics of ``serve_conv`` read them so, and
    read each router fault."""
    _conf, cfg, params, dims, tokens = toy
    ref = reference.slot_memory(7, dims, tokens)
    rows = ref["router_in"].reshape(-1, ref["router_in"].shape[-1])
    got = family.route_rows(cfg, family.router_of(params, 7), rows)
    sound = reference.routed(7, dims, 9, rows)
    read = LOOP.router_readings(got, sound)
    assert read["flips"] == 0.0 and read["gate_err"] < 1e-6
    for fault, floor in (("bias_in_gates", 1e-4), ("router_bf16", 1e-4)):
        low = reference.routed(7, dims, 9, rows, fault)
        assert LOOP.router_readings(low[:2], sound)["gate_err"] > floor, fault


def test_every_named_fault_moves_the_reference(toy):
    """Each fault of ``FAULTS`` changes the part of the reference it names
    (a layer alone, eagerly: the whole model under each is the rehearsal's
    and the chip's to read, PERF.md has the readings)."""
    import jax
    import jax.numpy as jnp

    conf, _cfg, _params, _dims, _tokens = toy
    arch = family.Arch.from_conf(conf)
    key = W.seed_key(7)
    x = jax.random.normal(jax.random.key(2), (1, 8, arch.hidden))

    def weights(layer, names):
        w = {
            n: family.draw(key, arch, n, layer, jnp.float32) for n in names
        }
        w["ln1"] = jnp.ones((arch.hidden,))
        w["q_head_norm"] = w["k_head_norm"] = jnp.ones((arch.head,))
        return w

    def moved(fn):
        run = jax.jit(fn, static_argnums=0)
        sound = np.asarray(run(False))
        return lambda fault: np.abs(np.asarray(run(fault)) - sound).max()

    assert reference.CONTROLS == (True, *reference.FAULTS)
    w_conv, w_attn = weights(3, family.CONV), weights(2, family.ATTENTION)
    w_gate = weights(2, family.ROUTER)
    h = x[0] / jnp.sqrt(jnp.mean(x[0] ** 2, -1, keepdims=True))
    conv = moved(lambda v: reference.conv_mixer(x, w_conv, arch, v)[1])
    attn = moved(lambda v: reference.attention(x[0], w_attn, arch, v)[2])
    gates = moved(lambda v: reference.select(h, w_gate, arch, v)[1])
    by = {
        "no_c_gate": conv, "taps_reversed": conv, "no_head_norm": attn,
        "bias_in_gates": gates, "router_bf16": gates,
    }
    assert set(by) == set(reference.FAULTS)
    for fault, read in by.items():
        assert read(fault) > 1e-4, fault
    # ... and moves nothing else: a fault of the router leaves a mixer be.
    assert conv("bias_in_gates") == attn("router_bf16") == gates("no_c_gate") == 0


# ----------------------------------------------- the new readers


def test_the_gconv_readers_on_a_hand_made_trace(tmp_path):
    """Eight convolution layers a tick under ``tk_attn_proj/
    tk_gconv_step``, the admission's under ``tk_attn_flash/tk_gconv_seq``:
    the time a layer a tick and a ``jit_admit`` call; a fusion without a
    name takes the side of what it calls; a program without the names, or
    another configuration, gives nothing to read."""
    from test_chipbench_scopes import (
        computation, instruction, ld, module, plane,
    )

    from chipbench.layer_metrics import _gconv

    _gconv._PARSED.clear()
    step = "jit(tick_block)/while/body/tk_attn_proj/tk_gconv_step/mul"
    tick = module("jit_tick_block", 5, [
        computation(7, "fused_computation.1", [
            instruction("mul.1", "multiply", step),
            instruction("add.2", "add", step),
            instruction("dot.3", "dot", "jit(tick_block)/tk_attn_proj/dot_general"),
        ]),
        computation(9, "main", [
            instruction("fusion.1", "fusion", step, [7]),
            instruction("fusion.2", "fusion", None, [7]),
            instruction("fusion.3", "fusion", "jit(tick_block)/tk_attn_proj/dot_general"),
            instruction("while.4", "while", step, [9]),
        ]),
    ])
    admit = module("jit_admit", 11, [computation(1, "main", [
        instruction("fusion.1", "fusion", "jit(admit)/while/body/tk_attn_flash/tk_gconv_seq/add"),
        instruction("fusion.2", "fusion", "jit(admit)/while/body/tk_attn_flash/mul"),
    ])])
    space = b"".join(ld(1, p) for p in (
        plane("/device:TPU:0", [("jit_tick_block(5)", None)]),
        plane("/host:metadata", [
            ("jit_tick_block(5)", tick), ("jit_admit(11)", admit),
        ]),
    ))
    where = tmp_path / ".chipbench_trace" / f"{CELL}-7" / "plugins" / "p"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(space)

    def op(program, name, seconds, opcode="fusion"):
        return {f"{program}/{name}": {
            "count": 1.0, "total_s": seconds, "opcode": opcode,
            "program": program,
        }}

    ops = {
        **op("jit_tick_block", "fusion.1", 0.040),
        **op("jit_tick_block", "fusion.2", 0.024),
        **op("jit_tick_block", "fusion.3", 0.500),
        **op("jit_tick_block", "while.4", 0.900, "while"),
        **op("jit_admit", "fusion.1", 0.030),
        **op("jit_admit", "fusion.2", 0.300),
    }
    run = {
        "root": tmp_path, "cell": {"name": CELL}, "seed": 7, "conf": CONF,
        "trace": {"ops": ops, "programs": {
            "jit_tick_block": {"count": 2.0, "total_s": 1.0},
            "jit_admit": {"count": 3.0, "total_s": 0.4},
        }},
    }
    us = common.load_named("layer_metrics", "gconv.step_us.tput", REPO)
    ms = common.load_named("layer_metrics", "gconv.seq_ms.tput", REPO)
    # 64 ms over 2 blocks x 128 ticks x 8 convolution layers; 30 ms over 3.
    assert us.read(run) == pytest.approx(1e6 * 0.064 / (2 * 128 * 8))
    assert ms.read(run) == pytest.approx(10.0)
    granite = json.loads(
        (REPO / "chipbench/configs/granite-4.0-h-small-10l-ep4.json").read_text()
    )
    for bare in (
        {**run, "trace": None}, {**run, "conf": granite},
        {**run, "trace": {**run["trace"], "ops": {
            k: v for k, v in ops.items() if k.endswith("fusion.3")
        }}},
    ):
        assert us.read(bare) is None and ms.read(bare) is None
