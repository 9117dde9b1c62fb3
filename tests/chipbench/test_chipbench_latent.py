"""The latent-attention, routed-expert configuration and its cell: the
file against the catalog's row, the family's draw, the plain reference
against the program at a toy size (logits and routing), the control seen
to fail the limit, the hand arithmetic of the two kernel counts, the new
readers on a hand-made trace, and the deck that gives every seed the same
sizes. The cell end to end as a rehearsal is a case of
``test_chipbench_rehearsal.py`` (every cell of BENCHMARK.json is)."""

from __future__ import annotations

import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from toy import make_toy_root  # noqa: E402

from chipbench import common, control, routing  # noqa: E402
from chipbench import weights as W  # noqa: E402
from chipbench.models import mla_moe_decoder as family  # noqa: E402
from chipbench.reference import mla_moe_decoder as reference  # noqa: E402

CELL, CONFIG = "kanana2.longform-drain", "kanana-2-30b-a3b-7l"
CONF = json.loads((REPO / "chipbench/configs" / f"{CONFIG}.json").read_text())
MIX = json.loads((REPO / "chipbench/workloads" / f"{CELL}.json").read_text())
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_READERS = (
    "moe.experts_ms.tput", "moe.stream_roofline_pct", "moe.load_max_over_mean",
    "mla.read_us.tput", "mla.read_roofline_pct",
)
NOT_PUBLISHED = {"model", "reference", "changed_from_source", "deployment", "assumed"}


def test_the_file_is_the_catalog_row_but_for_the_depth():
    if not CATALOG.is_file():
        pytest.skip("no catalog here")
    row = next(
        r for r in map(json.loads, CATALOG.read_text().splitlines())
        if r["name"] == "kanana-2-30b-a3b-instruct-2601"
    )
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers"]
    differs = [k for k, v in row["config"].items() if CONF.get(k, "absent") != v]
    assert differs == ["num_hidden_layers"]
    assert row["config"]["num_hidden_layers"] == 48 and CONF["num_hidden_layers"] == 7
    assert "48 in the source" in CONF["changed_from_source"]["num_hidden_layers"]


def test_the_cell_and_its_traffic_are_the_issue_s():
    dep = CONF["deployment"]
    assert (dep["slots"], dep["prompt_window"], dep["max_new"]) == (64, 512, 3584)
    assert (dep["ticks_per_sync"], dep["commit_every"]) == (128, 32)
    assert dep["kv_dtype"] is None and dep["mesh"] is None
    assert dep["prompt_partitions"] == 2
    # ``loops/serve.py`` whole, then the cached rows against the reference.
    assert dep["loop"] == MIX["loop"] == "serve_latent"
    assert MIX["traffic"] == {
        "kind": "backlog", "records": 1200, "deck": 64, "block": 16,
        "prompt_median": 192, "prompt_sigma": 0.8, "prompt_max": 512,
        "answer_median": 1024, "answer_sigma": 0.8, "answer_min": 2,
        "answer_max": 3584, "pairing_seed": 27, "tenants": 8,
        "tenant_zipf": 1.1,
    }
    assert MIX["warmup_records"] == 3 and MIX["trace"]["seconds"] == 14
    assert MIX["check"] == {
        "sample": 4, "max_logit_gap": 0.5, "probe_new": 256,
        "max_latent_row_err": MIX["check"]["max_latent_row_err"],
    }
    assert "residual_write_gain" not in CONF
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "backlog"
    listed = {
        m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])
    }
    assert not [n for n in listed if n.startswith("kvattn.")]
    assert {"tick_ms.tput", "prefill_ms.tput", "sched.slot_tick_use_pct",
            "committed_tokens_per_s.serve"} <= listed


def test_the_waiting_entries_run_once_they_are_appended(tmp_path, capsys):
    """The five readers of the two mechanisms have no entry yet
    (``chipbench/layer_metrics/waiting.json`` says why). Appended to a toy
    copy's ``per_layer`` they are well-formed entries, and a traced
    rehearsal reads through them: the counter's reader gives a number,
    the device-time readers nothing (a rehearsal has no trace), none
    raises."""
    from chipbench import run as runner

    waiting = json.loads(
        (REPO / "chipbench/layer_metrics/waiting.json").read_text()
    )["per_layer"]
    assert [m["name"] for m in waiting] == list(NEW_READERS)
    root = make_toy_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in waiting:
        assert set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads",
        }
        assert m["workloads"] == [CELL] and m["moves"] == "serve.tokens_per_s"
        assert m["layer"] in {e["layer"] for e in BENCH["per_layer"]}
        assert (REPO / "chipbench/layer_metrics" / f"{m['name']}.py").is_file()
        assert m["name"] not in {e["name"] for e in bench["per_layer"]}
    bench["per_layer"] += waiting
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc = runner.main(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.5", "--trace", "1"],
        root=root, rehearsal=True,
    )
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rc == 0 and last["checks_passed"] is True
    assert "moe.load_max_over_mean" in last["metric_names"]
    assert not {n for n in NEW_READERS if n != "moe.load_max_over_mean"} & set(
        last["metric_names"]
    )


def test_the_cut_by_hand():
    """ISSUE 27's arithmetic: an expert layer 640.0 M parameters, the dense
    layer 64.1 M, 8.86 GB of bfloat16 beside a 2.11 GB pool."""
    a = family.Arch.from_conf(CONF)
    attn = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert attn == 26_345_472
    expert_layer = attn + 3 * 2048 * 1536 + (2048 * 128 + 128) + 4608 + (
        128 * 3 * 2048 * 768
    )
    assert a.layer_params(True) == expert_layer == 640_029_312
    assert a.layer_params(False) == attn + 3 * 2048 * 6144 + 4608
    assert a.params == 2 * 128256 * 2048 + 2048 + 64_098_816 + 6 * expert_layer
    assert round(a.params * 2 / 1e9, 2) == 8.86
    dep = CONF["deployment"]
    pool = 7 * dep["slots"] * (dep["prompt_window"] + dep["max_new"]) * 576 * 2
    assert round(pool / 1e9, 2) == 2.11
    assert (a.params * 2 + pool) / 16e9 > 0.25  # the driver's floor


def test_kernel_counts_by_hand():
    moe = common.load_named("kernels", "moe", REPO)
    mla = common.load_named("kernels", "mla", REPO)
    assert moe.expert_bytes(CONF) == 3 * 2048 * 768 * 2 == 9_437_184
    assert moe.shared_bytes(CONF) == 2 * 9_437_184
    assert moe.expert_layers(CONF) == 6
    # A tick that touches 120 experts in each of six layers.
    assert moe.stream_bytes(CONF, 720, 1) == 720 * 9_437_184 + 6 * 18_874_368
    assert mla.row_bytes(CONF) == 1152
    assert mla.bytes_per_tick_slot(CONF) == 32 * 1152 + 32 * 1024
    # Tokens 1..3 of a request behind a 512 window read 513 + 514 + 515 rows.
    assert mla.positions_of_block(512, 1, 3) == 513 + 514 + 515
    assert mla.read_bytes(CONF, 1542, 3) == 7 * (1542 * 1152 + 3 * 69_632)
    import re

    assert re.search(moe.operand_pattern(CONF), "bf16[6,128,2048,768]{3,2,1,0}")
    assert re.search(moe.operand_pattern(CONF), "bf16[128,768,2048]{2,1,0}")
    assert re.search(moe.operand_pattern(CONF), "bf16[6,1536,2048]{2,1,0}")
    assert not re.search(moe.operand_pattern(CONF), "bf16[1,2048,6144]{2,1,0}")
    assert re.search(mla.pool_pattern(CONF), "bf16[7,64,4096,576]{3,2,1,0}")
    assert re.search(mla.scores_pattern(CONF), "f32[64,32,1,4096]{3,1,0,2}")


def test_the_new_readers_on_a_hand_made_trace(monkeypatch):
    """Device times told by operand shapes, counters by their sections;
    nothing to read gives None and does not raise."""
    from chipbench.layer_metrics import _latent_ops as L

    pool, w = "bf16[7,64,4096,576]{3,2,1,0}", "bf16[6,128,2048,768]{3,2,1,0}"
    ops = [
        (f"%fusion.1 = bf16[128,64,768]{{2,1,0}} fusion({w} %p, s32[] %l), kind=kOutput", 2e-3),
        (f"%fusion.2 = (f32[64,32]{{1,0}}, f32[64,32,1,4096]{{3,1,0,2}}) fusion({pool} %pool, bf16[64,32,576]{{2,1,0}} %q), kind=kOutput", 1e-3),
        ("%fusion.3 = f32[64,32,1,4096]{3,1,0,2} fusion(f32[64,32,1,4096]{3,1,0,2} %s), kind=kLoop", 5e-4),
        (f"%fusion.4 = {pool} fusion({pool} %pool, bf16[64,576]{{1,0}} %row), kind=kLoop", 9e-3),
        ("%fusion.5 = bf16[64,2048]{1,0} fusion(bf16[6,1536,2048]{2,1,0} %ws), kind=kOutput", 1e-3),
    ]
    counters = [
        {"scheduler": {"slot_ticks_run": 0}, "expert_layer": {
            "moe_experts_touched": 0, "moe_expert_load": []}},
        {"scheduler": {"slot_ticks_run": 64 * 256}, "expert_layer": {
            "moe_experts_touched": 256 * 6 * 120,
            "moe_expert_load": [30] * 127 + [60]}},
    ]
    run = {
        "trace": {"programs": {"jit_tick_block": {"count": 1.0, "total_s": 1.0}},
                  "host_t0": 0.0, "host_t1": 10.0},
        "conf": CONF, "root": REPO, "slots": 64, "counters": counters,
        "peaks": common.load_peaks("TPU v5 lite"), "prompt_window": 512,
        "requests": [{"syncs": [(5.0, 4)]}],
    }
    monkeypatch.setattr(L, "tick_ops", lambda run: ops if run.get("trace") else None)

    def read(name, run=run):
        return common.load_named("layer_metrics", name, REPO).read(run)

    assert read("moe.experts_ms.tput") == pytest.approx(1e3 * 3e-3 / 128)
    need = 720 * 9_437_184 + 6 * 18_874_368
    assert read("moe.stream_roofline_pct") == pytest.approx(
        100 * need / (3e-3 / 128 * 819e9)
    )
    assert read("moe.load_max_over_mean") == pytest.approx(60 / (3870 / 128))
    # The scatter (its result is the pool) is not the read.
    assert read("mla.read_us.tput") == pytest.approx(1e6 * 1.5e-3 / (128 * 7))
    rows = 513 + 514 + 515
    assert read("mla.read_roofline_pct") == pytest.approx(
        100 * 7 * (rows * 1152 + 3 * 69_632) / (1.5e-3 * 819e9)
    )
    bare = {**run, "trace": None, "counters": [{}, {}]}
    for name in NEW_READERS:
        assert read(name, bare) is None


def test_every_seed_gets_the_same_multiset_of_sizes():
    traffic = common.load_named("traffic", "backlog", REPO)
    frame = {"prompt_window": 512, "max_new": 3584, "vocab": 128256,
             "seconds": 40.0, "partitions": 2}
    decks = []
    for seed in (1, 27, 2**31 + 5):
        recs = traffic.generate(MIX["traffic"], seed, frame)["records"]
        assert len(recs) == 1200
        decks.append(Counter((len(r["tokens"]), r["max_new"]) for r in recs))
        assert max(len(r["tokens"]) for r in recs) <= 512
        assert 2 <= min(r["max_new"] for r in recs)
        assert max(r["max_new"] for r in recs) <= 3584
    assert decks[0] == decks[1] == decks[2]
    orders = [
        [r["max_new"] for r in traffic.generate(MIX["traffic"], s, frame)["records"]]
        for s in (1, 27)
    ]
    assert orders[0] != orders[1]


TOY = {
    **CONF, "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
    "num_attention_heads": 2, "num_key_value_heads": 2, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 24, "vocab_size": 96,
    "deployment": {**CONF["deployment"], "param_dtype": "float32",
                   "compute_dtype": "float32", "slots": 2,
                   "prompt_window": 8, "max_new": 8},
}


@pytest.mark.parametrize("seed", [0, 2**31 + 3])
def test_a_layer_drawn_alone_is_the_stacked_draw_s_layer(seed):
    import jax.numpy as jnp

    a = family.Arch.from_conf(TOY)
    key = W.seed_key(seed)
    tree = family.serving_tree(key, a, jnp.bfloat16)
    assert tree["dense_layers"]["w_gate"].shape == (1, 64, 96)
    assert tree["layers"]["w_gate"].shape == (2, 8, 64, 24)
    assert tree["layers"]["wkva"].shape == (2, 64, 40)
    for layer in range(a.layers):
        expert = a.is_expert_layer(layer)
        alone = family.layer_weights(key, a, layer, jnp.bfloat16, expert)
        group = tree["layers" if expert else "dense_layers"]
        for name, t in alone.items():
            stacked = np.asarray(group[name][layer - (1 if expert else 0)])
            assert (stacked == np.asarray(t)).all(), (layer, name)
    bias = np.asarray(tree["layers"]["router_bias"], np.float32)
    assert 0.003 < bias.std() < 0.03  # the assumed sigma of 0.01
    # Matmul weights at 1/sqrt(fan_in); the writes into the residual
    # stream also by 1/sqrt(2 x published depth); unit embedding rows.
    w = np.asarray(tree["layers"]["w_gate"], np.float32)
    assert w.std() == pytest.approx(1 / np.sqrt(64), rel=0.1)
    w = np.asarray(tree["layers"]["w_down"], np.float32)
    assert w.std() == pytest.approx(1 / np.sqrt(24 * 2 * 48), rel=0.1)
    assert np.asarray(tree["embed"], np.float32).std() == pytest.approx(1.0, rel=0.1)
    assert a.published_layers == 48 and a.layers == 3


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_the_reference_agrees_with_the_program_at_toy_size(seed):
    """Logits: the program's greedy continuation, teacher-forced through
    the reference, is the reference's own first choice at every position,
    to 1e-4 of a logit (float32 on both sides: the absorbed decode and the
    grouped expert sum reorder float32 additions, nothing else differs).
    Routing: the two choose the same experts for every token."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models.transformer import Transformer

    cfg = family.program_config(TOY, 16)
    params = family.serving_params(TOY, seed)
    model = jax.jit(Transformer(cfg).__call__)
    rng = np.random.default_rng(seed % 1000)
    toks = np.zeros((2, 16), np.int32)
    toks[:, :8] = rng.integers(1, 96, (2, 8))
    for t in range(8, 16):
        logits = np.asarray(model(params, jnp.asarray(toks[:, :t])))[:, -1]
        toks[:, t] = logits.argmax(-1)
    gap, top = reference.served_logit_gaps(
        seed, W.Dims.from_conf(TOY), toks, 7, 8
    )
    assert float(np.max(np.asarray(gap))) < 1e-4
    assert (np.asarray(top) == toks[:, 8:16]).all()
    row = routing.agreement(TOY, seed, rows=2, length=16)
    assert row["routing_set_agreement"] == 1.0 and row["expert_layers"] == 2


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_toy_root(tmp_path_factory.mktemp("chipbench_latent"))


def test_the_controls_fail_the_cached_rows_limit(toy_root, capsys):
    """``loops/serve_latent.py`` at toy size: the program (float32 here)
    holds both limits; the reference with its attention and expert matmuls
    in 8-bit floating point, put in the program's place, fails the cached
    rows' limit as the cell's file has it, in the rows the admission wrote
    and in those the ticks wrote; the expert matmuls alone move the rows
    1,000 times what the program reads; a stream displaced by one position
    fails the served tokens' limit. (PERF.md has the chip's readings,
    bfloat16 against the controls.)"""
    rc = control.main(
        # A window that finishes the toy's forty requests on a busy
        # machine too: the sample is drawn from what finished.
        ["--workload", CELL, "--seeds", "11,12", "--seconds", "8",
         "--control", "1"], root=toy_root, rehearsal=True,
    )
    rows = [
        json.loads(l)["reading"] for l in capsys.readouterr().out.splitlines()
        if l.startswith('{"reading"')
    ]
    assert rc == 0 and len(rows) == 2
    limit = MIX["check"]["max_latent_row_err"]
    for r in rows:
        assert r["correct"]
        compared = {c["check"]: c for c in r["compared"]}
        assert compared["latent_row_err.decode"]["limit"] == limit
        gap, err = r["control"]["served_logit_gap"], r["control"]["latent_row_err"]
        assert gap["program"] <= gap["limit"] < gap["displaced_stream"]
        assert gap["control"] > 100 * gap["program"]
        for region in ("prefill", "decode"):
            assert err["program"][region] < 1e-5
            assert err["control_layers"][region] > limit
            assert err["control_experts"][region] > 1000 * err["program"][region]


def test_tick_forms_times_both_forms_of_the_expert_sum(toy_root, capsys):
    """``chipbench/tick_forms.py`` at a toy cut on the CPU: a reading for
    each form, and the program's threshold back where it was."""
    from chipbench import tick_forms
    from torchkafka_tpu.ops import moe

    before = moe._GROUPED_MIN_PAIRS_PER_EXPERT
    rc = tick_forms.main(
        ["--workload", CELL, "--slots", "4", "--window", "8", "--new", "16",
         "--ticks", "2"], root=toy_root, rehearsal=True,
    )
    rows = [
        json.loads(l)["reading"] for l in capsys.readouterr().out.splitlines()
        if l.startswith('{"reading"')
    ]
    assert rc == 0 and [r["form"] for r in rows] == ["as_built", "grouped"]
    assert all(r["tick_ms"] > 0 and r["admit_s"] > 0 for r in rows)
    assert rows[0]["pairs_per_expert_a_tick"] == 4 * 6 / 128
    assert moe._GROUPED_MIN_PAIRS_PER_EXPERT == before


def test_row_err_is_the_median_row_of_the_worst_layer():
    loop = common.load_named("loops", "serve_latent", REPO)
    want = np.ones((2, 1, 6, 4), np.float32)
    rows = want.copy()
    rows[0, 0, 0] *= 1.5  # one row of six far off: the median is not moved
    rows[1, 0, 3:] *= 1.01  # half of the second layer's rows 1% off
    rows[1, 0, 5] *= 3.0
    assert loop.row_err(rows, want, slice(0, 3)) == pytest.approx(0.0)
    assert loop.row_err(rows, want, slice(3, None)) == pytest.approx(0.01)
    # Which slot served which prompt is read from the first layer's rows.
    held = np.zeros((2, 3, 4, 4), np.float32)
    held[:, 2], held[:, 0] = 1.0, 2.0
    wanted = np.stack([np.full((2, 4, 4), 2.0), np.full((2, 4, 4), 1.0)], 1)
    assert (loop.rows_of(held, wanted, 4)[0, :, 0, 0] == [2.0, 1.0]).all()
    with pytest.raises(common.Refused, match="share a slot"):
        loop.rows_of(held, np.full((2, 2, 4, 4), 2.0), 4)


def test_the_reference_rounds_the_part_it_is_asked_to():
    """``lowp`` by part: the experts' matmuls alone leave the first
    (dense-MLP) layer's attention, hence the second layer's cached rows'
    inputs, to the MLP's rounding only; the read alone leaves the first
    layer's rows as they are; every part leaves the routing's scores in
    float32 but ``True``."""
    a = family.Arch.from_conf(TOY)
    toks = np.random.default_rng(0).integers(1, 96, (2, 12), dtype=np.int32)
    dims = W.Dims.from_conf(TOY)
    family.program_config(TOY, 16)  # registers the family's sizes
    exact = reference.cached_rows(3, dims, toks)
    assert exact.shape == (a.layers, 2, 12, a.rank + a.rope)
    for part, first_layer_moves in (("layers", True), ("experts", False),
                                    ("read", False)):
        low = reference.cached_rows(3, dims, toks, lowp=part)
        assert (np.abs(low[0] - exact[0]).max() > 1e-4) == first_layer_moves
        assert np.abs(low[-1] - exact[-1]).max() > 1e-5
    assert set(reference.LOWP_PARTS) == {False, True, "layers", "experts", "read"}
