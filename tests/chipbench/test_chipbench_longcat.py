"""The configuration ``longcat-flash-omni-4l-ep32`` (one chip's share of
LongCat-Flash-Omni's language model) and its cell: the file against the
catalog's row and ISSUE 31's arithmetic, the family's draw, the plain
reference against the program at a toy size (logits, cached rows,
routing), all the shares of a toy adding up to the uncut reference's
layer, every named control seen to fail a limit, the hand arithmetic of
the two kernel counts, the four new readers on a hand-made trace and
through a toy benchmark once their waiting entries are appended, and the
deck that gives every seed the same sizes. The cell end to end as a
rehearsal is a case of ``test_chipbench_rehearsal.py`` (every cell of
BENCHMARK.json is)."""

from __future__ import annotations

import json
import re
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from toy import make_toy_root  # noqa: E402

from chipbench import common, control  # noqa: E402
from chipbench import weights as W  # noqa: E402
from chipbench.models import longcat_decoder as family  # noqa: E402
from chipbench.reference import longcat_decoder as reference  # noqa: E402

CELL, CONFIG = "longcat.reasoning-drain", "longcat-flash-omni-4l-ep32"
CONF = json.loads((REPO / "chipbench/configs" / f"{CONFIG}.json").read_text())
MIX = json.loads((REPO / "chipbench/workloads" / f"{CELL}.json").read_text())
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_READERS = (
    "moe.zero_share_pct", "moe.local_pairs_per_expert",
    "moe.held_stream_roofline_pct", "mla.block_read_roofline_pct",
)
REDUCED = ["num_layers", "n_routed_experts", "vocab_size", "rms_norm_eps"]
TEN = (
    "sched.occupancy_pct", "tick_ms.tput", "prefill_ms.tput",
    "commit_ms.serve", "committed_tokens_per_s.serve", "sched.admit_fill_pct",
    "sched.slot_tick_use_pct", "sched.host_ms_per_sync",
    "source.poll_ms.serve", "commit.flush_ms.serve",
)


# ------------------------------------------------- the file's contract


def test_the_file_is_the_catalog_row_but_for_the_four_cuts():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED
    assert sorted(CONF["changed_from_source"]) == sorted(REDUCED)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    if not CATALOG.is_file():
        pytest.skip("no catalog here")
    row = next(
        r for r in map(json.loads, CATALOG.read_text().splitlines())
        if r["name"] == "LongCat-Flash-Omni"
    )
    assert entry["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if CONF.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED)
    # The published counts stand beside the cut ones.
    for key in ("num_layers", "n_routed_experts", "vocab_size", "rms_norm_eps"):
        assert CONF[f"published_{key}"] == row["config"][key]
    assert (CONF["num_layers"], CONF["n_routed_experts"], CONF["vocab_size"]) == (
        4, 16, 16384,
    )


def test_no_width_differs_and_the_harness_names_are_aliases():
    for key in ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
                "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
                "qk_nope_head_dim", "v_head_dim", "moe_topk",
                "zero_expert_num", "num_attention_heads"):
        assert key not in REDUCED and key in CONF
    assert CONF["num_hidden_layers"] == CONF["num_layers"]
    assert CONF["intermediate_size"] == CONF["ffn_hidden_size"]
    assert CONF["num_key_value_heads"] == CONF["num_attention_heads"]
    # The deployment and every assumed size are stated.
    dep, assumed = CONF["deployment"], CONF["assumed"]
    assert (dep["chips_sharing_a_layer"], dep["pipeline_stages"],
            dep["layers_a_stage"], dep["vocabulary_shards"]) == (32, 7, 4, 8)
    assert dep["pipeline_stages"] * dep["layers_a_stage"] == CONF["published_num_layers"]
    assert dep["chips_sharing_a_layer"] * CONF["n_routed_experts"] == (
        CONF["published_n_routed_experts"]
    )
    assert dep["vocabulary_shards"] * CONF["vocab_size"] == CONF["published_vocab_size"]
    assert dep["experts_held"] == [0, 16]
    for key in ("not_in_config_json", "e_score_correction_bias", "deployment",
                "not_built", "slots", "ticks_per_sync", "commit_every",
                "weights", "loop", "kv_kernel", "broker"):
        assert assumed[key]
    for word in ("encoders", "codec decoder", "multi-token-prediction"):
        assert word in assumed["not_built"]


def test_the_cell_and_its_traffic_are_the_issue_s():
    dep = CONF["deployment"]
    assert (dep["slots"], dep["prompt_window"], dep["max_new"]) == (128, 512, 1536)
    assert (dep["ticks_per_sync"], dep["commit_every"]) == (128, 32)
    assert dep["kv_dtype"] is None and dep["mesh"] is None
    assert dep["kv_kernel"] is False and dep["prompt_partitions"] == 2
    assert (dep["param_dtype"], dep["compute_dtype"]) == ("bfloat16", "bfloat16")
    assert "[8, 128, 2048, 576]" in dep["kv_layout"]
    assert dep["loop"] == MIX["loop"] == "serve_share"
    assert MIX["traffic"] == {
        "kind": "backlog", "records": 1200, "deck": 64, "block": 16,
        "prompt_median": 192, "prompt_sigma": 0.8, "prompt_max": 512,
        "answer_median": 768, "answer_sigma": 0.8, "answer_min": 2,
        "answer_max": 1536, "pairing_seed": 31, "tenants": 8,
        "tenant_zipf": 1.1,
    }
    assert MIX["warmup_records"] == 3 and MIX["trace"]["seconds"] == 14
    assert set(MIX["check"]) == {
        "sample", "max_logit_gap", "probe_new", "max_latent_row_err",
        "max_held_pair_missing",
    }
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {
        "name": CELL, "config": CONFIG, "traffic": "backlog", "chips": 1,
        "why": cell["why"],
    }
    assert "64" in cell["why"] and "attention" in cell["why"]
    assert BENCH["workloads"][-1] is cell and BENCH["configs"][-1]["name"] == CONFIG
    listed = {
        m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])
    }
    assert listed == set(TEN)
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL  # appended, nothing else moved
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "serve.tokens_per_s")
    assert e2e["workloads"] == [
        "mistral7b.backlog-drain", "kanana2.longform-drain", CELL,
    ]


def test_the_cut_by_hand():
    """ISSUE 31's arithmetic: a block 90.6 M of attention and 226.5 M of
    dense FFN, 638.8 M a layer outside the experts, 37.75 M an expert,
    5.17 B parameters and 10.34 GB beside a 2.42 GB pool."""
    a = family.Arch.from_conf(CONF)
    attn = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
            + 64 * 128 * 6144)
    assert attn == 90_570_752
    ffn = 3 * 6144 * 12288
    assert ffn == 226_492_416
    norms = 2 * 6144 + 1536 + 512
    assert a.block_params == attn + ffn + norms
    router = 6144 * 768 + 768
    outside = 2 * a.block_params + router
    assert round(outside / 1e6, 1) == 638.9  # the issue's 638.8 M + norms
    assert a.expert_params == 3 * 6144 * 2048 == 37_748_736
    assert a.layer_params == outside + 16 * a.expert_params
    assert a.params == 4 * a.layer_params + 2 * 16384 * 6144 + 6144
    assert round(a.params / 1e9, 2) == 5.17
    assert round(a.params * 2 / 1e9, 2) == 10.35  # (the issue's 10.34: twice 5.17)
    dep = CONF["deployment"]
    pool = 8 * dep["slots"] * (dep["prompt_window"] + dep["max_new"]) * 576 * 2
    assert round(pool / 1e9, 2) == 2.42
    assert round((a.params * 2 + pool) / 1e9, 2) == 12.76  # the issue's total
    assert round((a.params * 2 + pool) / 2**30, 1) == 11.9
    assert (a.params * 2 + pool) / 16e9 > 0.25  # the driver's floor
    # No chip holds one layer whole: 512 experts are 38.7 GB.
    assert round(512 * a.expert_params * 2 / 1e9, 1) == 38.7
    # A token's 12 pairs: 4 zero, 0.25 local, 7.75 absent, by expectation.
    assert (12 * 256 / 768, 12 * 16 / 768, 12 * 496 / 768) == (4.0, 0.25, 7.75)


def test_kernel_counts_by_hand():
    share = common.load_named("kernels", "moe_share", REPO)
    blocks = common.load_named("kernels", "mla_blocks", REPO)
    assert share.expert_bytes(CONF) == 3 * 6144 * 2048 * 2 == 75_497_472
    assert share.held(CONF) == 16 and share.layers(CONF) == 4
    # A tick that touches 14 held experts in each of four layers.
    assert share.stream_bytes(CONF, 56) == 56 * 75_497_472
    assert blocks.blocks(CONF) == 8 and blocks.row_bytes(CONF) == 1152
    assert blocks.bytes_per_tick_slot(CONF) == 64 * 1152 + 64 * 1024
    assert blocks.positions_of_block(512, 1, 3) == 513 + 514 + 515
    assert blocks.read_bytes(CONF, 1542, 3) == 8 * (1542 * 1152 + 3 * 139_264)
    assert re.search(share.operand_pattern(CONF), "bf16[4,16,6144,2048]{3,2,1,0}")
    assert re.search(share.operand_pattern(CONF), "bf16[16,2048,6144]{2,1,0}")
    assert not re.search(share.operand_pattern(CONF), "bf16[4,2,6144,12288]{3,2,1,0}")
    assert re.search(blocks.pool_pattern(CONF), "bf16[8,128,2048,576]{3,2,1,0}")
    assert re.search(blocks.scores_pattern(CONF), "f32[128,64,1,2048]{3,1,0,2}")


# ------------------------------------------------------ the new readers


def _run(counters, requests=()):
    return {
        "trace": {"programs": {"jit_tick_block": {"count": 1.0, "total_s": 1.0}},
                  "host_t0": 0.0, "host_t1": 10.0},
        "conf": CONF, "root": REPO, "slots": 128, "counters": counters,
        "peaks": common.load_peaks("TPU v5 lite"), "prompt_window": 512,
        "requests": list(requests), "cell": {"name": CELL}, "seed": 1,
    }


def test_the_new_readers_on_a_hand_made_trace(monkeypatch):
    """Device times told by operand shapes, counters by their sections;
    nothing to read gives None and does not raise."""
    from chipbench.layer_metrics import _latent_ops as L

    pool, w = "bf16[8,128,2048,576]{3,2,1,0}", "bf16[4,16,6144,2048]{3,2,1,0}"
    ops = [
        (f"%fusion.1 = bf16[16,128,2048]{{2,1,0}} fusion({w} %p, s32[] %l), kind=kOutput", 2e-3),
        (f"%fusion.2 = (f32[128,64]{{1,0}}, f32[128,64,1,2048]{{3,1,0,2}}) fusion({pool} %pool, bf16[128,64,576]{{2,1,0}} %q), kind=kOutput", 1e-3),
        ("%fusion.3 = f32[128,64,1,2048]{3,1,0,2} fusion(f32[128,64,1,2048]{3,1,0,2} %s), kind=kLoop", 5e-4),
        # The scatter (its result is the pool) is not the read.
        (f"%fusion.4 = {pool} fusion({pool} %pool, bf16[128,576]{{1,0}} %row), kind=kLoop", 9e-3),
        ("%fusion.5 = bf16[128,6144]{1,0} fusion(bf16[4,2,12288,6144]{3,2,1,0} %wd), kind=kOutput", 4e-3),
    ]
    counters = [
        {"scheduler": {"slot_ticks_run": 0}, "expert_layer": {
            "moe_experts_touched": 0, "moe_assignments": 0,
            "moe_zero_assignments": 0, "moe_local_assignments": 0,
            "experts_held": [0, 16]}},
        {"scheduler": {"slot_ticks_run": 128 * 256}, "expert_layer": {
            "moe_experts_touched": 256 * 4 * 14,
            "moe_assignments": 256 * 4 * 128 * 12,
            "moe_zero_assignments": 256 * 4 * 128 * 4,
            "moe_local_assignments": 256 * 4 * 32,
            "experts_held": [0, 16]}},
    ]
    run = _run(counters, [{"syncs": [(5.0, 4)]}])
    monkeypatch.setattr(
        L, "tick_ops", lambda run: ops if run.get("trace") else None
    )

    def read(name, run=run):
        return common.load_named("layer_metrics", name, REPO).read(run)

    assert read("moe.zero_share_pct") == pytest.approx(100 / 3)
    assert read("moe.local_pairs_per_expert") == pytest.approx(32 / 16)
    need = 4 * 14 * 75_497_472
    assert read("moe.held_stream_roofline_pct") == pytest.approx(
        100 * need / (2e-3 / 128 * 819e9)
    )
    rows = 513 + 514 + 515
    assert read("mla.block_read_roofline_pct") == pytest.approx(
        100 * 8 * (rows * 1152 + 3 * 139_264) / (1.5e-3 * 819e9)
    )
    bare = {**run, "trace": None, "counters": [{}, {}]}
    for name in NEW_READERS:
        assert read(name, bare) is None
    # A program that counts no fates (the parent, the other latent cell).
    old = {**run, "counters": [
        {"scheduler": {"slot_ticks_run": 0}, "expert_layer": {"moe_assignments": 0}},
        {"scheduler": {"slot_ticks_run": 64}, "expert_layer": {"moe_assignments": 9}},
    ]}
    assert read("moe.zero_share_pct", old) is None
    assert read("moe.local_pairs_per_expert", old) is None
    kanana = json.loads(
        (REPO / "chipbench/configs/kanana-2-30b-a3b-7l.json").read_text()
    )
    for name in ("moe.held_stream_roofline_pct", "mla.block_read_roofline_pct"):
        assert read(name, {**run, "conf": kanana}) is None


def test_the_waiting_entries_run_once_they_are_appended(tmp_path, capsys):
    """The four readers have no entry yet (``chipbench/layer_metrics/
    waiting.longcat.json`` says why). Appended to a toy copy's
    ``per_layer`` they are well-formed entries, and a traced rehearsal
    reads through them: the counters' readers give numbers, the
    device-time readers nothing (a rehearsal has no trace), none raises."""
    from chipbench import run as runner

    waiting = json.loads(
        (REPO / "chipbench/layer_metrics/waiting.longcat.json").read_text()
    )
    assert "test_chipbench_named.py" in waiting["why"]
    waiting = waiting["per_layer"]
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
        if m["name"].endswith("roofline_pct"):
            assert m["unit"] == "%" and m["source"] == "device_trace"
    bench["per_layer"] += waiting
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc = runner.main(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.5", "--trace", "1"],
        root=root, rehearsal=True,
    )
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rc == 0 and last["checks_passed"] is True
    assert {"moe.zero_share_pct", "moe.local_pairs_per_expert"} <= set(
        last["metric_names"]
    )
    assert not {"moe.held_stream_roofline_pct",
                "mla.block_read_roofline_pct"} & set(last["metric_names"])


def test_every_seed_gets_the_same_multiset_of_sizes():
    traffic = common.load_named("traffic", "backlog", REPO)
    frame = {"prompt_window": 512, "max_new": 1536, "vocab": 16384,
             "seconds": 40.0, "partitions": 2}
    decks = []
    for seed in (1, 31, 2**31 + 5):
        recs = traffic.generate(MIX["traffic"], seed, frame)["records"]
        assert len(recs) == 1200
        decks.append(Counter((len(r["tokens"]), r["max_new"]) for r in recs))
        assert max(len(r["tokens"]) for r in recs) <= 512
        assert 2 <= min(r["max_new"] for r in recs)
        assert max(r["max_new"] for r in recs) <= 1536
        # Token ids from the 16,384-row slice this chip holds.
        assert max(int(r["tokens"].max()) for r in recs) < 16384
    assert decks[0] == decks[1] == decks[2]


# ------------------------------------- the draw, the reference, the share

TOY = {
    **CONF, "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 2, "num_key_value_heads": 2, "kv_lora_rank": 32,
    "q_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "published_n_routed_experts": 8, "n_routed_experts": 2,
    "zero_expert_num": 4, "moe_topk": 3, "expert_ffn_hidden_size": 24,
    "vocab_size": 96,
    "deployment": {**CONF["deployment"], "param_dtype": "float32",
                   "compute_dtype": "float32", "slots": 2,
                   "prompt_window": 8, "max_new": 8, "experts_held": [4, 2]},
}


@pytest.mark.parametrize("seed", [0, 2**31 + 3])
def test_a_part_drawn_alone_is_the_stacked_draw_s_part(seed):
    import jax.numpy as jnp

    a = family.Arch.from_conf(TOY)
    key = W.seed_key(seed)
    tree = family.serving_tree(key, a, jnp.bfloat16)
    layers = tree["layers"]
    assert layers["w_gate"].shape == (2, 2, 64, 96)
    assert layers["we_gate"].shape == (2, 2, 64, 24)
    assert layers["wqa"].shape == (2, 2, 64, 24)
    assert layers["wkva"].shape == (2, 2, 64, 40)
    assert layers["router"].shape == (2, 64, 12)
    for layer in range(a.layers):
        for block in (0, 1):
            alone = family.block_weights(key, a, layer, block, jnp.bfloat16)
            for name, t in alone.items():
                stacked = np.asarray(layers[name][layer, block])
                assert (stacked == np.asarray(t)).all(), (layer, block, name)
        branch = family.branch_weights(key, a, layer, jnp.bfloat16)
        for name, t in branch.items():
            assert (np.asarray(layers[name][layer]) == np.asarray(t)).all()
    # An expert's weights do not depend on which chip holds it: expert 5
    # of the share [4, 6) is expert 5 of the share [5, 7).
    other = family.branch_weights(key, a.hold(5, 2), 1, jnp.bfloat16)
    assert (np.asarray(other["we_up"][0])
            == np.asarray(layers["we_up"][1, 1])).all()
    assert (np.asarray(other["router"]) == np.asarray(layers["router"][1])).all()
    bias = np.asarray(layers["router_bias"], np.float32)
    assert 0.0003 < bias.std() < 0.003  # the assumed sigma of 0.0009
    w = np.asarray(layers["w_gate"], np.float32)
    assert w.std() == pytest.approx(1 / np.sqrt(64), rel=0.1)
    # wo and w_down by 1/sqrt(4 x 28 published layers); we_down not.
    w = np.asarray(layers["w_down"], np.float32)
    assert w.std() == pytest.approx(1 / np.sqrt(96 * 4 * 28), rel=0.1)
    w = np.asarray(layers["we_down"], np.float32)
    assert w.std() == pytest.approx(1 / np.sqrt(24), rel=0.1)
    assert np.asarray(tree["embed"], np.float32).std() == pytest.approx(1.0, rel=0.1)
    assert a.published_layers == 28 and a.layers == 2


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_the_reference_agrees_with_the_program_at_toy_size(seed):
    """Logits: the program's greedy continuation, teacher-forced through
    the reference, is the reference's own first choice at every position,
    to 1e-4 of a logit (float32 on both sides). Cached rows: what the
    program's forward would cache, [2L, ...] with block i of layer l at 2l
    + i, is what the reference says a cache holds. Routing: the two choose
    the same outputs for every token."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models.generate import latent_forward
    from torchkafka_tpu.models.transformer import Transformer

    cfg = family.program_config(TOY, 16)
    assert (cfg.attn_blocks, cfg.experts_held, cfg.zero_experts) == (2, (4, 2), 4)
    assert (cfg.router_score, cfg.norm_topk, cfg.q_lora_rank) == ("softmax", False, 24)
    params = family.serving_params(TOY, seed)
    model = jax.jit(Transformer(cfg).__call__)
    rng = np.random.default_rng(seed % 1000)
    toks = np.zeros((2, 16), np.int32)
    toks[:, :8] = rng.integers(1, 96, (2, 8))
    for t in range(8, 16):
        logits = np.asarray(model(params, jnp.asarray(toks[:, :t])))[:, -1]
        toks[:, t] = logits.argmax(-1)
    dims = W.Dims.from_conf(TOY)
    gap, top = reference.served_logit_gaps(seed, dims, toks, 7, 8)
    assert float(np.max(np.asarray(gap))) < 1e-4
    assert (np.asarray(top) == toks[:, 8:16]).all()
    want = reference.cached_rows(seed, dims, toks)
    _logits, rows, routing = latent_forward(params, Transformer(cfg), jnp.asarray(toks))
    assert want.shape == rows.shape == (4, 2, 16, 40)
    np.testing.assert_allclose(np.asarray(rows), want, atol=2e-5, rtol=0)
    again, imprint, chosen = reference.share_rows(seed, dims, toks)
    assert (again == want).all() and imprint.shape == (1, 2, 16, 40)
    assert (np.sort(chosen, -1) == np.sort(np.asarray(routing), -1)).all()
    # A held expert's part shows in the next layer's row at the positions
    # of the tokens that chose one, and nowhere else.
    local = ((chosen[0] >= 4) & (chosen[0] < 6)).any(-1)
    assert ((imprint[0] != 0).any(-1) == local).all() and local.any()


def test_all_the_shares_of_a_toy_add_up_to_the_uncut_reference_s_layer():
    """Four chips hold two of eight experts each. The PROGRAM's expert
    layer on each share, summed, with the zero experts' term, the
    attention and the dense FFNs counted once, is the layer of the
    reference that holds all eight."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models.transformer import Transformer

    seed, key = 7, W.seed_key(7)
    arch = family.Arch.from_conf(TOY)
    whole = arch.hold(0, 8)
    x = np.asarray(
        jax.random.normal(jax.random.key(1), (2, 12, 64)), np.float32
    )
    uncut, (m, s_whole, local_whole), idx, _lat = reference.layer_forward(
        key, jnp.asarray(x), 1, whole, jnp.float32
    )
    zero_term = np.asarray(s_whole) - np.asarray(local_whole)
    assert np.abs(zero_term).max() > 1e-3
    total, with_local = None, 0
    for chip in range(4):
        conf = {**TOY, "deployment": {**TOY["deployment"],
                                      "experts_held": [2 * chip, 2]}}
        cfg = family.program_config(conf, 16)
        layer = jax.tree.map(
            lambda t: t[1], family.serving_params(conf, seed)["layers"]
        )
        y, _stats, (_latents, routing) = Transformer(cfg)._layer_capture(
            jnp.asarray(x), layer
        )
        assert (np.sort(np.asarray(routing), -1) == np.sort(np.asarray(idx), -1)).all()
        # This chip's layer output less what every chip computes alike
        # (attention, the dense FFNs, the zero experts' term) is its held
        # experts' part.
        _h, (_m, s_here, local_here), _i, _l = reference.layer_forward(
            key, jnp.asarray(x), 1, arch.hold(2 * chip, 2), jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(_h), atol=2e-5, rtol=0
        )
        part = np.asarray(y) - (np.asarray(_h) - np.asarray(local_here))
        with_local += int(np.abs(np.asarray(local_here)).max() > 1e-4)
        total = np.asarray(y) if total is None else total + part
    assert with_local == 4  # every share had pairs of its own
    np.testing.assert_allclose(total, np.asarray(uncut), atol=5e-5, rtol=0)
    # And the sum of the held parts alone is the uncut branch's.
    np.testing.assert_allclose(
        np.asarray(s_whole),
        zero_term + sum(
            np.asarray(reference.layer_forward(
                key, jnp.asarray(x), 1, arch.hold(2 * c, 2), jnp.float32
            )[1][2]) for c in range(4)
        ), atol=2e-5, rtol=0,
    )


def test_the_reference_s_faults_are_the_named_ones():
    family.program_config(TOY, 16)  # registers the family's sizes
    dims = W.Dims.from_conf(TOY)
    toks = np.random.default_rng(0).integers(1, 96, (2, 12), dtype=np.int32)
    exact = reference.cached_rows(3, dims, toks)
    assert exact.shape == (4, 2, 12, 40)
    assert reference.FAULTS == (
        "no_branch", "no_zero_term", "held_off_by_one", "block1_reads_block0",
        "no_q_scale", "no_kv_scale",
    )
    moved = {
        f: np.abs(reference.cached_rows(3, dims, toks, lowp=f) - exact).max(
            axis=(1, 2, 3)
        ) for f in reference.FAULTS + ("layers", "read")
    }
    # The first block of the first layer caches a function of the token
    # alone: only a fault of ITS projection moves it.
    for fault, by_block in moved.items():
        assert (by_block[0] > 1e-6) == (fault in ("no_kv_scale", "layers")), fault
    # A fault of the branch shows from the NEXT layer's rows on.
    for fault in ("no_branch", "no_zero_term"):
        assert moved[fault][1] == 0 and moved[fault][2] > 1e-4
    assert moved["block1_reads_block0"][1] == 0  # the row WRITTEN is right
    assert moved["block1_reads_block0"][2] > 1e-4
    assert moved["no_q_scale"][1] > 1e-5
    a = family.Arch.from_conf(TOY)
    assert reference._held(a, "held_off_by_one").held_first == 5
    assert reference._held(a.hold(6, 2), "held_off_by_one").held_first == 5


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_toy_root(tmp_path_factory.mktemp("chipbench_longcat"))


def test_every_named_control_fails_a_limit(toy_root, capsys):
    """``loops/serve_share.py`` at toy size: the program (float32 here)
    holds every limit as the cell's file has them; each of ISSUE 31's
    controls, put in the program's place, fails one, and every number
    compared is read from the rows the probe server's own admit and tick
    left in the pool: 8-bit matmul operands, the second block attending
    over the first block's rows and a dropped scale by the rows' median;
    the expert branch left out and the held range one expert off by the
    share of a held expert's part the rows lack; its zero experts' term
    left out by the rows' median. (PERF.md has the chip's readings,
    bfloat16 against the controls.)"""
    rc = control.main(
        ["--workload", CELL, "--seeds", "11", "--seconds", "6",
         "--control", "1"], root=toy_root, rehearsal=True,
    )
    rows = [
        json.loads(l)["reading"] for l in capsys.readouterr().out.splitlines()
        if l.startswith('{"reading"')
    ]
    assert rc == 0 and len(rows) == 1
    r = rows[0]
    assert r["correct"]
    compared = {c["check"]: c for c in r["compared"]}
    row_limit = MIX["check"]["max_latent_row_err"]
    pair_limit = MIX["check"]["max_held_pair_missing"]
    assert compared["latent_row_err.decode"]["limit"] == row_limit
    assert compared["held_pair_missing.prefill"]["limit"] == pair_limit
    gap = r["control"]["served_logit_gap"]
    rows_, pairs = r["control"]["latent_row_err"], r["control"]["held_pair_missing"]
    assert gap["program"] <= gap["limit"] < gap["displaced_stream"]
    for region in ("prefill", "decode"):
        assert rows_["program"][region] < 1e-5
        assert pairs["program"][region] < 1e-3 < pair_limit
        for fault in ("layers", "block1_reads_block0", "no_kv_scale"):
            assert rows_[f"control_{fault}"][region] > row_limit, fault
        # The toy's branch is a small part of its stream (the chip reads
        # 0.19-0.21 for either fault against the limit): visible here,
        # over the limit there.
        for fault in ("no_zero_term", "no_branch"):
            assert rows_[f"control_{fault}"][region] > (
                1000 * rows_["program"][region]
            ), fault
        # The toy keeps the published q rank, 1536, beside a hidden size of
        # 256: the scale dropped here is 0.41 (2.0 at the published widths,
        # where the chip reads 0.25-0.30 against the limit), so the toy holds
        # this control visible and the chip's run holds it over the limit.
        assert rows_["control_no_q_scale"][region] > row_limit / 3
        # The median row cannot see one expert of sixteen; the share of
        # its part that the rows lack is all of it.
        assert rows_["control_held_off_by_one"][region] < row_limit
        # (The zero experts' term goes with the branch, and has a part
        # along the held experts' direction: over 1.)
        assert pairs["control_no_branch"][region] > 0.9
    # (This toy keeps the router's 768 outputs: its hundred tokens need not
    # send one to the expert dropped. The test below routes to 12.)
    assert 0 <= max(pairs["control_held_off_by_one"].values()) < 1.1


@pytest.mark.parametrize("fault,reads", [
    (False, 0.0), ("held_off_by_one", 1.0), ("no_branch", 1.0),
    ("experts", 0.0), ("no_kv_scale", None),
])
def test_what_the_rows_lack_of_a_held_expert_s_part(fault, reads):
    """The reference with a fault, put in the program's place on the path
    of ``loops/serve_share.py``: a held range one expert off (expert 4 of
    [4, 6) dropped) and a branch left out lack a whole part; 8-bit expert
    matmuls keep its direction and lack none."""
    loop = common.load_named("loops", "serve_share", REPO)
    family.program_config(TOY, 16)
    dims = W.Dims.from_conf(TOY)
    toks = np.random.default_rng(1).integers(1, 96, (2, 40), dtype=np.int32)
    want, imprint, chosen = reference.share_rows(3, dims, toks)
    assert ((chosen[0] == 4).any(-1).sum(), (chosen[0] == 5).any(-1).sum()) > (8, 8)
    low = reference.cached_rows(3, dims, toks, lowp=fault)
    got = loop.held_pair_missing(
        low, want, imprint, chosen, (4, 2), slice(0, None)
    )
    if reads is None:  # every row moves: not this number's fault to tell
        assert got >= 0
    else:
        assert got == pytest.approx(reads, abs=0.15)


def test_tick_forms_times_both_forms_of_the_held_experts_sum(toy_root, capsys):
    """``chipbench/tick_forms.py`` takes the configuration as it is and
    puts the program's threshold back where it was. A held share has ONE
    form, the compacted one, whatever the threshold: the tool's two
    readings are two timings of it."""
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
    assert moe._GROUPED_MIN_PAIRS_PER_EXPERT == before


def test_held_pair_missing_is_the_share_of_the_part_the_rows_lack():
    loop = common.load_named("loops", "serve_share", REPO)
    rng = np.random.default_rng(0)
    want = rng.normal(size=(4, 1, 40, 8)).astype(np.float32)  # L 2, S 1, T 40
    imprint = np.zeros((1, 1, 40, 8), np.float32)
    chosen = np.full((2, 1, 40, 3), 9)  # an absent expert everywhere
    # Experts 4 and 5 are held; tokens 0..9 chose 4 and 20..29 chose 5.
    chosen[0, 0, :10, 1], chosen[0, 0, 20:30, 2] = 4, 5
    imprint[0, 0, :10] = rng.normal(size=(10, 8))
    imprint[0, 0, 20:30] = rng.normal(size=(10, 8))
    every = slice(0, None)
    args = (want, imprint, chosen, (4, 2))
    assert loop.held_pair_missing(want, *args, every) == 0.0
    # Error across a row's numbers projects to little; the part left out
    # of expert 5's tokens reads 1, of ONE of its tokens (a near-tie that
    # fell the other way) nothing: the median passes it by.
    noisy = want + 0.01 * rng.normal(size=want.shape).astype(np.float32)
    assert loop.held_pair_missing(noisy, *args, every) < 0.02
    lacking, one = noisy.copy(), noisy.copy()
    lacking[2, 0, 20:30] -= imprint[0, 0, 20:30]
    one[2, 0, 20] -= imprint[0, 0, 20]
    assert loop.held_pair_missing(lacking, *args, every) == pytest.approx(1, abs=0.02)
    assert loop.held_pair_missing(one, *args, every) < 0.02
    # Another expert's output in its place: a direction of its own.
    other = lacking.copy()
    other[2, 0, 20:30] += np.roll(imprint[0, 0, 20:30], 3, axis=0)
    assert loop.held_pair_missing(other, *args, every) > 0.5
    # By region: tokens 20..29 alone are in the second.
    assert loop.held_pair_missing(lacking, *args, slice(0, 20)) < 0.02
    assert loop.held_pair_missing(lacking, *args, slice(20, None)) > 0.9
    # The last layer's experts show in no row.
    chosen[1, 0, :5, 0] = 4
    assert loop.held_pair_missing(want, *args, every) == 0.0
