"""The configuration ``keye-vl-2.0-30b-a3b-8l-ep8`` (one chip's share of
Keye-VL-2.0-30B-A3B's language model: grouped-query attention whose
queries read the 2,048 cached positions a learned indexer chose, 16 of 128
softmax-routed experts, an eighth of the vocabulary) and its cell:
BENCHMARK.json's entries (the files, the lists, order and membership), the
file against the catalog's row and ISSUE 49's arithmetic, the plain
reference against the program on seeded weights at a size where the
selection bites (the forward's logits, the selected sets of layer 0; what
a slot keeps, through ``StreamingGenerator``: rows and index keys, not
tokens), the shares summed to the uncut layer, every control failing the
loop's comparisons, and the new readers on a hand-made trace. The cell end
to end as a rehearsal, probe and all, is a case of
``test_chipbench_rehearsal.py`` (every cell of BENCHMARK.json is); the
compile for a described v5e is ``test_chipbench_keye_compile``."""

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
from chipbench.models import keye_decoder as family  # noqa: E402
from chipbench.reference import keye_decoder as reference  # noqa: E402

CELL, CONFIG = "keye2.long-document-drain", "keye-vl-2.0-30b-a3b-8l-ep8"
CONF = json.loads((REPO / "chipbench/configs" / f"{CONFIG}.json").read_text())
MIX = json.loads((REPO / "chipbench/workloads" / f"{CELL}.json").read_text())
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
BEFORE = "granite4h.multi-session-drain"  # the cell appended before this one
REDUCED = ["num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"]
NEW_METRICS = (
    "dsa.index_us.tput", "dsa.attend_us.tput", "dsa.index_roofline_pct",
    "dsa.attend_roofline_pct", "dsa.selected_share_pct",
    "flash.sel_roofline_pct",
)
LOOP = common.load_named("loops", "serve_sparse", REPO)
STATE = common.load_named("loops", "serve_state", REPO)


def toy_conf(**kw) -> dict:
    """The rehearsal's cut in float32: the toy's widths, 2 layers, a
    top-k of 8, 8 experts of which 2 are held."""
    conf = copy.deepcopy(CONF)
    conf.update(TOY_KEYS)
    conf.update(copy.deepcopy(LOOP.REHEARSAL["config"]))
    conf["deployment"].update(LOOP.REHEARSAL["deployment"])
    conf["deployment"].update(compute_dtype="float32", param_dtype="float32")
    conf.update(kw)
    return conf


# ------------------------------------- BENCHMARK.json's entries


def test_benchmark_json_names_the_configuration_and_the_cell():
    entry = BENCH["configs"][-1]
    assert entry["name"] == CONFIG and entry["reduced"] == REDUCED
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    cell = BENCH["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONFIG, "backlog", 1,
    )
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert "3 local pairs" in cell["why"] and "6x" in cell["why"]
    assert len(BENCH["configs"]) == len(BENCH["workloads"]) == 9
    bench, cell2, conf, mix = runner.load_cell(REPO, CELL)
    assert cell2 == cell and conf == CONF and mix == MIX

    def reports(name):
        return {
            m["name"] for sec in ("end_to_end", "per_layer") for m in bench[sec]
            if name in m.get("workloads", ())
        }

    # serve.tokens_per_s and the nineteen per-layer metrics the cell before
    # it reports outside ``ssd.*``, and the six this PR brings.
    before = {n for n in reports(BEFORE) if not n.startswith("ssd.")}
    assert len(before) == 20 and reports(CELL) == before | set(NEW_METRICS)
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", ())
        if CELL in listed and m["name"] not in NEW_METRICS:
            # Appended behind the cell before it, nothing else moved.
            assert listed[-2:] == [BEFORE, CELL]
    names = [m["name"] for m in bench["per_layer"]]
    assert tuple(names[-6:]) == NEW_METRICS
    for name in NEW_METRICS:
        m = bench["per_layer"][names.index(name)]
        assert m["workloads"] == [CELL] and m["moves"] == "serve.tokens_per_s"
        assert m["layer"] == "kernels"
        assert m["source"] == (
            "program_counter" if name == "dsa.selected_share_pct"
            else "device_trace"
        )
        assert (REPO / "chipbench/layer_metrics" / f"{name}.py").is_file()
    assert all(
        n.endswith("_pct") and m["unit"] == "%" and m["better"] == "higher"
        for n, m in zip(names, bench["per_layer"]) if "roofline" in n
    )


def test_the_traffic_is_the_issue_s_letter_for_letter():
    assert MIX["loop"] == "serve_sparse"
    assert MIX["traffic"] == {
        "kind": "backlog", "records": 600, "deck": 64, "block": 16,
        "prompt_median": 6144, "prompt_sigma": 0.25, "prompt_max": 8192,
        "answer_median": 1536, "answer_sigma": 0.6, "answer_min": 2,
        "answer_max": 4096, "tenants": 8, "tenant_zipf": 1.1,
        "pairing_seed": 49,
    }
    assert MIX["warmup_records"] == 3
    dep = CONF["deployment"]
    assert (dep["slots"], dep["prompt_window"], dep["max_new"]) == (48, 8192, 4096)
    assert (dep["ticks_per_sync"], dep["commit_every"]) == (128, 32)
    assert dep["prompt_partitions"] == 2 and dep["kv_kernel"] is False
    assert dep["kv_dtype"] is None and dep["compute_dtype"] == "bfloat16"
    assert dep["mesh"] is None and dep["delivery"] == "at-least-once"
    assert (dep["chips_sharing_a_layer"], dep["experts_held"]) == (8, [0, 16])
    # The probe's contexts pass the top-k by a wide margin.
    assert dep["prompt_window"] >= 4 * CONF["sa_config"]["topk"]


# ------------------------------------------------- the file's contract


def test_the_file_is_the_catalog_row_but_for_the_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(CONF["changed_from_source"]) == sorted(REDUCED)
    if not CATALOG.is_file():
        pytest.skip("no catalog here")
    row = next(
        r for r in map(json.loads, CATALOG.read_text().splitlines())
        if r["name"] == "Keye-VL-2.0-30B-A3B"
    )
    assert entry["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if CONF.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED)
    assert CONF["published_num_hidden_layers"] == row["config"]["num_hidden_layers"]
    assert CONF["published_num_experts"] == row["config"]["num_experts"]
    assert CONF["published_vocab_size"] == row["config"]["vocab_size"]
    # No width is among them, and the nested groups are whole.
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert CONF["sa_config"] == row["config"]["sa_config"]
    for said in (
        "indexer_input", "indexer_rope", "indexer_score", "chunk_sizes",
        "not_in_config_json", "mrope", "intermediate_size", "slots", "weights",
    ):
        assert said in CONF["assumed"]
    assert {"vision_tower", "exchange"} <= set(CONF["not_built"])


def test_the_cut_by_hand():
    """ISSUE 49's count, reckoned again from the widths."""
    a = family.Arch.from_conf(CONF)
    attention = 2048 * (32 + 4 + 4) * 128 + 32 * 128 * 2048
    indexer = 2048 * 16 * 64 + 2048 * 64 + 2048 * 16
    assert (attention, indexer) == (18_874_368, 2_260_992)
    outside = attention + indexer + 2048 * 128 + 2 * 2048
    assert outside == 21_401_600
    expert = 3 * 2048 * 768
    assert expert == 4_718_592 and a.layer_params == outside + 16 * expert
    assert 8 * a.layer_params == 775_192_576
    assert a.params == 852_985_856 and 2 * a.params == pytest.approx(1.71e9, rel=5e-3)
    assert (a.router, a.first, a.experts, a.top_k) == (128, 0, 16, 8)
    assert (a.vocab * 8, a.layers * 6) == (151_936, 48)
    # Slot memory, bf16: K and V 2,048 B a position a layer, the key 128 B.
    positions = 48 * (8192 + 4096) * 8
    assert 2 * 4 * 128 * 2 == 2048 and 64 * 2 == 128
    assert positions * 2048 == pytest.approx(9.66e9, rel=1e-3)
    assert positions * 128 == pytest.approx(0.60e9, rel=1e-2)
    resident = positions * 2176 + 2 * a.params
    assert resident == pytest.approx(11.97e9, rel=1e-3)
    assert resident > 0.25 * 16 * 2**30


def test_the_program_s_config_is_the_file_s():
    import jax.numpy as jnp

    cfg = family.program_config(CONF, 12288)
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads) == (2048, 8, 32, 4)
    assert (cfg.head_dim, cfg.vocab_size, cfg.rope_theta) == (128, 18992, 1e7)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (16, 64, 2048)
    assert (cfg.n_experts, cfg.expert_top_k, cfg.expert_d_ff) == (128, 8, 768)
    assert cfg.experts_held == (0, 16) and cfg.router_width == 128
    assert cfg.norm_topk and cfg.router_score == "softmax"
    assert cfg.window_pattern == (False,) and cfg.dtype == jnp.bfloat16
    assert cfg.is_sparse and cfg.moe_partial and not cfg.n_shared_experts
    # 3 local pairs a held expert a tick: the loop of one-expert tiles.
    from torchkafka_tpu.ops import moe

    assert moe.expert_form(cfg, 48) == "compacted"


# --------------------------- the reference against the program, at a toy size


@pytest.fixture(scope="module")
def toy():
    import jax

    conf = toy_conf()
    cfg = family.program_config(conf, 32)
    params = family.serving_params(conf, 7)
    arch = family.Arch.from_conf(conf)
    rng = np.random.default_rng(11)
    tokens = rng.integers(1, conf["vocab_size"], (3, 28)).astype(np.int32)
    jax.config.update("jax_default_matmul_precision", "highest")
    yield conf, cfg, params, arch, tokens
    jax.config.update("jax_default_matmul_precision", None)


def test_the_forward_gives_the_reference_s_logits_and_selected_sets(toy):
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models.transformer import (
        Transformer, _rms_norm, index_project,
    )
    from torchkafka_tpu.ops import dsa

    conf, cfg, params, arch, tokens = toy
    assert arch.topk == 8 < tokens.shape[1]  # the selection bites
    got = Transformer(cfg)(params, jnp.asarray(tokens))
    want = reference.logits(7, arch, jnp.float32, tokens)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # Layer 0 reads the embedding itself: the sets are the reference's.
    layer = jax.tree.map(lambda t: t[0], params["layers"])
    x = params["embed"][jnp.asarray(tokens)]
    h = _rms_norm(x, layer["ln1"])
    qi, ki, w = index_project(h, layer, cfg, jnp.arange(28), cfg.rope_theta)
    mask = dsa.select_mask(qi, ki, w, cfg.index_topk)
    weights = reference._weights(W.seed_key(7), arch, 0, jnp.float32)
    for row in range(len(tokens)):
        seen = reference.attention(x[row], weights, arch, False)[3]
        assert ((mask[row] != 0) == seen).all()
        assert (seen.sum(-1) == np.minimum(np.arange(28) + 1, 8)).all()


def test_the_slots_hold_the_reference_s_rows_and_index_keys(toy):
    """Through ``StreamingGenerator``: the compiled admit, then ticks; a
    window of 16 under top-k 8, so both write under a biting selection."""
    import torchkafka_tpu as tk

    conf, _cfg, params, _arch, tokens = toy
    window, new = 16, 9
    cfg = family.program_config(conf, window + new)
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    for row in tokens:
        broker.produce("p", row[:window].tobytes())
    from torchkafka_tpu.serve import StreamingGenerator

    server = StreamingGenerator(
        tk.MemoryConsumer(broker, "p", group_id="g"), params, cfg, slots=4,
        prompt_len=window, max_new=new, ticks_per_sync=4,
    )
    served = np.zeros((len(tokens), window + new), np.int32)
    served[:, :window] = tokens[:, :window]
    for rec, toks in server.run(max_records=len(tokens), idle_timeout_ms=200):
        served[rec.offset, window:] = toks
    assert server.metrics.summary()["kv_backend"]["layout"] == "indexed"
    cut = window + new - 2
    rows = LOOP.unpack(
        np.asarray(server.cache_tensors[0][:, :, :cut]),
        conf["num_key_value_heads"], conf["head_dim"],
    )
    keys = np.asarray(server.cache_tensors[1][:, :, :, :cut]).swapaxes(-1, -2)
    dims = W.Dims.from_conf(conf)
    ref = reference.slot_memory(7, dims, served[:, : window + new - 1])
    at = LOOP.slots_of(rows[:, :3], ref["rows"][:, :, :cut], window)
    assert sorted(at) == [0, 1, 2]
    np.testing.assert_allclose(
        rows[:, at], ref["rows"][:, :, :cut], rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        keys[:, at], ref["index"][:, :, :cut], rtol=1e-4, atol=1e-4
    )
    # The served tokens are the reference's first choices (logits, so a
    # near-tie says by how much).
    gap, _top = reference.served_logit_gaps(7, dims, served, window - 1, new)
    assert float(np.max(gap)) < 1e-4


def test_the_four_shares_sum_to_the_uncut_layer(toy):
    """The reference's own layer: the 4 shares of 2 of 8 experts, each
    with its experts' weights by their published index, summed, are the
    layer that holds all 8; the attention is every share's alike."""
    import jax.numpy as jnp

    _conf, _cfg, _params, arch, tokens = toy
    key = W.seed_key(7)
    x = reference._embed(key, jnp.asarray(tokens), arch, jnp.float32)[0]
    whole = arch.share(0, 8)
    att, *_ = reference.attention(
        x, reference._weights(key, whole, 0, jnp.float32), whole, False
    )
    want, _local, _edges = reference.experts(
        x + att, reference._weights(key, whole, 0, jnp.float32), whole, False
    )
    total = jnp.zeros_like(want)
    for first in range(0, 8, 2):
        part = arch.share(first, 2)
        got, _local, _edges = reference.experts(
            x + att, reference._weights(key, part, 0, jnp.float32), part, False
        )
        total = total + got
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(want).max()) > 0


def test_every_control_fails_the_loop_s_comparisons(toy):
    """Each control in the program's place reads past the rehearsal's
    limits in at least one comparison; the sound reference reads 0."""
    conf, _cfg, _params, _arch, tokens = toy
    dims = W.Dims.from_conf(conf)
    names = (reference.SHIFTS, reference.EDGES, reference.LAST_PARTS)
    sound = reference.slot_memory(7, dims, tokens)
    ref = {**sound, "window": 16, "topk": 8}
    lim = LOOP.limits(LOOP.REHEARSAL["check"], names)

    def fails(memory):
        read = LOOP.readings(STATE, memory, ref, names)
        return [
            f"{check}.{part}" for check, by in read.items()
            for part, value in by.items() if not value <= lim[check][part]
        ]

    assert fails((sound["rows"], sound["index"], sound["hidden"])) == []
    controls = reference.CONTROLS + reference.TEST_CONTROLS
    assert {"attend_all_valid", "topk_half", "held_one_off", "topk_short",
            "w_unsigned", "index_key_before", True} == set(controls)
    for which in controls:
        low = reference.slot_memory(7, dims, tokens, lowp=which)
        failed = fails((low["rows"], low["index"], low["hidden"]))
        assert failed, which
        if which == "held_one_off":  # (the range moves up: the first is lost)
            assert "held_edge_missing.first" in failed
        if which in reference.SHIFTS:
            assert any(f.startswith(f"selection_shift.{which}") for f in failed)
        if which == "index_key_before":
            assert any(f.startswith("index_row_err") for f in failed)


def test_the_pool_s_words_unpack_to_the_rows():
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.ops import dsa

    ks = jax.random.split(jax.random.key(0), 2)
    k = jax.random.normal(ks[0], (3, 5, 4, 128), jnp.bfloat16)
    v = jax.random.normal(ks[1], (3, 5, 4, 128), jnp.bfloat16)
    words = dsa.pack_rows(k, v)
    words = np.asarray(words.reshape(3, 5, *dsa.row_tile(words.shape[-1])))
    assert words.shape == (3, 5, 4, 128) and words.dtype == np.int32
    want = np.concatenate([
        np.asarray(a.astype(jnp.float32)).reshape(3, 5, -1) for a in (k, v)
    ], axis=-1)
    assert (LOOP.unpack(words, 4, 128) == want).all()


# ----------------------------------------------- the new readers


def test_the_readers_on_a_hand_made_trace():
    """Eight calls a tick of each decode kernel in a tick program: the
    time a call, and the shares of the roofline by the bytes of the
    slot-ticks SERVED at the lengths they had; the selected flash forward
    by the causal triangle; a program without the kernels, or a
    configuration without an indexer, gives nothing to read."""
    def reader(name):
        return common.load_named("layer_metrics", name, REPO)

    k = common.load_named("kernels", "dsa", REPO)
    trace = {
        "kernels": {
            "jit_tick_block/tk_dsa_index.3": {
                "program": "jit_tick_block", "total_s": 0.2, "count": 800,
                "text": "",
            },
            "jit_tick_block/tk_dsa_attend.5": {
                "program": "jit_tick_block", "total_s": 1.6, "count": 800,
                "text": "",
            },
            "jit_admit/tk_flash_fwd_sel.1": {
                "program": "jit_admit", "total_s": 0.08, "count": 8,
                "text": "%x = bf16[32,8192,128] custom-call(bf16[32,8192,128]{2,1,0} %q)",
            },
        },
        "host_t0": 10.0, "host_t1": 20.0,
    }
    requests = [
        # Two syncs inside the trace: the first brings the admission's
        # token, which no tick served.
        {"syncs": [(11.0, 129), (15.0, 128), (25.0, 128)]},
        {"syncs": [(5.0, 129), (12.0, 100)]},
    ]
    counters = [
        {"kv_pool": {"sparse_positions_selected": 10, "sparse_positions_valid": 50}},
        {"kv_pool": {"sparse_positions_selected": 2058, "sparse_positions_valid": 10290}},
    ]
    run = {"trace": trace, "conf": CONF, "requests": requests, "root": REPO,
           "prompt_window": 8192, "counters": counters,
           "peaks": {"hbm_bytes_s": 819e9, "bf16_flops": 197e12}}
    assert reader("dsa.index_us.tput").read(run) == pytest.approx(250.0)
    assert reader("dsa.attend_us.tput").read(run) == pytest.approx(2000.0)
    held = sum(8192 + j for j in range(1, 257)) + sum(
        8192 + j for j in range(129, 229)
    )
    ticks = 256 + 100
    want = 100 * k.index_bytes(CONF, held) / (0.2 * 819e9)
    assert k.index_bytes(CONF, held) == 8 * held * 128
    assert reader("dsa.index_roofline_pct").read(run) == pytest.approx(want)
    want = 100 * k.attend_bytes(CONF, ticks * 2048) / (1.6 * 819e9)
    assert k.attend_bytes(CONF, 1) == 8 * 2048
    assert reader("dsa.attend_roofline_pct").read(run) == pytest.approx(want)
    assert 0 < want < 100
    assert reader("dsa.selected_share_pct").read(run) == pytest.approx(20.0)
    flops = 8 * 32 * 4.0 * (8192 * 8193 // 2) * 128
    assert reader("flash.sel_roofline_pct").read(run) == pytest.approx(
        100 * flops / (0.08 * 197e12)
    )
    bare = {**run, "trace": {**trace, "kernels": {}}, "counters": [{}, {}]}
    granite = json.loads(
        (REPO / "chipbench/configs/granite-4.0-h-small-10l-ep4.json").read_text()
    )
    for name in NEW_METRICS:
        assert reader(name).read(bare) is None
        if name != "dsa.selected_share_pct":
            assert reader(name).read({**run, "conf": granite}) is None
            assert reader(name).read({**run, "trace": None}) is None
