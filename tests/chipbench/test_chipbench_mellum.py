"""The configuration ``mellum2-12b-a2.5b-8l`` (two periods of
Mellum2-12B-A2.5B: three sliding-window layers to one full YaRN layer, 64
softmax-routed experts beside grouped-query attention) and its cell:
BENCHMARK.json's two entries, the file against the catalog's row and ISSUE
34's arithmetic, YaRN by hand, the family's draw, the plain reference
against the program at a toy size that keeps every mechanism (the forward;
prefill then decode through the two pools past two wraps of the ring),
every named control seen to fail, the windowed flash forward against the
masked dense form, a reused slot's stale ring, the refusals, the defaults,
the counters, the readers on a hand-made trace and through a toy benchmark
once their waiting entries are appended. The cell end to end as a
rehearsal is a case of ``test_chipbench_rehearsal.py`` (every cell of
BENCHMARK.json is)."""

from __future__ import annotations

import copy
import json
import math
import re
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from toy import make_toy_root  # noqa: E402

from chipbench import common  # noqa: E402
from chipbench import run as runner  # noqa: E402
from chipbench import weights as W  # noqa: E402
from chipbench.models import mellum_decoder as family  # noqa: E402
from chipbench.reference import mellum_decoder as reference  # noqa: E402

CELL, CONFIG = "mellum2.repo-context-drain", "mellum2-12b-a2.5b-8l"
CONF = json.loads((REPO / "chipbench/configs" / f"{CONFIG}.json").read_text())
MIX = json.loads((REPO / "chipbench/workloads" / f"{CELL}.json").read_text())
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PARENT_CONFIGS = (
    "mistral-7b-v0.3-w8", "internlm2-1.8b-1chip", "internlm2-1.8b",
    "kanana-2-30b-a3b-7l", "longcat-flash-omni-4l-ep32",
)
PARENT_CELLS = (
    "mistral7b.backlog-drain", "internlm2-1.8b.pretrain-4k-1chip",
    "internlm2-1.8b.pretrain-4k-2x2", "kanana2.longform-drain",
    "longcat.reasoning-drain",
)
SERVING = (
    "serve.tokens_per_s", "sched.occupancy_pct", "tick_ms.tput",
    "prefill_ms.tput", "commit_ms.serve", "committed_tokens_per_s.serve",
    "sched.admit_fill_pct", "sched.slot_tick_use_pct",
    "sched.host_ms_per_sync", "source.poll_ms.serve", "commit.flush_ms.serve",
)
NEW_READERS = (
    "kv.window_read_us.tput", "kv.full_read_us.tput",
    "kv.window_read_roofline_pct", "kv.full_read_roofline_pct",
    "flash.window_roofline_pct", "moe.stack_experts_ms.tput",
    "moe.stack_stream_roofline_pct",
)
# A cut that keeps every mechanism: two periods of (slide, slide, slide,
# full), a window the answers wrap more than twice, heads wider than
# hidden / heads, 8 experts top-2, YaRN on the full kind.
TOY = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 48, "num_hidden_layers": 8,
    "sliding_window": 8, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 24, "vocab_size": 64,
}


def toy_conf(dtype: str = "float32") -> dict:
    conf = copy.deepcopy(CONF)
    conf.update(TOY)
    conf["deployment"].update(compute_dtype=dtype, param_dtype=dtype)
    return conf


# ------------------------------------- step 0: BENCHMARK.json's entries


def test_benchmark_json_names_the_configuration_and_the_cell():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "backlog", 1,
    )
    # After every entry that was there: one put first reads as a change.
    # (Not "last": the next PR's entries come after these.)
    assert BENCH["configs"].index(entry) == BENCH["workloads"].index(cell) == 5
    bench, cell2, conf, mix = runner.load_cell(REPO, CELL)
    assert cell2 == cell and conf == CONF and mix == MIX
    reports = [
        m["name"] for sec in ("end_to_end", "per_layer") for m in bench[sec]
        if CELL in m.get("workloads", ())
    ]
    assert sorted(reports) == sorted(SERVING)
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", ())
        if "longcat.reasoning-drain" in cells:
            assert cells.index(CELL) == cells.index("longcat.reasoning-drain") + 1


def test_the_parent_s_five_configurations_and_cells_are_still_there():
    assert tuple(c["name"] for c in BENCH["configs"][:5]) == PARENT_CONFIGS
    assert tuple(w["name"] for w in BENCH["workloads"][:5]) == PARENT_CELLS
    for name in PARENT_CELLS:
        runner.load_cell(REPO, name)


# ------------------------------------------------- the file's contract


def test_the_file_is_the_catalog_row_but_for_the_depth():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(CONF["changed_from_source"]) == ["num_hidden_layers"]
    assert (CONF["num_hidden_layers"], CONF["published_num_hidden_layers"]) == (
        8, 28,
    )
    if not CATALOG.is_file():
        pytest.skip("no catalog here")
    row = next(
        r for r in map(json.loads, CATALOG.read_text().splitlines())
        if r["name"] == "Mellum2-12B-A2.5B-Instruct"
    )
    assert entry["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if CONF.get(k, "absent") != v]
    # The two lists of layer types are the source's first eight entries.
    assert sorted(differs) == [
        "layer_types", "mlp_layer_types", "num_hidden_layers",
    ]
    for key in ("layer_types", "mlp_layer_types"):
        assert CONF[key] == row["config"][key][:8]
    assert CONF["rope_parameters"] == row["config"]["rope_parameters"]


def test_published_widths_none_cut_and_the_harness_name_is_an_alias():
    a = family.Arch.from_conf(CONF)
    assert (a.hidden, a.heads, a.kv_heads, a.head_dim) == (2304, 32, 4, 128)
    assert a.head_dim != a.hidden // a.heads == 72
    assert (a.experts, a.top_k, a.expert_ffn, a.vocab) == (64, 8, 896, 98304)
    assert (a.window, a.pattern) == (1024, (True, True, True, False))
    assert a.rope_window == family.Rope(theta=500000.0)
    assert a.rope_full == family.Rope(
        theta=500000.0, factor=16.0, original=8192, beta_fast=32.0,
        beta_slow=1.0, attention_factor=1.2772588722239782,
    )
    assert CONF["rope_theta"] == 500000 and "rope_theta" in CONF["harness_names"]
    dims = W.Dims.from_conf(CONF)
    assert (dims.head_dim, dims.layers, dims.rope_theta) == (128, 8, 5e5)
    dep = CONF["deployment"]
    assert (dep["slots"], dep["prompt_window"], dep["max_new"]) == (128, 4096, 1024)
    assert (dep["ticks_per_sync"], dep["commit_every"], dep["kv_dtype"],
            dep["kv_kernel"], dep["mesh"]) == (128, 32, None, False, None)
    for key in ("not_in_config_json", "not_built", "layer_types", "broker",
                "slots", "ticks_per_sync", "weights"):
        assert key in CONF["assumed"]


def test_the_cell_and_its_traffic_are_the_issue_s():
    t = MIX["traffic"]
    assert (t["kind"], t["records"], t["deck"], t["block"]) == (
        "backlog", 1200, 64, 16,
    )
    assert (t["prompt_median"], t["prompt_sigma"], t["prompt_max"]) == (
        2048, 0.5, 4096,
    )
    assert (t["answer_median"], t["answer_sigma"], t["answer_min"],
            t["answer_max"]) == (384, 0.8, 2, 1024)
    assert (t["pairing_seed"], t["tenants"], t["tenant_zipf"]) == (34, 8, 1.1)
    assert (MIX["warmup_records"], MIX["trace"]["seconds"],
            MIX["check"]["sample"]) == (3, 14.0, 4)
    # loops/serve.py whole, behind the rehearsal's cuts and the controls.
    assert MIX["loop"] == CONF["deployment"]["loop"] == "serve_kinds"


def test_the_cut_by_hand():
    a = family.Arch.from_conf(CONF)
    attention = 2304 * 4096 * 2 + 2304 * 512 * 2
    one_expert = 3 * 2304 * 896
    assert (attention, one_expert) == (21_233_664, 6_193_152)
    layer = attention + 4_608 + 147_456 + 64 * one_expert
    assert layer == a.layer_params == 417_747_456
    assert a.params == 8 * layer + 2 * 98304 * 2304 + 2304 == 3_794_966_784
    full = a.pool_bytes(False, 128, 4096 + 1024)
    ring = a.pool_bytes(True, 128, 4096 + 1024)
    assert full == 2 * 2 * 128 * 5120 * 4 * 128 * 2 == 2_684_354_560
    assert ring == 2 * 6 * 128 * 1024 * 4 * 128 * 2 == 1_610_612_736
    assert round(full / 1e9, 2) == 2.68 and round(ring / 1e9, 2) == 1.61
    # Weights and both pools: seven tenths of the chip; a pool of whole
    # contexts for all eight layers would not fit beside the weights.
    assert 0.69 < (2 * a.params + full + ring) / 17.18e9 < 0.70
    assert 2 * a.params + 4 * full > 16e9
    whole = dataclasses_replace_layers(a, 28)
    assert round(whole.params / 1e9, 2) == 12.15


def dataclasses_replace_layers(a, layers):
    import dataclasses

    return dataclasses.replace(a, layers=layers)


# ------------------------------------------------------- YaRN by hand


def test_yarn_by_hand_at_the_published_parameters():
    """low, high, the ramp's inverse frequencies and the attention factor
    worked from the formula, against the reference's own and the
    program's table."""
    from torchkafka_tpu.models.transformer import RopeKind

    a = family.Arch.from_conf(CONF)
    r = a.rope_full
    ln = math.log(500000.0)
    low = 128 * math.log(8192 / (32 * 2 * math.pi)) / (2 * ln)
    high = 128 * math.log(8192 / (1 * 2 * math.pi)) / (2 * ln)
    assert (math.floor(low), math.ceil(high)) == (18, 35)
    assert reference.yarn_range(r, 128) == (18, 35)
    assert r.attention_factor == pytest.approx(0.1 * math.log(16) + 1, abs=1e-12)
    plain = [500000.0 ** (-2 * i / 128) for i in range(64)]
    want = []
    for i in range(64):
        ramp = min(max((i - 18) / (35 - 18), 0.0), 1.0)
        want.append(plain[i] * (1 - ramp) + plain[i] / 16 * ramp)
    # Untouched below the ramp, a sixteenth above it, mixed inside.
    assert want[18] == plain[18] and want[35] == plain[35] / 16
    assert want[26] == pytest.approx(plain[26] * (1 - 8 / 17 * 15 / 16))
    got = np.asarray(reference.inv_freq(r, 128))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    kind = RopeKind(
        theta=r.theta, factor=r.factor, original_len=r.original,
        beta_fast=r.beta_fast, beta_slow=r.beta_slow,
        attention_factor=r.attention_factor,
    )
    assert kind.correction_range(128) == (18, 35)
    np.testing.assert_allclose(kind.inv_freq(128), want, rtol=1e-6)
    # The sliding kind is the plain table.
    np.testing.assert_allclose(
        np.asarray(reference.inv_freq(a.rope_window, 128)), plain, rtol=2e-6
    )


# ------------------------------------------------ the family's draw


@pytest.mark.parametrize("seed", [0, 2**31 + 3])
def test_a_layer_drawn_alone_is_the_stacked_draw_s_layer(seed):
    import jax.numpy as jnp

    a = family.Arch.from_conf(toy_conf())
    key = W.seed_key(seed)
    tree = family.serving_tree(key, a, jnp.bfloat16)
    for layer in (0, 5):
        alone = family.layer_weights(key, a, layer, jnp.bfloat16)
        for name in family.LAYER_TENSORS:
            np.testing.assert_array_equal(
                np.asarray(tree["layers"][name][layer]), np.asarray(alone[name])
            )
    # An expert's matrices are keyed by its index: the same whatever the
    # count drawn beside it.
    import dataclasses

    fewer = dataclasses.replace(a, experts=3)
    np.testing.assert_array_equal(
        np.asarray(family.draw(key, fewer, "w_up", 2, jnp.bfloat16)),
        np.asarray(tree["layers"]["w_up"][2, :3]),
    )
    # Unit embedding rows; the residual writes scaled by 1/sqrt(2 * 28).
    assert float(jnp.std(tree["embed"].astype(jnp.float32))) == pytest.approx(
        1.0, rel=0.05
    )
    assert float(jnp.std(tree["layers"]["wo"].astype(jnp.float32))) == pytest.approx(
        1 / math.sqrt(4 * 16) / math.sqrt(56), rel=0.05
    )


# ------------------------------------- the reference against the program


def _program(conf, seed, max_seq_len=64):
    cfg = family.program_config(conf, max_seq_len, attn_impl="dense")
    return cfg, family.serving_params(conf, seed)


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_the_reference_agrees_with_the_program_s_forward(seed):
    """Float32 on both sides: what is left is the order of summation
    (1e-4 of logits of order 1)."""
    import jax.numpy as jnp

    from torchkafka_tpu.models.transformer import Transformer

    conf = toy_conf()
    cfg, params = _program(conf, seed)
    assert cfg.head_dim == 16 != cfg.d_model // cfg.n_heads
    assert cfg.window_pattern == (True, True, True, False) and cfg.routed_moe
    a = family.Arch.from_conf(conf)
    tokens = np.random.default_rng(seed % 97).integers(1, 64, (2, 40))
    got = Transformer(cfg)(params, jnp.asarray(tokens, jnp.int32))
    want = reference.logits(seed, a, jnp.float32, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def _serve(conf, seed, prompts, *, slots, window, new, ticks=4, budgets=None):
    """Serve ``prompts`` in order through ``StreamingGenerator``: (tokens
    by offset, the server's summary)."""
    import torchkafka_tpu as tk
    from torchkafka_tpu.serve import StreamingGenerator

    cfg, params = _program(conf, seed, window + new)
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    for i, p in enumerate(prompts):
        headers = ()
        if budgets is not None:
            headers = (("max_new", str(budgets[i]).encode()),)
        broker.produce("p", np.asarray(p, np.int32).tobytes(), headers=headers)
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    loop = common.load_named("loops", "serve", REPO)
    server = StreamingGenerator(
        consumer, params, cfg, slots=slots, prompt_len=window, max_new=new,
        ticks_per_sync=ticks, max_new_of=loop.budget_of,
    )
    out = {}
    for rec, toks in server.run(max_records=len(prompts), idle_timeout_ms=200):
        out[rec.offset] = np.asarray(toks, np.int32)
    summary = server.metrics.summary()
    pools = [np.asarray(c) for c in server.cache_tensors]
    server.close()
    return out, summary, pools


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_prefill_then_decode_through_both_pools_past_two_wraps(seed):
    """A prompt window of 16 and 30 new tokens over a window of 8: the
    ring wraps more than three times. The served tokens, teacher-forced
    through the reference's full forward (no cache, no ring), are its own
    first choices to within float32's summation order: a logit gap of
    1e-3 at most, where a ring read one row short, a dropped window or a
    missing YaRN read tenths."""
    import jax.numpy as jnp

    conf = toy_conf()
    window, new = 16, 30
    rng = np.random.default_rng(seed % 89)
    prompts = [rng.integers(1, 64, n) for n in (16, 9, 13, 16, 5)]
    out, summary, pools = _serve(
        conf, seed, prompts, slots=2, window=window, new=new
    )
    assert summary["kv_backend"]["layout"] == "by_kind"
    assert [p.shape for p in pools] == [
        (2, 2, 46, 32), (2, 2, 46, 32), (6, 2, 8, 32), (6, 2, 8, 32),
    ]
    toks = np.zeros((len(prompts), window + new), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : len(p)] = p
        toks[i, window:] = out[i]
    cfg = family.program_config(conf, window + new)  # registers the sizes
    dims = W.Dims.from_conf(conf)
    gap, top = reference.served_logit_gaps(seed, dims, toks, window - 1, new)
    assert float(np.max(gap)) < 1e-3
    assert float(np.mean(np.asarray(top) == toks[:, window:])) > 0.98
    # The counters: the pool by kind and the live expert layer.
    pool = summary["kv_pool"]
    assert (pool["window"], pool["window_layers"], pool["full_layers"]) == (8, 6, 2)
    assert pool["bytes_window"] == sum(p.nbytes for p in pools[2:])
    assert pool["bytes_full"] == sum(p.nbytes for p in pools[:2])
    served = summary["scheduler"]["slot_ticks_served"]
    # Every served tick is past the window (16 > 8): a whole ring a layer.
    assert pool["window_positions_valid"] == 6 * 8 * served
    assert pool["full_positions_valid"] == 2 * sum(
        sum(range(window + 1, window + new)) for _ in prompts
    )
    assert pool["window_positions_read"] >= pool["window_positions_valid"]
    assert pool["full_positions_read"] >= pool["full_positions_valid"]
    e = summary["expert_layer"]
    assert e["moe_assignments"] == sum(e["moe_expert_load"]) > 0
    assert e["moe_experts_touched"] > 0 and e["experts_held"] == [0, 8]
    assert e["moe_assignments"] >= 8 * cfg.expert_top_k * served


@pytest.mark.slow  # 27 s on the CPU: twelve reference passes, each compiled
def test_every_control_reads_beyond_the_sound_gap():
    """The comparison that decides ``correct`` at the toy's size: each
    control's first choices, probed by the sound reference, lie ten times
    and more beyond what a sound run reads there (1e-3 at most)."""
    conf = toy_conf()
    seed, window, new = 3, 16, 30
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 64, 16) for _ in range(4)]
    out, _s, _p = _serve(conf, seed, prompts, slots=2, window=window, new=new)
    toks = np.zeros((4, window + new), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :window], toks[i, window:] = p, out[i]
    family.program_config(conf, window + new)
    dims = W.Dims.from_conf(conf)
    for which in reference.CONTROLS:
        _g, top = reference.served_logit_gaps(
            seed, dims, toks, window - 1, new, lowp=which
        )
        gap, _t = reference.served_logit_gaps(
            seed, dims, toks, window - 1, new, probe=np.asarray(top)
        )
        assert float(np.max(gap)) > 0.01, which


def test_a_reused_slot_serves_the_same_bytes_after_a_longer_request():
    """Stale-ring poison: one slot serves a long request and then a short
    one; the short one's tokens are those of a fresh server."""
    conf = toy_conf()
    rng = np.random.default_rng(5)
    long_p, short_p = rng.integers(1, 64, 16), rng.integers(1, 64, 4)
    both, _s, _p = _serve(
        conf, 9, [long_p, short_p], slots=1, window=16, new=30, budgets=[30, 12]
    )
    alone, _s, _p = _serve(
        conf, 9, [short_p], slots=1, window=16, new=30, budgets=[12]
    )
    assert len(both[0]) == 30 and len(both[1]) == 12
    np.testing.assert_array_equal(both[1], alone[0])


# ------------------------------------------- the windowed flash forward


@pytest.mark.parametrize("s,window,block_q,block_k", [
    (96, 40, 16, 16),  # S not a multiple of W, W not of the block
    (80, 24, 16, 8), (64, 8, 32, 16), (48, 17, 8, 16),
    (64, 100, 16, 16),  # a window wider than the sequence
])
def test_the_windowed_flash_forward_is_the_masked_dense_form(
    s, window, block_q, block_k
):
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.ops import flash
    from torchkafka_tpu.ops.attention import mha

    key = jax.random.key(s + window)
    q = jax.random.normal(jax.random.fold_in(key, 1), (2, s, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 2), (2, s, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 3), (2, s, 2, 16))
    got = flash.flash_forward(
        q, k, v, scale=0.25, window=window, block_q=block_q, block_k=block_k,
        interpret=True,
    )
    kk, vv = flash._repeat_kv(q, k, v)
    want = mha(q, kk, vv, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # No key block wholly outside a q-block's window is visited.
    visited = flash._window_blocks(s, block_q, block_k, window)
    assert visited <= -(-(window + block_q - 1) // block_k) + 1
    assert visited <= -(-s // block_k)


def test_the_windowed_call_has_a_name_of_its_own():
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.ops import flash

    q = jnp.zeros((1, 256, 2, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(
        lambda q: flash.flash_forward(q, q, q, scale=1.0, window=128)
    )(q))
    assert "tk_flash_fwd_win" in text
    causal = str(jax.make_jaxpr(
        lambda q: flash.flash_forward(q, q, q, scale=1.0)
    )(q))
    assert "tk_flash_fwd" in causal and "tk_flash_fwd_win" not in causal


# ------------------------------------------------------- the refusals


def _toy_cfg(**extra):
    return family.program_config(toy_conf(), 64, **extra)


def test_what_is_not_built_refuses_with_its_reason():
    import jax
    import optax

    import torchkafka_tpu as tk
    from torchkafka_tpu.kvcache import PagedKVConfig, resolve_kv_backend
    from torchkafka_tpu.models.generate import generate
    from torchkafka_tpu.models.quant import quantize_params
    from torchkafka_tpu.models.transformer import make_train_step
    from torchkafka_tpu.serve_spec import SpecStreamingGenerator

    cfg = _toy_cfg()
    ask = dict(max_len=64, slots=4, backend="cpu")
    ok = resolve_kv_backend(cfg, **ask)
    assert (ok.layout, ok.int8, ok.kernel) == ("by_kind", False, False)
    mesh = jax.make_mesh((2,), ("tp",))
    for kwargs, word in (
        ({"kv_dtype": "int8"}, "lower bound"),
        ({"kv_kernel": True}, "never falls back"),
        ({"kv_pages": PagedKVConfig(block_size=8, num_blocks=64)}, "ring"),
        ({"mesh": mesh}, "one device"),
    ):
        with pytest.raises(ValueError, match="slot pool by layer kind") as e:
            resolve_kv_backend(cfg, **ask, **kwargs)
        assert word in str(e.value)
    params = family.serving_params(toy_conf(), 0)
    for refused in (
        lambda: make_train_step(cfg, jax.make_mesh((1,), ("data",)), optax.sgd(0.1)),
        lambda: generate(params, cfg, np.zeros((1, 4), np.int32), 4),
        lambda: quantize_params(params, cfg),
        lambda: SpecStreamingGenerator(None, params, cfg, draft_layers=1),
    ):
        with pytest.raises(ValueError, match="kinds of layer") as e:
            refused()
        assert "take no window" in str(e.value)
    # Warm resume falls back to cold replay: no resume program is built.
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    server = tk.serve.StreamingGenerator(
        tk.MemoryConsumer(broker, "p", group_id="g"), params, cfg, slots=2,
        prompt_len=8, max_new=8,
    )
    assert server._resume_supported() is False and server._resume_exec is None
    server.close()


def test_the_config_refuses_what_the_pattern_cannot_mean():
    from torchkafka_tpu.models.transformer import RopeKind, TransformerConfig

    base = dict(n_layers=8, n_heads=4, n_kv_heads=2, d_model=32)
    for kwargs, word in (
        (dict(window_pattern=(True, False, False)), "whole periods"),
        (dict(window_pattern=(True, False)), "sliding_window"),
        (dict(sliding_window=8), "window_pattern"),
        (dict(rope_full=RopeKind(1e4, factor=4.0, original_len=64)), "window_pattern"),
        (dict(window_pattern=(True,), sliding_window=4, attn_impl="ring"),
         "sequence-parallel"),
        (dict(stated_head_dim=-1), "stated_head_dim"),
    ):
        with pytest.raises(ValueError, match=word):
            TransformerConfig(**base, **kwargs)
    # The routed layer beside grouped-query attention is the renormalised
    # softmax top-k alone; every other refusal keeps its message.
    for kwargs in (
        dict(router_score="sigmoid"), dict(n_shared_experts=1),
        dict(first_dense_layers=1), dict(routed_scaling=2.0),
    ):
        with pytest.raises(ValueError, match="built together only"):
            TransformerConfig(**base, n_experts=4, expert_d_ff=8, **kwargs)
    with pytest.raises(ValueError, match="routed expert layer"):
        TransformerConfig(**base, expert_d_ff=8)


def test_a_config_without_the_new_fields_builds_what_it_built():
    """The defaults: the width is ``d_model // n_heads``, no pattern, the
    softmax family stays ``_moe_mlp``'s, and stating the defaults lowers
    to the same program as leaving them out."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models.transformer import (
        Transformer, TransformerConfig, init_params,
    )

    small = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                 n_kv_heads=2, d_ff=48, max_seq_len=32, attn_impl="dense")
    old = TransformerConfig(**small)
    assert (old.head_dim, old.window_pattern, old.sliding_window) == (8, (), 0)
    assert old.rope_window is old.rope_full is None
    moe = TransformerConfig(**small, n_experts=4)
    assert moe.is_moe and not moe.routed_moe
    stated = TransformerConfig(
        **small, stated_head_dim=0, sliding_window=0, window_pattern=(),
        rope_window=None, rope_full=None,
    )
    assert stated == old
    tokens = jnp.zeros((1, 16), jnp.int32)
    texts = []
    for cfg in (old, stated):
        params = jax.eval_shape(lambda c=cfg: init_params(jax.random.key(0), c))
        texts.append(jax.jit(Transformer(cfg).__call__).lower(params, tokens).as_text())
    assert texts[0] == texts[1]


# -------------------------------------------------------- the readers


def _run(counters, requests=()):
    return {
        "trace": {
            "programs": {"jit_tick_block": {"count": 1.0, "total_s": 1.0}},
            "kernels": {"jit_admit/tk_flash_fwd_win.1": {
                "program": "jit_admit", "count": 12.0, "total_s": 6e-3,
                "text": "%tk_flash_fwd_win.1 = (bf16[32,4096,128]{2,1,0}, "
                        "f32[32,4096,1]{2,1,0}) custom-call(bf16[32,4096,128]"
                        "{2,1,0} %q, bf16[4,4096,128]{2,1,0} %k)",
            }},
            "host_t0": 0.0, "host_t1": 10.0,
        },
        "conf": CONF, "root": REPO, "slots": 128, "counters": counters,
        "peaks": common.load_peaks("TPU v5 lite"), "prompt_window": 4096,
        "requests": list(requests), "cell": {"name": CELL}, "seed": 1,
    }


def test_kernel_counts_by_hand():
    kinds = common.load_named("kernels", "kv_kinds", REPO)
    assert kinds.row_bytes(CONF) == 2 * 4 * 128 * 2 == 2048
    assert kinds.layers(CONF, True) == 6 and kinds.layers(CONF, False) == 2
    assert kinds.pool_pattern(CONF, True) == r"bf16\[6,128,1024,512\]"
    assert kinds.pool_pattern(CONF, False) == r"bf16\[2,128,5120,512\]"
    assert kinds.read_bytes(CONF, 1000) == 2_048_000
    win = common.load_named("kernels", "flash_window", REPO)
    pairs = 1024 * 1025 // 2 + (4096 - 1024) * 1024
    assert win.window_pairs(4096, 1024) == pairs == 3_670_528
    assert win.window_pairs(8, 100) == 36  # a window wider than the row
    assert win.call_flops(32, 4096, 128, 1024) == 32 * 4 * pairs * 128
    assert win.operands("custom-call(bf16[32,4096,128]{2,1,0} %q") == (
        32, 4096, 128,
    )
    stack = common.load_named("kernels", "moe_stack", REPO)
    assert stack.expert_bytes(CONF) == 3 * 2304 * 896 * 2 == 12_386_304
    assert stack.stream_bytes(CONF, 8 * 64) == 6_341_787_648  # a tick's 6.34 GB
    for shape, reads in (
        ("bf16[512,2304,896]", True), ("bf16[8,64,896,2304]", True),
        ("bf16[64,2304,896]", True), ("bf16[32,2304]", False),
        ("bf16[2304,98304]", False),
    ):
        assert bool(re.search(stack.operand_pattern(CONF), shape)) == reads


def test_the_new_readers_on_a_hand_made_trace(monkeypatch):
    """Device times told by operand shapes, counters by their sections;
    nothing to read gives None and does not raise."""
    from chipbench.layer_metrics import _latent_ops as L

    ring, slab = "bf16[6,128,1024,512]{3,2,1,0}", "bf16[2,128,5120,512]{3,2,1,0}"
    ops = [
        (f"%fusion.1 = f32[128,32,1024]{{2,1,0}} fusion({ring} %k, s32[] %l, bf16[128,32,512]{{2,1,0}} %q), kind=kOutput", 3e-3),
        (f"%fusion.2 = f32[128,32,512]{{2,1,0}} fusion({ring} %v, s32[] %l, f32[128,32,1024]{{2,1,0}} %p), kind=kOutput", 3e-3),
        # The scatter (its result is the pool) is not the read.
        (f"%fusion.3 = {ring} fusion({ring} %k, bf16[128,512]{{1,0}} %row), kind=kLoop", 9e-3),
        (f"%fusion.4 = f32[128,32,5120]{{2,1,0}} fusion({slab} %k, s32[] %l, bf16[128,32,512]{{2,1,0}} %q), kind=kOutput", 4e-3),
        # A tile of the compacted form: one expert out of every layer's.
        ("%fusion.5 = bf16[32,896]{1,0} fusion(bf16[32,2304]{1,0} %rows, bf16[512,2304,896]{2,1,0} %w, s32[] %e), kind=kOutput", 5e-3),
        ("%fusion.6 = bf16[32,2304]{1,0} fusion(bf16[512,896,2304]{2,1,0} %w, s32[] %e, bf16[32,896]{1,0} %g), kind=kOutput", 3e-3),
        # The weighted scatter back reads no expert.
        ("%fusion.7 = f32[128,2304]{1,0} fusion(f32[128,2304]{1,0} %acc, bf16[32,2304]{1,0} %y), kind=kLoop", 7e-3),
    ]
    counters = [
        {"scheduler": {"slot_ticks_run": 0},
         "kv_pool": {"window_positions_valid": 0, "full_positions_valid": 0},
         "expert_layer": {"moe_experts_touched": 0}},
        {"scheduler": {"slot_ticks_run": 128 * 256},
         "kv_pool": {"window_positions_valid": 256 * 6 * 100 * 1024,
                     "full_positions_valid": 256 * 2 * 100 * 4600},
         "expert_layer": {"moe_experts_touched": 256 * 8 * 60}},
    ]
    run = _run(counters)
    monkeypatch.setattr(
        L, "tick_ops", lambda run: ops if run.get("trace") else None
    )

    def read(name, run=run):
        return common.load_named("layer_metrics", name, REPO).read(run)

    # 128 ticks traced: 6 ms of ring reads over 6 layers, 4 of slab over 2.
    assert read("kv.window_read_us.tput") == pytest.approx(1e6 * 6e-3 / 128 / 6)
    assert read("kv.full_read_us.tput") == pytest.approx(1e6 * 4e-3 / 128 / 2)
    assert read("kv.window_read_roofline_pct") == pytest.approx(
        100 * 6 * 100 * 1024 * 2048 / (6e-3 / 128 * 819e9)
    )
    assert read("kv.full_read_roofline_pct") == pytest.approx(
        100 * 2 * 100 * 4600 * 2048 / (4e-3 / 128 * 819e9)
    )
    assert read("flash.window_roofline_pct") == pytest.approx(
        100 * 12 * 32 * 4 * 3_670_528 * 128 / (6e-3 * 197e12)
    )
    # The experts out of stacks of every layer's: the products alone.
    assert read("moe.stack_experts_ms.tput") == pytest.approx(1e3 * 8e-3 / 128)
    assert read("moe.stack_stream_roofline_pct") == pytest.approx(
        100 * 8 * 60 * 3 * 2304 * 896 * 2 / (8e-3 / 128 * 819e9)
    )
    bare = {**run, "trace": None, "counters": [{}, {}]}
    for name in NEW_READERS:
        assert read(name, bare) is None
    # A program that counts no pool by kind, a configuration without one.
    old = {**run, "counters": [{"scheduler": {"slot_ticks_run": 0}},
                               {"scheduler": {"slot_ticks_run": 64}}]}
    kanana = json.loads(
        (REPO / "chipbench/configs/kanana-2-30b-a3b-7l.json").read_text()
    )
    for name in NEW_READERS:
        if "roofline" in name and "flash" not in name:
            assert read(name, old) is None
        assert read(name, {**run, "conf": kanana}) is None


def test_the_waiting_entries_run_once_they_are_appended(tmp_path, capsys):
    """The seven entries wait outside BENCHMARK.json (``chipbench/
    layer_metrics/waiting.mellum2.json`` says why). Appended to a toy
    copy's ``per_layer`` they are well-formed entries, and a traced
    rehearsal reads through them: the device-time readers give nothing (a
    rehearsal has no trace), none raises."""
    waiting = json.loads(
        (REPO / "chipbench/layer_metrics/waiting.mellum2.json").read_text()
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
        assert CELL in m["workloads"] and m["moves"] == "serve.tokens_per_s"
        assert m["layer"] in {e["layer"] for e in BENCH["per_layer"]}
        assert (REPO / "chipbench/layer_metrics" / f"{m['name']}.py").is_file()
        assert m["name"] not in {e["name"] for e in bench["per_layer"]}
        assert m["source"] == "device_trace"
        if m["name"].endswith("roofline_pct"):
            assert m["unit"] == "%"
    bench["per_layer"] += waiting
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc = runner.main(
        ["--workload", CELL, "--seed", "5", "--seconds", "0.5", "--trace", "1"],
        root=root, rehearsal=True,
    )
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rc == 0 and last["checks_passed"] is True
    assert not set(NEW_READERS) & set(last["metric_names"])
