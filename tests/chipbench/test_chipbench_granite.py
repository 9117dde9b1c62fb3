"""The configuration ``granite-4.0-h-small-10l-ep4`` (one chip's share of
granite-4.0-h-small: one period of nine Mamba-2 mixers to one
grouped-query layer without positions, 18 of 72 softmax-routed experts
beside a shared one in EVERY layer, a quarter of the tied vocabulary) and
its cell: BENCHMARK.json's entries (the files, the lists, order and
membership), the file against the catalog's row and ISSUE 45's
arithmetic, the plain reference against the program on seeded weights at
a size that keeps every mechanism (a Mamba-2 layer, the period's forward,
what a slot keeps; through ``StreamingGenerator``: logits, not tokens),
the four shares summed to the uncut layer, the tied sliced head, the
draws, and the new readers on a hand-made trace. The cell end to end as a
rehearsal, probe and all, is a case of ``test_chipbench_rehearsal.py``
(every cell of BENCHMARK.json is); the compile for a described v5e is
``test_chipbench_granite_compile``."""

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
from chipbench.models import granite_decoder as family  # noqa: E402
from chipbench.reference import granite_decoder as reference  # noqa: E402

CELL, CONFIG = "granite4h.multi-session-drain", "granite-4.0-h-small-10l-ep4"
CONF = json.loads((REPO / "chipbench/configs" / f"{CONFIG}.json").read_text())
MIX = json.loads((REPO / "chipbench/workloads" / f"{CELL}.json").read_text())
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PARENT_CONFIGS = (
    "mistral-7b-v0.3-w8", "internlm2-1.8b-1chip", "internlm2-1.8b",
    "kanana-2-30b-a3b-7l", "longcat-flash-omni-4l-ep32",
    "mellum2-12b-a2.5b-8l", "ling-3.0-flash-7l-ep8",
)
PARENT_CELLS = (
    "mistral7b.backlog-drain", "internlm2-1.8b.pretrain-4k-1chip",
    "internlm2-1.8b.pretrain-4k-2x2", "kanana2.longform-drain",
    "longcat.reasoning-drain", "mellum2.repo-context-drain",
    "ling3.long-decode-drain",
)
REDUCED = ["num_hidden_layers", "num_local_experts", "vocab_size"]
NEW_METRICS = ("ssd.step_us.tput", "ssd.step_roofline_pct")
SSD_LOOP = common.load_named("loops", "serve_ssd", REPO)


def toy_conf(**kw) -> dict:
    """The rehearsal's cut in float32: the toy's widths, one period of
    four layers (M M A M), 8 experts of which 4 are held."""
    conf = copy.deepcopy(CONF)
    conf.update(TOY_KEYS)
    conf.update(SSD_LOOP.REHEARSAL["config"])
    conf["deployment"].update(SSD_LOOP.REHEARSAL["deployment"])
    conf["deployment"].update(compute_dtype="float32", param_dtype="float32")
    conf.update(kw)
    return conf


# ------------------------------------- BENCHMARK.json's entries


def test_benchmark_json_names_the_configuration_and_the_cell():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == REDUCED
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "backlog", 1,
    )
    assert len(cell["why"]) <= 200 and "4x" in cell["why"]
    # After every entry that was there: one put first reads as a change.
    configs = [c["name"] for c in BENCH["configs"]]
    cells = [w["name"] for w in BENCH["workloads"]]
    assert configs.index(CONFIG) == cells.index(CELL) == 7
    assert tuple(configs[:7]) == PARENT_CONFIGS
    assert tuple(cells[:7]) == PARENT_CELLS
    bench, cell2, conf, mix = runner.load_cell(REPO, CELL)
    assert cell2 == cell and conf == CONF and mix == MIX
    ling = {
        m["name"] for sec in ("end_to_end", "per_layer") for m in bench[sec]
        if "ling3.long-decode-drain" in m.get("workloads", ())
    }
    reports = {
        m["name"] for sec in ("end_to_end", "per_layer") for m in bench[sec]
        if CELL in m.get("workloads", ())
    }
    # serve.tokens_per_s and the nineteen per-layer metrics the cell before
    # it reports but the delta rule's two, and the two this PR brings.
    kda = {"kda.step_us.tput", "kda.step_roofline_pct"}
    assert len(ling) == 22 and reports == (ling - kda) | set(NEW_METRICS)
    assert not {n for n in reports if n.startswith(("kvattn.", "kda."))}
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", ())
        if CELL in listed and m["name"] not in NEW_METRICS:
            # Appended behind the cell before it, nothing else moved.
            assert listed.index(CELL) == listed.index(
                "ling3.long-decode-drain"
            ) + 1
    names = [m["name"] for m in bench["per_layer"]]
    assert all("workloads" in m for m in bench["per_layer"])
    for name in NEW_METRICS:
        m = bench["per_layer"][names.index(name)]
        assert m["workloads"] == [CELL] and m["moves"] == "serve.tokens_per_s"
        assert (m["layer"], m["source"]) == ("kernels", "device_trace")
        assert (REPO / "chipbench/layer_metrics" / f"{name}.py").is_file()
        assert names.index(name) > names.index("kda.step_roofline_pct")
    for name in PARENT_CELLS:
        runner.load_cell(REPO, name)


def test_the_traffic_is_the_issue_s_letter_for_letter():
    assert MIX["loop"] == "serve_ssd"
    assert MIX["traffic"] == {
        "kind": "backlog", "records": 2400, "deck": 64, "block": 16,
        "prompt_median": 512, "prompt_sigma": 0.8, "prompt_max": 1024,
        "answer_median": 512, "answer_sigma": 0.8, "answer_min": 2,
        "answer_max": 3072, "tenants": 8, "tenant_zipf": 1.1,
        "pairing_seed": 45,
    }
    assert MIX["warmup_records"] == 3 and MIX["trace"] == {"seconds": 14.0}
    assert (MIX["check"]["probe_new"], MIX["check"]["probe_slots"]) == (256, 32)
    dep = CONF["deployment"]
    assert (dep["slots"], dep["prompt_window"], dep["max_new"]) == (128, 1024, 3072)
    assert (dep["ticks_per_sync"], dep["commit_every"]) == (128, 32)
    assert dep["prompt_partitions"] == 2 and dep["kv_kernel"] is False
    assert dep["mesh"] is None and dep["delivery"] == "at-least-once"
    assert dep["state_dtype"] == "float32" and dep["compute_dtype"] == "bfloat16"
    assert (dep["chips_sharing_a_layer"], dep["pipeline_stages"]) == (4, 4)


# ------------------------------------------------- the file's contract


def test_the_file_is_the_catalog_row_but_for_the_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(CONF["changed_from_source"]) == sorted(REDUCED)
    if not CATALOG.is_file():
        pytest.skip("no catalog here")
    row = next(
        r for r in map(json.loads, CATALOG.read_text().splitlines())
        if r["name"] == "granite-4.0-h-small"
    )
    assert entry["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if CONF.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED)
    for key in REDUCED:
        assert CONF[f"published_{key}"] == row["config"][key]
    # No width is among them.
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for said in (
        "layer_types", "intermediate_size", "mamba_mixer",
        "position_embedding_type", "attention_multiplier",
        "residual_multiplier", "embedding_multiplier", "routing",
        "rms_norm_eps", "state_space_parameters", "state_dtype", "embedding",
        "not_built", "deployment", "slots",
    ):
        assert said in CONF["assumed"]


def test_the_cut_by_hand():
    """ISSUE 45's count, reckoned again from the widths."""
    a = family.Arch.from_conf(CONF)
    d = 4096
    mamba = (
        d * (8192 + 8448 + 128) + 4 * 8448 + 8448  # in-projection, conv, bias
        + 3 * 128 + 8192 + 8192 * d  # dt_bias, A_log, D; the norm; out
    )
    attention = d * 32 * 128 + 2 * d * 8 * 128 + 32 * 128 * d
    assert a.block_params(True) == mamba == 102_286_976
    assert a.block_params(False) == attention == 41_943_040
    assert a.expert_params == 3 * d * 768 == 9_437_184
    assert a.shared_params == 3 * d * 1536 == 18_874_368
    assert a.router_params == d * 72 == 294_912
    branch = 294_912 + 18_874_368 + 18 * 9_437_184
    by_hand = (
        25_088 * d + d + 9 * mamba + attention + 10 * (branch + 2 * d)
    )
    assert a.params == by_hand == 2_955_758_208
    assert round(2 * a.params / 1e9, 2) == 5.91
    # The whole model, and one period uncut: what no chip holds.
    whole = 40 * 2 * d + d + 100_352 * d + 36 * mamba + 4 * attention + 40 * (
        294_912 + 18_874_368 + 72 * 9_437_184
    )
    assert round(whole / 1e9, 2) == 32.21
    period = sum(a.layer_params(l, 72) for l in range(10)) + 100_352 * d
    assert round(period / 1e9, 2) == 8.36
    assert a.pattern == (True,) * 5 + (False,) + (True,) * 4
    assert a.kind_layers(False) == [5] and len(a.kind_layers(True)) == 9
    assert (a.held_first, a.held_count, a.experts, a.top_k) == (0, 18, 72, 10)
    assert a.vocab * 4 == CONF["published_vocab_size"]
    assert (a.inner, a.channels, a.kv_row) == (8192, 8448, 1024)
    # The slot memory at 128 slots of 1024 + 3072 positions.
    state = 9 * 128 * 128 * 64 * 128 * 4
    tails = 9 * 128 * 3 * 8448 * 2
    pool = 2 * 128 * 4096 * 1024 * 2
    assert round(state / 1e9, 2) == 4.83 and round(tails / 1e9, 2) == 0.06
    assert round(pool / 1e9, 2) == 2.15
    assert round((2 * a.params + state + tails + pool) / 1e9, 2) == 12.95
    assert round(128 * 10 / 72, 1) == 17.8  # local pairs a held expert a tick
    # The kernel's counts, from the same widths.
    k = common.load_named("kernels", "ssd", REPO)
    assert k.mamba_layers(CONF) == 9 and k.state_bytes(CONF) == 4 * 2**20
    assert k.step_bytes(CONF, 128) == 9 * 128 * (
        8 * 2**20 + (4 * 8192 + 256) * 4
    )
    assert k.step_bytes(CONF, 128) / 1e9 == pytest.approx(9.82, abs=0.01)


def test_the_program_s_config_is_the_file_s():
    cfg = family.program_config(CONF, 4096)
    assert cfg.linear_pattern == (True,) * 5 + (False,) + (True,) * 4
    assert (cfg.linear_kind, cfg.first_dense_layers, cfg.n_layers) == ("ssd", 0, 10)
    assert (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_state_dim) == (128, 64, 128)
    assert (cfg.ssd_chunk, cfg.linear_conv, cfg.ssd_conv_dim) == (256, 4, 8448)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_top_k) == (72, (0, 18), 10)
    assert (cfg.router_score, cfg.norm_topk, cfg.routed_scaling) == (
        "softmax", True, 1.0,
    )
    assert cfg.n_shared_experts * cfg.moe_d_ff == 1536 and not cfg.is_mla
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 128)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier) == (12.0, 0.22)
    assert (cfg.attention_multiplier, cfg.logits_scaling) == (0.0078125, 16.0)
    assert cfg.attn_scale == 1 / 128 and cfg.norm_eps == 1e-5
    assert cfg.tie_embeddings and not cfg.use_rope
    assert cfg.hybrid_layers(True) == 9 and cfg.cache_layers == 1
    import jax

    from torchkafka_tpu.ops import moe

    # A tick's 128 rows: 17.8 local pairs a held expert, the grouped
    # kernels; an admission's trip keeps the loop (its absent pairs).
    assert moe.expert_form(cfg, 128) == "grouped"
    assert moe.expert_form(cfg, 3 * 1024) == "compacted"
    shapes = jax.eval_shape(lambda: family.serving_params(CONF, 0))
    assert sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)
    ) == 2_955_758_208
    assert "lm_head" not in shapes and shapes["embed"].shape == (25_088, 4096)
    assert shapes["layers"]["w_gate"].shape == (10, 18, 4096, 768)
    assert shapes["layers"]["s_in"].shape == (9, 4096, 8192 + 8448)
    assert shapes["layers"]["s_in_dt"].shape == (9, 4096, 128)
    assert shapes["layers"]["wk"].shape == (1, 4096, 8, 128)
    assert shapes["layers"]["router"].shape == (10, 4096, 72)
    assert "router_bias" not in shapes["layers"]


def test_the_decays_spread_as_the_file_says():
    """What ``assumed.state_space_parameters`` says of the draw: with ``h
    W_dt`` of unit variance a head's decay a token spreads from about 0.2
    to 0.998 (the 2nd and the 98th percentile)."""
    import jax
    import jax.numpy as jnp

    a = family.Arch.from_conf(CONF)
    key = W.seed_key(5)
    rate = jnp.exp(family.draw(key, a, "s_alog", 1, jnp.float32))
    bias = family.draw(key, a, "s_dt", 1, jnp.float32)
    assert rate.shape == bias.shape == (128,)
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 16.0
    step = np.asarray(jax.nn.softplus(bias))
    assert 0.001 <= step.min() * 1.001 and step.max() <= 0.1 * 1.001
    dt = jax.nn.softplus(jax.random.normal(key, (256, 128)) + bias)
    decay = np.asarray(jnp.exp(-rate * dt)).ravel()
    assert np.quantile(decay, 0.02) < 0.2 and np.quantile(decay, 0.98) > 0.997
    taps = family.draw(key, a, "s_conv", 1, jnp.float32)
    assert taps.shape == (4, 8448) and float(jnp.abs(taps).max()) <= 0.5
    # A row of the tied matrix does not depend on the slice.
    rows = family.embed_rows(key, a, jnp.float32, first=100, count=4)
    np.testing.assert_array_equal(
        rows, family.embed_rows(key, a, jnp.float32, count=104)[100:]
    )


# ------------------------------- the program against the plain reference


@pytest.fixture(scope="module")
def toy():
    """(conf, cfg, params, dims, tokens) at the rehearsal's cut."""
    import jax

    conf = toy_conf()
    cfg = family.program_config(conf, 40)
    params = family.serving_params(conf, 7)
    tokens = np.asarray(
        jax.random.randint(jax.random.key(3), (2, 40), 0, conf["vocab_size"])
    )
    return conf, cfg, params, W.Dims.from_conf(conf), tokens


def test_a_mamba_layer_is_the_reference_s(toy):
    """The first layer alone, on the embedded tokens: the stream after its
    mixer, the state after the last token and the conv tail, the program's
    chunked scan against the reference's token-serial recurrence."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models import linear_attn
    from torchkafka_tpu.models.transformer import _rms_norm

    conf, cfg, params, _dims, tokens = toy
    arch = family.Arch.from_conf(conf)
    x = 12.0 * jnp.asarray(params["embed"])[tokens].astype(jnp.float32)
    layer = {
        n: t[0] for n, t in params["layers"].items()
        if n.startswith("s_") or n == "ln1"
    }
    heads, state, tail = linear_attn.attend_sequence(
        _rms_norm(x, layer["ln1"], cfg.norm_eps), layer, cfg
    )
    assert layer["s_out"].shape == (8, 64, 256)
    got = x + 0.22 * jnp.einsum(
        "bshe,hed->bsd", heads, linear_attn.out_projection(layer, cfg)
    )
    w = jax.tree.map(
        lambda t: t.astype(jnp.float32),
        family.layer_weights(W.seed_key(7), arch, 0, jnp.float32),
    )
    with jax.default_matmul_precision("highest"):
        want, want_state, want_tail, snap = reference.mamba_mixer(
            x, w, arch, False, snap_at=16
        )
        early = reference.mamba_mixer(x[:, :16], w, arch, False)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5)
    np.testing.assert_allclose(
        tail.reshape(want_tail.shape), want_tail, atol=5e-6
    )
    np.testing.assert_allclose(snap[0], early[1], atol=5e-6)
    np.testing.assert_allclose(snap[1], early[2], atol=5e-6)
    assert state.shape == (2, 8, 64, 128) and tail.shape == (2, 3 * (512 + 256))


def test_the_period_s_forward_gives_the_reference_s_logits(toy):
    import jax

    from torchkafka_tpu.models import Transformer
    from torchkafka_tpu.models.generate import prefill

    conf, cfg, params, dims, tokens = toy
    got = jax.jit(Transformer(cfg).__call__)(params, tokens)
    want = reference.logits(7, dims, tokens)
    assert got.shape == want.shape == (2, 40, conf["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(np.abs(want).max()) > 1e-3  # (the logits' own size)
    # What a slot would keep, every layer of the period.
    _logits, (states, tails, k_rows, v_rows) = prefill(params, cfg, tokens, 40)
    kept = reference.slot_memory(7, dims, tokens)
    assert kept["states"].shape == states.shape == (3, 2, 8, 64, 128)
    np.testing.assert_allclose(states, kept["states"], atol=5e-5)
    np.testing.assert_allclose(
        np.reshape(tails, kept["tails"].shape), kept["tails"], atol=5e-5
    )
    rows = np.concatenate([k_rows, v_rows], axis=-1)
    np.testing.assert_allclose(rows, kept["rows"], atol=5e-5)
    assert kept["chosen"].shape == (4, 2, 40, 2)
    assert kept["imprint"].shape == (1, 2, 40, 512)


def test_the_served_tokens_and_the_slot_memory_are_the_reference_s(toy):
    """Three prompts through ``StreamingGenerator`` (the compiled admit,
    then tick blocks over state, tail and K/V pool): every served token's
    logit against the reference's best at its position (logits, not
    tokens), and what the slots hold at the end against the reference's
    token-serial recurrence."""
    import torchkafka_tpu as tk
    from torchkafka_tpu.serve import StreamingGenerator

    conf, cfg, params, dims, tokens = toy
    p, new = 16, 9  # the admission's token and two blocks of four ticks
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    prompts = np.concatenate([tokens[:, :p], tokens[:1, 20: 20 + p]])
    for row in prompts:
        broker.produce("p", row.astype(np.int32).tobytes())
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    server = StreamingGenerator(
        consumer, params, cfg, slots=4, prompt_len=p, max_new=new,
        ticks_per_sync=4,
    )
    served = np.zeros((3, p + new), np.int32)
    served[:, :p] = prompts
    for rec, toks in server.run(max_records=3, idle_timeout_ms=100):
        served[rec.offset, p:] = toks
    summary = server.metrics.summary()
    assert summary["linear_state"]["kind"] == "ssd"
    assert summary["linear_state"]["step"] == "xla"  # (no TPU here)
    assert summary["linear_state"]["chunk"] == 8
    assert summary["kv_pool"]["full_layers"] == 1
    assert summary["kv_pool"]["full_positions_read"] > 0
    assert summary["expert_layer"]["experts_held"] == [0, 4]
    assert summary["expert_layer"]["moe_local_assignments"] > 0
    assert summary["kv_backend"]["layout"] == "state"
    gap, _top = reference.served_logit_gaps(7, dims, served, p - 1, new)
    assert float(np.max(gap)) <= 2e-6
    states, tails, pool_k, pool_v = (np.asarray(c) for c in server.cache_tensors)
    kept = reference.slot_memory(7, dims, served[:, : p + new - 1])
    rows = np.concatenate([pool_k, pool_v], axis=-1)[:, :, : p + new - 2]
    slots = SSD_LOOP.slots_of(rows, kept["rows"], p)
    np.testing.assert_allclose(states[:, slots], kept["states"], atol=5e-5)
    np.testing.assert_allclose(
        tails[:, slots].reshape(kept["tails"].shape), kept["tails"], atol=5e-5
    )
    np.testing.assert_allclose(
        rows[:, slots], kept["rows"][:, :, : p + new - 2], atol=5e-5
    )
    server.close()
    consumer.close()


# ------------------------------------------------------------ the share


def test_the_four_shares_sum_to_the_uncut_layer():
    """Eight experts in four shares of two, top 4: each chip computes its
    held experts' part and, like every chip, the shared expert. The four
    parts, the shared expert counted once, sum to what ONE chip holding
    all eight experts computes: the program's routed layer a share at a
    time against the reference's uncut layer."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.ops.moe import routed_moe_mlp

    conf = toy_conf(num_experts_per_tok=4)
    key, layer = W.seed_key(11), 2
    h = jax.random.normal(jax.random.key(1), (1, 48, conf["hidden_size"]))
    conf["deployment"]["experts_held"] = [0, 8]
    conf["num_local_experts"] = 8
    whole = family.Arch.from_conf(conf)
    w = jax.tree.map(
        lambda t: t.astype(jnp.float32),
        family.layer_weights(key, whole, layer, jnp.float32),
    )
    with jax.default_matmul_precision("highest"):
        # ``mlp`` norms its input and adds the residual: hand it a row
        # that is already unit-RMS and take the residual off again.
        x = h[0] * jax.lax.rsqrt(
            jnp.mean(h[0] ** 2, -1, keepdims=True) + whole.rms_eps
        )
        y, _local, idx, _margin = reference.mlp(x, w, whole, False)
        _i, gates = reference.route(x, w, whole)
    uncut = np.asarray(y - x) / whole.residual_mult
    np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-6)
    parts, shared = [], None
    for share in range(4):
        conf["deployment"]["experts_held"] = [2 * share, 2]
        conf["num_local_experts"] = 2
        arch = family.Arch.from_conf(conf)
        cfg = family.program_config(conf, 64)
        wg = family.layer_weights(key, arch, layer, jnp.float32)
        prog = {
            **{n: wg[n] for n in family.BRANCH},
            **{f"w_{n[3:]}": wg[n] for n in family.EXPERT},
        }
        assert cfg.experts_held == (2 * share, 2) and cfg.n_experts == 8
        out, chosen = routed_moe_mlp(x[None], prog, cfg)
        np.testing.assert_array_equal(np.sort(chosen[0]), np.sort(idx))
        zero = {n: jnp.zeros_like(wg[n]) for n in ("ws_gate", "ws_up", "ws_down")}
        alone = routed_moe_mlp(x[None], {**prog, **zero}, cfg)[0][0]
        parts.append(np.asarray(alone))
        # What every chip computes alike: the shared expert, counted once.
        shared = np.asarray(out[0] - alone)
    assert np.abs(shared).max() > 0.01
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=2e-5)
    # A share alone is not the layer: the cut is real.
    assert np.abs(parts[0] + shared - uncut).max() > 0.01


def test_the_tied_head_over_a_slice_is_the_slice_of_the_whole(toy):
    """The head is the embedding's transpose, and this chip's rows of it
    are the first quarter of the published matrix: the program's logits
    over the slice are the first columns of the reference's over the whole
    vocabulary (token ids from the slice), divided by ``logits_scaling``."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models import Transformer

    conf, cfg, params, _dims, tokens = toy
    assert "lm_head" not in params and cfg.tie_embeddings
    got = np.asarray(jax.jit(Transformer(cfg).__call__)(params, tokens[:1]))
    arch = family.Arch.from_conf(conf)
    whole = arch.slice_vocab(4 * arch.vocab)
    x = reference.forward(7, arch, jnp.float32, tokens[:1])
    x = reference.rms_norm(x, jnp.ones((arch.hidden,)), arch.rms_eps)
    table = family.embed_rows(W.seed_key(7), whole, jnp.float32)
    assert table.shape == (4 * conf["vocab_size"], conf["hidden_size"])
    np.testing.assert_array_equal(table[: arch.vocab], params["embed"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jnp.einsum("bsd,vd->bsv", x, table)) / 16.0
    np.testing.assert_allclose(got, want[..., : arch.vocab], atol=2e-6)


def test_every_named_fault_moves_the_reference(toy):
    """Each fault of ``FAULTS`` changes what the reference's slot memory
    or stream reads at the toy size (the chip's readings against the
    limits are PERF.md's)."""
    conf, _cfg, _params, dims, tokens = toy
    sound = reference.slot_memory(7, dims, tokens, snap_at=16)
    assert sound["states_at"].shape == sound["states"].shape
    moved = {
        "state_bf16": "states", "decay_bf16": "states", "no_skip": "hidden",
        "conv_tail_one_early": "tails", "sqrt_scale": "hidden",
        "no_renorm": "hidden", "held_one_off": "last_parts",
    }
    assert set(moved) == set(reference.FAULTS)
    for fault, name in moved.items():
        low = reference.slot_memory(7, dims, tokens, lowp=fault, snap_at=16)
        err = np.abs(low[name] - sound[name]).max() / np.abs(sound[name]).max()
        assert err > 1e-4, (fault, name, err)


# ----------------------------------------------- the new readers


def test_the_step_s_readers_on_a_hand_made_trace():
    """Nine calls a tick of ``tk_ssd_step`` in a tick program: the time a
    call, and the share of the roofline by the bytes of the slot-ticks
    SERVED; a program without the kernel, or a configuration without
    state-space layers, gives nothing to read."""
    us = common.load_named("layer_metrics", "ssd.step_us.tput", REPO)
    pct = common.load_named("layer_metrics", "ssd.step_roofline_pct", REPO)
    k = common.load_named("kernels", "ssd", REPO)
    trace = {
        "kernels": {
            "jit_tick_block/tk_ssd_step.3": {
                "program": "jit_tick_block", "total_s": 0.9, "count": 900,
            },
            "jit_admit/tk_flash_fwd.1": {
                "program": "jit_admit", "total_s": 5.0, "count": 10,
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
    run = {"trace": trace, "conf": CONF, "requests": requests, "root": REPO,
           "peaks": {"hbm_bytes_s": 819e9}}
    assert us.read(run) == pytest.approx(1000.0)
    ticks = 128 + 128 + 100
    want = 100 * k.step_bytes(CONF, ticks) / (0.9 * 819e9)
    assert pct.read(run) == pytest.approx(want) and 0 < want < 100
    bare = {**run, "trace": {**trace, "kernels": {}}}
    assert us.read(bare) is None and pct.read(bare) is None
    ling = json.loads(
        (REPO / "chipbench/configs/ling-3.0-flash-7l-ep8.json").read_text()
    )
    assert us.read({**run, "conf": ling}) is None
    assert us.read({**run, "trace": None}) is None
