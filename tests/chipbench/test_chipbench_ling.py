"""The configuration ``ling-3.0-flash-7l-ep8`` (one chip's share of
Ling-3.0-flash: the leading dense layer and one period of five
linear-attention (KDA) layers to one latent-attention layer, one group of
64 of 512 sigmoid-routed experts chosen by group) and its cell:
BENCHMARK.json's entries, the file against the catalog's row and ISSUE
41's arithmetic, the plain reference against the program on seeded
weights at a size that keeps every mechanism (a KDA layer, the period's
forward, prefill then decode through the slot state: logits, not tokens),
the shares of all the groups summed to the uncut layer, group selection
by hand, every named control seen to fail a comparison, and the new
readers on a hand-made trace. The cell end to end as a rehearsal is a
case of ``test_chipbench_rehearsal.py`` (every cell of BENCHMARK.json
is); the compile for a described v5e is ``test_chipbench_ling_compile``."""

from __future__ import annotations

import copy
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from toy import TOY_KEYS, make_toy_root  # noqa: E402

from chipbench import common  # noqa: E402
from chipbench import control, weights as W  # noqa: E402
from chipbench import run as runner  # noqa: E402
from chipbench.models import ling_decoder as family  # noqa: E402
from chipbench.reference import ling_decoder as reference  # noqa: E402

CELL, CONFIG = "ling3.long-decode-drain", "ling-3.0-flash-7l-ep8"
CONF = json.loads((REPO / "chipbench/configs" / f"{CONFIG}.json").read_text())
MIX = json.loads((REPO / "chipbench/workloads" / f"{CELL}.json").read_text())
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PARENT_CONFIGS = (
    "mistral-7b-v0.3-w8", "internlm2-1.8b-1chip", "internlm2-1.8b",
    "kanana-2-30b-a3b-7l", "longcat-flash-omni-4l-ep32",
    "mellum2-12b-a2.5b-8l",
)
PARENT_CELLS = (
    "mistral7b.backlog-drain", "internlm2-1.8b.pretrain-4k-1chip",
    "internlm2-1.8b.pretrain-4k-2x2", "kanana2.longform-drain",
    "longcat.reasoning-drain", "mellum2.repo-context-drain",
)
REDUCED = [
    "num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size",
]
NEW_METRICS = ("kda.step_us.tput", "kda.step_roofline_pct")
STATE_LOOP = common.load_named("loops", "serve_state", REPO)


def toy_conf(**kw) -> dict:
    """The rehearsal's cut in float32: the toy's widths, the dense layer
    and one whole period, 2 groups of 4 experts of which one is held."""
    conf = copy.deepcopy(CONF)
    conf.update(TOY_KEYS)
    conf.update(STATE_LOOP.REHEARSAL["config"])
    conf["deployment"].update(STATE_LOOP.REHEARSAL["deployment"])
    conf["deployment"].update(compute_dtype="float32", param_dtype="float32")
    conf.update(kw)
    return conf


# ------------------------------------- step 7: BENCHMARK.json's entries


def test_benchmark_json_names_the_configuration_and_the_cell():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == REDUCED
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "backlog", 1,
    )
    # After every entry that was there: one put first reads as a change.
    assert BENCH["configs"].index(entry) == BENCH["workloads"].index(cell) == 6
    bench, cell2, conf, mix = runner.load_cell(REPO, CELL)
    assert cell2 == cell and conf == CONF and mix == MIX
    mellum = {
        m["name"] for sec in ("end_to_end", "per_layer") for m in bench[sec]
        if "mellum2.repo-context-drain" in m.get("workloads", ())
    }
    reports = {
        m["name"] for sec in ("end_to_end", "per_layer") for m in bench[sec]
        if CELL in m.get("workloads", ())
    }
    # serve.tokens_per_s and the nineteen per-layer metrics the cell before
    # it reports, and the two this PR brings.
    assert len(mellum) == 20 and reports == mellum | set(NEW_METRICS)
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", ())
        if CELL in cells:
            assert cells[-1] == CELL
    for name in NEW_METRICS:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve.tokens_per_s"
        assert (m["layer"], m["source"]) == ("kernels", "device_trace")
        assert (REPO / "chipbench/layer_metrics" / f"{name}.py").is_file()
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW_METRICS)


def test_the_parent_s_six_configurations_and_cells_are_still_there():
    assert tuple(c["name"] for c in BENCH["configs"][:6]) == PARENT_CONFIGS
    assert tuple(w["name"] for w in BENCH["workloads"][:6]) == PARENT_CELLS
    for name in PARENT_CELLS:
        runner.load_cell(REPO, name)


def test_the_traffic_is_the_issue_s_letter_for_letter():
    assert MIX["loop"] == "serve_state"
    assert MIX["traffic"] == {
        "kind": "backlog", "records": 2400, "deck": 64, "block": 16,
        "prompt_median": 192, "prompt_sigma": 0.8, "prompt_max": 512,
        "answer_median": 1024, "answer_sigma": 0.8, "answer_min": 2,
        "answer_max": 3584, "pairing_seed": 41, "tenants": 8,
        "tenant_zipf": 1.1,
    }
    assert MIX["warmup_records"] == 3 and MIX["trace"] == {"seconds": 14.0}
    assert MIX["check"]["probe_new"] == 256
    dep = CONF["deployment"]
    assert (dep["slots"], dep["prompt_window"], dep["max_new"]) == (384, 512, 3584)
    assert (dep["ticks_per_sync"], dep["commit_every"]) == (128, 32)
    assert dep["prompt_partitions"] == 2 and dep["kv_kernel"] is False
    assert dep["mesh"] is None and dep["delivery"] == "at-least-once"
    assert dep["state_dtype"] == "float32" and dep["compute_dtype"] == "bfloat16"


# ------------------------------------------------- the file's contract


def test_the_file_is_the_catalog_row_but_for_the_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(CONF["changed_from_source"]) == sorted(REDUCED)
    if not CATALOG.is_file():
        pytest.skip("no catalog here")
    row = next(
        r for r in map(json.loads, CATALOG.read_text().splitlines())
        if r["name"] == "Ling-3.0-flash"
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
        "layer_kinds", "num_kv_heads_for_linear_attn", "kda_safe_gate",
        "use_qk_norm", "gated_attention_proj_granularity_type", "group_score",
        "decay_parameters", "not_built",
    ):
        assert said in CONF["assumed"]


def test_the_cut_by_hand():
    """ISSUE 41's count, reckoned again from the widths."""
    a = family.Arch.from_conf(CONF)
    d = 2560
    kda_block = (
        d * 12288 + 4 * 12288 + d * 4096 + 32 + 4096  # q|k|v, taps, decay
        + 2 * d * 32 + 4096 * d + 128 + 2 * d  # beta, gate, out, the norms
    )
    mla_block = (
        d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + 32 * 128 * d
        + d * 32 + 2 * d
    )
    assert a.block_params(True) == kda_block == 52_651_168
    assert a.block_params(False) == mla_block == 31_970_816
    assert a.expert_params == 3 * d * 768 == 5_898_240
    assert a.router_params == d * 512 + 512 == 1_311_232
    assert a.dense_ffn_params == 3 * d * 6144 == 47_185_920
    expert_layer = 1_311_232 + 5_898_240 + 64 * 5_898_240
    by_hand = (
        2 * 19_648 * d + d + (kda_block + 47_185_920)
        + 5 * (kda_block + expert_layer) + (mla_block + expert_layer)
    )
    assert a.params == by_hand == 2_803_845_056
    assert round(2 * a.params / 1e9, 2) == 5.61
    assert a.pattern == (True,) * 5 + (False,)
    assert a.kind_layers(True) == [0, 1, 2, 3, 4, 5] and a.kind_layers(False) == [6]
    assert (a.held_first, a.held_count, a.experts, a.groups) == (0, 64, 512, 8)
    assert a.experts // a.groups == a.held_count  # one group a chip
    assert a.vocab * 8 == CONF["published_vocab_size"]
    # The slot memory at 384 slots of 512 + 3584 positions.
    state = 6 * 384 * 32 * 128 * 128 * 4
    tails = 6 * 384 * 3 * 12288 * 2
    pool = 1 * 384 * 4096 * 576 * 2
    assert round(state / 1e9, 2) == 4.83 and round(tails / 1e9, 2) == 0.17
    assert round(pool / 1e9, 2) == 1.81
    assert round((2 * a.params + state + tails + pool) / 1e9, 2) == 12.42
    assert round(state / 384 / (576 * 2)) == 10_923  # latent positions a slot
    assert 384 * 8 / 512 == 6  # local pairs a held expert a tick
    # The kernel's counts, from the same widths.
    k = common.load_named("kernels", "kda", REPO)
    assert k.linear_layers(CONF) == 6 and k.state_bytes(CONF) == 2 * 2**20
    assert k.step_bytes(CONF, 384) == 6 * 384 * (4 * 2**20 + 6 * 32 * 128 * 4)


def test_the_program_s_config_is_the_file_s():
    cfg = family.program_config(CONF, 4096)
    assert cfg.linear_pattern == (True,) * 5 + (False,)
    assert (cfg.first_dense_layers, cfg.n_layers) == (1, 7)
    assert (cfg.n_experts, cfg.experts_held) == (512, (0, 64))
    assert (cfg.n_group, cfg.topk_group, cfg.expert_top_k) == (8, 4, 8)
    assert cfg.routed_scaling == 2.5 and cfg.attn_gate
    assert (cfg.linear_head_dim, cfg.linear_conv) == (128, 4)
    assert cfg.linear_lower_bound == -5.0
    assert cfg.hybrid_layers(True) == 6 and cfg.cache_layers == 1
    import jax

    shapes = jax.eval_shape(lambda: family.serving_params(CONF, 0))
    assert sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)
    ) == 2_803_845_056
    assert shapes["layers"]["w_gate"].shape == (6, 64, 2560, 768)
    assert shapes["layers"]["lqkv"].shape == (5, 2560, 12288)
    assert shapes["layers"]["wq"].shape == (1, 2560, 32, 192)
    assert shapes["dense_layers"]["w_gate"].shape == (1, 2560, 6144)


def test_the_decays_spread_over_the_gate_s_range():
    """What ``assumed.decay_parameters`` says of the draw: with ``W_f x``
    of unit variance the log-decay a channel covers three decades."""
    import jax
    import jax.numpy as jnp

    a = family.Arch.from_conf(CONF)
    key = W.seed_key(5)
    dt = family.draw(key, a, "l_dt", 1, jnp.float32)
    rate = jnp.exp(family.draw(key, a, "l_alog", 1, jnp.float32))
    assert dt.shape == (32, 128) and -6 <= float(dt.min()) < float(dt.max()) <= 2
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 2.0
    z = rate[:, None] * (jax.random.normal(key, (64, 32, 128)) + dt)
    g = np.asarray(-5.0 * jax.nn.sigmoid(z)).ravel()
    assert np.quantile(g, 0.9) > -0.02 and np.quantile(g, 0.1) < -3.0
    taps = family.draw(key, a, "lconv", 1, jnp.float32)
    assert taps.shape == (4, 12288) and abs(float(taps.std()) - 0.5) < 0.02


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


def test_a_kda_layer_is_the_reference_s(toy):
    """The leading dense layer alone, on the embedded tokens: the stream
    after its attention, the state after the last token and the conv
    tail, the program's chunkwise form against the reference's
    token-serial recurrence."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models import linear_attn
    from torchkafka_tpu.models.transformer import _rms_norm

    conf, cfg, params, _dims, tokens = toy
    arch = family.Arch.from_conf(conf)
    x = jnp.asarray(params["embed"])[tokens].astype(jnp.float32)
    layer = jax.tree.map(lambda t: t[0], params["dense_layers"])
    heads, state, tail = linear_attn.attend_sequence(
        _rms_norm(x, layer["ln1"]), layer, cfg
    )
    got = x + jnp.einsum("bshe,hed->bsd", heads, layer["lo"])
    w = jax.tree.map(
        lambda t: t.astype(jnp.float32),
        family.layer_weights(W.seed_key(7), arch, 0, jnp.float32),
    )
    with jax.default_matmul_precision("highest"):
        want, want_state, want_tail = reference.linear_attention(
            x, w, arch, False
        )
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5)
    np.testing.assert_allclose(tail, want_tail, atol=1e-6)
    assert state.shape == (2, 2, 128, 128) and tail.shape == (2, 3, 768)


def test_the_period_s_forward_gives_the_reference_s_logits(toy):
    import jax

    from torchkafka_tpu.models import Transformer

    conf, cfg, params, dims, tokens = toy
    got = jax.jit(Transformer(cfg).__call__)(params, tokens)
    want = reference.logits(7, dims, tokens)
    assert got.shape == want.shape == (2, 40, conf["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-4)
    # What a slot would keep, every layer of the period.
    from torchkafka_tpu.models.generate import prefill

    _logits, (states, tails, rows) = prefill(params, cfg, tokens, 40)
    kept = reference.slot_memory(7, dims, tokens)
    assert kept["states"].shape == states.shape == (6, 2, 2, 128, 128)
    np.testing.assert_allclose(states, kept["states"], atol=5e-5)
    np.testing.assert_allclose(tails, kept["tails"], atol=5e-5)
    np.testing.assert_allclose(rows, kept["rows"], atol=5e-5)
    assert kept["chosen"].shape == (6, 2, 40, 2)
    assert kept["imprint"].shape == (1, 2, 40, 576)


def test_prefill_then_decode_through_the_slot_state_gives_the_reference_s_logits(toy):
    """Sixteen tokens through the admission's forward, then twenty-four
    through ``slot_layer_step`` a token at a time over the states, the
    conv tails and the latent pool: the logits at every decoded position
    against the reference's full forward over the whole row."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.models import linear_attn
    from torchkafka_tpu.models.generate import head_logits, prefill
    from torchkafka_tpu.models.quant import embed_rows
    from torchkafka_tpu.models.transformer import hybrid_groups, scan_hybrid

    conf, cfg, params, dims, tokens = toy
    p, total = 16, tokens.shape[1]
    want = np.asarray(reference.logits(7, dims, tokens))
    logits, (states, tails, rows) = prefill(params, cfg, tokens[:, :p], total)
    np.testing.assert_allclose(logits, want[:, p - 1], atol=2e-4)
    pool = jnp.zeros((1, 2, total, cfg.latent_dim), cfg.dtype)
    caches = (states, tails, pool.at[:, :, :p].set(rows))

    @jax.jit
    def tick(caches, tok, pos):
        x = embed_rows(params["embed"], tok, cfg.dtype)[:, None, :]

        def body(carry, layer, linear, row):
            x, caches = carry
            x, caches, _ = linear_attn.slot_layer_step(
                x, layer, linear, row, caches, pos, None, cfg
            )
            return (x, caches), None

        for key, pattern, lin0, lat0 in hybrid_groups(cfg):
            (x, caches), _ = scan_hybrid(
                cfg, params[key], pattern, (x, caches), body, lin0, lat0
            )
        return head_logits(params, cfg, x, 0), caches

    for at in range(p, total):
        got, caches = tick(
            caches, jnp.asarray(tokens[:, at]), jnp.full((2,), at, jnp.int32)
        )
        np.testing.assert_allclose(got, want[:, at], atol=3e-4)


# ------------------------------------------------------------ the share


def test_the_groups_shares_sum_to_the_uncut_layer():
    """Eight groups of two experts, four kept, top 4: each group's chip
    computes its held experts' part and, like every chip, the shared
    expert. The eight parts, the shared expert counted once, sum to what
    ONE chip holding all sixteen experts computes: the program's routed
    layer a share at a time against the reference's uncut layer."""
    import jax
    import jax.numpy as jnp

    from torchkafka_tpu.ops.moe import routed_moe_mlp

    conf = toy_conf(
        num_experts=2, published_num_experts=16, n_group=8, topk_group=4,
        num_experts_per_tok=4,
    )
    key, layer = W.seed_key(11), 3
    h = jax.random.normal(jax.random.key(1), (1, 48, conf["hidden_size"]))
    conf["deployment"]["experts_held"] = [0, 16]
    conf["num_experts"] = 16
    whole = family.Arch.from_conf(conf)
    w = jax.tree.map(
        lambda t: t.astype(jnp.float32),
        family.layer_weights(key, whole, layer, jnp.float32),
    )
    w["ln2"] = jnp.ones_like(w["ln2"])
    with jax.default_matmul_precision("highest"):
        # ``mlp`` norms its input and adds the residual: hand it a row
        # that is already unit-RMS and take the residual off again.
        x = h[0] * jax.lax.rsqrt(jnp.mean(h[0] ** 2, -1, keepdims=True) + 1e-6)
        y, local, _idx, _margin = reference.mlp(x, w, whole, False)
    uncut = np.asarray(y - x)
    x3 = x[None]
    parts = []
    for group in range(8):
        conf["deployment"]["experts_held"] = [2 * group, 2]
        conf["num_experts"] = 2
        arch = family.Arch.from_conf(conf)
        cfg = family.program_config(conf, 64)
        wg = family.layer_weights(key, arch, layer, jnp.float32)
        prog = {
            **{n: wg[n] for n in family.BRANCH},
            **{f"w_{n[3:]}": wg[n] for n in family.EXPERT},
        }
        assert cfg.experts_held == (2 * group, 2) and cfg.n_group == 8
        out, chosen = routed_moe_mlp(x3, prog, cfg)
        zero = {n: jnp.zeros_like(wg[n]) for n in ("ws_gate", "ws_up", "ws_down")}
        alone = routed_moe_mlp(x3, {**prog, **zero}, cfg)[0][0]
        parts.append(np.asarray(alone))
        # What every chip computes alike: the shared expert, counted once.
        shared = np.asarray(out[0] - alone)
        # A token reaches at most topk_group = 4 of the eight chips.
        assert max(
            len(set(row.tolist())) for row in np.asarray(chosen[0]) // 2
        ) <= 4
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=2e-5)
    np.testing.assert_allclose(sum(parts), np.asarray(local), atol=2e-5)
    assert np.abs(sum(parts)).max() > 10 * 2e-5


def test_the_reference_s_group_selection_against_a_case_by_hand():
    """As ``tests/test_linear_attn.py`` holds the program's: the two best
    experts in group 1, the best pair in group 0."""
    import jax.numpy as jnp

    conf = toy_conf()
    arch = family.Arch.from_conf(conf)
    assert (arch.experts, arch.groups, arch.top_groups, arch.top_k) == (8, 2, 1, 2)
    scores = jnp.array([0.8, 0.75, 0.1, 0.1, 0.9, 0.6, 0.1, 0.1])
    d = conf["hidden_size"]
    router = jnp.zeros((d, 8)).at[0].set(jnp.log(scores / (1 - scores)))
    h = jnp.zeros((1, d)).at[0, 0].set(1.0)
    w = {"router": router, "router_bias": jnp.zeros((8,))}
    idx, weights = reference.route(h, w, arch, False)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1]
    np.testing.assert_allclose(float(weights.sum()), arch.scaling, rtol=1e-5)
    idx, _ = reference.route(h, w, arch, "no_group_selection")
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 4]


# ------------------------------------------------------- the controls


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_toy_root(tmp_path_factory.mktemp("chipbench"))


def test_every_control_fails_a_comparison(toy_root, capsys):
    """``chipbench/control.py --control 1`` at the rehearsal's size, in
    float32 under the rehearsal's limits: the sound run passes every
    comparison, and each control put in the program's place fails at
    least one; the faults ISSUE 41 names fail what was built for them."""
    rc = control.main(
        ["--workload", CELL, "--seeds", "5", "--seconds", "1.5",
         "--control", "1"], root=toy_root, rehearsal=True,
    )
    lines = capsys.readouterr().out.splitlines()
    reading = next(
        json.loads(l)["reading"] for l in lines if l.startswith('{"reading"')
    )
    assert rc == 0 and reading["correct"] is True
    found = reading["control"]
    assert set(found["controls"]) == {
        "e4m3", *reference.FAULTS, "displaced_stream",
    }
    for name, fails in found["fails"].items():
        assert fails, f"{name} passes every comparison: {found['controls'][name]}"
    assert "state_err.first_layer" in found["fails"]["state_bf16"]
    for fault in ("decay_a_head", "no_safe_gate"):
        assert "state_err.worst_layer" in found["fails"][fault]
    assert {"latent_row_err.prefill", "latent_row_err.decode"} & set(
        found["fails"]["no_group_selection"]
    )
    assert found["fails"]["conv_tail_one_early"] == ["conv_tail_err.worst_layer"]
    # The last layer's parts, which no slot keeps: by the stream after it.
    assert found["fails"]["no_output_gate"] == ["last_layer_missing.gate"]
    assert {
        "held_pair_missing.prefill", "held_pair_missing.decode",
        "last_layer_missing.experts",
    } <= set(found["fails"]["held_one_off"])
    for part in ("experts", "gate"):
        assert found["controls"]["held_one_off" if part == "experts" else
                                 "no_output_gate"]["last_layer_missing"][
            part] == pytest.approx(1.0, abs=0.1)
    assert found["fails"]["displaced_stream"] == ["served_logit_gap"]
    # The sound run's own numbers lie under every limit.
    program, limits = found["program"], found["limits"]
    assert program["served_logit_gap"] <= limits["served_logit_gap"]
    for check in ("state_err", "conv_tail_err", "latent_row_err",
                  "held_pair_missing", "last_layer_missing"):
        for part, value in program[check].items():
            assert value <= limits[check][part]


def test_the_probe_s_answers_end_on_a_sync():
    length = STATE_LOOP.probe_length
    assert length(256, 3584, 128) == 257  # the admission's token, two blocks
    assert length(10, 16, 4) == 13 and length(9, 16, 4) == 9
    assert length(256, 200, 128) == 129  # what max_new holds
    with pytest.raises(common.Refused):
        length(256, 100, 128)


def test_the_probe_fills_its_slots_with_distinct_prompts():
    """The sampled requests' prompts first, then others of the run's,
    padded to the window as the server pads them, none twice."""
    from types import SimpleNamespace

    out = {
        "prompt_window": 8,
        "sample": {"toks": np.array([[1, 2, 3, 0, 0, 0, 0, 0, 9, 9]])},
        "requests": [
            {"prompt": np.array([1, 2, 3]), "prompt_len": 3},
            {"prompt": np.array([4, 5]), "prompt_len": 2},
            {"prompt": np.array([4, 5]), "prompt_len": 2},
            {"prompt": np.array([6]), "prompt_len": 1},
        ],
    }
    ctx = SimpleNamespace(seed=4144500069)
    got = STATE_LOOP.probe_prompts(ctx, out, 3)
    assert got.shape == (3, 8) and got.dtype == np.int32
    assert got[0].tolist() == [1, 2, 3, 0, 0, 0, 0, 0]
    assert sorted(map(tuple, got[1:, :2])) == [(4, 5), (6, 0)]
    # No more than the run has distinct prompts.
    assert STATE_LOOP.probe_prompts(ctx, out, 8).shape == (3, 8)


def test_a_part_that_is_missing_reads_one_and_a_near_tie_is_told():
    """``part_missing`` by hand, and ``near_ties``: the window's tokens
    with a local pair, how many are padding, how many read over a half
    and the reference's margin at those."""
    rng = np.random.default_rng(0)
    part = rng.normal(size=(2, 6, 16)).astype(np.float32)
    part[0, 1] = 0.0  # a token without the part
    noise = 0.01 * rng.normal(size=part.shape).astype(np.float32)
    missing, has = STATE_LOOP.part_missing(noise, part)
    assert not has[0, 1] and has.sum() == 11
    assert np.abs(missing[has]).max() < 0.02
    missing, _ = STATE_LOOP.part_missing(noise - part, part)
    np.testing.assert_allclose(missing[has], 1.0, atol=0.02)
    other = rng.normal(size=part.shape).astype(np.float32)
    # Another expert's output in its place: the part lacking, and a
    # direction of its own that projects to little.
    wrong, _ = STATE_LOOP.part_missing(0.1 * other - part, part)
    assert abs(float(np.median(wrong[has])) - 1.0) < 0.1
    assert STATE_LOOP.last_layer_missing(part + noise, part, part) < 0.02
    assert STATE_LOOP.last_layer_missing(noise, part, part) == pytest.approx(
        1.0, abs=0.02
    )
    # Slot 1's padding (positions 3, 4, 5 of a window of 6) was served
    # with another expert: three tokens over a half, all padding.
    rows = (part + noise)[None].copy()
    rows[0, 1, 3:] -= part[1, 3:]
    prompts = np.array([[5, 6, 7, 8, 9, 3], [4, 4, 4, 0, 0, 0]])
    margin = np.full((1, 2, 6), 0.01, np.float32)
    margin[0, 1, 3:] = 0.0004
    told = STATE_LOOP.near_ties(rows, {
        "window": 6, "rows": part[None], "imprint": part[None],
        "margin": margin,
    }, prompts)
    assert told["tokens"] == 11 and told["padding"] == 3
    assert told["over_half"] == told["over_half_padding"] == 3
    assert told["slots_with_one_over_half"] == 1
    assert told["mean"] == pytest.approx(3 / 11, abs=0.02)
    assert abs(told["median"]) < 0.02
    assert told["margin_median"]["every"] == pytest.approx(0.01)
    assert told["margin_median"]["over_half"] == pytest.approx(0.0004)


# ----------------------------------------------------------- the readers


def test_the_new_readers_on_a_hand_made_run():
    """A tick traced twice with six kernel calls each, 300 slot-ticks
    served in the traced part: microseconds a call and the share of the
    roofline by ``chipbench/kernels/kda.py``'s bytes; a run without a
    trace, a configuration without linear layers and a program without
    the kernel read nothing."""
    peaks = common.load_peaks("TPU v5 lite")
    run = {
        "conf": CONF, "root": REPO, "peaks": peaks,
        "trace": {
            "host_t0": 10.0, "host_t1": 20.0,
            "kernels": {
                "jit_tick_block/tk_kda_step.3": {
                    "count": 8, "total_s": 0.016, "program": "jit_tick_block",
                    "text": "",
                },
                "jit_tick_block/tk_kda_step.7": {
                    "count": 4, "total_s": 0.008, "program": "jit_tick_block",
                    "text": "",
                },
                "jit_admit/tk_gmm_down.1": {
                    "count": 4, "total_s": 1.0, "program": "jit_admit",
                    "text": "",
                },
            },
        },
        "requests": [
            # 1 + 99 before the trace, then 200 ticks in it.
            {"syncs": [(5.0, 100), (12.0, 100), (19.0, 100)]},
            # Admitted inside the traced part: its first token is the
            # admission's.
            {"syncs": [(15.0, 101), (25.0, 50)]},
        ],
    }
    us = common.load_named("layer_metrics", NEW_METRICS[0], REPO).read(run)
    assert us == pytest.approx(1e6 * 0.024 / 12)
    pct = common.load_named("layer_metrics", NEW_METRICS[1], REPO).read(run)
    need = 6 * 300 * (2 * 2 * 2**20 + 6 * 32 * 128 * 4)
    assert pct == pytest.approx(100 * need / (0.024 * peaks["hbm_bytes_s"]))
    for name in NEW_METRICS:
        read = common.load_named("layer_metrics", name, REPO).read
        assert read({**run, "trace": None}) is None
        kanana = json.loads(
            (REPO / "chipbench/configs/kanana-2-30b-a3b-7l.json").read_text()
        )
        assert read({**run, "conf": kanana}) is None
        bare = copy.deepcopy(run)
        del bare["trace"]["kernels"]["jit_tick_block/tk_kda_step.3"]
        del bare["trace"]["kernels"]["jit_tick_block/tk_kda_step.7"]
        assert read(bare) is None


def test_the_new_files_are_committed_with_the_benchmark():
    """Every file of ISSUE 41's step 7 is there and tracked or staged."""
    files = [
        f"chipbench/configs/{CONFIG}.json", f"chipbench/workloads/{CELL}.json",
        "chipbench/models/ling_decoder.py",
        "chipbench/reference/ling_decoder.py",
        "chipbench/loops/serve_state.py", "chipbench/kernels/kda.py",
        *(f"chipbench/layer_metrics/{m}.py" for m in NEW_METRICS),
    ]
    for f in files:
        assert (REPO / f).is_file(), f
    if not (REPO / ".git").exists():
        pytest.skip("an export, not a checkout")
    ignored = subprocess.run(
        ["git", "check-ignore", *files], cwd=REPO, capture_output=True,
        text=True,
    ).stdout.split()
    assert ignored == []
