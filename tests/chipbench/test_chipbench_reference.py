"""Each plain reference against the program at a toy size on the CPU, and
the comparison seen to fail in a lower precision than the configuration
states: with the control (the reference in 8-bit floating point, put in
the program's place) and with the program itself run in bfloat16 against
limits set for float32."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from toy import make_toy_root  # noqa: E402

from chipbench import control  # noqa: E402
from chipbench import run as runner  # noqa: E402
from chipbench import weights as W  # noqa: E402

SERVE, TRAIN = "mistral7b.backlog-drain", "internlm2-1.8b.pretrain-4k-1chip"


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_toy_root(tmp_path_factory.mktemp("chipbench_ref"))


def readings(root, cell, seeds, capsys, seconds="0.3"):
    rc = control.main(
        ["--workload", cell, "--seeds", seeds, "--seconds", seconds,
         "--control", "1"], root=root, rehearsal=True,
    )
    rows = [
        json.loads(l)["reading"] for l in capsys.readouterr().out.splitlines()
        if l.startswith('{"reading"')
    ]
    return rc, rows


def test_serving_reference_holds_the_program_and_fails_the_control(
    toy_root, capsys
):
    # A window long enough to finish the toy's forty requests: the
    # comparison then has the longest of them to read.
    rc, rows = readings(toy_root, SERVE, "11,12", capsys, seconds="2")
    assert rc == 0 and len(rows) == 2
    for r in rows:
        gap = r["control"]["served_logit_gap"]
        assert r["correct"] and gap["program"] <= gap["limit"]
        # 8-bit operands put other tokens first, far below the
        # reference's best: more than three times the limit.
        assert gap["control"] > 3 * gap["limit"]


def test_training_reference_holds_the_program_and_fails_the_control(
    toy_root, capsys
):
    rc, rows = readings(toy_root, TRAIN, "11,12", capsys)
    assert rc == 0 and len(rows) == 2
    limits = json.loads(
        (toy_root / "chipbench/workloads" / f"{TRAIN}.json").read_text()
    )["check"]
    for r in rows:
        c = r["control"]
        assert r["correct"]
        assert c["loss1_rel_gap"]["program"] < limits["max_loss1_rel_gap"]
        assert c["grad_norm_worst_leaf_gap"]["program"] < limits["max_grad_norm_gap"]
        # The control fails the loss and the gradient, each by far.
        assert c["loss1_rel_gap"]["control"] > 10 * limits["max_loss1_rel_gap"]
        assert c["grad_norm_worst_leaf_gap"]["control"] > 10 * limits["max_grad_norm_gap"]


def test_the_program_in_bfloat16_fails_limits_set_for_float32(
    toy_root, tmp_path, capsys
):
    import shutil

    root = tmp_path / "bf16"
    shutil.copytree(toy_root, root)
    path = root / "chipbench/configs/internlm2-1.8b-1chip.json"
    conf = json.loads(path.read_text())
    conf["deployment"]["compute_dtype"] = "bfloat16"
    path.write_text(json.dumps(conf))
    rc = runner.main(
        ["--workload", TRAIN, "--seed", "4", "--seconds", "0.3", "--trace", "0"],
        root=root, rehearsal=True,
    )
    lines = capsys.readouterr().out.splitlines()
    assert rc == 0 and json.loads(lines[-1])["checks_passed"] is False
    failed = {
        json.loads(l)["compared"]["check"] for l in lines
        if '"compared"' in l and not json.loads(l)["compared"]["ok"]
    }
    assert "loss1_rel_gap" in failed or "grad_norm_worst_leaf_gap" in failed


@pytest.mark.parametrize("seed", [0, 9, 2**31 + 3])
def test_a_layer_drawn_alone_is_the_stacked_draw_s_layer(seed):
    import jax.numpy as jnp

    dims = W.Dims(hidden=64, layers=3, heads=2, kv_heads=1, head_dim=32,
                  ffn=48, vocab=96, rope_theta=1e4, rms_eps=1e-6)
    key = W.seed_key(seed)
    tree = W.serving_tree(key, dims)
    for layer in range(dims.layers):
        alone = W.draw_int8(key, dims, "w_down", layer)
        assert (np.asarray(tree["layers"]["w_down"]["q"][layer]) == np.asarray(alone)).all()
    assert tree["layers"]["wo"]["scale"].shape == (3, 1, 1, 64)
    assert tree["embed"]["scale"].shape == (96, 1)
    assert tree["lm_head"]["scale"].shape == (1, 96)
    train = W.training_tree(key, dims, jnp.bfloat16)
    alone = W.draw_normal(key, dims, "wq", 2, jnp.bfloat16)
    assert (np.asarray(train["layers"]["wq"][2]) == np.asarray(alone)).all()
    other = W.serving_tree(W.seed_key(seed + 1), dims)
    assert (np.asarray(other["embed"]["q"]) != np.asarray(tree["embed"]["q"])).any()
    # Dequantised, a tensor has the initialiser's standard deviation.
    w = np.asarray(tree["layers"]["w_gate"]["q"], np.float32) * W.int8_scale(dims, "w_gate")
    assert w.std() == pytest.approx(1 / np.sqrt(dims.hidden), rel=0.05)
