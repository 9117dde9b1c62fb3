"""Every cell end to end at a toy size on the CPU, through the runner's
rehearsal path (which prints no device metric and no verdict under a
device's name); the runner's refusal to measure without a TPU; and the
comparison seen to fail when the timed path is broken underneath."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from toy import OPEN_LOOP_CELL, OPEN_LOOP_E2E, make_toy_root  # noqa: E402

from chipbench import run as runner  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
DRAIN = next(w["name"] for w in BENCH["workloads"] if w["traffic"] == "backlog")


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_toy_root(tmp_path_factory.mktemp("chipbench"))


def rehearse(root, cell, seed, capsys, seconds="0.5", trace="0"):
    rc = runner.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", seconds,
         "--trace", trace],
        root=root, rehearsal=True,
    )
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    return rc, lines


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
@pytest.mark.parametrize("cell", CELLS + [OPEN_LOOP_CELL])
def test_cell_end_to_end_at_toy_size(toy_root, cell, seed, capsys):
    bench = json.loads((toy_root / "BENCHMARK.json").read_text())
    rc, lines = rehearse(toy_root, cell, seed, capsys)
    assert rc == 0
    last = json.loads(lines[-1])
    # A rehearsal: counts and checks, nothing a driver could read as a
    # measurement on a device.
    assert last["rehearsal"] is True and last["cell"] == cell
    for key in ("correct", "metrics", "device"):
        assert key not in last
    assert last["checks_passed"] is True, lines
    assert last["attempted"] > 0 and last["failed"] == 0
    reported = [
        m["name"] for m in bench["end_to_end"]
        if "workloads" not in m or cell in m["workloads"]
    ]
    if cell == OPEN_LOOP_CELL:
        assert set(OPEN_LOOP_E2E) < set(reported)
    assert last["metric_names"] == sorted(reported)
    # Every line but the last keeps clear of what the driver reads.
    for line in lines[:-1]:
        obj = json.loads(line)
        assert not {"correct", "metrics", "device", "attempted"} & set(obj)
    compared = [json.loads(l)["compared"] for l in lines if '"compared"' in l]
    assert compared and all("limit" in c or "at_least" in c for c in compared)


def test_no_tpu_no_measurement(capsys):
    """Here JAX is held to the CPU: the runner prints no result and exits
    with another code than 0."""
    rc = runner.main(
        ["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"]
    )
    out = capsys.readouterr()
    assert rc == 3
    assert "refused" in out.err and "no fallback" in out.err
    assert not [l for l in out.out.splitlines() if "correct" in l]


def test_unknown_cell_is_refused(toy_root, capsys):
    rc, _ = rehearse(toy_root, "no-such-cell", 1, capsys)
    assert rc == 3


def test_broken_serving_path_is_not_correct(toy_root, capsys, monkeypatch):
    """A token altered where it is produced: the served tokens no longer
    follow the model, and the comparison with the reference says so."""
    import torchkafka_tpu.serve as serve

    honest = serve._pick_slots

    def altered(logits, key_data, idx, **kw):
        return (honest(logits, key_data, idx, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(serve, "_pick_slots", altered)
    rc, lines = rehearse(toy_root, DRAIN, 5, capsys)
    last = json.loads(lines[-1])
    assert rc == 0 and last["checks_passed"] is False
    assert failed_checks(lines) == ["served_logit_gap"]


def failed_checks(lines) -> list[str]:
    rows = [json.loads(l)["compared"] for l in lines if '"compared"' in l]
    return [c["check"] for c in rows if not c["ok"]]


def test_a_rarer_commit_is_not_correct(toy_root, capsys, monkeypatch):
    """At-least-once with commits four times rarer than the configuration
    states: every token is right and every offset is committed by the
    last flush, and the run is still not correct, because a crash would
    replay more than the configuration allows. (On the backlog, where the
    count between two commits does not hang on the wall clock.)"""
    import torchkafka_tpu.serve as serve

    honest = serve.StreamingGenerator.__init__

    def lazy(self, *a, commit_every=32, **kw):
        honest(self, *a, commit_every=4 * commit_every, **kw)

    monkeypatch.setattr(serve.StreamingGenerator, "__init__", lazy)
    rc, lines = rehearse(toy_root, DRAIN, 5, capsys, seconds="1.5")
    last = json.loads(lines[-1])
    assert rc == 0 and last["checks_passed"] is False
    failed = failed_checks(lines)
    assert failed and set(failed) <= {
        "completions_uncommitted_at_close", "completions_between_commits",
    }


def test_broken_training_step_is_not_correct(toy_root, capsys, monkeypatch):
    """A step that returns its state unchanged: the loss is still right,
    the gradient and the parameters' change are not."""
    import torchkafka_tpu.models as models

    honest = models.make_train_step

    def lazy(cfg, mesh, optimizer):
        init_fn, step_fn = honest(cfg, mesh, optimizer)

        def step(params, opt_state, tokens, mask):
            import jax

            keep = jax.tree.map(lambda a: a.copy(), (params, opt_state))
            _p, _o, loss = step_fn(params, opt_state, tokens, mask)
            return keep[0], keep[1], loss

        return init_fn, step

    monkeypatch.setattr(models, "make_train_step", lazy)
    rc, lines = rehearse(
        toy_root, "internlm2-1.8b.pretrain-4k-1chip", 5, capsys
    )
    last = json.loads(lines[-1])
    assert rc == 0 and last["checks_passed"] is False
    failed = {
        json.loads(l)["compared"]["check"] for l in lines
        if '"compared"' in l and not json.loads(l)["compared"]["ok"]
    }
    assert "change_after_3_steps" in failed
    assert "grad_norm_worst_leaf_gap" in failed
    assert "loss1_rel_gap" not in failed
