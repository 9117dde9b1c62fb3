"""The crash matrix: a REAL subprocess SIGKILLed at every registered
crash point, with the at-least-once invariants asserted at each one.

Each case: the parent hosts the broker (``BrokerServer`` over an
``InMemoryBroker``), spawns ``_crash_worker.py`` with
``TORCHKAFKA_CRASHPOINT=<point>:<at>:kill:<marker>``, and waits for the
corpse. The child writes the marker file atomically just before
``os.kill(SIGKILL)``, so the parent can prove the death happened AT the
armed point (a child that exited for any other reason fails the test).
Then the parent audits the state the death left behind, runs the SAME
worker logic in-process as the recovery incarnation, and audits again:

- commit ledger: the committed watermark NEVER covers a prompt without a
  durable completion (or DLQ copy) — loss is impossible, duplicates are
  bounded and byte-identical;
- DLQ/watermark discipline: a poison record's offset retires only after
  its DLQ copy is durable; redelivery re-quarantines idempotently;
- journal: a torn journal write is invisible (recovery parses the
  previous complete file) and partial generations warm-resume to
  byte-identical completions;
- checkpoint: a torn checkpoint step is invisible (restore falls back to
  the newest complete step) and commit-then-crash-before-save resumes by
  seeking BACK to the checkpoint watermark.

Completeness is enforced: a crash point present in
``REGISTERED_CRASH_POINTS`` but absent from the matrix fails the suite
(``test_matrix_covers_every_registered_point``). The full matrix is
``chaos`` + ``slow`` (run it with ``-m chaos``); one representative
serve-mode and ckpt-mode death stay in tier-1.
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import torchkafka_tpu as tk
from torchkafka_tpu.checkpoint.manager import StreamCheckpointer
from torchkafka_tpu.journal import DecodeJournal
from torchkafka_tpu.resilience.crashpoint import REGISTERED_CRASH_POINTS
from torchkafka_tpu.source.records import TopicPartition

from tests import _crash_worker as W

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_crash_worker.py")

# point -> (worker mode, Nth arrival to kill at). The arrival counts are
# chosen so the death lands mid-stream: some work committed, some in
# flight, some not yet fetched.
MATRIX: dict[str, tuple[str, int]] = {
    "post_poll": ("serve", 2),
    "pre_commit": ("serve", 2),
    "mid_tick": ("serve", 6),
    "post_dlq_pre_retire": ("serve", 1),
    "journal_mid_write": ("serve", 3),
    "post_commit_pre_checkpoint": ("ckpt", 2),
    "checkpoint_mid_write": ("ckpt", 2),
    # Process-fleet liveness windows (fleet/proc.py + fleet/supervisor.py):
    # heartbeats run in loop mode there, one renewal per pump, so the
    # arrival count tracks serving progress — 12 lands mid-stream with
    # completions emitted and work in flight.
    "heartbeat_pre_send": ("fleet", 12),
    "journal_handoff_pre_load": ("fleet", 2),
    "lease_expired_pre_fence": ("sweep", 1),
    # Exactly-once transactional serving (serve.py exactly_once=True over
    # TransactionalProducer). Arrival counts land each death mid-stream:
    # begin 2 = the second window's (empty) transaction just opened;
    # produce 3 = the second window holds one output, more coming;
    # commit 2 = the second window fully staged (records + offsets),
    # the atomic flip not yet asked for; post-commit 2 = the second
    # window committed ON the broker, ack never observed.
    "txn_begin_post": ("txn", 2),
    "txn_produce_mid": ("txn", 3),
    "txn_pre_commit": ("txn", 2),
    "txn_post_commit_pre_ack": ("txn", 2),
    # Broker-side durability windows (source/wal.py + source/memory.py):
    # the CHILD is the broker here, SIGKILLed inside its own WAL/commit
    # code while the parent drives transactional traffic. Arrival counts
    # land mid-stream against the deterministic append schedule: prime =
    # 14 appends (2 topics + 12 produces), join 15, init_pid 16, then 5
    # per 3-record batch (begin + 3 produces + commit marker) — 24 dies
    # writing batch 2's second produce (batch 1 committed), 26 dies ON
    # batch 2's commit-marker append; the marker points count commit_txn
    # arrivals, so 2 = batch 2's atomic flip.
    "wal_append_mid": ("broker", 24),
    "wal_pre_fsync": ("broker", 26),
    "txn_marker_pre_append": ("broker", 2),
    "txn_marker_post_append_pre_ack": ("broker", 2),
    # Dies inside the startup REPLAY over a WAL a previous broker life
    # left behind (event 10 is mid-prime): recovery must be re-runnable.
    "recovery_mid_replay": ("broker", 10),
    # Disaggregated prefill (fleet/prefill.py + serve.py adoption): a
    # prefill worker dying between harvest and publish (arrival 2 = the
    # second handoff's publish window, the first already on the transfer
    # plane), and an exactly-once decode replica dying between an
    # adopted payload's upload and the slot's activation.
    "prefill_handoff_pre_publish": ("dgpre", 2),
    "decode_adopt_pre_activate": ("dgdec", 2),
    # Autoscale supervisor windows (fleet/supervisor.py scale()): the
    # SUPERVISOR is SIGKILLed mid-scale-event — at the first scale-up
    # spawn decision and at the first scale-down drain order. The child
    # hosts a WAL-backed fleet, so the broker truth the death leaves
    # behind is recoverable and a fresh supervisor converges to the
    # controller's target.
    "scale_up_pre_spawn": ("scaleup", 1),
    "scale_down_mid_drain": ("scaledown", 1),
    # Replicated-cell windows (source/replication.py + source/cluster.py):
    # the CHILD hosts a whole 1-leader + 2-follower quorum cell and the
    # armed kill takes the entire cell process. Ship arrivals track the
    # leader's WAL appends one-for-one (the replicator ships every
    # appended frame), so the broker-mode schedule carries over: 24 dies
    # after the leader appended batch 2's second produce but before any
    # follower saw it (unacked — promotion must not surface it as
    # committed), 26 dies after a MAJORITY holds batch 2's commit marker
    # but before the client's ack (promotion must replay it and answer
    # the retry idempotently). election_pre_promote fires inside the
    # election the child runs against itself (kill_leader trigger file),
    # AFTER the epoch bump fenced the old leader but BEFORE the winner
    # promoted — the parent's offline re-election must converge on the
    # same durable prefix.
    # Rolling weight hot-swap windows (fleet/rollout.py + serve.py
    # swap_params): one exactly-once replica executing a scripted
    # canary→swap rollout. Arrival 1 everywhere — each window is
    # reached exactly once per rollout: the canary's verdict fires the
    # pump the first completion batch retires (the slice == slots, so
    # compared jumps 0→n in one sweep, BEFORE any swap attempt);
    # pre_swap/mid_apply fire inside the quiesced swap_params call,
    # either side of the journal's durable version flip.
    "canary_pre_verdict": ("rollout", 1),
    "rollout_pre_swap": ("rollout", 1),
    "swap_mid_apply": ("rollout", 1),
    # Online draft distillation windows (distill/trainer.py +
    # serve_spec.py swap_draft_params): pre_publish arrival 1 = the
    # trainer's FIRST checkpoint publish (draft trained, nothing on the
    # checkpoint plane yet — the publish dies whole); pre_apply arrival
    # 1 = the serving side's live draft swap, after validation, before
    # any tree is applied (the incumbent draft must keep serving).
    "distill_pre_publish": ("distill", 1),
    "draft_swap_pre_apply": ("distill", 1),
    "repl_frame_pre_ship": ("cell", 24),
    "repl_frame_post_majority_pre_ack": ("cell", 26),
    "election_pre_promote": ("cell", 1),
}

# The tier-1 representative subset: one mid-serve death (commit path) and
# one mid-checkpoint death (torn save). Everything else — the txn and
# broker-side points included — is chaos+slow (tier-1 wall-clock is
# budgeted; scenarios 18/19 in test_harness keep a tier-1 exactly-once
# SIGKILL and a tier-1 broker crash-recovery anyway).
TIER1 = ("pre_commit", "checkpoint_mid_write")


def _spawn(mode: str, port: int, workdir: str, point: str, at: int):
    env = dict(os.environ)  # the child configures CPU itself
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    marker = os.path.join(workdir, "marker")
    env["TORCHKAFKA_CRASHPOINT"] = f"{point}:{at}:kill:{marker}"
    log = open(os.path.join(workdir, "child.log"), "wb")
    proc = subprocess.Popen(
        [sys.executable, WORKER, mode, "localhost", str(port), workdir],
        env=env, stdout=log, stderr=subprocess.STDOUT,
    )
    log.close()
    return proc, marker


def _reap_group(broker, group_id: str) -> None:
    # The in-memory broker has no session timeout; evicting the corpse's
    # membership here is exactly what Kafka's session.timeout.ms reaper
    # does to a SIGKILLed client — without it the dead member would own
    # its partitions forever and recovery could never be assigned them.
    grp = broker._groups.get(group_id)
    for member in list(grp.members) if grp else ():
        broker.leave(group_id, member)


def _outputs_by_key(broker):
    """Output-topic records grouped by prompt key → list of token arrays."""
    tp = TopicPartition(W.OUT_TOPIC, 0)
    out: dict[bytes, list] = {}
    for rec in broker.fetch(tp, 0, 100000):
        out.setdefault(rec.key, []).append(
            np.frombuffer(rec.value, dtype=np.int32)
        )
    return out


def _committed(broker, group=W.GROUP):
    return {
        p: broker.committed(group, TopicPartition(W.PROMPT_TOPIC, p)) or 0
        for p in range(W.PARTS)
    }


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """The no-kill run: key → completion tokens (the poison key gets
    dead-lettered, so it has no entry)."""
    broker = tk.InMemoryBroker()
    W.prime_topics(broker)
    W.run_serve(broker, str(tmp_path_factory.mktemp("crash-ref")))
    outs = _outputs_by_key(broker)
    assert set(outs) == {str(i).encode() for i in range(W.N_PROMPTS)}
    return {k: v[0] for k, v in outs.items()}


def _run_serve_case(tmp_path, reference, point: str, at: int):
    broker = tk.InMemoryBroker()
    W.prime_topics(broker)
    workdir = str(tmp_path / point)
    os.makedirs(workdir, exist_ok=True)
    with tk.BrokerServer(broker) as server:
        proc, marker = _spawn("serve", server.port, workdir, point, at)
        proc.wait(timeout=180)
    with open(os.path.join(workdir, "child.log"), "rb") as f:
        log = f.read().decode(errors="replace")
    assert proc.returncode == -signal.SIGKILL, (
        f"worker exited {proc.returncode}, not SIGKILL — the armed point "
        f"{point!r} was never reached?\n{log}"
    )
    with open(marker) as f:
        assert f.read().strip() == f"{point}:{at}"
    _reap_group(broker, W.GROUP)

    # ---- invariants at the moment of death --------------------------------
    committed = _committed(broker)
    outs = _outputs_by_key(broker)
    dlq = broker.fetch(TopicPartition(W.DLQ_TOPIC, 0), 0, 1000)
    poison_tp, poison_off = 0, W.N_PROMPTS // W.PARTS
    for p, wm in committed.items():
        end = broker.end_offset(TopicPartition(W.PROMPT_TOPIC, p))
        assert wm <= end
        for off in range(wm):
            # Every committed offset is covered by durable output (or, for
            # the poison record, a durable DLQ copy): commit-past-loss is
            # the invariant every crash point must preserve.
            if (p, off) == (poison_tp, poison_off):
                assert dlq, "poison offset committed with no DLQ copy"
                continue
            key = str(off * W.PARTS + p).encode()
            assert key in outs, (
                f"committed {p}:{off} (prompt {key}) has no durable output"
            )
    # The journal the corpse left is parseable — a torn tmp write is
    # invisible (journal_mid_write kills INSIDE the tmp write to pin it).
    jpath = os.path.join(workdir, "journal.json")
    journal_entries = DecodeJournal.load(jpath)
    if point == "journal_mid_write":
        assert os.path.exists(jpath + ".tmp"), "expected the torn tmp"

    # ---- recovery: same worker logic, in-process --------------------------
    W.run_serve(broker, workdir)

    outs = _outputs_by_key(broker)
    assert set(outs) == set(reference), (
        "lost completions after recovery: "
        f"{set(reference) ^ set(outs)}"
    )
    for key, copies in outs.items():
        for c in copies:  # duplicates allowed, divergence not
            np.testing.assert_array_equal(c, reference[key], err_msg=str(key))
    dlq = broker.fetch(TopicPartition(W.DLQ_TOPIC, 0), 0, 1000)
    assert len(dlq) >= 1  # quarantined at least once (maybe re-quarantined)
    assert all(r.value == W.POISON for r in dlq)
    assert b"poison" not in outs  # never served as a completion
    final = _committed(broker)
    for p in range(W.PARTS):
        assert final[p] == broker.end_offset(
            TopicPartition(W.PROMPT_TOPIC, p)
        ), f"partition {p} not fully committed after recovery"
    return journal_entries


def _run_ckpt_case(tmp_path, point: str, at: int):
    broker = tk.InMemoryBroker()
    W.prime_topics(broker)
    workdir = str(tmp_path / point)
    os.makedirs(workdir, exist_ok=True)
    with tk.BrokerServer(broker) as server:
        proc, marker = _spawn("ckpt", server.port, workdir, point, at)
        proc.wait(timeout=180)
    with open(os.path.join(workdir, "child.log"), "rb") as f:
        log = f.read().decode(errors="replace")
    assert proc.returncode == -signal.SIGKILL, (
        f"worker exited {proc.returncode}; point {point!r} never reached?"
        f"\n{log}"
    )
    with open(marker) as f:
        assert f.read().strip() == f"{point}:{at}"
    _reap_group(broker, "ckpt")

    root = os.path.join(workdir, "ckpts")
    ckptr = StreamCheckpointer(root, keep=16)
    committed = _committed(broker, group="ckpt")

    # ---- invariants at the moment of death --------------------------------
    # The first chunk's save (step 0) completed before the armed second
    # arrival killed the child, so restore MUST fall back to it — the torn
    # or missing step is invisible.
    steps = ckptr.steps()
    assert steps, "no complete checkpoint survived the death"
    state, offsets, step = ckptr.restore(step=None)
    assert step == steps[-1]
    if point == "checkpoint_mid_write":
        # Payload + offsets written, rename pending: the torn step must be
        # on disk as .tmp and excluded from steps().
        torn = [d for d in os.listdir(root) if d.endswith(".tmp")]
        assert torn, "expected a torn .tmp step dir"
        assert int(torn[0].split(".")[0]) not in steps
    for tp, off in offsets.items():
        # The checkpoint is never AHEAD of the commit log (commit happens
        # first); resume seeks BACK to the checkpoint — re-consume, never
        # lose.
        assert off <= committed[tp.partition], (tp, off, committed)
    if point == "post_commit_pre_checkpoint":
        # The defining window: the second commit landed, its save did not.
        assert sum(committed.values()) > sum(offsets.values())

    # ---- recovery: same worker logic, in-process --------------------------
    W.run_ckpt(broker, workdir)
    final_state, final_offsets, final_step = ckptr.restore(step=None)
    assert final_step > step
    for tp, off in final_offsets.items():
        assert off == broker.end_offset(tp), (tp, off)
    # Folded counts are at-least-once: every record folded >= 1 time
    # across incarnations; the recovery's resume-seek re-consumed the
    # commit/checkpoint gap rather than skipping it.
    assert int(final_state["folded"]) >= (
        sum(final_offsets.values()) - sum(offsets.values())
    )


def _committed_outputs(broker, topic, parts=1, raw=False):
    """Committed-view (read_committed) records of ``topic`` by key —
    the downstream consumer's truth in exactly-once mode. ``raw=True``
    keeps byte values (DLQ payloads are not token arrays)."""
    out: dict[bytes, list] = {}
    for p in range(parts):
        recs, _ = broker.fetch_stable(TopicPartition(topic, p), 0, 100000)
        for rec in recs:
            out.setdefault(rec.key, []).append(
                rec.value if raw else np.frombuffer(rec.value, dtype=np.int32)
            )
    return out


def _run_txn_case(tmp_path, reference, point: str, at: int):
    """The exactly-once matrix: a real subprocess serving in
    transactional mode, SIGKILLed at a txn crash point. The at-least-
    once audits become exactly-once ones: at death AND after recovery,
    the COMMITTED view of the output topic holds each completion at
    most / exactly once (duplicates == 0, not bounded), every committed
    offset is covered by a committed output or committed DLQ copy, and
    a commit forged from the corpse's stale epoch bounces off the fence
    with the watermark untouched."""
    from torchkafka_tpu.errors import ProducerFencedError

    broker = tk.InMemoryBroker()
    W.prime_topics(broker)
    workdir = str(tmp_path / point)
    os.makedirs(workdir, exist_ok=True)
    with tk.BrokerServer(broker) as server:
        proc, marker = _spawn("txn", server.port, workdir, point, at)
        proc.wait(timeout=180)
    with open(os.path.join(workdir, "child.log"), "rb") as f:
        log = f.read().decode(errors="replace")
    assert proc.returncode == -signal.SIGKILL, (
        f"worker exited {proc.returncode}, not SIGKILL — the armed point "
        f"{point!r} was never reached?\n{log}"
    )
    with open(marker) as f:
        assert f.read().strip() == f"{point}:{at}"
    _reap_group(broker, W.GROUP)

    # ---- exactly-once invariants at the moment of death -------------------
    committed = _committed(broker)
    outs = _committed_outputs(broker, W.OUT_TOPIC)
    dlq = _committed_outputs(broker, W.DLQ_TOPIC, raw=True)
    poison_tp, poison_off = 0, W.N_PROMPTS // W.PARTS
    for key, copies in outs.items():
        assert len(copies) == 1, (
            f"duplicate committed output for {key!r} at death"
        )
        np.testing.assert_array_equal(copies[0], reference[key])
    for p, wm in committed.items():
        assert wm <= broker.end_offset(TopicPartition(W.PROMPT_TOPIC, p))
        for off in range(wm):
            if (p, off) == (poison_tp, poison_off):
                assert dlq, "poison offset committed with no committed DLQ copy"
                continue
            key = str(off * W.PARTS + p).encode()
            assert key in outs, (
                f"committed {p}:{off} (prompt {key}) has no committed output"
            )
    # The corpse's journal parses (same torn-write contract as serve mode).
    DecodeJournal.load(os.path.join(workdir, "journal.json"))

    # ---- recovery: same worker logic, in-process --------------------------
    # Constructing the recovery TransactionalProducer re-inits the
    # transactional id: epoch bump, corpse's open transaction aborted.
    W.run_serve_txn(broker, workdir)

    outs = _committed_outputs(broker, W.OUT_TOPIC)
    assert set(outs) == set(reference), (
        "lost completions after recovery: "
        f"{set(reference) ^ set(outs)}"
    )
    for key, copies in outs.items():
        # THE exactly-once assertion: not bounded, zero duplicates.
        assert len(copies) == 1, (
            f"{len(copies)} committed copies of {key!r} after recovery"
        )
        np.testing.assert_array_equal(copies[0], reference[key], err_msg=str(key))
    dlq = _committed_outputs(broker, W.DLQ_TOPIC, raw=True)
    assert list(dlq) == [b"poison"]
    assert len(dlq[b"poison"]) == 1, "poison dead-lettered more than once"
    assert b"poison" not in outs
    final = _committed(broker)
    for p in range(W.PARTS):
        assert final[p] == broker.end_offset(
            TopicPartition(W.PROMPT_TOPIC, p)
        ), f"partition {p} not fully committed after recovery"

    # ---- the fence: a forged stale-epoch commit bounces -------------------
    pid, cur_epoch = broker.init_producer_id(W.TXN_ID)
    wm_before = _committed(broker)
    with pytest.raises(ProducerFencedError):
        broker.begin_txn(pid, cur_epoch - 1)
    with pytest.raises(ProducerFencedError):
        broker.commit_txn(pid, cur_epoch - 1)
    assert _committed(broker) == wm_before, "forged commit moved the watermark"


@pytest.fixture(scope="module")
def fleet_reference(tmp_path_factory):
    """The no-kill fleet-mode run: key → completion tokens."""
    broker = tk.InMemoryBroker()
    W.prime_fleet_topics(broker)
    rc = W.run_fleet(broker, str(tmp_path_factory.mktemp("fleet-ref")))
    assert rc == 0
    outs = _fleet_outputs(broker)
    assert set(outs) == {str(i).encode() for i in range(W.FLEET_PROMPTS)}
    return {k: v[0] for k, v in outs.items()}


def _fleet_outputs(broker):
    tp = TopicPartition(W.FLEET_OUT, 0)
    out: dict[bytes, list] = {}
    for rec in broker.fetch(tp, 0, 100000):
        out.setdefault(rec.key, []).append(
            np.frombuffer(rec.value, dtype=np.int32)
        )
    return out


def _run_fleet_case(tmp_path, fleet_reference, point: str, at: int):
    """A process-fleet replica SIGKILLed at a liveness crash point: the
    at-least-once audit (commit never covers a prompt without durable
    output), then recovery as a FRESH incarnation (new member id, same
    shared journal dir — the startup scan IS the cross-process
    handoff), byte-identical and fully committed."""
    broker = tk.InMemoryBroker()
    W.prime_fleet_topics(broker)
    workdir = str(tmp_path / point)
    os.makedirs(workdir, exist_ok=True)
    with tk.BrokerServer(broker) as server:
        proc, marker = _spawn("fleet", server.port, workdir, point, at)
        proc.wait(timeout=180)
    with open(os.path.join(workdir, "child.log"), "rb") as f:
        log = f.read().decode(errors="replace")
    assert proc.returncode == -signal.SIGKILL, (
        f"worker exited {proc.returncode}, not SIGKILL — point {point!r} "
        f"never reached?\n{log}"
    )
    with open(marker) as f:
        assert f.read().strip() == f"{point}:{at}"
    _reap_group(broker, W.FLEET_GROUP)

    # ---- invariants at the moment of death ------------------------------
    outs = _fleet_outputs(broker)
    for p in range(W.FLEET_PARTS):
        tp = TopicPartition(W.FLEET_TOPIC, p)
        wm = broker.committed(W.FLEET_GROUP, tp) or 0
        assert wm <= broker.end_offset(tp)
        for off in range(wm):
            key = str(off * W.FLEET_PARTS + p).encode()
            assert key in outs, (
                f"committed {p}:{off} (prompt {key}) has no durable output"
            )
    # The corpse's journal parses (or is absent) — never wedges recovery.
    DecodeJournal.load(os.path.join(workdir, "journals", "m0.json"))

    # ---- recovery: a fresh incarnation, in-process ----------------------
    rc = W.run_fleet(broker, workdir, member="m1")
    assert rc == 0
    outs = _fleet_outputs(broker)
    assert set(outs) == set(fleet_reference), (
        f"lost completions: {set(fleet_reference) ^ set(outs)}"
    )
    for key, copies in outs.items():
        for c in copies:  # duplicates allowed, divergence not
            np.testing.assert_array_equal(
                c, fleet_reference[key], err_msg=str(key)
            )
    for p in range(W.FLEET_PARTS):
        tp = TopicPartition(W.FLEET_TOPIC, p)
        assert (broker.committed(W.FLEET_GROUP, tp) or 0) \
            == broker.end_offset(tp), f"partition {p} not fully committed"


def _run_sweep_case(tmp_path, point: str, at: int):
    """A supervisor dies BETWEEN observing an expired lease and fencing:
    the zombie stays a member — yet its own post-mortem commit
    self-fences (commit-time reap) with the watermark unmoved, and a
    recovery sweep finishes the fencing idempotently."""
    from torchkafka_tpu.errors import CommitFailedError
    from torchkafka_tpu.fleet.supervisor import sweep_expired

    broker = tk.InMemoryBroker(session_timeout_s=W.SWEEP_TIMEOUT_S)
    W.prime_fleet_topics(broker)
    workdir = str(tmp_path / point)
    os.makedirs(workdir, exist_ok=True)
    with tk.BrokerServer(broker) as server:
        proc, marker = _spawn("sweep", server.port, workdir, point, at)
        proc.wait(timeout=120)
    with open(os.path.join(workdir, "child.log"), "rb") as f:
        log = f.read().decode(errors="replace")
    assert proc.returncode == -signal.SIGKILL, (
        f"sweeper exited {proc.returncode}; point {point!r} never "
        f"reached?\n{log}"
    )
    with open(marker) as f:
        assert f.read().strip() == f"{point}:{at}"

    # ---- the window: observed-expired, not yet fenced -------------------
    info = broker.membership(W.SWEEP_GROUP)
    assert info["members"] == ["zombie"], info
    assert info["leases"]["zombie"] <= 0
    join_gen = info["generation"]
    # The zombie's own commit self-fences — watermark untouched.
    tp = TopicPartition(W.FLEET_TOPIC, 0)
    with pytest.raises(CommitFailedError):
        broker.commit(W.SWEEP_GROUP, {tp: 1}, member_id="zombie",
                      generation=join_gen)
    assert broker.committed(W.SWEEP_GROUP, tp) is None
    assert "zombie" in broker.membership(W.SWEEP_GROUP)["fenced"]

    # ---- recovery: the sweep is idempotent; the group serves on --------
    assert sweep_expired(broker, W.SWEEP_GROUP) == []
    c = tk.MemoryConsumer(broker, W.FLEET_TOPIC, group_id=W.SWEEP_GROUP,
                          member_id="fresh")
    got = []
    while True:
        records = c.poll(max_records=64, timeout_ms=100)
        if not records:
            break
        got.extend(records)
        c.commit()
    c.close()
    assert len(got) == W.FLEET_PROMPTS
    for p in range(W.FLEET_PARTS):
        tp = TopicPartition(W.FLEET_TOPIC, p)
        assert broker.committed(W.SWEEP_GROUP, tp) == broker.end_offset(tp)


def _bw_committed_outputs(broker):
    """read_committed view of the broker-matrix output topic, by key."""
    out: dict[bytes, list[bytes]] = {}
    recs, _ = broker.fetch_stable(TopicPartition(W.BW_OUT, 0), 0, 100000)
    for rec in recs:
        out.setdefault(rec.key, []).append(rec.value)
    return out


def _bw_audit(broker, *, complete: bool) -> None:
    """The exactly-once invariants over a recovered broker: every
    committed output at most (``complete``: exactly) one copy per key
    and byte-correct, every committed source offset covered by a
    committed output, no unsettled transaction gating the LSO."""
    outs = _bw_committed_outputs(broker)
    expected = {
        str(i).encode(): W.bw_transform(f"prompt-{i:02d}".encode())
        for i in range(W.BW_PROMPTS)
    }
    for key, copies in outs.items():
        assert len(copies) == 1, (
            f"{len(copies)} committed copies of {key!r}"
        )
        assert copies[0] == expected[key], key
    for p in range(W.BW_PARTS):
        tp = TopicPartition(W.BW_TOPIC, p)
        wm = broker.committed(W.BW_GROUP, tp) or 0
        end = broker.end_offset(tp)
        assert wm <= end
        for off in range(wm):
            key = str(off * W.BW_PARTS + p).encode()
            assert key in outs, (
                f"committed {p}:{off} (prompt {key}) has no committed "
                "output — the offset/output atom split"
            )
        if complete:
            assert wm == end, f"partition {p} not fully committed"
    if complete:
        assert set(outs) == set(expected), (
            "lost prompts: ", set(expected) - set(outs),
        )
    # Every transaction settled at recovery: nothing gates the LSO.
    for topic, parts in ((W.BW_TOPIC, W.BW_PARTS), (W.BW_OUT, 1)):
        for p in range(parts):
            tp = TopicPartition(topic, p)
            assert broker.last_stable_offset(tp) == broker.end_offset(tp)


def _run_broker_case(tmp_path, point: str, at: int):
    """The broker is the corpse: a real subprocess hosting a WAL-backed
    ``InMemoryBroker`` is SIGKILLed inside its own durability code while
    the parent drives a transactional consume-transform-produce workload
    against it (or, for ``recovery_mid_replay``, inside its startup
    replay over a WAL a previous life built). The parent audits by
    RECOVERING the wal dir in-process: exactly-once invariants at death,
    a full re-drive to completion, and recovery idempotence."""
    from torchkafka_tpu.errors import BrokerUnavailableError

    workdir = str(tmp_path / point)
    os.makedirs(workdir, exist_ok=True)
    wal_dir = os.path.join(workdir, "wal")

    if point == "recovery_mid_replay":
        # A previous broker life builds the WAL in-process: a full
        # committed drive plus a DANGLING open transaction, then an
        # unclean end (no close — the log tail is whatever durability
        # left). The armed child then dies replaying event `at`.
        prior = tk.InMemoryBroker(wal_dir=wal_dir, wal_durability="commit")
        W.prime_bw_topics(prior)
        assert W.drive_bw_txn(prior) is True
        pid, epoch = prior.init_producer_id(W.BW_TXN_ID)
        prior.begin_txn(pid, epoch)
        prior.txn_produce(pid, epoch, W.BW_OUT, b"dangling", partition=0)
        del prior  # crash: never closed, never flushed
        proc, marker = _spawn("broker", 0, workdir, point, at)
        proc.wait(timeout=120)
        assert not os.path.exists(os.path.join(workdir, "port")), (
            "the recovering broker served before finishing replay"
        )
        drove = False
    else:
        proc, marker = _spawn("broker", 0, workdir, point, at)
        port_path = os.path.join(workdir, "port")
        deadline = time.monotonic() + 60
        while not os.path.exists(port_path):
            if proc.poll() is not None:
                break
            if time.monotonic() > deadline:
                raise TimeoutError("broker child never published a port")
            time.sleep(0.01)
        assert proc.poll() is None, "broker died before serving"
        with open(port_path) as f:
            port = int(f.read())
        client = tk.BrokerClient("localhost", port, timeout_s=10)
        drove = False
        try:
            W.prime_bw_topics(client)
            drove = W.drive_bw_txn(client)
        except BrokerUnavailableError:
            pass
        finally:
            client.close()
        proc.wait(timeout=120)
        assert drove is False, (
            f"workload completed without the broker dying — arrival "
            f"count {at} for {point!r} is past the schedule"
        )
    with open(os.path.join(workdir, "child.log"), "rb") as f:
        log = f.read().decode(errors="replace")
    assert proc.returncode == -signal.SIGKILL, (
        f"broker exited {proc.returncode}, not SIGKILL — point {point!r} "
        f"never reached?\n{log}"
    )
    with open(marker) as f:
        assert f.read().strip() == f"{point}:{at}"

    # ---- invariants at the moment of death (recover the corpse's WAL) ----
    recovered = tk.InMemoryBroker(wal_dir=wal_dir, wal_durability="commit")
    info = recovered.recovery_info
    assert info is not None and info["replayed_events"] > 0
    if point == "wal_append_mid":
        # The armed kill fired INSIDE a frame body: the torn tail must
        # have been detected and truncated, never replayed.
        assert info["truncated_bytes"] > 0, info
    _bw_audit(recovered, complete=False)

    # ---- recovery: re-drive the same workload to completion -------------
    _reap_group(recovered, W.BW_GROUP)
    if point == "recovery_mid_replay":
        # The prior life fully committed its drive: the re-drive just
        # confirms nothing re-delivers and the dangling txn left no
        # committed trace.
        assert b"dangling" not in [
            r.value
            for r in recovered.fetch_stable(
                TopicPartition(W.BW_OUT, 0), 0, 100000
            )[0]
        ]
    assert W.drive_bw_txn(recovered, member="drv-recovery") is True
    _bw_audit(recovered, complete=True)
    recovered.close()

    # ---- recovery is idempotent: a second recovery reproduces the state --
    again = tk.InMemoryBroker(wal_dir=wal_dir, wal_durability="commit")
    assert again.recovery_info["truncated_bytes"] == 0  # repaired already
    _bw_audit(again, complete=True)
    for p in range(W.BW_PARTS):
        tp = TopicPartition(W.BW_TOPIC, p)
        assert again.end_offset(tp) == recovered.end_offset(tp)
        assert again.committed(W.BW_GROUP, tp) == \
            recovered.committed(W.BW_GROUP, tp)
    again.close()


def _elect_offline(workdir: str) -> str:
    """The parent's stand-in for the election a dead cell never finished:
    scan the FOLLOWER WALs (the leader's disk is the casualty — that is
    the drill) and return the member dir holding the longest clean frame
    prefix, exactly the candidate the in-process election would promote.
    Majority-acked frames are on >= quorum replicas, so the longest
    follower prefix holds every frame any client was ever acked."""
    from torchkafka_tpu.source import wal as walmod

    cell_dir = os.path.join(workdir, "cell")
    best, best_n = None, -1
    for i in range(1, W.CELL_REPLICAS):
        d = os.path.join(cell_dir, f"member-{i:02d}")
        events, _ = walmod.replay(d, repair=False)
        if len(events) > best_n:
            best, best_n = d, len(events)
    assert best is not None, "no follower WAL to promote"
    return best


def _run_cell_case(tmp_path, point: str, at: int):
    """The whole CELL is the corpse: a subprocess hosting a 1-leader +
    2-follower quorum cell is SIGKILLed inside the leader's ship path
    (mid-replication windows) or inside its own kill_leader election
    (``election_pre_promote``), while the parent drives the same
    transactional workload as the broker matrix. The parent audits by
    running the election OFFLINE — promote the longest follower WAL
    through broker recovery — and asserting the exactly-once invariants,
    a full re-drive, and promotion idempotence."""
    from torchkafka_tpu.errors import BrokerUnavailableError

    workdir = str(tmp_path / point)
    os.makedirs(workdir, exist_ok=True)
    proc, marker = _spawn("cell", 0, workdir, point, at)
    port_path = os.path.join(workdir, "port")
    deadline = time.monotonic() + 60
    while not os.path.exists(port_path):
        if proc.poll() is not None:
            break
        if time.monotonic() > deadline:
            raise TimeoutError("cell child never published a port")
        time.sleep(0.01)
    assert proc.poll() is None, "cell died before serving"
    with open(port_path) as f:
        port = int(f.read())
    client = tk.BrokerClient("localhost", port, timeout_s=10)
    drove = False
    try:
        W.prime_bw_topics(client)
        drove = W.drive_bw_txn(client)
    except BrokerUnavailableError:
        pass
    finally:
        client.close()
    if point == "election_pre_promote":
        # The armed point is NOT on the serve path: the workload must
        # complete first, then the parent orders the leader-kill drill
        # and the child dies inside its own election.
        assert drove is True, "workload should complete before the drill"
        trigger = os.path.join(workdir, "kill_leader")
        with open(trigger + ".tmp", "w") as f:
            f.write("now\n")
        os.replace(trigger + ".tmp", trigger)
        proc.wait(timeout=120)
    else:
        proc.wait(timeout=120)
        assert drove is False, (
            f"workload completed without the cell dying — arrival "
            f"count {at} for {point!r} is past the schedule"
        )
    with open(os.path.join(workdir, "child.log"), "rb") as f:
        log = f.read().decode(errors="replace")
    assert proc.returncode == -signal.SIGKILL, (
        f"cell exited {proc.returncode}, not SIGKILL — point {point!r} "
        f"never reached?\n{log}"
    )
    with open(marker) as f:
        assert f.read().strip() == f"{point}:{at}"

    # ---- promotion: elect the longest follower prefix, recover it ------
    winner_dir = _elect_offline(workdir)
    if point == "repl_frame_pre_ship":
        # The leader's own WAL holds the frame that never shipped; the
        # promoted follower must NOT — the mutation was never acked.
        # (Checked BEFORE promotion: recovery may legitimately append a
        # txn_abort repair marker to the winner's WAL.)
        from torchkafka_tpu.source import wal as walmod

        leader_dir = os.path.join(workdir, "cell", "member-00")
        leader_events, _ = walmod.replay(leader_dir, repair=False)
        winner_events, _ = walmod.replay(winner_dir, repair=False)
        assert len(leader_events) > len(winner_events), (
            "pre-ship death should leave the leader ahead of every "
            "follower"
        )
        # And the follower log is a strict PREFIX of the leader's.
        assert leader_events[: len(winner_events)] == winner_events
    promoted = tk.InMemoryBroker(wal_dir=winner_dir, wal_durability="commit")
    info = promoted.recovery_info
    assert info is not None and info["replayed_events"] > 0
    _bw_audit(promoted, complete=point == "election_pre_promote")

    # ---- recovery: re-drive the same workload to completion -----------
    _reap_group(promoted, W.BW_GROUP)
    assert W.drive_bw_txn(promoted, member="drv-promoted") is True
    _bw_audit(promoted, complete=True)
    promoted.close()

    # ---- promotion is idempotent: a second recovery reproduces it ------
    again = tk.InMemoryBroker(wal_dir=winner_dir, wal_durability="commit")
    assert again.recovery_info["truncated_bytes"] == 0
    _bw_audit(again, complete=True)
    for p in range(W.BW_PARTS):
        tp = TopicPartition(W.BW_TOPIC, p)
        assert again.committed(W.BW_GROUP, tp) is not None
    again.close()


@pytest.fixture(scope="module")
def dg_reference(tmp_path_factory):
    """The no-kill disaggregated reference: one prefill pass fills the
    handoff topic, one exactly-once decode pass adopts and serves —
    key → completion tokens in the committed view. (Greedy decode is a
    pure function of (params, prompt), and adoption is bitwise the
    local prefill, so this also defines byte-truth for every kill
    case.)"""
    broker = tk.InMemoryBroker()
    W.prime_dg_topics(broker)
    wd = str(tmp_path_factory.mktemp("dg-ref"))
    W.run_dg_prefill(broker, wd)
    W.run_dg_decode(broker, wd)
    outs = _committed_outputs(broker, W.DG_OUT)
    assert set(outs) == {str(i).encode() for i in range(W.DG_PROMPTS)}
    assert all(len(v) == 1 for v in outs.values())
    return {k: v[0] for k, v in outs.items()}


def _dg_committed(broker):
    return {
        p: broker.committed(W.DG_GROUP, TopicPartition(W.DG_TOPIC, p)) or 0
        for p in range(W.DG_PARTS)
    }


def _dg_audit_death(broker, reference) -> None:
    """Exactly-once invariants at the moment of death: the committed
    view holds each completion at most once and byte-correct, and every
    committed decode-group offset is covered by a committed output."""
    outs = _committed_outputs(broker, W.DG_OUT)
    for key, copies in outs.items():
        assert len(copies) == 1, f"duplicate committed output for {key!r}"
        np.testing.assert_array_equal(copies[0], reference[key])
    for p, wm in _dg_committed(broker).items():
        assert wm <= broker.end_offset(TopicPartition(W.DG_TOPIC, p))
        for off in range(wm):
            key = str(off * W.DG_PARTS + p).encode()
            assert key in outs, (
                f"committed {p}:{off} (prompt {key}) has no committed output"
            )


def _dg_audit_complete(broker, reference) -> None:
    outs = _committed_outputs(broker, W.DG_OUT)
    assert set(outs) == set(reference), (
        f"lost completions: {set(reference) ^ set(outs)}"
    )
    for key, copies in outs.items():
        # THE exactly-once assertion: dups == 0, not bounded.
        assert len(copies) == 1, (
            f"{len(copies)} committed copies of {key!r} after recovery"
        )
        np.testing.assert_array_equal(copies[0], reference[key], err_msg=str(key))
    for p in range(W.DG_PARTS):
        tp = TopicPartition(W.DG_TOPIC, p)
        assert (broker.committed(W.DG_GROUP, tp) or 0) == \
            broker.end_offset(tp), f"partition {p} not fully committed"


def _run_dgpre_case(tmp_path, dg_reference, point: str, at: int):
    """A PREFILL worker SIGKILLed between harvesting a prompt's filled
    KV and publishing its handoff: the handoff never reaches the
    transfer plane, the prefill group's offset for it stays uncommitted
    (at-least-once on the handoff plane), and the decode path — which
    never depends on a handoff existing — still serves everything
    exactly once after a fresh prefill incarnation re-serves the gap."""
    broker = tk.InMemoryBroker()
    W.prime_dg_topics(broker)
    workdir = str(tmp_path / point)
    os.makedirs(workdir, exist_ok=True)
    with tk.BrokerServer(broker) as server:
        proc, marker = _spawn("dgpre", server.port, workdir, point, at)
        proc.wait(timeout=180)
    with open(os.path.join(workdir, "child.log"), "rb") as f:
        log = f.read().decode(errors="replace")
    assert proc.returncode == -signal.SIGKILL, (
        f"worker exited {proc.returncode}, not SIGKILL — point {point!r} "
        f"never reached?\n{log}"
    )
    with open(marker) as f:
        assert f.read().strip() == f"{point}:{at}"
    _reap_group(broker, W.DG_PREFILL_GROUP)

    # ---- invariants at the moment of death ------------------------------
    # Arrival `at` fired before the at-th publish: at-1 handoffs made it.
    published = broker.fetch(TopicPartition(W.DG_HANDOFF, 0), 0, 1000)
    assert len(published) == at - 1
    # The prefill group never committed past its published work: every
    # unpublished prompt re-delivers to the next incarnation.
    for p in range(W.DG_PARTS):
        tp = TopicPartition(W.DG_TOPIC, p)
        wm = broker.committed(W.DG_PREFILL_GROUP, tp) or 0
        handed = {
            (r.key) for r in published
        }
        for off in range(wm):
            key = str(off * W.DG_PARTS + p).encode()
            assert key in handed, (
                f"prefill group committed {p}:{off} ({key}) with no "
                "published handoff — the mid-transfer loss window"
            )
    # The decode group is untouched (nothing served yet).
    assert sum(_dg_committed(broker).values()) == 0

    # ---- recovery: fresh prefill incarnation + decode to completion -----
    W.run_dg_prefill(broker, workdir)
    handed = broker.fetch(TopicPartition(W.DG_HANDOFF, 0), 0, 1000)
    assert len({r.key for r in handed}) == W.DG_PROMPTS, (
        "recovery did not re-serve the unpublished handoffs"
    )
    W.run_dg_decode(broker, workdir)
    _dg_audit_complete(broker, dg_reference)


def _run_dgdec_case(tmp_path, dg_reference, point: str, at: int):
    """An exactly-once DECODE replica SIGKILLed between uploading an
    adopted handoff's KV payload and activating the slot: the record was
    never emitted to any ledger snapshot, so it re-delivers and
    re-adopts — committed duplicates stay zero, byte-identical."""
    broker = tk.InMemoryBroker()
    W.prime_dg_topics(broker)
    workdir = str(tmp_path / point)
    os.makedirs(workdir, exist_ok=True)
    # The transfer plane is pre-filled by an in-process prefill pass, so
    # the child's death lands in ADOPTION, not local prefill.
    W.run_dg_prefill(broker, workdir)
    with tk.BrokerServer(broker) as server:
        proc, marker = _spawn("dgdec", server.port, workdir, point, at)
        proc.wait(timeout=180)
    with open(os.path.join(workdir, "child.log"), "rb") as f:
        log = f.read().decode(errors="replace")
    assert proc.returncode == -signal.SIGKILL, (
        f"worker exited {proc.returncode}, not SIGKILL — point {point!r} "
        f"never reached?\n{log}"
    )
    with open(marker) as f:
        assert f.read().strip() == f"{point}:{at}"
    _reap_group(broker, W.DG_GROUP)

    # ---- exactly-once invariants at the moment of death -----------------
    _dg_audit_death(broker, dg_reference)

    # ---- recovery: same decode logic, in-process ------------------------
    # Constructing the recovery TransactionalProducer re-inits DG_TXN_ID:
    # epoch bump, the corpse's open transaction aborted.
    W.run_dg_decode(broker, workdir)
    _dg_audit_complete(broker, dg_reference)


def _sc_outputs(broker):
    tp = TopicPartition(W.SC_OUT, 0)
    out: dict[bytes, list] = {}
    for rec in broker.fetch(tp, 0, 100000):
        out.setdefault(rec.key, []).append(
            np.frombuffer(rec.value, dtype=np.int32)
        )
    return out


@pytest.fixture(scope="module")
def sc_reference(tmp_path_factory):
    """No-kill byte-truth for the scale matrix: greedy decode is a pure
    function of (params, prompt), shared by every fleet process."""
    import torchkafka_tpu as _tk
    from torchkafka_tpu.serve import StreamingGenerator

    cfg, params = W.build_model()
    prompts = W.sc_prompts()
    broker = _tk.InMemoryBroker()
    broker.create_topic("ref", partitions=W.SC_PARTS)
    for i in range(W.SC_PROMPTS):
        broker.produce("ref", prompts[i].tobytes(),
                       partition=i % W.SC_PARTS, key=str(i).encode())
    c = _tk.MemoryConsumer(broker, "ref", group_id="ref")
    gen = StreamingGenerator(
        c, params, cfg, slots=W.SLOTS, prompt_len=W.P, max_new=W.MAX_NEW,
        commit_every=2, ticks_per_sync=1,
    )
    ref = {rec.key: toks for rec, toks in gen.run(idle_timeout_ms=400)}
    c.close()
    return ref


def _reap_orphan_workers(fleet_dir: str, timeout_s: float = 60.0) -> None:
    """The SIGKILLed supervisor's worker grandchildren deliberately RIDE
    broker outages (the broker-restart drill's contract: retry forever,
    the broker comes back on the same port) — but this broker died WITH
    the supervisor, so the parent plays init: SIGKILL the orphans
    (their uncommitted work re-delivers to the recovery fleet; exactly
    the at-least-once contract this matrix audits) and wait for the
    journal locks they hold to go stale so the recovery workers steal
    them instead of refusing."""
    journal_dir = os.path.join(fleet_dir, "journals")
    deadline = time.monotonic() + timeout_s
    live: list[int] = []
    while time.monotonic() < deadline:
        live = []
        if os.path.isdir(journal_dir):
            for name in os.listdir(journal_dir):
                if not name.endswith(".lock"):
                    continue
                try:
                    with open(os.path.join(journal_dir, name)) as f:
                        pid = int(f.read().strip() or 0)
                    os.kill(pid, 0)
                except (OSError, ValueError):
                    continue  # gone or unreadable: stale
                live.append(pid)
        if not live:
            return
        for pid in live:
            try:  # only ever a fleet worker of THIS case's fleet dir
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    cmd = f.read()
                if b"torchkafka_tpu.fleet.proc" in cmd \
                        and fleet_dir.encode() in cmd:
                    os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        time.sleep(0.1)
    raise TimeoutError(f"orphan workers still alive: {live}")


def _run_scale_case(tmp_path, sc_reference, point: str, at: int):
    """The SUPERVISOR SIGKILLed mid-scale-event. At death: the group
    never saw a half-born member (scale-up) / the fleet's committed
    watermark covers only durable outputs (both). Recovery: a fresh
    supervisor over the recovered WAL broker and the SAME workdir
    converges to the controller's target with zero lost records,
    byte-identical completions."""
    mode, direction = MATRIX[point][0], (
        "up" if point == "scale_up_pre_spawn" else "down"
    )
    target = 2 if direction == "up" else 1
    workdir = str(tmp_path / point)
    os.makedirs(workdir, exist_ok=True)
    proc, marker = _spawn(mode, 0, workdir, point, at)
    proc.wait(timeout=420)
    with open(os.path.join(workdir, "child.log"), "rb") as f:
        log = f.read().decode(errors="replace")
    assert proc.returncode == -signal.SIGKILL, (
        f"supervisor exited {proc.returncode}, not SIGKILL — point "
        f"{point!r} never reached?\n{log}"
    )
    with open(marker) as f:
        assert f.read().strip() == f"{point}:{at}"
    fleet_dir = os.path.join(workdir, "fleet")
    _reap_orphan_workers(fleet_dir)

    # ---- invariants at the moment of death (recover the corpse's WAL;
    # the child's session timeout, so memberships restore instead of the
    # lease-less drop-and-rejoin path) ----
    recovered = tk.InMemoryBroker(
        wal_dir=os.path.join(workdir, "wal"), wal_durability="commit",
        session_timeout_s=2.0,
    )
    members = recovered.membership(W.SC_GROUP)["members"]
    if point == "scale_up_pre_spawn":
        # The window: target decided, slot chosen, replacement NOT yet
        # spawned — no half-born member may exist.
        assert members == ["r000i000"], members
    else:
        # The SIGTERM was in flight when the supervisor died; whether
        # the victim's drain-leave raced the broker's death, no member
        # beyond the two originals ever existed.
        assert set(members) <= {"r000i000", "r001i001"}, members
    outs = _sc_outputs(recovered)
    for p in range(W.SC_PARTS):
        tp = TopicPartition(W.SC_TOPIC, p)
        wm = recovered.committed(W.SC_GROUP, tp) or 0
        assert wm <= recovered.end_offset(tp)
        for off in range(wm):
            key = str(off * W.SC_PARTS + p).encode()
            assert key in outs, (
                f"committed {p}:{off} (prompt {key}) has no durable output"
            )
    for key, copies in outs.items():
        for c in copies:  # duplicates allowed, divergence not
            np.testing.assert_array_equal(c, sc_reference[key], err_msg=str(key))

    # ---- recovery: a fresh supervisor converges to the target -----------
    from torchkafka_tpu.fleet import ProcessFleet

    for member in list(members):
        recovered.leave(W.SC_GROUP, member)  # reap the corpse's workers
    fleet = ProcessFleet(
        W.sc_model_spec(), topic=W.SC_TOPIC, prompt_len=W.P,
        max_new=W.MAX_NEW, workdir=fleet_dir, replicas=target,
        partitions=W.SC_PARTS, slots=W.SLOTS, commit_every=2,
        journal_cadence=1, session_timeout_s=2.0,
        heartbeat_interval_s=0.2, respawn=True, group=W.SC_GROUP,
        out_topic=W.SC_OUT, broker=recovered,
    )
    try:
        fleet.start()
        fleet.wait(lambda f: f.fully_committed(), timeout_s=300)
        # The controller's target, reached and held.
        assert len(fleet.live()) == target, fleet.diagnose()
        fleet.drain()
        fleet.wait(
            lambda f: all(not i.running for i in f.incarnations),
            timeout_s=120,
        )
        fleet.poll_once()
        assert fleet.fully_committed()
        res = fleet.results()
        assert set(res) == set(sc_reference), (
            f"lost completions: {set(sc_reference) ^ set(res)}"
        )
        for key, copies in res.items():
            for _member, toks in copies:
                np.testing.assert_array_equal(
                    toks, sc_reference[key], err_msg=str(key)
                )
    finally:
        fleet.close()


@pytest.fixture(scope="module")
def ro_reference(tmp_path_factory):
    """Byte-truth PER MODEL VERSION for the rollout matrix: greedy
    decode of every rollout prompt under the v0 (boot, seed-0) and v1
    (checkpoint, seed-1) weights. The two references disagree, so an
    output can only pass the audit under the version its "mv" tag
    claims — the never-half-old/half-new check is exact."""
    from torchkafka_tpu.fleet.proc import build_model
    from torchkafka_tpu.serve import StreamingGenerator

    prompts = W.ro_prompts()
    refs: dict[int, dict] = {}
    for version, seed in ((0, 0), (1, 1)):
        cfg, params = build_model(W.ro_model_spec(seed=seed))
        broker = tk.InMemoryBroker()
        broker.create_topic("ref", partitions=W.RO_PARTS)
        for i in range(W.RO_PROMPTS):
            broker.produce("ref", prompts[i].tobytes(),
                           partition=i % W.RO_PARTS, key=str(i).encode())
        c = tk.MemoryConsumer(broker, "ref", group_id="ref")
        gen = StreamingGenerator(
            c, params, cfg, slots=W.SLOTS, prompt_len=W.P,
            max_new=W.MAX_NEW, commit_every=2, ticks_per_sync=1,
        )
        refs[version] = {
            rec.key: toks for rec, toks in gen.run(idle_timeout_ms=400)
        }
        c.close()
    assert any(
        not np.array_equal(refs[0][k], refs[1][k]) for k in refs[0]
    ), "v0 and v1 references coincide — the version audit would be vacuous"
    return refs


def _ro_committed(broker):
    """Committed-view rollout outputs by key → list of (mv tag, tokens):
    the downstream consumer's truth, version tags included."""
    out: dict[bytes, list] = {}
    recs, _ = broker.fetch_stable(TopicPartition(W.RO_OUT, 0), 0, 100000)
    for rec in recs:
        mv = dict(rec.headers or ()).get("mv", b"?")
        out.setdefault(rec.key, []).append(
            (mv, np.frombuffer(rec.value, dtype=np.int32))
        )
    return out


def _ro_audit(broker, ro_reference, *, complete: bool):
    """Exactly-once + version-integrity invariants: each key committed
    at most (``complete``: exactly) once; every output's tokens are
    byte-identical to the reference OF THE VERSION ITS TAG CLAIMS —
    a half-swapped tree would match neither; every committed offset is
    covered by a committed output."""
    outs = _ro_committed(broker)
    for key, copies in outs.items():
        assert len(copies) == 1, (
            f"{len(copies)} committed copies of {key!r}"
        )
        mv, toks = copies[0]
        assert mv in (b"0", b"1"), (key, mv)
        np.testing.assert_array_equal(
            toks, ro_reference[int(mv)][key],
            err_msg=f"{key!r} tagged mv={mv!r} but tokens do not match "
            "that version's reference — half-old/half-new params",
        )
    for p in range(W.RO_PARTS):
        tp = TopicPartition(W.RO_TOPIC, p)
        wm = broker.committed(W.RO_GROUP, tp) or 0
        assert wm <= broker.end_offset(tp)
        for off in range(wm):
            key = str(off * W.RO_PARTS + p).encode()
            assert key in outs, (
                f"committed {p}:{off} (prompt {key}) has no committed output"
            )
        if complete:
            assert wm == broker.end_offset(tp), (
                f"partition {p} not fully committed"
            )
    if complete:
        assert set(outs) == {
            str(i).encode() for i in range(W.RO_PROMPTS)
        }, "lost completions"
    return outs


def _run_rollout_case(tmp_path, ro_reference, point: str, at: int):
    """An exactly-once replica SIGKILLed inside the rollout plane. The
    journal's durable model_version — flipped BEFORE the in-memory
    rebind — is the single restart authority: at death the committed
    view and the journal are consistent with exactly one side of each
    window, and the recovery incarnation (same member id, same journal)
    restores the journaled version, re-reads the scripted directives
    from offset 0, completes the swap, and serves the remainder under
    v1 — zero lost, zero committed duplicates, every version tag true."""
    import json

    broker = tk.InMemoryBroker()
    W.prime_rollout_topics(broker)
    workdir = str(tmp_path / point)
    os.makedirs(workdir, exist_ok=True)
    with tk.BrokerServer(broker) as server:
        proc, marker = _spawn("rollout", server.port, workdir, point, at)
        proc.wait(timeout=180)
    with open(os.path.join(workdir, "child.log"), "rb") as f:
        log = f.read().decode(errors="replace")
    assert proc.returncode == -signal.SIGKILL, (
        f"worker exited {proc.returncode}, not SIGKILL — point {point!r} "
        f"never reached?\n{log}"
    )
    with open(marker) as f:
        assert f.read().strip() == f"{point}:{at}"
    _reap_group(broker, W.RO_GROUP)

    # ---- invariants at the moment of death ------------------------------
    jpath = os.path.join(workdir, "journals", "m0.json")
    meta_v = DecodeJournal.load_meta(jpath).get("model_version")
    outs = _ro_audit(broker, ro_reference, complete=False)
    # Whichever side of the flip the death landed on, the corpse never
    # emitted a v1 output: the rebind either never happened (pre_swap,
    # pre_verdict) or died before the first post-swap admission
    # (mid_apply kills between flip and rebind).
    assert all(c[0][0] == b"0" for c in outs.values()), (
        "a v1-tagged output committed before the swap completed"
    )
    if point == "swap_mid_apply":
        # The defining window: version 1 DURABLE, rebind never reached.
        assert meta_v is not None and int(meta_v) == 1, meta_v
    else:
        # The flip was never reached: journal meta absent or still 0.
        assert meta_v in (None, 0), meta_v
    if point == "canary_pre_verdict":
        # Died holding the verdict: neither the canary report nor any
        # swap ack ever made the control topic — the incumbent was
        # still serving and the (scripted) controller saw nothing.
        ctl = broker.fetch(TopicPartition(W.RO_CTL, 0), 0, 1000)
        kinds = [
            (json.loads(r.value) or {}).get("t") for r in ctl
        ]
        assert "canary_report" not in kinds, kinds
        assert "ack" not in kinds, kinds

    # ---- recovery: same member id, same journal, in-process -------------
    # Constructing the recovery TransactionalProducer re-inits the
    # replica-indexed transactional id (epoch bump: the corpse's open
    # transaction aborts); the journal meta restore rebuilds the
    # journaled version's weights from the checkpoint topic BEFORE the
    # first token; the control topic replays the scripted directives.
    rc = W.run_rollout(broker, workdir, member="m0")
    assert rc == 0
    outs = _ro_audit(broker, ro_reference, complete=True)
    final_v = DecodeJournal.load_meta(jpath).get("model_version")
    assert final_v is not None and int(final_v) == 1, (
        f"journal version {final_v!r} after recovery — swap never landed"
    )
    assert any(c[0][0] == b"1" for c in outs.values()), (
        "no v1 output after recovery — the rollout never completed"
    )


@pytest.fixture(scope="module")
def dl_reference():
    """Byte-truth for the distill matrix: PLAIN greedy decode of every
    prompt (both waves) under the target weights. The draft — trained,
    refreshed, or mid-kill — only proposes; the target's verification
    commits, so every committed distill-mode output must match this
    speculation-free reference bit for bit."""
    from torchkafka_tpu.serve import StreamingGenerator

    prompts = W.dl_prompts()
    cfg, params = W.build_model()
    broker = tk.InMemoryBroker()
    broker.create_topic("ref", partitions=W.DL_PARTS)
    for i in range(len(prompts)):
        broker.produce("ref", prompts[i].tobytes(),
                       partition=i % W.DL_PARTS, key=str(i).encode())
    c = tk.MemoryConsumer(broker, "ref", group_id="ref")
    gen = StreamingGenerator(
        c, params, cfg, slots=W.SLOTS, prompt_len=W.P,
        max_new=W.MAX_NEW, commit_every=2, ticks_per_sync=1,
    )
    ref = {rec.key: toks for rec, toks in gen.run(idle_timeout_ms=400)}
    gen.close()
    c.close()
    assert len(ref) == len(prompts)
    return ref


def _dl_outputs(broker):
    out: dict[bytes, list] = {}
    for rec in broker.fetch(TopicPartition(W.DL_OUT, 0), 0, 100000):
        out.setdefault(rec.key, []).append(
            np.frombuffer(rec.value, dtype=np.int32)
        )
    return out


def _dl_audit(broker, dl_reference, *, complete: bool):
    """Committed-tokens invariants for the distill matrix: every output
    copy byte-identical to the speculation-free reference (at-least-once
    duplicates allowed, divergence never), committed watermarks covered
    by outputs, and — the corpus-hygiene half — every frame on the
    distill topic decodes and carries EXACTLY its key's committed
    tokens (the trainer only ever learns the committed view)."""
    from torchkafka_tpu.distill import decode_completion

    outs = _dl_outputs(broker)
    for key, copies in outs.items():
        for toks in copies:
            np.testing.assert_array_equal(
                toks, dl_reference[key], err_msg=str(key)
            )
    prompts = W.dl_prompts()
    by_prompt = {
        prompts[i].tobytes(): str(i).encode() for i in range(len(prompts))
    }
    corpus_keys = set()
    for rec in broker.fetch(TopicPartition(W.DL_DISTILL, 0), 0, 100000):
        frame = decode_completion(rec.value)  # raises on any torn frame
        key = by_prompt[np.asarray(frame["prompt"], np.int32).tobytes()]
        np.testing.assert_array_equal(
            np.asarray(frame["tokens"], np.int32), dl_reference[key],
            err_msg=f"corpus frame for {key!r} diverges from committed",
        )
        corpus_keys.add(key)
    assert corpus_keys <= set(outs), "corpus frame without an output"
    if complete:
        assert set(outs) == set(by_prompt.values()), "lost completions"
        assert corpus_keys == set(outs), (
            "committed completion missing from the training corpus"
        )
    return outs


def _run_distill_case(tmp_path, dl_reference, point: str, at: int):
    """The closed distillation loop SIGKILLed at its two windows. Either
    death leaves the serving contract untouched — the draft is advisory:
    pre_publish dies with the checkpoint plane still empty (the trained
    state was process memory; nothing torn lands), pre_apply dies with
    v1 published but never applied. The recovery incarnation is the SAME
    three-stage runner: it re-serves what was uncommitted, re-trains
    from the corpus group's offsets, (re)publishes, swaps, and finishes
    the post-swap wave — with every committed token, both waves, both
    lives, byte-identical to the speculation-free reference."""
    from torchkafka_tpu.errors import CheckpointWireError
    from torchkafka_tpu.source.checkpoint_wire import fetch_checkpoint

    broker = tk.InMemoryBroker()
    W.prime_distill_topics(broker)
    workdir = str(tmp_path / point)
    os.makedirs(workdir, exist_ok=True)
    with tk.BrokerServer(broker) as server:
        proc, marker = _spawn("distill", server.port, workdir, point, at)
        proc.wait(timeout=180)
    with open(os.path.join(workdir, "child.log"), "rb") as f:
        log = f.read().decode(errors="replace")
    assert proc.returncode == -signal.SIGKILL, (
        f"worker exited {proc.returncode}, not SIGKILL — point {point!r} "
        f"never reached?\n{log}"
    )
    with open(marker) as f:
        assert f.read().strip() == f"{point}:{at}"
    _reap_group(broker, W.DL_GROUP)
    _reap_group(broker, W.DL_TRAIN_GROUP)

    # ---- invariants at the moment of death ------------------------------
    outs = _dl_audit(broker, dl_reference, complete=False)
    wave1 = {str(i).encode() for i in range(W.DL_WAVE1)}
    assert set(outs) == wave1, "stage-A serving incomplete at death"
    n_prompts = sum(
        broker.end_offset(TopicPartition(W.DL_TOPIC, p))
        for p in range(W.DL_PARTS)
    )
    if point == "distill_pre_publish":
        # The first publish died whole: the checkpoint plane is EMPTY —
        # no manifest, no torn chunk — and the swap stage never ran.
        assert broker.end_offset(TopicPartition(W.DL_CKPT, 0)) == 0
        with pytest.raises(CheckpointWireError):
            fetch_checkpoint(broker, W.DL_CKPT, 1)
        assert n_prompts == W.DL_WAVE1
        # The steps BEFORE the doomed publish committed their corpus
        # offsets (commit-after-step): progress durable, publish lost.
        committed = broker.committed(
            W.DL_TRAIN_GROUP, TopicPartition(W.DL_DISTILL, 0)
        ) or 0
        assert committed >= 2, committed
    else:  # draft_swap_pre_apply
        # v1 made the plane intact; the swap died before applying it —
        # and before any wave-2 admission, so no post-swap serving.
        _flat, manifest = fetch_checkpoint(broker, W.DL_CKPT, 1)
        assert manifest["kind"] == "draft"
        assert n_prompts == W.DL_WAVE1 + W.DL_WAVE2

    # ---- recovery: the same three-stage runner, in-process --------------
    W.run_distill(broker, workdir)
    _dl_audit(broker, dl_reference, complete=True)
    _flat, manifest = fetch_checkpoint(broker, W.DL_CKPT, 1)
    assert manifest["kind"] == "draft"


FULL_POINTS = [p for p in MATRIX if p not in TIER1]


class TestCrashMatrix:
    def test_matrix_covers_every_registered_point(self):
        """Registry-vs-matrix completeness: registering a crash point
        without adding a subprocess kill for it fails the suite."""
        assert set(MATRIX) == set(REGISTERED_CRASH_POINTS), (
            "crash points registered but not matrix-covered: "
            f"{set(REGISTERED_CRASH_POINTS) - set(MATRIX)}; "
            "matrix entries no longer registered: "
            f"{set(MATRIX) - set(REGISTERED_CRASH_POINTS)}"
        )
        assert all(p in MATRIX for p in TIER1)

    @pytest.mark.chaos
    @pytest.mark.parametrize("point", TIER1)
    def test_crash_point_tier1(self, tmp_path, request, point):
        """The tier-1 representative deaths: one mid-serve (outputs
        durable, offsets not yet committed), one mid-checkpoint (torn
        step dir)."""
        _dispatch_case(tmp_path, request, point)

    @pytest.mark.chaos
    @pytest.mark.slow
    @pytest.mark.parametrize("point", FULL_POINTS)
    def test_crash_point_full(self, tmp_path, request, point):
        """The rest of the matrix (run with ``-m chaos``)."""
        _dispatch_case(tmp_path, request, point)


def _dispatch_case(tmp_path, request, point: str) -> None:
    # getfixturevalue keeps each mode's module-scoped reference lazy: a
    # fleet-only run never pays for the serve-mode reference build.
    mode, at = MATRIX[point]
    if mode == "serve":
        _run_serve_case(
            tmp_path, request.getfixturevalue("reference"), point, at
        )
    elif mode == "txn":
        # Greedy decode is a pure function of (params, prompt): the
        # serve-mode no-kill reference defines byte-truth for the
        # transactional worker too (same model seed, same prompts).
        _run_txn_case(
            tmp_path, request.getfixturevalue("reference"), point, at
        )
    elif mode == "ckpt":
        _run_ckpt_case(tmp_path, point, at)
    elif mode == "fleet":
        _run_fleet_case(
            tmp_path, request.getfixturevalue("fleet_reference"), point, at
        )
    elif mode == "rollout":
        _run_rollout_case(
            tmp_path, request.getfixturevalue("ro_reference"), point, at
        )
    elif mode == "distill":
        _run_distill_case(
            tmp_path, request.getfixturevalue("dl_reference"), point, at
        )
    elif mode == "sweep":
        _run_sweep_case(tmp_path, point, at)
    elif mode == "broker":
        _run_broker_case(tmp_path, point, at)
    elif mode == "cell":
        _run_cell_case(tmp_path, point, at)
    elif mode == "dgpre":
        _run_dgpre_case(
            tmp_path, request.getfixturevalue("dg_reference"), point, at
        )
    elif mode == "dgdec":
        _run_dgdec_case(
            tmp_path, request.getfixturevalue("dg_reference"), point, at
        )
    elif mode in ("scaleup", "scaledown"):
        _run_scale_case(
            tmp_path, request.getfixturevalue("sc_reference"), point, at
        )
    else:  # pragma: no cover - matrix typo guard
        raise ValueError(f"unknown matrix mode {mode!r}")
