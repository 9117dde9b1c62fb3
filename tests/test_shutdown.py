"""Cooperative preemption drain (utils/shutdown.py).

The hard-kill path (nothing committed → re-delivery) is covered by the
pod/chaos/checkpoint suites; these tests pin the GRACEFUL path: SIGTERM →
flag at the loop safe point → commit + checkpoint → clean exit with zero
replay on resume.
"""

import os
import pathlib
import signal
import subprocess
import sys
import textwrap
import time

import pytest

import torchkafka_tpu as tk


class TestShutdownSignal:
    def test_flag_set_on_signal(self):
        with tk.ShutdownSignal(signals=(signal.SIGUSR2,)) as stop:
            assert not stop.requested
            signal.raise_signal(signal.SIGUSR2)
            assert stop.requested
            assert stop.received_signal == signal.SIGUSR2

    def test_handlers_restored_on_exit(self):
        before = signal.getsignal(signal.SIGUSR2)
        with tk.ShutdownSignal(signals=(signal.SIGUSR2,)):
            assert signal.getsignal(signal.SIGUSR2) is not before
        assert signal.getsignal(signal.SIGUSR2) is before

    def test_reuse_starts_fresh(self):
        """A drained instance re-entered later must NOT report the previous
        run's signal as an immediate drain request."""
        stop = tk.ShutdownSignal(signals=(signal.SIGUSR2,))
        with stop:
            signal.raise_signal(signal.SIGUSR2)
            assert stop.requested
        with stop:
            assert not stop.requested
            assert stop.received_signal is None

    def test_not_reentrant(self):
        with tk.ShutdownSignal(signals=(signal.SIGUSR2,)) as stop:
            with pytest.raises(RuntimeError, match="re-entrant"):
                stop.__enter__()

    def test_non_main_thread_rejected(self):
        import threading

        err: list = []

        def run():
            try:
                tk.ShutdownSignal(signals=(signal.SIGUSR2,)).__enter__()
            except RuntimeError as e:
                err.append(e)

        t = threading.Thread(target=run)
        t.start()
        t.join()
        assert err and "main thread" in str(err[0])


DRAIN_SCRIPT = textwrap.dedent(
    """
    import json, signal, sys, time
    from torchkafka_tpu.utils.devices import force_cpu_devices
    force_cpu_devices(2)
    import numpy as np
    import torchkafka_tpu as tk

    out_path, ready_path = sys.argv[1], sys.argv[2]
    broker = tk.InMemoryBroker(commit_log_path=out_path + ".commits")
    broker.create_topic("t", partitions=2)
    for i in range(10_000):
        broker.produce("t", np.int32([i] * 4).tobytes(), partition=i % 2)
    consumer = tk.MemoryConsumer(broker, "t", group_id="g")
    consumed = 0
    with tk.ShutdownSignal() as stop, tk.KafkaStream(
        consumer, tk.fixed_width(4, np.int32), batch_size=8,
        to_device=False, idle_timeout_ms=4000, owns_consumer=True,
    ) as stream:
        for batch, token in stream:
            consumed += batch.valid_count
            assert token.commit()
            if consumed == 64:
                open(ready_path, "w").write("ready")  # parent: fire now
            if stop.requested:
                # Drain: this batch is committed; record the watermark.
                break
            time.sleep(0.005)  # pace the loop so the signal lands mid-run
    committed = {
        p: broker.committed("g", tk.TopicPartition("t", p)) for p in (0, 1)
    }
    json.dump({"consumed": consumed, "committed": committed},
              open(out_path, "w"))
    """
)


class TestGracefulDrain:
    def test_sigterm_drains_commit_and_exits_zero(self, tmp_path):
        """SIGTERM mid-stream: the loop finishes its batch, commits, and
        exits 0 with committed == consumed — a resume replays nothing."""
        script = tmp_path / "drain.py"
        script.write_text(DRAIN_SCRIPT)
        out = tmp_path / "out.json"
        ready = tmp_path / "ready"
        repo_root = str(pathlib.Path(__file__).resolve().parent.parent)
        env = dict(os.environ)
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, str(script), str(out), str(ready)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        deadline = time.time() + 120
        while not ready.exists():
            assert proc.poll() is None, proc.communicate()[1].decode()
            assert time.time() < deadline, "worker never reached steady state"
            time.sleep(0.02)
        proc.send_signal(signal.SIGTERM)
        stdout, stderr = proc.communicate(timeout=120)
        assert proc.returncode == 0, stderr.decode()
        import json

        result = json.loads(out.read_text())
        consumed = result["consumed"]
        # Drained early (the signal worked), and every consumed record's
        # offset is durable: zero replay on resume.
        assert consumed < 10_000
        durable = sum(v or 0 for v in result["committed"].values())
        assert durable == consumed


class TestHandlerEdges:
    def test_partial_install_rolls_back(self):
        before = signal.getsignal(signal.SIGUSR2)
        stop = tk.ShutdownSignal(signals=(signal.SIGUSR2, 99999))
        with pytest.raises((ValueError, OSError)):
            stop.__enter__()
        # The successfully-installed handler was rolled back, and the
        # instance is reusable.
        assert signal.getsignal(signal.SIGUSR2) is before
        with tk.ShutdownSignal(signals=(signal.SIGUSR2,)) as ok:
            assert not ok.requested
