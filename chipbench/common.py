"""What the runner and the loops share: the run's context (clocks, phases,
the benchmark's own host spans, the profiler's window), the list of
numbers compared with their limits, device facts."""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

from chipbench import stats

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent


class Refused(RuntimeError):
    """The run cannot be a measurement (no chip, the kernel did not
    engage, something compiled in the window): no result line, exit != 0."""


def pct(values, q):
    values = list(values)
    return stats.percentile(values, q) if values else 0.0


def say(kind: str, facts) -> None:
    """A line for the reader of the log. Never the last line, and never
    with a key the driver reads."""
    print(json.dumps({"note": kind, "facts": facts}, default=str), flush=True)


def load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_file_module(path: Path):
    """Import one file by its path: metric readers and kernel counts are
    found by the names BENCHMARK.json gives, dots and all."""
    if not path.is_file():
        raise Refused(f"{path} is named and does not exist")
    name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in str(path)
    )
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_named(kind: str, name: str, root: Path = ROOT):
    """``chipbench/<kind>/<name>.py``."""
    return load_file_module(root / "chipbench" / kind / f"{name}.py")


def memory_peak_bytes(devices) -> int:
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    ]
    return int(max(peaks)) if peaks else 0


def process_age_s() -> float:
    """Seconds since this process started, by the kernel's own stamps
    (the interpreter's start-up is set-up too)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0.0


class Checks:
    """Each number compared, beside its limit; ``correct`` is all of
    them."""

    def __init__(self) -> None:
        self.rows: list[dict] = []

    def at_most(self, name: str, value: float, limit: float) -> bool:
        ok = bool(value <= limit) and value == value
        self.rows.append(
            {"check": name, "value": value, "limit": limit, "ok": ok}
        )
        return ok

    def at_least(self, name: str, value: float, limit: float) -> bool:
        ok = bool(value >= limit) and value == value
        self.rows.append(
            {"check": name, "value": value, "at_least": limit, "ok": ok}
        )
        return ok

    def exact(self, name: str, difference) -> bool:
        """An exact comparison: the limit is 0."""
        return self.at_most(name, abs(difference), 0)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def report(self) -> None:
        for r in self.rows:
            print(json.dumps({"compared": r}), flush=True)


class CompileWatch:
    """Counts what XLA compiles, from JAX's own monitoring events, so the
    run can say that nothing compiled inside the window."""

    def __init__(self) -> None:
        import jax.monitoring

        self.compiles = 0
        self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _ev(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class RunContext:
    """One run of one cell."""

    def __init__(self, *, cell, conf, mix, seed, seconds, trace, devices,
                 t_start, rehearsal=False, root: Path = ROOT) -> None:
        self.cell, self.conf, self.mix = cell, conf, mix
        self.seed, self.seconds, self.trace = seed, float(seconds), bool(trace)
        self.devices, self.root = devices, root
        self.rehearsal = rehearsal
        self.t_start = t_start  # perf_counter reading at process start
        self.checks = Checks()
        self.phases: dict[str, float] = {}
        self.spans: dict[str, list[tuple[float, float]]] = {}
        self.watch = CompileWatch()
        self.t0 = self.t_close = None
        self.setup_s = None
        self._compiles_at_open = 0
        self.compiles_in_window = 0
        self._trace_dir = None
        self._trace_state = "off"
        self._trace_t = [None, None]
        self.model = importlib.import_module(conf["model"])
        self.reference = importlib.import_module(conf["reference"])
        self.traffic = load_named("traffic", mix["traffic"]["kind"], root)

    say = staticmethod(say)

    @contextlib.contextmanager
    def phase(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) + (
                time.perf_counter() - t
            )

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span of the benchmark's own, kept in memory and also
        written into the profiler's trace when one is running."""
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append(
                    (t, time.perf_counter() - t)
                )

    # ------------------------------------------------------------ the window

    def open_window(self) -> None:
        self._compiles_at_open = self.watch.compiles
        self.t0 = time.perf_counter()
        self.setup_s = (self.t0 - self.t_start)
        if self.trace and not self.rehearsal:
            self._trace_state = "armed"

    def close_window(self) -> None:
        self.t_close = time.perf_counter()
        if self._trace_state == "on":
            self._stop_trace()
        self.compiles_in_window = self.watch.compiles - self._compiles_at_open

    # ------------------------------------------------------------ the trace

    def trace_tick(self, now: float) -> None:
        """Called by the loop between its units of work: starts the
        profiler ``trace.seconds`` before the window's end and stops it at
        the first call after the end (or as the window closes). The last
        part of the window is traced, not all of it: a trace of the whole
        would be too large to read back, and stopping the profiler stalls
        the host for seconds, which inside the window would queue the
        arrivals behind it."""
        if self._trace_state in ("off", "done"):
            return
        length = float(self.mix.get("trace", {}).get("seconds", 6.0))
        end = self.t0 + self.seconds
        if self._trace_state == "armed" and now >= end - length:
            self._start_trace()
        elif self._trace_state == "on" and now >= end:
            self._stop_trace()

    def _start_trace(self) -> None:
        import jax

        self._trace_dir = self.root / ".chipbench_trace" / (
            f"{self.cell['name']}-{self.seed}"
        )
        if self._trace_dir.exists():
            import shutil

            shutil.rmtree(self._trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self._trace_dir), profiler_options=opts)
        self._trace_t[0] = time.perf_counter()
        self._trace_state = "on"

    def _stop_trace(self) -> None:
        import jax

        self._trace_t[1] = time.perf_counter()
        jax.profiler.stop_trace()
        self._trace_state = "done"

    def finish_trace(self, run: dict) -> None:
        """Reduce the profiler's file to what the metric readers use."""
        run["trace"] = None
        if not self.trace or self.rehearsal:
            return  # a rehearsal has no device to trace
        if self._trace_state != "done":
            raise Refused("the window closed before the trace was taken")
        from chipbench import xplane

        files = sorted(self._trace_dir.rglob("*.xplane.pb"))
        if not files:
            raise Refused(f"no .xplane.pb under {self._trace_dir}")
        with self.phase("reduce_trace"):
            run["trace"] = xplane.reduce_file(
                files[-1], n_devices=len(self.devices)
            )
        run["trace"]["host_window_s"] = self._trace_t[1] - self._trace_t[0]
        run["trace"]["host_t0"] = self._trace_t[0]
        run["trace"]["host_t1"] = self._trace_t[1]


def device_facts(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def load_peaks(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise Refused(
            f"no published peaks for device_kind={kind!r} in "
            f"chipbench/peaks.json (known: {sorted(table)})"
        )
    return table[kind]


def stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
