"""Shared machinery of the on-chip benchmark.

Everything a cell needs is found by name: ``BENCHMARK.json`` at the root of
the checkout lists the cells and metrics, ``workloads/<cell>.json`` holds a
cell's traffic and limits, ``configs/<config>.json`` its configuration (with
the driver it runs under and its plain reference beside it),
``drivers/<driver>.py`` the executor, and ``e2e_metrics/<name>.py`` /
``layer_metrics/<name>.py`` one reader per metric.  Adding a cell, a
configuration or a metric adds files; no file here changes.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: JAX's persistent compile cache: one fixed path inside the checkout
CACHE_DIR = os.path.join(HERE, ".jax_cache")
#: where a traced run writes its profile (removed once it is reduced)
TRACE_DIR = os.path.join(HERE, ".traces")


def use_cache(jax) -> None:
    """JAX's persistent compile cache at :data:`CACHE_DIR`, every program
    kept, so that only a cell's first run in a checkout compiles."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a benchmark file by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of BENCHMARK.json with its files loaded."""

    def __init__(self, name: str, bench: dict | None = None,
                 overrides: dict | None = None):
        bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
        entries = {w["name"]: w for w in bench["workloads"]}
        if name not in entries:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                             f"{sorted(entries)}")
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == self.entry["config"])
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.config_dir = os.path.dirname(os.path.join(ROOT, cfg_entry["file"]))
        self.workload = load_json(os.path.join(HERE, "workloads",
                                               name + ".json"))
        self.traffic = dict(self.workload["traffic"])
        self.limits = dict(self.workload["limits"])
        for key, val in (overrides or {}).items():
            getattr(self, key).update(val)
        self.e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if _reports(m, name)]

    def driver(self):
        d = self.config["driver"]
        return load_module(os.path.join(HERE, "drivers", d + ".py"), d)

    def reference(self):
        path = os.path.join(self.config_dir, self.config["reference"])
        return load_module(path, os.path.basename(path)[:-3])


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class CompileClock:
    """Sums JAX's own trace, lowering and backend-compile durations, and
    counts the programs compiled apart from those loaded from the
    persistent cache (JAX times a cache load as a backend compile)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.backend = 0
        self.cache_loads = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            if event == self.EVENTS[-1]:
                self.backend += 1

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_loads += 1

    @property
    def count(self) -> int:
        return self.backend - self.cache_loads


class Spans:
    """Benchmark-side host spans around the calls into each layer.

    Each span is timed on the host clock and, while a trace is recorded,
    also written into the profiler's trace as a ``TraceAnnotation``."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.seconds: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        ann = (jax.profiler.TraceAnnotation(name) if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds.setdefault(name, []).append(time.perf_counter() - t0)


class Setup:
    """Set-up breakdown, printed on an earlier line of every run."""

    def __init__(self, t_process: float):
        self.t_process = t_process
        self.t_last = t_process
        self.parts: dict[str, float] = {}

    def lap(self, name: str) -> None:
        now = time.perf_counter()
        self.parts[name] = self.parts.get(name, 0.0) + now - self.t_last
        self.t_last = now

    def total(self) -> float:
        return time.perf_counter() - self.t_process


class Window:
    """The measured window: host-clock bounds, the compile count inside
    it, and (when traced) the profiler trace of exactly this span."""

    def __init__(self, seconds: float, trace: bool, clock: CompileClock,
                 devices):
        self.seconds, self.trace, self.clock = seconds, trace, clock
        self.devices = devices
        self.summary = None

    def __enter__(self):
        import shutil

        import jax
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.compiles0 = self.clock.count
        self.loads0 = self.clock.cache_loads
        self.t0 = time.perf_counter()
        return self

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def over(self) -> bool:
        return self.elapsed() >= self.seconds

    def __exit__(self, *exc):
        import shutil

        import jax
        self.t1 = time.perf_counter()
        self.compiles = self.clock.count - self.compiles0
        self.cache_loads = self.clock.cache_loads - self.loads0
        if self.trace:
            self._ann.__exit__(*exc)
            jax.profiler.stop_trace()
            if exc[0] is None:
                from trace_reduce import find_xplane, reduce_trace
                ids = {d.id for d in self.devices}
                self.summary = reduce_trace(find_xplane(TRACE_DIR), ids)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return False

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def key_from_seed(seed: int):
    """A PRNG key for any whole-number seed (wider than 32 bits too)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def memory_peak_bytes(devices) -> int:
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices if d.memory_stats()]
    return int(max(peaks)) if peaks else 0


def device_info(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in peaks.json "
                       f"({sorted(table)})")
    return table[kind]


def log(*args) -> None:
    print(*args, file=sys.stdout, flush=True)
