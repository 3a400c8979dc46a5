"""Phase-level profiler: compile vs execute vs host-callback attribution.

PR 7's span tracer answers *where host wall time went* per phase; this
module answers *what the phase spent it on*.  :func:`profile_phase`
wraps a phase (the same names ``trace_span`` uses — with
``session(profile=True)`` or ``REPRO_TELEMETRY_PROFILE=1`` every
``trace_span`` becomes a ``profile_phase`` automatically) and emits one
``profile`` stream record attributing the phase's wall clock:

``compile_s``    jaxpr tracing + MLIR lowering + XLA backend compile
                 seconds inside the phase (a load from the persistent
                 compile cache included), measured via the
                 ``jax.monitoring`` duration events — so a *silent
                 recompile* (shape drift, weak-type flapping, cache
                 key bugs) shows up as nonzero ``compile_s`` +
                 ``retraces``/``lowerings``/``compiles``/``cache_loads``
                 counts long after warmup;
``callback_s``   host seconds spent inside telemetry ``io_callback``
                 flushes (``TelemetrySession.callback_seconds``) — the
                 live cost of observation itself;
``execute_s``    the remainder (device execute + host driver).

It also records the device ``peak_bytes_in_use`` watermark when the
backend exposes ``memory_stats()`` (TPU/GPU; CPU returns none — the
field is simply absent, the schema keeps it optional).

Ordered callbacks can land slightly after the dispatching phase
returns, so ``callback_s`` attribution is per-phase *approximate*; the
per-session total is exact.

With no active telemetry session everything here is a no-op.
"""
from __future__ import annotations

import time
from contextlib import contextmanager, nullcontext
from typing import Dict, Optional

# jax.monitoring duration events that constitute "compile" time.  The
# mapped name is the counter a firing increments.  JAX times a load from
# the persistent compile cache as a backend compile, so ``compiles`` is
# the backend count less the cache loads.
_COMPILE_EVENTS: Dict[str, str] = {
    "/jax/core/compile/jaxpr_trace_duration": "retraces",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

# process-lifetime accumulators; phases snapshot + diff them
_COUNTERS = {"compile_s": 0.0, "retraces": 0, "lowerings": 0, "backend": 0,
             "cache_loads": 0}
_LISTENING = False


def _on_event_duration(event: str, duration: float, **kwargs) -> None:
    if event in _COMPILE_EVENTS:
        _COUNTERS["compile_s"] += float(duration)
        _COUNTERS[_COMPILE_EVENTS[event]] += 1


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT:
        _COUNTERS["cache_loads"] += 1


def ensure_listener() -> bool:
    """Register the jax.monitoring listeners once per process.
    Returns False when the monitoring API is unavailable (profiler then
    reports wall/callback attribution only)."""
    global _LISTENING
    if _LISTENING:
        return True
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        monitoring.register_event_listener(_on_event)
    except Exception:
        return False
    _LISTENING = True
    return True


def compile_counters() -> Dict[str, float]:
    """A snapshot of the process-lifetime compile accumulators:
    ``compile_s`` (trace + lowering + backend seconds, cache loads
    included), ``retraces``, ``lowerings``, ``compiles`` (XLA backend
    compiles) and ``cache_loads`` (programs loaded from the persistent
    compile cache instead)."""
    c = dict(_COUNTERS)
    c["compiles"] = c.pop("backend") - c["cache_loads"]
    return c


def device_peak_bytes() -> Optional[int]:
    """``peak_bytes_in_use`` of the first local device, when the backend
    tracks it (TPU/GPU; CPU ``memory_stats()`` is None)."""
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None


@contextmanager
def profile_phase(name: str, **args):
    """Wrap one host-side phase: span-trace it AND emit a ``profile``
    stream record attributing its wall time.  No-op without a session."""
    from repro.telemetry.stream import current_session, emit
    sess = current_session()
    if sess is None:
        yield
        return
    listening = ensure_listener()
    before = compile_counters()
    cb_before = sess.callback_seconds
    t0 = time.perf_counter()
    span = (sess.tracer.span(name, **args) if sess.tracer is not None
            else nullcontext())
    try:
        with span:
            yield
    finally:
        wall = time.perf_counter() - t0
        after = compile_counters()
        compile_s = (after["compile_s"] - before["compile_s"]
                     if listening else 0.0)
        callback_s = sess.callback_seconds - cb_before
        rec = {
            "seq": sess.next_seq(), "phase": name,
            "wall_s": wall, "compile_s": compile_s,
            "execute_s": max(0.0, wall - compile_s - callback_s),
            "callback_s": callback_s,
            **{k: int(after[k] - before[k]) for k in
               ("retraces", "lowerings", "compiles", "cache_loads")},
        }
        peak = device_peak_bytes()
        if peak is not None:
            rec["peak_bytes"] = float(peak)
        emit("profile", rec)
