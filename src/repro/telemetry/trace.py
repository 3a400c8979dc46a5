"""Span tracer: Chrome/Perfetto trace-event JSON for engine phases.

``trace_span("round_fold")`` wraps any host-side phase — engine setup,
kernel dispatch/autotune, mesh step construction, per-step driver loops —
and records a complete ("ph": "X") trace event with microsecond
timestamps.  The resulting file loads directly in ``chrome://tracing`` /
Perfetto (``{"traceEvents": [...]}`` format).

Spans wrapped around *jitted* bodies measure trace/compile/autotune
time (the body runs once per compilation) — that is the intended
semantics: dispatch-time attribution, not per-execution device timing.

While a ``jax.profiler`` trace is being recorded, every ``trace_span`` is
also written into it as a ``jax.profiler.TraceAnnotation`` of the same
name and arguments, with or without a telemetry session, so program
spans sit on the profiler's clock beside the device's ops.

With no telemetry session and no profiler trace ``trace_span`` returns a
reusable no-op context manager — no allocation on the off path.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager, nullcontext
from pathlib import Path
from typing import List, Optional

from jax.profiler import TraceAnnotation


class SpanTracer:
    """Collects trace events; ``save`` writes Chrome trace JSON."""

    def __init__(self, path=None):
        self.path = Path(path) if path else None
        self.events: List[dict] = []
        self._t0 = time.perf_counter()
        self._pid = os.getpid()

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    @contextmanager
    def span(self, name: str, **args):
        ts = self._now_us()
        try:
            yield
        finally:
            dur = self._now_us() - ts
            self.events.append({
                "name": name, "ph": "X", "ts": ts, "dur": dur,
                "pid": self._pid, "tid": threading.get_ident() & 0xFFFF,
                "args": {k: _arg(v) for k, v in args.items()},
            })

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event."""
        self.events.append({
            "name": name, "ph": "i", "ts": self._now_us(), "s": "p",
            "pid": self._pid, "tid": threading.get_ident() & 0xFFFF,
            "args": {k: _arg(v) for k, v in args.items()},
        })

    def save(self, path=None) -> Optional[Path]:
        """Write ``{"traceEvents": [...]}``; returns the path (None when
        the tracer has nowhere to write)."""
        out = Path(path) if path else self.path
        if out is None:
            return None
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(
            {"traceEvents": self.events,
             "displayTimeUnit": "ms"}) + "\n", encoding="utf-8")
        return out


def _arg(value):
    if hasattr(value, "item"):
        return value.item()
    if isinstance(value, (str, int, float, bool, type(None))):
        return value
    return str(value)


_NULL_SPAN = nullcontext()


def trace_span(name: str, **args):
    """Span of a host-side phase.  Usage:
    ``with trace_span("gfl.input", round=i): ...``

    It goes to the active session's tracer (Chrome JSON), and, while a
    ``jax.profiler`` trace is being recorded, into that trace as a
    ``TraceAnnotation``.  Profiling sessions (``session(profile=True)`` /
    ``REPRO_TELEMETRY_PROFILE=1``) additionally attribute every span's
    wall time to compile/execute/callback via the ``profile`` stream
    (:mod:`repro.telemetry.profile`).  With neither a session nor a
    profiler trace it is a no-op."""
    from repro.telemetry.stream import current_session
    sess = current_session()
    if sess is None:
        span = _NULL_SPAN
    elif sess.profile:
        from repro.telemetry.profile import profile_phase
        span = profile_phase(name, **args)
    elif sess.tracer is not None:
        span = sess.tracer.span(name, **args)
    else:
        span = _NULL_SPAN
    if not TraceAnnotation.is_enabled():
        return span
    ann = TraceAnnotation(name, **{k: _arg(v) for k, v in args.items()})
    return ann if span is _NULL_SPAN else _both(ann, span)


@contextmanager
def _both(ann, span):
    with ann, span:
        yield
