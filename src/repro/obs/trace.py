"""Span tracer: every span on the profiler's clock, and on request a Chrome
trace-event export (Perfetto / chrome://tracing).

Every :func:`span` (and :func:`traced` call) opens a
``jax.profiler.TraceAnnotation`` of the same name, its attributes the
annotation's metadata. While a profiler session is active
(``jax.profiler.start_trace`` / ``trace``) the span lands in the
``.xplane.pb`` on the host plane of its thread, on the clock the device
operations use; with no session an annotation costs about a microsecond.
Each span also reads ``time.perf_counter()`` on entry and exit and keeps
the pair (``t0``, ``t1``, ``seconds``), so a caller can add the span's
length to a counter without reading the clock again.

Chrome export: one process-wide :data:`TRACER` collects duration events
("B"/"E" pairs) from every instrumented layer — serve-engine request
lifecycles, decode rounds, executor layers, autotuner candidates — and
exports them as the Chrome trace-event JSON format, which loads directly
in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``. It is OFF
by default and gated by the ``REPRO_TRACE`` env var (any value other than
``""``/``"0"``), read once when the tracer is constructed;
:func:`enable`/:func:`disable` toggle it programmatically. When it is off,
``begin``/``end``/``complete`` return immediately and spans emit no
events.

Clocks: Chrome event timestamps are the spans' ``perf_counter`` stamps
(monotonic, so intervals can never go negative under wall-clock
adjustment), rebased to the tracer's construction instant and expressed
in microseconds as the trace format requires. The wall-clock time of that
instant is recorded in the export's ``otherData`` so absolute times are
recoverable.

Lanes: ``tid`` defaults to the real thread id, but callers may pass a
synthetic lane id — the serve engines replay each retired request's
lifecycle (queue-wait -> prefill -> generate) onto its own fresh lane, so
overlapping requests render as parallel tracks and B/E pairs still nest
properly per lane. Replayed events are Chrome-only: a profiler annotation
cannot be back-dated.
"""
from __future__ import annotations

import functools
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

ENV_VAR = "REPRO_TRACE"

# Synthetic-lane allocator: lanes are process-unique so replayed request
# lifecycles from any engine never interleave on one track.
_LANE_BASE = 1 << 20
_lane_counter = itertools.count(1)


def _env_enabled() -> bool:
    return os.environ.get(ENV_VAR, "") not in ("", "0")


def next_lane() -> int:
    """A fresh synthetic tid for one replayed span stack (see module doc)."""
    return _LANE_BASE + next(_lane_counter)


class _Span:
    """Context manager for one span: a profiler annotation, a
    ``perf_counter`` pair, and a balanced B/E pair on the owning tracer
    when it is enabled at entry.

    Attributes passed at construction ride on the annotation and the "B"
    event; attributes added via :meth:`set` during the span join the
    annotation's metadata and ride on the "E" event (Perfetto merges both
    into the slice's args).
    """
    __slots__ = ("_tr", "_name", "_tid", "_cat", "_attrs", "_exit_attrs",
                 "_ann", "_emit", "t0", "t1")

    def __init__(self, tr: "Tracer", name: str, tid, cat: str, attrs: dict):
        self._tr = tr
        self._name = name
        self._tid = tid
        self._cat = cat
        self._attrs = attrs
        self._exit_attrs: Dict[str, Any] = {}
        self._ann = None
        self._emit = False
        self.t0 = self.t1 = 0.0

    @property
    def seconds(self) -> float:
        """perf_counter seconds from entry to exit."""
        return self.t1 - self.t0

    def set(self, **attrs):
        """Attach attributes discovered mid-span (e.g. a measured time)."""
        self._exit_attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)
        return self

    def __enter__(self):
        self._ann = TraceAnnotation(self._name, **self._attrs)
        self._ann.__enter__()
        self._emit = self._tr.enabled
        self.t0 = time.perf_counter()
        if self._emit:
            self._tr.begin(self._name, ts=self.t0, tid=self._tid,
                           cat=self._cat, **self._attrs)
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self._emit:
            self._tr.end(self._name, ts=self.t1, tid=self._tid,
                         cat=self._cat, **self._exit_attrs)
        self._ann.__exit__(*exc)
        return False


class Tracer:
    """Thread-safe collector of Chrome trace duration events."""

    def __init__(self, enabled: Optional[bool] = None):
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._enabled = _env_enabled() if enabled is None else enabled
        self._t0 = time.perf_counter()
        self._wall_t0 = time.time()
        self._pid = os.getpid()

    # ------------------------------------------------------------- gating --

    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self):
        self._enabled = True

    def disable(self):
        self._enabled = False

    def clear(self):
        with self._lock:
            self._events = []

    # ------------------------------------------------------------ emitting --

    def _ts_us(self, t: Optional[float]) -> float:
        """perf_counter seconds (or now) -> trace-relative microseconds."""
        t = time.perf_counter() if t is None else t
        return (t - self._t0) * 1e6

    def _emit(self, ph: str, name: str, ts: Optional[float], tid, cat: str,
              attrs: dict):
        ev = {"ph": ph, "name": name, "cat": cat, "ts": self._ts_us(ts),
              "pid": self._pid,
              "tid": threading.get_ident() if tid is None else tid}
        if attrs:
            ev["args"] = dict(attrs)
        with self._lock:
            self._events.append(ev)

    def begin(self, name: str, *, ts: Optional[float] = None, tid=None,
              cat: str = "repro", **attrs):
        """Open a span. ``ts`` is an optional recorded perf_counter stamp."""
        if self._enabled:
            self._emit("B", name, ts, tid, cat, attrs)

    def end(self, name: str, *, ts: Optional[float] = None, tid=None,
            cat: str = "repro", **attrs):
        if self._enabled:
            self._emit("E", name, ts, tid, cat, attrs)

    def complete(self, name: str, t_start: float, t_end: float, *, tid=None,
                 cat: str = "repro", **attrs):
        """One balanced B/E pair from two recorded perf_counter stamps —
        how engines replay a request lifecycle at retirement."""
        if self._enabled:
            self._emit("B", name, t_start, tid, cat, attrs)
            self._emit("E", name, t_end, tid, cat, {})

    def span(self, name: str, *, tid=None, cat: str = "repro", **attrs):
        """Context manager measuring the enclosed block as one span."""
        return _Span(self, name, tid, cat, attrs)

    # ------------------------------------------------------------- export --

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def to_chrome(self) -> dict:
        """The Chrome trace-event JSON object (load in Perfetto as-is)."""
        return {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {
                "wall_clock_t0": self._wall_t0,
                "pid": self._pid,
                "source": "repro.obs.trace",
            },
        }

    def export(self, path: str) -> str:
        """Write the trace JSON to ``path``; returns the path."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


# Process-wide tracer: the instance every instrumented layer emits to.
TRACER = Tracer()


def enabled() -> bool:
    return TRACER.enabled


def enable():
    TRACER.enable()


def disable():
    TRACER.disable()


def clear():
    TRACER.clear()


def span(name: str, *, tid=None, cat: str = "repro", **attrs):
    """Module-level span on the process tracer (the common call site)."""
    return TRACER.span(name, tid=tid, cat=cat, **attrs)


def export(path: str) -> str:
    return TRACER.export(path)


def traced(name: Optional[str] = None, *, cat: str = "repro"):
    """Decorator form: trace every call of ``fn`` as one span.

    ``@traced()`` uses the function's qualname; ``@traced("label")`` names
    the span explicitly.
    """
    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with TRACER.span(label, cat=cat):
                return fn(*args, **kwargs)
        return wrapper
    return deco
