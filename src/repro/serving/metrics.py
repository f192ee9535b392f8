"""Serving-tier observability: counters, latency histograms and spans.

The serving tier (admission control, deadline scheduling, lease failover)
emits its accounting through a :class:`MetricsRegistry` — a flat namespace
of named :class:`Counter`\\ s and :class:`Histogram`\\ s, optionally labeled
by tenant (``admissions[tenant-a]``).  Everything is in-process and cheap:
counters are a lock + number, histograms keep a bounded window of recent
observations so per-tenant p50/p99 stay O(window) to compute and O(1) to
record.

**Spans.**  The probe path marks its layer boundaries with :func:`span`
(and :func:`timed` where it needs the duration itself, as ``ProbeReport``'s
stage times do).  Tracing is off by default and switched for the whole
process by :func:`set_tracing`:

- off, a span is one flag check returning a shared null context; a
  :func:`timed` span still reads ``time.perf_counter()`` twice;
- on, every span logs ``name, span_id, parent_id, trace_id, thread,
  start_ns, end_ns, attrs, compiles, compile_s`` in a bounded in-memory log
  that :func:`drain` returns and clears.  Timestamps are ``time.time_ns()``,
  the clock of the JAX profiler's host events (an ``.xplane.pb`` stores them
  as offsets from its ``profile_start_time``), and each span also enters a
  ``jax.profiler.TraceAnnotation`` of its name, so under the profiler it
  lies on the host plane beside the device's operations.  The current span
  is a ``contextvars`` variable: a thread started under
  ``contextvars.copy_context()`` names the span that started it as parent.
  A span without a parent takes ``trace_id`` from its caller (the
  micro-batcher passes its batch sequence number); its descendants share it.
- on, one ``jax.monitoring`` listener counts XLA backend compilations: each
  adds one to ``compiles`` and its seconds to ``compile_s`` of the innermost
  open span on the compiling thread, and to the counters
  ``compiles[<span name>]`` / ``compile_s[<span name>]`` (``[none]`` outside
  any span) of the registry passed to :func:`set_tracing`.

:func:`count` adds to a counter of that registry, and only while tracing is on.

:func:`self_ns` is a span's duration minus the union of its children's.

Nothing here imports jax or the runtime at import time — the registry is
safe to use from any layer (scheduler, lease table, micro-batcher) without
import cycles; :func:`set_tracing` imports jax when it turns tracing on.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading
import time
from collections import deque
from typing import Deque, Dict, Iterable, List, Optional


class Counter:
    """Monotonic thread-safe counter (of events, or of seconds)."""

    __slots__ = ("_lock", "_value")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: float = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Histogram:
    """Sliding-window histogram over the most recent ``window`` observations.

    Percentiles are computed over the window (nearest-rank), which is what a
    serving dashboard wants: recent latency, not lifetime latency.  ``count``
    and ``total`` are lifetime aggregates.
    """

    __slots__ = ("_lock", "_window", "_count", "_total")

    def __init__(self, window: int = 2048) -> None:
        self._lock = threading.Lock()
        self._window: Deque[float] = deque(maxlen=max(1, window))
        self._count = 0
        self._total = 0.0

    def observe(self, value: float) -> None:
        with self._lock:
            self._window.append(float(value))
            self._count += 1
            self._total += float(value)

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def total(self) -> float:
        with self._lock:
            return self._total

    def percentile(self, p: float) -> float:
        """Nearest-rank percentile (``p`` in [0, 100]) over the window;
        0.0 when nothing has been observed."""
        with self._lock:
            data = sorted(self._window)
        if not data:
            return 0.0
        rank = min(len(data) - 1, max(0, round((p / 100.0) * (len(data) - 1))))
        return data[int(rank)]


class MetricsRegistry:
    """Named counters/histograms with an optional per-tenant label.

    ``registry.counter("admissions", tenant="a")`` returns (creating on
    first use) the counter registered under ``admissions[a]``; without a
    tenant the bare name is the key.  :meth:`snapshot` flattens everything
    into a plain dict for logs / reports / assertions.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._histograms: Dict[str, Histogram] = {}

    @staticmethod
    def _key(name: str, tenant: Optional[str]) -> str:
        return f"{name}[{tenant}]" if tenant is not None else name

    def counter(self, name: str, tenant: Optional[str] = None) -> Counter:
        key = self._key(name, tenant)
        with self._lock:
            c = self._counters.get(key)
            if c is None:
                c = self._counters[key] = Counter()
            return c

    def histogram(
        self, name: str, tenant: Optional[str] = None, *, window: int = 2048
    ) -> Histogram:
        key = self._key(name, tenant)
        with self._lock:
            h = self._histograms.get(key)
            if h is None:
                h = self._histograms[key] = Histogram(window)
            return h

    def counter_value(self, name: str, tenant: Optional[str] = None) -> float:
        key = self._key(name, tenant)
        with self._lock:
            c = self._counters.get(key)
        return c.value if c is not None else 0

    def snapshot(self) -> Dict[str, float]:
        """Flat, JSON-able view: every counter's value plus each histogram's
        ``.count`` / ``.p50`` / ``.p99``."""
        with self._lock:
            counters = dict(self._counters)
            hists = dict(self._histograms)
        out: Dict[str, float] = {k: float(c.value) for k, c in counters.items()}
        for k, h in hists.items():
            out[f"{k}.count"] = float(h.count)
            out[f"{k}.p50"] = h.percentile(50)
            out[f"{k}.p99"] = h.percentile(99)
        return out


# -- spans ---------------------------------------------------------------

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SPAN_LOG_CAPACITY = 1 << 16

_registry: Optional[MetricsRegistry] = None  # set while tracing is on
_annotation = None  # jax.profiler.TraceAnnotation, bound while tracing is on
_current: "contextvars.ContextVar[Optional[_Span]]" = contextvars.ContextVar(
    "repro_span", default=None
)
_log: Deque[tuple] = deque(maxlen=SPAN_LOG_CAPACITY)
_ids = itertools.count(1)


_NULL = contextlib.nullcontext()  # what span() returns while tracing is off


class _Stopwatch:
    """What :func:`timed` returns while tracing is off: the duration only."""

    __slots__ = ("_t0", "seconds")

    def __enter__(self) -> "_Stopwatch":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0


class _Span:
    __slots__ = ("name", "attrs", "span_id", "parent_id", "trace_id", "start_ns",
                 "end_ns", "compiles", "compile_s", "_token", "_annotation")

    def __init__(self, name: str, trace_id: Optional[int], attrs: dict) -> None:
        self.name = name
        self.attrs = attrs
        self.trace_id = trace_id
        self.compiles = 0
        self.compile_s = 0.0

    def __enter__(self) -> "_Span":
        parent = _current.get()
        self.span_id = next(_ids)
        self.parent_id = parent.span_id if parent is not None else None
        if parent is not None:
            self.trace_id = parent.trace_id
        self._token = _current.set(self)
        self._annotation = _annotation(self.name)
        self.start_ns = time.time_ns()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._annotation.__exit__(None, None, None)
        self.end_ns = time.time_ns()
        _current.reset(self._token)
        registry = _registry
        if registry is not None and len(_log) == _log.maxlen:
            registry.counter("spans_dropped").inc()
        _log.append((self.name, self.span_id, self.parent_id, self.trace_id,
                     threading.current_thread().name, self.start_ns, self.end_ns,
                     self.attrs, self.compiles, self.compile_s))

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def span(name: str, *, trace_id: Optional[int] = None, **attrs):
    """Context manager marking one layer boundary; a shared no-op while
    tracing is off.  ``trace_id`` names the trace of a span that has no
    parent; a child always takes its parent's."""
    if _registry is None:
        return _NULL
    return _Span(name, trace_id, attrs)


def timed(name: str, **attrs):
    """:func:`span` whose ``seconds`` is read after it exits.  On, that is
    the logged span's duration; off, two ``time.perf_counter()`` reads."""
    if _registry is None:
        return _Stopwatch()
    return _Span(name, None, attrs)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name`` of the registry that tracing is on
    with; nothing while tracing is off."""
    registry = _registry
    if registry is not None:
        registry.counter(name).inc(n)


def _on_compile(event: str, secs: float, **_) -> None:
    registry = _registry
    if event != BACKEND_COMPILE_EVENT or registry is None:
        return
    s = _current.get()
    if s is not None:
        s.compiles += 1
        s.compile_s += secs
    where = s.name if s is not None else "none"
    registry.counter("compiles", where).inc()
    registry.counter("compile_s", where).inc(secs)


def set_tracing(registry: Optional[MetricsRegistry]) -> None:
    """Switch program tracing for the whole process: on, tallying compiles
    into ``registry``; off with ``None`` (spans already logged stay until
    :func:`drain`)."""
    global _registry, _annotation
    if (registry is None) != (_registry is None):
        import jax

        if registry is not None:
            _annotation = jax.profiler.TraceAnnotation
            jax.monitoring.register_event_duration_secs_listener(_on_compile)
        else:
            jax.monitoring.unregister_event_duration_listener(_on_compile)
    _registry = registry


_FIELDS = ("name", "span_id", "parent_id", "trace_id", "thread", "start_ns",
           "end_ns", "attrs", "compiles", "compile_s")


def drain() -> List[dict]:
    """Return the logged spans as plain dicts, in the order they ended, and
    clear the log."""
    out = []
    while _log:
        out.append(dict(zip(_FIELDS, _log.popleft())))
    return out


def self_ns(parent: dict, spans: Iterable[dict]) -> int:
    """Self time of the drained span ``parent``: its duration minus the
    part of it that its children in ``spans`` cover (the union of their
    intervals, clipped to ``parent``)."""
    s0, s1 = parent["start_ns"], parent["end_ns"]
    covered, reach = 0, s0
    for c0, c1 in sorted((max(c["start_ns"], s0), min(c["end_ns"], s1))
                         for c in spans if c["parent_id"] == parent["span_id"]):
        c0 = max(c0, reach)
        if c1 > c0:
            covered += c1 - c0
            reach = c1
    return (s1 - s0) - covered
