"""Zero-dependency tracer: nestable spans over a contextvar.

A :class:`Span` measures one unit of work (``perf_counter`` wall time),
carries free-form attributes and point-in-time events, and nests: the
span active when another opens becomes its parent. The active span lives
in a :data:`contextvars.ContextVar`, so nesting follows the call stack —
including across ``await``-free thread hops when the submitted task is
wrapped with :func:`wrap` (worker threads start with an empty context;
the wrapper re-plants the caller's active span for the task's duration).

Design constraints:

- **Near-zero overhead when disabled.** ``span()``/``trace()`` check one
  module global and return shared no-op singletons — no Span object, no
  attrs dict, no contextvar write. ``hyperspace.obs.enabled`` routes
  here (config.py).
- **Spans always close.** ``__exit__`` runs on ``BaseException`` too, so
  a simulated crash (faults.CrashPoint) or an injected FaultError still
  records ``error=`` and the duration before propagating — the fault
  plane is *more* visible under tracing, never less.
- **Recording needs an active trace.** ``span()`` is a no-op unless some
  enclosing :func:`trace` established a root (``session.run`` and
  ``Action.run`` do). Instrumented library code can therefore call
  ``span()`` unconditionally; outside a traced request nothing records.
- **One clock with the device.** While a profiler session records, a
  recorded span also enters a profiler annotation of its bare name on
  the thread it runs on, so the span lands in the profile beside the
  device's ops. This module stays stdlib-only: the package's jax shim
  (compat.py) installs the annotation class through :func:`bridge`.
  With no session recording, the cost is one static check per span.

Finished root traces go to the JSON-lines sink when one is configured
(``hyperspace.obs.sink``), and the last root is kept in-process for
``session.last_profile()`` / tests (:func:`last_trace`).
"""

from __future__ import annotations

import collections
import contextvars
import itertools
import json
import os
import threading
import time
from typing import Any, Callable

# Span names a spawned WORKER process may emit — the coordinator's lane
# vocabulary for traces that ship back across the process boundary as
# to_json() dicts and are adopted into the recent-root ring
# (adopt_root; parallel/procpool.py ships them). Declared for the same
# reason stats.KNOWN_COUNTERS is: an undeclared worker span name is a
# typo'd (or unreviewed) lane the chrome exporter and /debug/trace
# would silently grow. Statically enforced over the inferred spawn
# domain by analysis rule HSL022 (docs/static_analysis.md); keep it a
# plain literal of string constants — the analyzer reads it by AST.
KNOWN_WORKER_SPANS = (
    "build.p1.worker",
    "build.p1.decode",
    "build.p1.spill",
    "build.p2.worker",
    "build.p2.read",
    "build.p2.sort",
    "build.p2.write",
    "io.read",
    "io.footers",
    "device.stage",
)

_current: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "hyperspace_obs_span", default=None
)
# Root-trace id of the active trace (None outside one). Distinct from
# _current so events/children anywhere in the tree can cite the ROOT id
# without a parent-pointer walk (spans only link downward).
_trace_id: contextvars.ContextVar["str | None"] = contextvars.ContextVar(
    "hyperspace_obs_trace_id", default=None
)

_enabled = True  # hyperspace.obs.enabled; module-global fast path
_sink_path: str | None = None  # hyperspace.obs.sink; None = no export
_sink_lock = threading.Lock()
_last_trace: "Span | None" = None  # most recently finished ROOT span
# Bounded ring of recently finished root spans — the live feed behind
# /debug/trace and the chrome exporter (docs/observability.md). Kept
# small: a root span tree is a few KB; 32 of them is bounded memory.
RECENT_ROOTS_MAX = 32
_recent_lock = threading.Lock()
_recent_roots: collections.deque = collections.deque(maxlen=RECENT_ROOTS_MAX)
_trace_seq = itertools.count(1)  # itertools.count is GIL-atomic
# Profiler annotation class (jax.profiler.TraceAnnotation once compat.py
# has run :func:`bridge`); None leaves spans off the profiler's trace.
_annotation = None


class Span:
    """One timed unit of work. Use as a context manager; attributes via
    ``set(k=v)`` (chainable), point events via ``add_event``."""

    __slots__ = (
        "name", "attrs", "children", "events", "start_s", "wall_s",
        "error", "tid", "trace_id", "_token", "_ann",
    )

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.children: list[Span] = []
        self.events: list[dict] = []
        self.start_s: float | None = None
        self.wall_s: float | None = None
        self.error: str | None = None
        self.tid: int | None = None  # OS thread the span ran on
        self.trace_id: str | None = None  # set on ROOT spans only
        self._token = None
        self._ann = None  # the open profiler annotation, if one records

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def rename(self, name: str) -> "Span":
        self.name = name
        return self

    def add_event(self, name: str, **attrs) -> None:
        self.events.append({"name": name, **attrs})

    def __enter__(self) -> "Span":
        parent = _current.get()
        if parent is not None:
            # list.append is atomic under the GIL — worker threads
            # re-planted on this parent via wrap() attach children
            # concurrently without a lock.
            parent.children.append(self)
        self._token = _current.set(self)
        self.tid = threading.get_ident()
        ann = _annotation
        if ann is not None and ann.is_enabled():
            # The bare name: attrs stay on the span tree, and readers of
            # the profile match names exactly.
            self._ann = ann(self.name)
            self._ann.__enter__()
        self.start_s = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        # BaseException included: a CrashPoint flying through still
        # closes (and error-tags) every open span on its way out.
        self.wall_s = time.perf_counter() - (self.start_s or 0.0)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if exc is not None and self.error is None:
            self.error = f"{exc_type.__name__}: {exc}"
        _current.reset(self._token)
        return False

    def self_s(self) -> float:
        """Wall time NOT attributed to child spans."""
        own = self.wall_s or 0.0
        return max(0.0, own - sum(c.wall_s or 0.0 for c in self.children))

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()

    def to_json(self) -> dict:
        out: dict[str, Any] = {"name": self.name, "wall_s": self.wall_s}
        # Timeline fields for the chrome exporter (obs/export.py):
        # start_s is this process's perf_counter clock (comparable across
        # spans of one process; the exporter normalizes), tid lanes the
        # span onto the OS thread it ran on.
        if self.start_s is not None:
            out["t0_s"] = self.start_s
        if self.tid is not None:
            out["tid"] = self.tid
        if self.trace_id is not None:
            out["trace_id"] = self.trace_id
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        if self.error is not None:
            out["error"] = self.error
        if self.events:
            out["events"] = list(self.events)
        if self.children:
            out["children"] = [c.to_json() for c in self.children]
        return out


class _NoopSpan:
    """Shared do-nothing span: the disabled/untraced fast path. One
    module-level instance; every method is a cheap no-op so call sites
    never branch."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NoopSpan":
        return self

    def rename(self, name: str) -> "_NoopSpan":
        return self

    def add_event(self, name: str, **attrs) -> None:
        pass


NOOP = _NoopSpan()


class _TraceHandle:
    """Context manager establishing (or joining) a trace. Entering yields
    the root span; exiting a true root records it as the last trace and
    emits one JSON line to the sink."""

    __slots__ = ("_span", "_is_root", "_id_token")

    def __init__(self, span: Span):
        self._span = span
        self._is_root = False
        self._id_token = None

    def __enter__(self) -> Span:
        self._is_root = _current.get() is None
        if self._is_root:
            # Root id: pid-qualified so sink lines from several processes
            # stay distinguishable after aggregation.
            self._span.trace_id = f"{os.getpid()}-{next(_trace_seq)}"
            self._id_token = _trace_id.set(self._span.trace_id)
        return self._span.__enter__()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._span.__exit__(exc_type, exc, tb)
        if self._is_root:
            global _last_trace
            _last_trace = self._span
            _trace_id.reset(self._id_token)
            with _recent_lock:
                _recent_roots.append(self._span)
            _emit(self._span)
            _journal_root(self._span)
        return False


class _NoopTrace:
    __slots__ = ()

    def __enter__(self) -> _NoopSpan:
        return NOOP

    def __exit__(self, *exc) -> bool:
        return False


_NOOP_TRACE = _NoopTrace()


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> None:
    """`hyperspace.obs.enabled` (config.py routes here). Process-global,
    like the metrics it feeds."""
    global _enabled
    _enabled = bool(on)


def configure(sink: str | None = ...) -> None:
    """Adjust module-global tracer config (`hyperspace.obs.*` keys).
    `sink` is a JSON-lines path receiving one event per finished root
    trace; None disables export."""
    global _sink_path
    if sink is not ...:
        _sink_path = str(sink) if sink else None


def sink_path() -> str | None:
    return _sink_path


def bridge(annotation) -> None:
    """Put recorded spans on the profiler's clock: `annotation` is a
    context-manager class built from a span's name, with a static
    ``is_enabled()`` that is true only while a profiler session records
    (``jax.profiler.TraceAnnotation``; compat.py installs it). None
    removes the bridge."""
    global _annotation
    _annotation = annotation


def trace(name: str, **attrs):
    """Open a ROOT span (or a plain child span when a trace is already
    active — nested requests don't double-root). No-op when disabled."""
    if not _enabled:
        return _NOOP_TRACE
    return _TraceHandle(Span(name, attrs))


def span(name: str, **attrs):
    """Open a child span under the active trace. Returns the shared
    no-op singleton when disabled or untraced — nothing is allocated."""
    if not _enabled or _current.get() is None:
        return NOOP
    return Span(name, attrs)


def current_span() -> "Span | None":
    return _current.get()


def annotate(**attrs) -> None:
    """Attach attributes to the active span, if any (used by code that
    has evidence but did not open the span — e.g. a rule recording why
    it failed)."""
    cur = _current.get()
    if cur is not None:
        cur.attrs.update(attrs)


def event(name: str, **attrs) -> None:
    """Record a point-in-time event on the active span (retry attempts,
    evictions). No-op when untraced."""
    if not _enabled:
        return
    cur = _current.get()
    if cur is not None:
        cur.add_event(name, **attrs)


def wrap(fn: Callable) -> Callable:
    """Propagate the caller's active span into a worker-thread task.

    ThreadPoolExecutor workers start with an empty context, so spans
    opened inside them would silently detach; wrapping the submitted
    callable re-plants the submitting thread's active span for the
    task's duration (each task sets/resets its own thread's context —
    safe under arbitrary pool fan-out)."""
    if not _enabled:
        return fn
    parent = _current.get()
    if parent is None:
        return fn

    def run(*args, **kwargs):
        token = _current.set(parent)
        try:
            return fn(*args, **kwargs)
        finally:
            _current.reset(token)

    return run


def span_from_json(d: dict) -> Span:
    """Rebuild a Span tree from its :meth:`Span.to_json` dict — the
    inverse used to adopt a worker PROCESS's finished trace into this
    process (spans only ship across process boundaries as dicts)."""
    s = Span(str(d.get("name", "?")), dict(d.get("attrs") or {}))
    s.wall_s = d.get("wall_s")
    s.start_s = d.get("t0_s")
    s.tid = d.get("tid")
    s.trace_id = d.get("trace_id")
    s.error = d.get("error")
    s.events = list(d.get("events") or [])
    s.children = [span_from_json(c) for c in d.get("children") or ()]
    return s


def adopt_root(root: "dict | None") -> None:
    """Adopt a FOREIGN root span — a worker process's finished trace,
    shipped back as its ``to_json()`` dict — into this process's
    recent-root ring and sink. The root keeps its own pid-qualified
    trace_id, so the chrome exporter lanes it on the worker's pid track
    (one lane per worker process). Never raises on a malformed dict
    (adoption is telemetry, not control flow)."""
    if not _enabled or not root:
        return
    try:
        span = span_from_json(root)
    except (TypeError, ValueError, AttributeError):
        return
    with _recent_lock:
        _recent_roots.append(span)
    _emit(span)
    _journal_root(span)


def _journal_root(span: "Span") -> None:
    """Durable tap: completed root spans (local and adopted) also land
    in the telemetry journal (obs/journal.py). The journal is advisory
    and off by default; `to_json` is only paid when it is on."""
    from hyperspace_tpu.obs import journal as _journal

    if _journal.enabled():
        _journal.record_span(span.to_json())


def last_trace() -> "Span | None":
    """The most recently finished root span (None before the first)."""
    return _last_trace


def current_trace_id() -> "str | None":
    """The active root trace's id (None outside a trace) — the
    correlation key structured events carry (obs/events.py)."""
    return _trace_id.get()


def recent_roots(limit: int | None = None) -> "list[Span]":
    """The most recently finished root spans, oldest first (bounded at
    RECENT_ROOTS_MAX). Feeds /debug/trace and the chrome exporter."""
    with _recent_lock:
        roots = list(_recent_roots)
    return roots if limit is None else roots[-int(limit):]


def reset() -> None:
    """Drop the last trace, recent roots, and sink config (test
    isolation)."""
    global _last_trace, _sink_path
    _last_trace = None
    _sink_path = None
    with _recent_lock:
        _recent_roots.clear()


def _emit(root: Span) -> None:
    """Append one JSON line per finished root trace to the sink. Export
    must never fail a query: errors are swallowed."""
    if _sink_path is None:
        return
    # Wall-clock stamp (not a duration): sink lines are correlated with
    # external logs, which speak wall time.
    line = json.dumps({"ts": time.time(), "trace": root.to_json()}, default=str)
    try:
        with _sink_lock, open(_sink_path, "a") as f:
            f.write(line + "\n")
    except OSError:
        pass
