"""Nested span tracing with Chrome trace-event / flat-jsonl export.

Zero-dependency, off by default.  Call :func:`enable` to install a
process-global :class:`Tracer`; instrumented code wraps work in

    with span("pnr", variant="PE_3x3", app="conv4"):
        ...

When tracing is disabled, :func:`span` returns a shared no-op context
manager singleton — no allocation, no clock reads — so instrumentation
left in hot paths costs ~nothing.  When enabled, spans collect into a
tree (exception-safe: a raising body still closes its span and records
the error) and export as

* Chrome trace-event JSON (``{"traceEvents": [...]}``) loadable in
  Perfetto / ``chrome://tracing``; nesting is encoded by time
  containment, one track per thread that recorded spans, with extra
  tracks (``tid``) for out-of-band events such as XLA compiles (see
  :mod:`repro.obs.jaxprof`);
* flat jsonl — one object per span with its slash-joined ``path``,
  depth, start, duration, thread and attrs (consumed by
  ``results/make_tables.py stages`` and ``python -m repro.obs.report``).

Threads and tasks.  The service runs its event loop on one thread and
each batch on an executor thread, with many requests in flight as
asyncio tasks.  Each thread and each task therefore keeps its own stack
of open spans (a :class:`contextvars.ContextVar` holding an immutable
tuple, owned by the tracer that pushed it, so a stack left behind by a
disabled tracer is never seen by the next one): a span nests under the
innermost span open *in the same thread or task*, and new roots are
added under a lock.  A task created while a span is open starts from
that stack, so its spans nest there; a plain thread starts empty.  Each
:class:`Span` records the thread it ran on.  A region that begins in
one coroutine and ends in another (the batcher's queue wait) is recorded
after the fact with :func:`record_span` under an explicit parent.

Profiler clock.  While tracing is on, every :func:`span` also enters a
``jax.profiler.TraceAnnotation`` of the same name, so in any
``jax.profiler`` trace the program's stages sit on the host plane, on
the device trace's own clock.  That mirror is only sound for a span
that opens and closes on one thread with no ``await`` in between (the
annotation is thread-local), which :func:`span` requires of its
callers.  Spans that cross an ``await`` open with :func:`async_span`
and are not mirrored, nor are spans recorded with :func:`record_span`.

Timestamps come from ``time.perf_counter`` relative to tracer creation.
"""

from __future__ import annotations

import contextvars
import json
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

__all__ = ["Span", "Tracer", "span", "async_span", "event", "record_span",
           "current_span", "enable", "disable", "current"]


class Span:
    """One timed region; ``children`` makes the tree."""

    __slots__ = ("name", "t0", "t1", "attrs", "children", "error",
                 "thread")

    def __init__(self, name: str, t0: float,
                 attrs: Optional[Dict[str, Any]] = None):
        self.name = name
        self.t0 = t0
        self.t1 = t0
        self.attrs: Dict[str, Any] = attrs or {}
        self.children: List[Span] = []
        self.error: str = ""
        self.thread: int = threading.get_ident()

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Span({self.name!r}, dur={self.dur:.6f}, "
                f"children={len(self.children)})")


#: (owning tracer, open spans innermost last) of the running thread/task
_OPEN: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_open_spans", default=(None, ()))


class _SpanCtx:
    """Context manager that opens/closes one span on the caller's stack,
    inside ``annotation`` (a profiler annotation) when one is given."""

    __slots__ = ("_tracer", "_span", "_annotation")

    def __init__(self, tracer: "Tracer", sp: Span, annotation=None):
        self._tracer = tracer
        self._span = sp
        self._annotation = annotation

    def __enter__(self) -> Span:
        if self._annotation is not None:
            self._annotation.__enter__()
        self._tracer._push(self._span)
        return self._span

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self._span.error = f"{exc_type.__name__}: {exc}"
        self._tracer._pop(self._span)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        return False            # never suppress


class _NullCtx:
    """Shared do-nothing context manager used while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NULL_CTX = _NullCtx()


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation``, or None where jax is missing."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:           # pragma: no cover - jax is baked in
        return None
    return TraceAnnotation


class Tracer:
    """Collects a forest of spans; exports Chrome JSON and flat jsonl."""

    def __init__(self):
        self._origin = time.perf_counter()
        self.roots: List[Span] = []
        self._lock = threading.Lock()
        self._thread_names: Dict[int, str] = {}
        # out-of-band complete events (e.g. XLA compiles): extra tracks
        self._tracks: Dict[str, List[Span]] = {}
        self._annotation = _profiler_annotation()

    # -- recording ---------------------------------------------------------
    def now(self) -> float:
        return time.perf_counter() - self._origin

    def span(self, name: str, **attrs: Any) -> _SpanCtx:
        """A span that opens and closes on one thread with no ``await``
        in between; mirrored on the profiler's clock."""
        mirror = self._annotation
        return _SpanCtx(self, Span(name, 0.0, attrs or None),
                        mirror(name) if mirror is not None else None)

    def async_span(self, name: str, **attrs: Any) -> _SpanCtx:
        """A span that may cross an ``await``; not mirrored."""
        return _SpanCtx(self, Span(name, 0.0, attrs or None))

    def open_spans(self) -> Tuple[Span, ...]:
        """The running thread's or task's open spans, innermost last."""
        owner, stack = _OPEN.get()
        return stack if owner is self else ()

    def event(self, name: str, **attrs: Any) -> Span:
        """Zero-duration marker attached at the current tree position."""
        sp = Span(name, self.now(), attrs or None)
        stack = self.open_spans()
        self._attach(sp, stack[-1] if stack else None)
        return sp

    def record(self, name: str, start: float, end: float,
               parent: Optional[Span] = None, **attrs: Any) -> Span:
        """Record a finished region into the main tree under ``parent``
        (a new root when None); ``start``/``end`` are
        ``time.perf_counter()`` readings."""
        sp = Span(name, start - self._origin, attrs or None)
        sp.t1 = end - self._origin
        self._attach(sp, parent)
        return sp

    def add_complete(self, name: str, t0: float, dur: float,
                     track: str = "main", **attrs: Any) -> Span:
        """Record an already-finished region on a named side track."""
        sp = Span(name, t0, attrs or None)
        sp.t1 = t0 + dur
        with self._lock:
            self._tracks.setdefault(track, []).append(sp)
        return sp

    def _attach(self, sp: Span, parent: Optional[Span]) -> None:
        if sp.thread not in self._thread_names:
            self._thread_names[sp.thread] = threading.current_thread().name
        if parent is not None:
            parent.children.append(sp)
        else:
            with self._lock:
                self.roots.append(sp)

    def _push(self, sp: Span) -> None:
        stack = self.open_spans()
        sp.t0 = sp.t1 = self.now()
        self._attach(sp, stack[-1] if stack else None)
        _OPEN.set((self, stack + (sp,)))

    def _pop(self, sp: Span) -> None:
        sp.t1 = self.now()
        # exception-safe even if an inner span leaked: unwind to `sp`
        stack = self.open_spans()
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is sp:
                for leaked in stack[i + 1:]:
                    leaked.t1 = sp.t1
                _OPEN.set((self, stack[:i]))
                return

    # -- queries -----------------------------------------------------------
    def iter_spans(self) -> Iterator[tuple]:
        """Yield ``(span, depth, path)`` depth-first over the main tree,
        every root whatever thread recorded it."""

        def walk(sp: Span, depth: int, prefix: str):
            path = f"{prefix}/{sp.name}" if prefix else sp.name
            yield sp, depth, path
            for ch in list(sp.children):
                yield from walk(ch, depth + 1, path)

        with self._lock:
            roots = list(self.roots)
        for root in roots:
            yield from walk(root, 0, "")

    def span_names(self) -> set:
        names = {sp.name for sp, _, _ in self.iter_spans()}
        for track in self._tracks.values():
            names.update(sp.name for sp in track)
        return names

    def thread_name(self, sp: Span) -> str:
        return self._thread_names.get(sp.thread, str(sp.thread))

    # -- export ------------------------------------------------------------
    def to_chrome(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (``ph: "X"`` complete events): one
        track per thread, the first named ``pipeline``."""
        events: List[Dict[str, Any]] = []

        def emit(sp: Span, tid: int) -> None:
            args = dict(sp.attrs)
            if sp.error:
                args["error"] = sp.error
            events.append({
                "ph": "X", "name": sp.name, "cat": "repro",
                "ts": round(sp.t0 * 1e6, 3),
                "dur": round(max(sp.dur, 0.0) * 1e6, 3),
                "pid": 1, "tid": tid, "args": args})

        def track(tid: int, name: str) -> None:
            events.append({"ph": "M", "pid": 1, "tid": tid,
                           "name": "thread_name", "args": {"name": name}})

        tids: Dict[int, int] = {}
        for sp, _, _ in self.iter_spans():
            if sp.thread not in tids:
                tids[sp.thread] = 1 + len(tids)
                track(tids[sp.thread], "pipeline" if len(tids) == 1
                      else self.thread_name(sp))
            emit(sp, tids[sp.thread])
        if not tids:
            track(1, "pipeline")
        for i, (name, spans) in enumerate(sorted(self._tracks.items())):
            tid = 1 + max(1, len(tids)) + i
            track(tid, name)
            for sp in spans:
                emit(sp, tid)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write_chrome(self, path: str) -> None:
        doc = self.to_chrome()
        # every exported trace records what environment produced it
        from .manifest import capture
        doc["metadata"] = {"manifest": capture().to_dict()}
        with open(path, "w") as fh:
            json.dump(doc, fh)

    def to_rows(self) -> List[Dict[str, Any]]:
        """Flat rows for jsonl export (main tree + side tracks)."""
        rows = [{"name": sp.name, "path": path, "depth": depth,
                 "t0_s": round(sp.t0, 9), "dur_s": round(sp.dur, 9),
                 "error": sp.error, "attrs": sp.attrs,
                 "thread": self.thread_name(sp)}
                for sp, depth, path in self.iter_spans()]
        for track, spans in sorted(self._tracks.items()):
            rows.extend({"name": sp.name, "path": f"{track}/{sp.name}",
                         "depth": 1, "t0_s": round(sp.t0, 9),
                         "dur_s": round(sp.dur, 9), "error": sp.error,
                         "attrs": sp.attrs, "track": track}
                        for sp in spans)
        return rows

    def write_jsonl(self, path: str) -> None:
        with open(path, "w") as fh:
            for row in self.to_rows():
                fh.write(json.dumps(row) + "\n")


# ---------------------------------------------------------------------------
# process-global switch
# ---------------------------------------------------------------------------
_TRACER: Optional[Tracer] = None


def enable() -> Tracer:
    """Install (or return) the process-global tracer."""
    global _TRACER
    if _TRACER is None:
        _TRACER = Tracer()
    return _TRACER


def disable() -> Optional[Tracer]:
    """Stop tracing; returns the tracer so callers can still export it."""
    global _TRACER
    t, _TRACER = _TRACER, None
    return t


def current() -> Optional[Tracer]:
    return _TRACER


def span(name: str, **attrs: Any):
    """Open a span on the global tracer, or a shared no-op when off.  The
    span must open and close on one thread with no ``await`` in between:
    it is mirrored on the profiler's clock."""
    t = _TRACER
    if t is None:
        return _NULL_CTX
    return t.span(name, **attrs)


def async_span(name: str, **attrs: Any):
    """Like :func:`span`, for a region that crosses an ``await``: nested
    on the running task's own stack, not mirrored on the profiler."""
    t = _TRACER
    if t is None:
        return _NULL_CTX
    return t.async_span(name, **attrs)


def event(name: str, **attrs: Any) -> Optional[Span]:
    """Zero-duration marker on the global tracer (no-op when off)."""
    t = _TRACER
    if t is None:
        return None
    return t.event(name, **attrs)


def record_span(name: str, start: float, end: float,
                parent: Optional[Span] = None,
                **attrs: Any) -> Optional[Span]:
    """Record a finished region, ``time.perf_counter()`` readings
    ``start`` to ``end``, under ``parent`` (no-op when off)."""
    t = _TRACER
    if t is None:
        return None
    return t.record(name, start, end, parent, **attrs)


def current_span() -> Optional[Span]:
    """The innermost span open in the running thread or task, if any."""
    t = _TRACER
    if t is None:
        return None
    stack = t.open_spans()
    return stack[-1] if stack else None

