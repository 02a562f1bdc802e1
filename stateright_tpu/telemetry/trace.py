"""Trace events: spans + instants, ring buffer, JSONL sink, Chrome export.

Events are recorded in Chrome trace-event form directly (``name``, ``ph``,
``ts``/``dur`` in microseconds, ``pid``/``tid``, ``args``) so the JSONL
sink is a plain line-per-event stream and the Perfetto export is just an
envelope around the same dicts. Timestamps come from
``time.perf_counter_ns`` — monotonic, so span durations are exact even
across wall-clock adjustments.

The in-memory ring buffer is always on (bounded, last-N events) and the
no-sink path is the fast path: one small dict + a deque append per event.
Per-state recording is a design error — backends emit one span per
wave/block/drain, keeping overhead well under the always-on budget
(asserted by ``tests/test_telemetry.py``'s overhead micro-benchmark).

Every span records its own ``id`` and the ``parent`` that caused it: the
span open on the same thread (a per-thread stack), or, for a thread's
first span, the root a :class:`CheckTracer` view gives it (a checker's
``tpu_bfs.check`` span), so spans of one check form one tree across the
checker's worker thread and its caller's. Every span is also bridged
into a ``jax.profiler.TraceAnnotation`` of the same name, so a profiler
capture holds the program's spans on the device trace's clock; the
bridge needs jax already imported, and a disabled tracer opens none.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
import weakref
from collections import deque
from typing import Dict, IO, Iterable, List, Optional

RING_CAPACITY = 4096

# Span ids and check ids: process-wide sequences (``next`` on a count is
# atomic under the interpreter lock).
_span_ids = itertools.count(1)
_check_ids = itertools.count(1)


def _now_us() -> float:
    return time.perf_counter_ns() / 1000.0


def _flush_close(file, owns, lock):
    with lock:
        try:
            file.flush()
        except ValueError:
            pass  # already closed (idempotent close / atexit replay)
        if owns:
            file.close()


class JsonlSink:
    """Appends each event as one JSON line; thread-safe, flushed per
    write so a killed run still leaves a parseable prefix. ``close()``
    always flushes (even for caller-owned files) and every sink carries
    a ``weakref.finalize`` — it fires at interpreter exit so a short run
    that never detaches its sink still lands its tail events on disk,
    but unlike ``atexit.register(self.close)`` it does not pin the sink
    (and its fd) for the whole process lifetime: a long-lived service
    that churns through sinks gets each one flushed and released at GC."""

    def __init__(self, path_or_file):
        if hasattr(path_or_file, "write"):
            self._file: IO[str] = path_or_file
            self._owns = False
            self.path = getattr(path_or_file, "name", None)
        else:
            self._file = open(path_or_file, "w")
            self._owns = True
            self.path = os.fspath(path_or_file)
        self._lock = threading.Lock()
        self._finalizer = weakref.finalize(
            self, _flush_close, self._file, self._owns, self._lock
        )

    def write_event(self, event: Dict) -> None:
        line = json.dumps(event, separators=(",", ":"))
        try:
            with self._lock:
                self._file.write(line + "\n")
                self._file.flush()
        except ValueError:
            # remove_sink() can close this file while another checker's
            # worker thread is mid-_emit with a stale reference; telemetry
            # must never turn that race into a worker_error on an
            # otherwise healthy run. The event survives in the ring.
            pass

    def close(self) -> None:
        self._finalizer()  # at most once; later calls are no-ops


def _annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation`` for ``name``, or None
    while jax is not imported (a process that never imported jax has no
    profiler capture to line up with)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        anno = jax.profiler.TraceAnnotation(name)
    except AttributeError:  # jax still importing on another thread
        return None
    anno.__enter__()
    return anno


class _Span:
    """Context manager for one complete ("X") event. ``args`` is mutable
    until exit — callers fill in quantities only known at span end (a
    wave's new-unique count, dedup rate, occupancy). ``id`` is set on
    enter; ``parent`` is the span open on this thread then, else
    ``root``."""

    __slots__ = ("_tracer", "name", "args", "_t0", "id", "parent", "_anno")

    def __init__(self, tracer: "Tracer", name: str, args: Dict,
                 root: Optional[int] = None):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.id = None
        self.parent = root

    def set(self, **kwargs) -> "_Span":
        self.args.update(kwargs)
        return self

    def __enter__(self) -> "_Span":
        stack = self._tracer._stack()
        if stack:
            self.parent = stack[-1]
        self.id = next(_span_ids)
        stack.append(self.id)
        self._anno = _annotation(self.name)
        self._t0 = _now_us()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = _now_us()
        if self._anno is not None:
            self._anno.__exit__(None, None, None)
        stack = self._tracer._stack()
        if stack and stack[-1] == self.id:
            stack.pop()
        elif self.id in stack:
            # Closed out of order (a span that outlives one opened after
            # it, as a drain exit does an attribution window).
            stack.remove(self.id)
        self._tracer._emit(
            {
                "name": self.name,
                "ph": "X",
                "ts": self._t0,
                "dur": t1 - self._t0,
                "pid": os.getpid(),
                "tid": threading.get_ident(),
                "id": self.id,
                "parent": self.parent,
                "args": self.args,
            }
        )


class _NullSpan:
    """The disabled-tracer span: still yields an object with the span
    surface so call sites stay unconditional."""

    __slots__ = ("args",)
    id = None

    def __init__(self):
        self.args: Dict = {}

    def set(self, **kwargs) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass


_NULL_SPAN = _NullSpan()


class Tracer:
    def __init__(self, ring_capacity: int = RING_CAPACITY):
        self._ring: deque = deque(maxlen=ring_capacity)
        self._sinks: List[JsonlSink] = []
        self.enabled = True
        # Per-thread stack of open span ids: a new span's parent.
        self._local = threading.local()
        # Emit lock: the async wave engine's host worker closes wave
        # spans concurrently with the checker thread's drain/compile
        # spans (and the monitor's tracer-sink tap consumes both), so
        # the ring append + sink fan-out must be one atomic step —
        # unlocked, a tap could observe event B before event A from the
        # thread that emitted A first, and interleaved sink writes
        # would tear. deque.append alone is GIL-atomic; the
        # append-then-fan-out sequence is not.
        self._emit_lock = threading.Lock()

    # -- recording ---------------------------------------------------------

    def span(self, name: str, **args) -> "_Span":
        """``with tracer.span("tpu_bfs.wave", frontier=F) as sp: ...`` —
        the span records begin/duration on exit; fill late-bound args via
        ``sp.set(...)`` or ``sp.args[...]``."""
        return self._open(name, args, None)

    def _open(self, name: str, args: Dict, root: Optional[int]):
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args, root)

    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current_span(self) -> Optional[int]:
        """The id of the innermost span open on this thread, or None."""
        stack = self._stack()
        return stack[-1] if stack else None

    def instant(self, name: str, **args) -> None:
        """A point event (scope: thread)."""
        if not self.enabled:
            return
        self._emit(
            {
                "name": name,
                "ph": "i",
                "ts": _now_us(),
                "s": "t",
                "pid": os.getpid(),
                "tid": threading.get_ident(),
                "args": args,
            }
        )

    def _emit(self, event: Dict) -> None:
        # Never held while a signal handler might re-enter: the flight
        # recorder's events() read deliberately stays lock-free (retry
        # loop below) so a SIGTERM dump cannot deadlock against a
        # checker thread parked mid-emit.
        with self._emit_lock:
            self._ring.append(event)
            for sink in self._sinks:
                sink.write_event(event)

    # -- sinks and inspection ----------------------------------------------

    def add_sink(self, sink) -> "JsonlSink":
        """Attaches a sink (anything with ``write_event``); a str/path
        argument is wrapped in a ``JsonlSink``. Returns the sink."""
        if not hasattr(sink, "write_event"):
            sink = JsonlSink(sink)
        self._sinks.append(sink)
        return sink

    def remove_sink(self, sink, close: bool = True) -> None:
        if sink in self._sinks:
            self._sinks.remove(sink)
        if close and hasattr(sink, "close"):
            sink.close()

    def events(self) -> List[Dict]:
        """The ring buffer's current contents, oldest first. A worker
        thread appending mid-copy raises RuntimeError from deque
        iteration (the flight recorder's SIGTERM dump races live wave
        emission); retry — the ring is bounded, so each attempt is
        fast — then fall back to a per-index best-effort copy rather
        than losing the final-wave forensics entirely."""
        for _ in range(8):
            try:
                return list(self._ring)
            except RuntimeError:
                continue
        out: List[Dict] = []
        for i in range(len(self._ring)):
            try:
                out.append(self._ring[i])
            except IndexError:
                break
        return out

    def clear(self) -> None:
        self._ring.clear()


class RunScopedTracer:
    """A view of a tracer that stamps ``run_id`` into every span's and
    instant's args. Checkers spawned with ``run_id=`` emit through one of
    these, so a multi-run process's interleaved wave spans stay
    attributable — ``MonitorCore(run_filter=...)`` selects one run's
    stream, and trace readers can group by ``args.run_id``. Everything
    else (sinks, ring buffer, enablement) delegates to the wrapped
    tracer: the events still land in THE process-local stream.
    ``root`` parents the first span a thread opens through this view."""

    def __init__(self, run_id: str, tracer: Optional[Tracer] = None):
        self.run_id = run_id
        self._tracer = tracer if tracer is not None else get_tracer()
        self._stamp = {"run_id": run_id}
        self.root: Optional[int] = None

    def span(self, name: str, **args):
        for k, v in self._stamp.items():
            args.setdefault(k, v)
        return self._tracer._open(name, args, self.root)

    def instant(self, name: str, **args) -> None:
        for k, v in self._stamp.items():
            args.setdefault(k, v)
        self._tracer.instant(name, **args)

    def __getattr__(self, name):
        return getattr(self._tracer, name)


class CheckTracer(RunScopedTracer):
    """The view one check records through: every span and instant
    carries ``check`` (the ``run_id`` when given, else the next number
    of a per-process sequence) and, with a ``run_id``, ``run_id`` too.
    ``root`` starts as the span open on the spawning thread; the checker
    sets it to its ``tpu_bfs.check`` span once that opens, so the spans
    its caller's thread records for it (``discoveries()``) join the
    check's tree."""

    def __init__(self, run_id: Optional[str] = None,
                 tracer: Optional[Tracer] = None):
        super().__init__(run_id, tracer)
        self.check = run_id if run_id is not None else next(_check_ids)
        self._stamp = {"check": self.check}
        if run_id is not None:
            self._stamp["run_id"] = run_id
        self.root = self._tracer.current_span()


_default_tracer = Tracer()


def get_tracer(run_id: Optional[str] = None):
    """THE process-local tracer every backend records into; with a
    ``run_id``, a :class:`RunScopedTracer` view of it."""
    if run_id is None:
        return _default_tracer
    return RunScopedTracer(run_id, _default_tracer)


def span(name: str, **args) -> "_Span":
    return _default_tracer.span(name, **args)


def instant(name: str, **args) -> None:
    _default_tracer.instant(name, **args)


# -- Chrome trace-event export (Perfetto / chrome://tracing) ---------------


def chrome_trace(events: Optional[Iterable[Dict]] = None) -> Dict:
    """Wraps events (default: the default tracer's ring buffer) in the
    Chrome trace-event JSON envelope. The object form (not the bare
    array) is what Perfetto's JSON importer documents."""
    if events is None:
        events = _default_tracer.events()
    return {
        "traceEvents": list(events),
        "displayTimeUnit": "ms",
    }


def chrome_trace_from_jsonl(path) -> Dict:
    """Re-envelopes a JSONL sink file (one event per line) as Chrome
    trace JSON. Unparseable trailing lines (a killed run's partial
    write) are skipped, never fatal."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return chrome_trace(events)


def write_chrome_trace(path, events: Optional[Iterable[Dict]] = None) -> None:
    with open(path, "w") as f:
        json.dump(chrome_trace(events), f)
