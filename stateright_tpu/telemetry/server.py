"""Live run monitoring: streaming metrics, progress/ETA, stall watchdog,
crash flight recorder.

The monitor taps the ONE place every backend already reports through: the
telemetry tracer. ``MonitorCore`` subscribes to the default tracer as a
sink, so each wave/drain/block span a checker emits (GPUexplore-style
device exploration is opaque *between* waves — the wave boundary is
exactly where a live monitor can tap in) feeds three consumers without
touching any checker hot path:

- a ``ProgressEstimator`` (EWMA states/s, log-linear frontier growth-rate
  fit, an ETA band published as gauges),
- an ``EventBroker`` fanning wave-complete and storage-tier events to
  Server-Sent-Events clients (the Explorer dashboard), and
- a ``StallWatchdog`` that fires when no wave completes within a
  deadline (warning instant + metrics dump + optional ``jax.profiler``
  capture).

``MonitorServer`` wraps the core in an HTTP server:

- ``GET /metrics`` — Prometheus text exposition (sanitized names,
  counters suffixed ``_total``, log2 histograms as cumulative ``le``
  buckets, tier/storage gauges included);
- ``GET /status``  — JSON snapshot merging ``Checker.metrics()`` with
  the progress estimate (non-null ETA fields after >= 3 waves);
- ``GET /events``  — SSE stream of ``wave`` and ``storage`` events.

``FlightRecorder`` is the forensic half: on uncaught exception or
SIGTERM/SIGINT it atomically dumps the tracer ring buffer, a metrics
snapshot, and the checker's state digest to ``flight-<run_id>.json``
(rendered by ``scripts/flight_report.py``).

Everything here is stdlib-only and never blocks a checker: SSE client
queues are bounded and drop on overflow, and ``write_event`` is fully
exception-guarded (a monitor bug must never become a worker_error).
"""

from __future__ import annotations

import itertools
import json
import math
import os
import queue
import re
import signal
import sys
import threading
import time
import traceback
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

from .metrics import Counter, Gauge, Histogram, MetricsRegistry, metrics_registry
from .trace import Tracer, get_tracer

# -- Prometheus text exposition --------------------------------------------

_NAME_SANITIZE = re.compile(r"[^a-zA-Z0-9_:]")


def sanitize_metric_name(name: str, prefix: str = "stateright") -> str:
    """Dotted registry names to the Prometheus grammar: illegal chars
    become ``_``, a namespace prefix keeps them collision-free, and a
    leading digit (impossible after the prefix, kept for prefix="")
    gets an underscore."""
    out = _NAME_SANITIZE.sub("_", name)
    if prefix:
        out = f"{prefix}_{out}"
    if out and out[0].isdigit():
        out = "_" + out
    return out


def _label_str(labels: Optional[Dict[str, str]], extra: str = "") -> str:
    """Renders a label set (plus an optional pre-rendered ``k="v"`` pair
    like a histogram's ``le``) as the ``{...}`` suffix, or ``""``."""
    parts = []
    if labels:
        for k, v in sorted(labels.items()):
            v = str(v).replace("\\", "\\\\").replace('"', '\\"')
            parts.append(f'{k}="{v}"')
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def prometheus_text(registry: MetricsRegistry = None,
                    prefix: str = "stateright",
                    labels: Optional[Dict[str, str]] = None,
                    _seen_types: Optional[set] = None) -> str:
    """The full registry in Prometheus text exposition format (0.0.4).

    Counters gain the conventional ``_total`` suffix; gauges keep their
    registry name (unit suffixes like ``_seconds``/``_bytes`` are already
    part of the naming convention where they apply — e.g.
    ``tpu_bfs.warmup_seconds``, ``*.storage.host_bytes``); log2
    histograms render as cumulative ``le``-bucketed histograms with
    ``_sum``/``_count``. Unset gauges are elided rather than exported as
    fake zeros. ``labels`` attaches a constant label set to every series
    — the multi-run aggregate view exports each run's registry under a
    ``run_id`` label so same-named series never merge."""
    reg = registry if registry is not None else metrics_registry()
    lab = _label_str(labels)
    # Spec: at most one TYPE line per metric family. The multi-run
    # aggregate threads one `_seen_types` set through every registry so
    # same-named series from different runs share a single TYPE line.
    seen = _seen_types if _seen_types is not None else set()

    def type_line(lines, pname, kind):
        if pname not in seen:
            seen.add(pname)
            lines.append(f"# TYPE {pname} {kind}")

    lines: List[str] = []
    for name, inst in reg.instruments():
        if isinstance(inst, Counter):
            pname = sanitize_metric_name(name, prefix) + "_total"
            type_line(lines, pname, "counter")
            lines.append(f"{pname}{lab} {_fmt_value(inst.snapshot())}")
        elif isinstance(inst, Gauge):
            value = inst.snapshot()
            if value is None:
                continue
            pname = sanitize_metric_name(name, prefix)
            type_line(lines, pname, "gauge")
            lines.append(f"{pname}{lab} {_fmt_value(value)}")
        elif isinstance(inst, Histogram):
            snap = inst.snapshot()
            pname = sanitize_metric_name(name, prefix)
            type_line(lines, pname, "histogram")
            cum = 0
            for i, count in enumerate(snap["buckets_log2"]):
                cum += count
                if count:
                    le = _label_str(labels, f'le="{float(1 << i)}"')
                    lines.append(f"{pname}_bucket{le} {cum}")
            inf = _label_str(labels, 'le="+Inf"')
            lines.append(f'{pname}_bucket{inf} {snap["count"]}')
            lines.append(f"{pname}_sum{lab} {_fmt_value(snap['sum'])}")
            lines.append(f"{pname}_count{lab} {snap['count']}")
    return "\n".join(lines) + "\n"


def prometheus_text_all_runs(prefix: str = "stateright") -> str:
    """The aggregate exposition for a multi-run process: the default
    registry unlabeled, then every per-run registry
    (``telemetry.metrics.run_registries``) with a ``run_id`` label —
    same-named series from different runs stay distinct."""
    from .metrics import run_registries

    seen_types: set = set()
    parts = [prometheus_text(prefix=prefix, _seen_types=seen_types)]
    for run_id, reg in sorted(run_registries().items()):
        parts.append(
            prometheus_text(
                reg, prefix=prefix, labels={"run_id": run_id},
                _seen_types=seen_types,
            )
        )
    return "".join(parts)


def _fmt_value(v) -> str:
    if isinstance(v, float):
        return repr(v)
    return str(v)


def registry_hygiene_problems(registry: MetricsRegistry = None,
                              prefix: str = "stateright") -> List[str]:
    """The metric-registry lint (run as a tier-1 test): every registered
    name must survive the Prometheus sanitizer without colliding with a
    different registered name — two dotted names mapping to one
    exposition family would silently merge unrelated series. Counters
    are checked at their exported ``_total`` spelling, so a counter
    ``x.y`` and a gauge ``x.y_total`` collide too. Returns
    human-readable problem strings (empty == clean)."""
    reg = registry if registry is not None else metrics_registry()
    seen: Dict[str, str] = {}
    problems: List[str] = []
    for name, inst in reg.instruments():
        exported = sanitize_metric_name(name, prefix)
        if isinstance(inst, Counter):
            exported += "_total"
        if not re.fullmatch(r"[a-zA-Z_:][a-zA-Z0-9_:]*", exported):
            problems.append(
                f"{name!r} sanitizes to non-Prometheus name {exported!r}"
            )
            continue
        other = seen.get(exported)
        if other is not None and other != name:
            problems.append(
                f"{name!r} and {other!r} both export as {exported!r}"
            )
        seen[exported] = name
    return problems


# -- progress / ETA estimation ---------------------------------------------


class ProgressEstimator:
    """Per-wave progress model: EWMA unique-states/s, a log-linear fit of
    the frontier growth rate, and an ETA band.

    The total state count is unknowable mid-run, so the ETA is a *band*
    built from what BFS frontiers actually do — ramp, plateau, decay:

    - ``eta_s_low``  assumes only the current frontier remains
      (draining it at the EWMA rate);
    - ``eta_s_high`` extrapolates the fitted per-wave growth factor
      ``g`` geometrically — a decaying frontier converges to
      ``frontier * g/(1-g)`` extra states, a growing one is clamped to a
      ``HORIZON``-wave extrapolation (the honest "at least this long").

    Both are None until ``MIN_WAVES`` observations, non-null thereafter.
    A ``clock`` injection point keeps the math unit-testable."""

    MIN_WAVES = 3
    HORIZON_WAVES = 64
    FIT_WINDOW = 32

    def __init__(self, clock=time.monotonic, halflife_s: float = 10.0):
        self._clock = clock
        self._halflife_s = halflife_s
        # RLock: eta_band()/snapshot() hold it across their whole read
        # (a /status poll must not see wave N's count with wave N-1's
        # EWMA) and re-enter via frontier_growth().
        self._lock = threading.RLock()
        self._t0: Optional[float] = None
        self._last_t: Optional[float] = None
        self.waves = 0
        self.ewma_states_per_s: Optional[float] = None
        self.unique_total = 0
        self.generated_total = 0
        self.max_depth = 0
        self.dedup_hit_rate = 0.0
        self.last_frontier: Optional[float] = None
        # (cumulative wave index, log2 frontier) points for the fit.
        self._fit_points: deque = deque(maxlen=self.FIT_WINDOW)

    def observe(self, *, n_new: int, generated: int, frontier=None,
                depth=None, waves: int = 1, dedup_hit_rate=None,
                t: Optional[float] = None) -> None:
        """One wave's (or drain-aggregate's: ``waves`` > 1) completion."""
        now = self._clock() if t is None else t
        with self._lock:
            if self._t0 is None:
                self._t0 = now
            # waves=0 is legal (a drain whose only wave is re-emitted as
            # its own span): count nothing rather than inventing a wave.
            self.waves += max(0, int(waves))
            self.unique_total += int(n_new)
            self.generated_total += int(generated)
            if depth is not None:
                self.max_depth = max(self.max_depth, int(depth))
            if dedup_hit_rate is not None:
                self.dedup_hit_rate = float(dedup_hit_rate)
            elif generated:
                self.dedup_hit_rate = (generated - n_new) / generated
            if self._last_t is not None:
                dt = max(now - self._last_t, 1e-9)
                inst = n_new / dt
                alpha = 1.0 - 0.5 ** (dt / self._halflife_s)
                if self.ewma_states_per_s is None:
                    self.ewma_states_per_s = inst
                else:
                    self.ewma_states_per_s += alpha * (
                        inst - self.ewma_states_per_s
                    )
            self._last_t = now
            if frontier:
                self.last_frontier = float(frontier)
                self._fit_points.append(
                    (float(self.waves), math.log2(float(frontier)))
                )

    def frontier_growth(self) -> Optional[float]:
        """Fitted per-wave frontier growth factor (least squares over the
        recent ``(wave, log2 frontier)`` window); None under 2 points.
        > 1 means the BFS is still ramping, < 1 decaying toward done."""
        with self._lock:
            pts = list(self._fit_points)
        if len(pts) < 2:
            return None
        n = len(pts)
        mx = sum(x for x, _ in pts) / n
        my = sum(y for _, y in pts) / n
        var = sum((x - mx) ** 2 for x, _ in pts)
        if var == 0:
            return 1.0
        slope = sum((x - mx) * (y - my) for x, y in pts) / var
        return 2.0 ** slope

    def eta_band(self) -> Tuple[Optional[float], Optional[float]]:
        with self._lock:
            if (
                self.waves < self.MIN_WAVES
                or not self.last_frontier
                or not self.ewma_states_per_s
            ):
                return None, None
            rate = max(self.ewma_states_per_s, 1e-9)
            f = self.last_frontier
            g = self.frontier_growth() or 1.0
            low = f / rate
            if g < 1.0:
                remaining = f * g / (1.0 - g)
            else:
                # Still ramping: clamp the geometric extrapolation so the
                # band stays finite (it reads "at least", not "exactly").
                remaining = f * min(g, 4.0) * self.HORIZON_WAVES
            high = (f + remaining) / rate
            return low, max(low, high)

    def snapshot(self) -> Dict[str, object]:
        now = self._clock()
        with self._lock:
            eta_low, eta_high = self.eta_band()
            return {
                "waves": self.waves,
                "ewma_states_per_s": self.ewma_states_per_s,
                "frontier_growth": self.frontier_growth(),
                "frontier": self.last_frontier,
                "eta_s_low": eta_low,
                "eta_s_high": eta_high,
                "max_depth": self.max_depth,
                "dedup_hit_rate": self.dedup_hit_rate,
                "unique_states": self.unique_total,
                "states_generated": self.generated_total,
                "elapsed_s": (
                    now - self._t0 if self._t0 is not None else None
                ),
            }


def _default_run_id() -> str:
    """Shared by MonitorCore and a standalone FlightRecorder so their
    flight-<run_id>.json names stay glob-compatible."""
    return time.strftime("%Y%m%d-%H%M%S") + ("-%d" % os.getpid())


# -- SSE fan-out ------------------------------------------------------------

_SSE_CLOSE = (None, None)


class EventBroker:
    """Bounded fan-out from the tracer thread to SSE clients. Queues drop
    on overflow — a slow dashboard must never backpressure a checker."""

    QUEUE_DEPTH = 256

    def __init__(self, on_drop=None):
        self._lock = threading.Lock()
        self._queues: List["queue.Queue"] = []
        self.dropped = 0
        self._on_drop = on_drop

    def subscribe(self) -> "queue.Queue":
        q: "queue.Queue" = queue.Queue(maxsize=self.QUEUE_DEPTH)
        with self._lock:
            self._queues.append(q)
        return q

    def unsubscribe(self, q) -> None:
        with self._lock:
            if q in self._queues:
                self._queues.remove(q)

    def client_count(self) -> int:
        with self._lock:
            return len(self._queues)

    def publish(self, kind: str, payload: Dict) -> None:
        with self._lock:
            queues = list(self._queues)
        for q in queues:
            try:
                q.put_nowait((kind, payload))
            except queue.Full:
                with self._lock:  # publishers race from span-exit threads
                    self.dropped += 1
                if self._on_drop is not None:
                    self._on_drop()

    def close(self) -> None:
        """Wakes every client loop with the close sentinel."""
        with self._lock:
            queues = list(self._queues)
        for q in queues:
            try:
                q.put_nowait(_SSE_CLOSE)
            except queue.Full:
                pass


# -- stall watchdog ----------------------------------------------------------


class StallWatchdog:
    """Fires when no wave completes within ``deadline_s``: a warning
    instant in the trace, a metrics dump to stderr, and (optional) a
    ``jax.profiler`` capture into ``capture_dir`` so the wedge itself
    gets profiled. Fires once per stall — the next wave re-arms it.

    ``clock`` is injectable and ``poll()`` is callable directly, so the
    deadline logic unit-tests with a fake clock and no threads."""

    def __init__(self, deadline_s: float, registry: MetricsRegistry = None,
                 tracer: Tracer = None, clock=time.monotonic,
                 on_stall=None, capture_dir: Optional[str] = None,
                 capture_s: float = 3.0, done_fn=None):
        self.deadline_s = float(deadline_s)
        self._done_fn = done_fn
        self._registry = registry if registry is not None else metrics_registry()
        self._tracer = tracer if tracer is not None else get_tracer()
        self._clock = clock
        self._on_stall = on_stall
        self._capture_dir = capture_dir
        self._capture_s = capture_s
        self._last_pet = clock()
        # Generation counters instead of a boolean latch: pet() racing
        # poll() on a bare `_stalled` flag could latch True just after a
        # wave landed, permanently suppressing the NEXT genuine stall.
        # With generations, "fired once per stall" is simply "don't fire
        # twice for the same pet generation" — race-proof by construction.
        self._pet_gen = 1
        self._fired_gen = 0
        self._stalls = self._registry.counter("monitor.stalls")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def pet(self, t: Optional[float] = None) -> None:
        self._last_pet = self._clock() if t is None else t
        self._pet_gen += 1

    def poll(self, now: Optional[float] = None) -> bool:
        """One deadline check; True when THIS call fired a stall."""
        gen = self._pet_gen
        now = self._clock() if now is None else now
        idle = now - self._last_pet
        if idle <= self.deadline_s or gen == self._fired_gen:
            return False
        if self._done_fn is not None and self._done_fn():
            # Waves stopped because the check FINISHED, not wedged — a
            # monitor held open past completion must not cry stall (and
            # must not burn a pointless profiler capture) every deadline.
            return False
        self._fired_gen = gen
        self._stalls.inc()
        self._tracer.instant(
            "monitor.stall", idle_s=idle, deadline_s=self.deadline_s
        )
        try:
            snap = self._registry.snapshot()
            sys.stderr.write(
                "monitor.stall: no wave for %.1fs (deadline %.1fs); "
                "metrics %s\n"
                % (idle, self.deadline_s,
                   json.dumps(snap, sort_keys=True, default=str))
            )
            sys.stderr.flush()
        except Exception:  # noqa: BLE001 - diagnostics must not raise
            pass
        if self._on_stall is not None:
            try:
                self._on_stall(idle)
            except Exception:  # noqa: BLE001
                pass
        if self._capture_dir is not None:
            self._profiler_capture()
        return True

    def _profiler_capture(self) -> None:
        """Best effort: profile the stalled process for ``capture_s`` so
        the trace shows WHERE it is wedged (device call, host probe,
        compile). No-op when jax is unavailable."""
        try:
            import jax

            jax.profiler.start_trace(self._capture_dir)
            try:
                time.sleep(self._capture_s)
            finally:
                jax.profiler.stop_trace()
            self._tracer.instant(
                "monitor.stall_capture", dir=self._capture_dir
            )
        except Exception:  # noqa: BLE001 - profiler optional by design
            pass

    def start(self) -> "StallWatchdog":
        if self._thread is None:
            interval = max(min(self.deadline_s / 4.0, 1.0), 0.05)

            def loop():
                while not self._stop.wait(interval):
                    self.poll()

            self._thread = threading.Thread(
                target=loop, name="monitor-watchdog", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


# -- flight recorder ---------------------------------------------------------


class FlightRecorder:
    """Crash forensics: on uncaught exception or SIGTERM/SIGINT, dump the
    tracer ring buffer, a metrics snapshot, and the checker state digest
    to ``flight-<run_id>.json`` (atomic tmp+replace — a second signal
    mid-write must not leave torn JSON). ``scripts/flight_report.py``
    renders the file.

    ``install()`` chains — never replaces — the previous ``sys.excepthook``
    and signal handlers, and signal handlers are only installed from the
    main thread (the interpreter rejects them elsewhere)."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self, run_id: Optional[str] = None, out_dir: str = ".",
                 checker=None, registry: MetricsRegistry = None,
                 tracer: Tracer = None):
        self.run_id = run_id or _default_run_id()
        self.out_dir = out_dir
        self.checker = checker
        self._registry = registry if registry is not None else metrics_registry()
        self._tracer = tracer if tracer is not None else get_tracer()
        self._prev_excepthook = None
        self._prev_signal: Dict[int, object] = {}
        self._installed = False
        self._tmp_seq = itertools.count()
        self.last_dump_path: Optional[str] = None

    @property
    def path(self) -> str:
        return os.path.join(self.out_dir, f"flight-{self.run_id}.json")

    def install(self) -> "FlightRecorder":
        if self._installed:
            return self
        self._prev_excepthook = sys.excepthook
        sys.excepthook = self._on_exception
        if threading.current_thread() is threading.main_thread():
            for sig in self.SIGNALS:
                try:
                    self._prev_signal[sig] = signal.signal(
                        sig, self._on_signal
                    )
                except (ValueError, OSError):
                    pass
        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        if sys.excepthook is self._on_exception:
            sys.excepthook = self._prev_excepthook
        for sig, prev in self._prev_signal.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError, TypeError):
                pass
        self._prev_signal.clear()
        self._installed = False

    def _on_exception(self, exc_type, exc, tb) -> None:
        try:
            self.dump("exception", exc=(exc_type, exc, tb))
        except Exception:  # noqa: BLE001 - the hook must not mask the crash
            pass
        if self._prev_excepthook is not None:
            self._prev_excepthook(exc_type, exc, tb)

    def _on_signal(self, signum, frame) -> None:
        try:
            self.dump(signal.Signals(signum).name)
        except Exception:  # noqa: BLE001
            pass
        prev = self._prev_signal.get(signum)
        if callable(prev):
            prev(signum, frame)
            return
        # Re-deliver with the original disposition (default: terminate),
        # so `kill -TERM` still kills and the exit code stays honest.
        signal.signal(
            signum, prev if prev is not None else signal.SIG_DFL
        )
        signal.raise_signal(signum)

    @staticmethod
    def _bounded(fn, timeout_s: float = 2.0, default=None):
        """Runs ``fn`` on a side thread with a deadline. dump() executes
        inside signal handlers, where taking the (non-reentrant)
        registry/instrument locks directly could deadlock against the
        very frame the signal interrupted; a side thread blocks harmlessly
        instead and the dump proceeds without that section."""
        box: Dict[str, object] = {}

        def run():
            try:
                box["value"] = fn()
            except Exception as e:  # noqa: BLE001 - mid-crash best effort
                box["error"] = repr(e)

        t = threading.Thread(
            target=run, name="flight-dump-section", daemon=True
        )
        t.start()
        t.join(timeout_s)
        if "error" in box:
            return {"error": box["error"]}
        return box.get("value", default)

    def dump(self, reason: str, exc=None) -> str:
        """Writes the flight file; returns its path. Every section is
        individually guarded — a half-broken checker mid-crash must still
        yield the ring buffer and metrics."""
        record: Dict[str, object] = {
            "flight_recorder": 1,
            "run_id": self.run_id,
            "reason": reason,
            "pid": os.getpid(),
            "wall_time": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
            ),
        }
        if exc is not None:
            exc_type, exc_value, tb = exc
            record["exception"] = {
                "type": getattr(exc_type, "__name__", str(exc_type)),
                "message": str(exc_value),
                "traceback": "".join(
                    traceback.format_exception(exc_type, exc_value, tb)
                ),
            }
        else:
            record["exception"] = None
        record["metrics"] = self._bounded(
            self._registry.snapshot, default={}
        )
        record["digest"] = (
            self._bounded(self.checker.state_digest)
            if self.checker is not None
            else None
        )
        try:
            # events() retries the deque copy under concurrent appends
            # (worker threads keep emitting while a SIGTERM dump runs).
            record["ring"] = self._tracer.events()
        except Exception:  # noqa: BLE001
            record["ring"] = []
        path = self.path
        # Unique tmp per call: dump is not serialized (a SIGTERM handler
        # can interrupt an in-progress finally-block dump in the SAME
        # thread, so a lock would deadlock). Distinct tmp inodes mean the
        # interleaved dumps each complete whole; last replace wins and
        # the final file is never torn.
        tmp = f"{path}.tmp{next(self._tmp_seq)}"
        with open(tmp, "w") as f:
            json.dump(record, f, default=str)
        os.replace(tmp, path)
        self.last_dump_path = path
        return path


# -- the monitor core (tracer sink + status assembly) ------------------------


class MonitorCore:
    """The HTTP-free monitor: a tracer sink recognizing wave-level spans
    (``new_unique`` in args — the shape every device backend emits),
    host block spans (``unique_total``), and storage-tier spans
    (``.storage.`` in the name), feeding the estimator, the SSE broker,
    and the watchdog. Attach it with ``tracer.add_sink(core)``;
    ``MonitorServer`` does that for you."""

    def __init__(self, checker=None, registry: MetricsRegistry = None,
                 tracer: Tracer = None, run_id: Optional[str] = None,
                 stall_deadline_s: Optional[float] = None,
                 stall_capture_dir: Optional[str] = None,
                 clock=time.monotonic, run_filter: Optional[str] = None):
        self.checker = checker
        self.registry = registry if registry is not None else metrics_registry()
        self.tracer = tracer if tracer is not None else get_tracer()
        self.run_id = run_id or _default_run_id()
        # Per-run selection: with a ``run_filter``, only events stamped
        # with that ``run_id`` arg (checkers spawned with ``run_id=``
        # emit through a RunScopedTracer) feed this core — a multi-job
        # process can run one monitor per job without the jobs' waves
        # polluting each other's estimators. None = aggregate (default):
        # every wave from every run feeds the one estimator.
        self.run_filter = run_filter
        self.estimator = ProgressEstimator(clock=clock)
        # Slow-dashboard drops must be visible to operators, not just an
        # instance attribute: count them in the registry so /metrics and
        # /status carry them.
        self._c_sse_dropped = self.registry.counter("monitor.sse_dropped")
        self.broker = EventBroker(on_drop=self._c_sse_dropped.inc)
        self.closing = threading.Event()
        self._t0 = clock()
        self._clock = clock
        # Per-span-name high-water of ``unique_total`` so host block
        # spans (which carry totals, not deltas) yield new-unique deltas.
        # Locked: host engines exit block spans from N worker threads,
        # and an unsynchronized read-modify-write here would double-count
        # deltas against a stale high-water.
        self._block_unique: Dict[str, int] = {}
        self._block_lock = threading.Lock()
        self._g_rate = self.registry.gauge("monitor.states_per_second_ewma")
        self._g_growth = self.registry.gauge("monitor.frontier_growth")
        self._g_eta_low = self.registry.gauge("monitor.eta_low_seconds")
        self._g_eta_high = self.registry.gauge("monitor.eta_high_seconds")
        self._g_clients = self.registry.gauge("monitor.sse_clients")
        self._c_events = self.registry.counter("monitor.wave_events")
        self._c_errors = self.registry.counter("monitor.sink_errors")
        # Pipeline attribution (telemetry/attribution.py): cumulative
        # wall/phase sums over the run's `.pipeline` spans, surfaced as
        # monitor.pipeline.* shares in /status and /metrics. Cumulative —
        # a single wave's shares would flap with every checkpoint.
        self._c_pipeline = self.registry.counter("monitor.pipeline.events")
        self._g_pipe_util = self.registry.gauge(
            "monitor.pipeline.utilization"
        )
        self._g_pipe_host = self.registry.gauge(
            "monitor.pipeline.host_share"
        )
        self._g_pipe_gap = self.registry.gauge("monitor.pipeline.gap_share")
        # Coverage cartography (telemetry/coverage.py): the cumulative
        # `.coverage` spans refresh these + stream over SSE so the
        # Explorer's coverage panel and scrapers see action coverage and
        # vacuity risk live.
        self._c_coverage = self.registry.counter("monitor.coverage.events")
        self._g_cov_actions = self.registry.gauge(
            "monitor.coverage.action_coverage"
        )
        self._g_cov_dead = self.registry.gauge(
            "monitor.coverage.dead_actions"
        )
        self._g_cov_term = self.registry.gauge(
            "monitor.coverage.terminal_states"
        )
        self._g_cov_revisit = self.registry.gauge(
            "monitor.coverage.revisit_rate"
        )
        self._g_cov_sometimes = self.registry.gauge(
            "monitor.coverage.sometimes_witnessed"
        )
        self._pipe_wall_ms = 0.0
        self._pipe_device_ms = 0.0
        self._pipe_host_ms = 0.0
        self._pipe_gap_ms = 0.0
        # Fleet skew aggregation (telemetry/fleet.py): wave spans from a
        # sharded checker carry per-shard ``fleet_*`` columns; the fold
        # rebuilds the same skew/straggler view the in-checker
        # instruments publish — which makes this core the ONE scrape
        # target for a multi-process mesh (every controller emits
        # identical rows, so any process's monitor serves the fleet).
        from .fleet import FleetFold

        self.fleet = FleetFold()
        self._c_fleet = self.registry.counter("monitor.fleet.events")
        self.watchdog: Optional[StallWatchdog] = None
        if stall_deadline_s is not None:
            self.watchdog = StallWatchdog(
                stall_deadline_s, registry=self.registry,
                tracer=self.tracer, clock=clock,
                capture_dir=stall_capture_dir,
                done_fn=self._checker_done,
            ).start()
        self.tracer.add_sink(self)

    # -- sink surface (called from checker threads; must never raise) ------

    def write_event(self, event: Dict) -> None:
        try:
            self._consume(event)
        except Exception:  # noqa: BLE001 - monitor bugs stay monitor bugs
            self._c_errors.inc()

    def _consume(self, event: Dict) -> None:
        if event.get("ph") != "X":
            return
        name = event.get("name", "")
        args = event.get("args") or {}
        if (
            self.run_filter is not None
            and args.get("run_id") != self.run_filter
        ):
            return
        if "fleet_shards" in args:
            self.fleet.consume_span_args(args)
            self._c_fleet.inc()
            self.broker.publish("fleet", {
                "name": name,
                "skew": self.fleet.last_skew,
                "stragglers": self.fleet.stragglers(),
            })
        if "new_unique" in args:
            # Span `frontier` is the DISPATCH width (drains: F_max / G,
            # waves: the padded chunk width) — constant-ish all run. The
            # live quantities ride `ring_count` (drain pending total) and
            # `live_lanes` (pre-padding wave lanes); feed the estimator
            # those or the growth fit and ETA band would be flat
            # capacity-derived constants in the default deep-drain mode.
            live = next(
                (args[k] for k in ("ring_count", "live_lanes")
                 if args.get(k) is not None),
                args.get("frontier"),
            )
            self._on_wave(name, event, args,
                          n_new=int(args.get("new_unique") or 0),
                          generated=int(args.get("generated") or 0),
                          frontier=live,
                          # `waves=0` is meaningful (a drain whose final
                          # wave is counted by the following wave span) —
                          # only a MISSING arg defaults to 1.
                          waves=(int(args["waves"])
                                 if args.get("waves") is not None else 1))
        elif "unique_total" in args:
            # Host block span: totals, not deltas. Monotone per prefix.
            total = int(args.get("unique_total") or 0)
            with self._block_lock:
                prev = self._block_unique.get(name, 0)
                self._block_unique[name] = max(prev, total)
            self._on_wave(name, event, args,
                          n_new=max(0, total - prev),
                          generated=int(args.get("generated") or 0),
                          # `pending` is the worker's live outstanding
                          # count; `evaluated` is a block-width constant
                          # that would fake a seconds-scale ETA on an
                          # hours-long host run. Absent -> no fit, ETA
                          # stays honestly null.
                          frontier=args.get("pending"),
                          waves=1)
        elif name.endswith(".pipeline") and "wall_ms" in args:
            self._on_pipeline(name, args)
        elif name.endswith(".coverage") and "actions_fired" in args:
            self._on_coverage(name, args)
        elif ".storage." in name:
            self.broker.publish("storage", {
                "name": name,
                "ms": (event.get("dur") or 0.0) / 1000.0,
                "args": args,
            })

    def _on_wave(self, name, event, args, *, n_new, generated, frontier,
                 waves) -> None:
        self._c_events.inc()
        self.estimator.observe(
            n_new=n_new, generated=generated, frontier=frontier,
            depth=args.get("max_depth"), waves=waves,
            dedup_hit_rate=args.get("dedup_hit_rate"),
        )
        if self.watchdog is not None:
            self.watchdog.pet()
        est = self.estimator
        if est.ewma_states_per_s is not None:
            self._g_rate.set(est.ewma_states_per_s)
        growth = est.frontier_growth()
        if growth is not None:
            self._g_growth.set(growth)
        eta_low, eta_high = est.eta_band()
        if eta_low is not None:
            self._g_eta_low.set(eta_low)
            self._g_eta_high.set(eta_high)
        self._g_clients.set(self.broker.client_count())
        self.broker.publish("wave", {
            "name": name,
            "ms": (event.get("dur") or 0.0) / 1000.0,
            "frontier": frontier,
            "new_unique": n_new,
            "generated": generated,
            "waves": waves,
            "max_depth": args.get("max_depth"),
            "dedup_hit_rate": args.get("dedup_hit_rate"),
            "occupancy": args.get("occupancy"),
            "ewma_states_per_s": est.ewma_states_per_s,
            "eta_s_low": eta_low,
            "eta_s_high": eta_high,
        })

    def _on_pipeline(self, name, args) -> None:
        """One attribution span (args carry ``wall_ms``/``gap_ms`` and
        ``<phase>_ms``): accumulate, refresh the monitor.pipeline.*
        share gauges, and stream the per-wave breakdown over SSE."""
        from .attribution import HOST_OVERLAPPABLE_PHASES

        self._c_pipeline.inc()
        wall = float(args.get("wall_ms") or 0.0)
        device = float(args.get("device_ms") or 0.0)
        host = sum(
            float(args.get(f"{p}_ms") or 0.0)
            for p in HOST_OVERLAPPABLE_PHASES
        )
        gap = float(args.get("gap_ms") or 0.0)
        self._pipe_wall_ms += wall
        self._pipe_device_ms += device
        self._pipe_host_ms += host
        self._pipe_gap_ms += gap
        if self._pipe_wall_ms > 0:
            self._g_pipe_util.set(self._pipe_device_ms / self._pipe_wall_ms)
            self._g_pipe_host.set(self._pipe_host_ms / self._pipe_wall_ms)
            self._g_pipe_gap.set(self._pipe_gap_ms / self._pipe_wall_ms)
        self.broker.publish("pipeline", {
            "name": name,
            "wall_ms": wall,
            "phases_ms": {
                k[: -len("_ms")]: v
                for k, v in args.items()
                if k.endswith("_ms") and k != "wall_ms"
            },
            "utilization": (
                self._pipe_device_ms / self._pipe_wall_ms
                if self._pipe_wall_ms
                else None
            ),
        })

    def _on_coverage(self, name, args) -> None:
        """One cumulative coverage span (telemetry/coverage.py): refresh
        the monitor.coverage.* gauges and stream the payload over SSE —
        the Explorer panel re-pulls the per-action counters from /status
        on this signal."""
        self._c_coverage.inc()
        fired = args.get("actions_fired")
        total = args.get("actions_total")
        if fired is not None and total:
            self._g_cov_actions.set(fired / total)
        if args.get("dead_actions") is not None:
            self._g_cov_dead.set(args["dead_actions"])
        if args.get("terminals") is not None:
            self._g_cov_term.set(args["terminals"])
        if args.get("revisit_rate") is not None:
            self._g_cov_revisit.set(args["revisit_rate"])
        if args.get("sometimes_witnessed") is not None:
            self._g_cov_sometimes.set(args["sometimes_witnessed"])
        self.broker.publish("coverage", {
            "name": name,
            **{
                k: args.get(k)
                for k in (
                    "evaluated", "terminals", "actions_fired",
                    "actions_total", "dead_actions", "revisit_rate",
                    "sometimes_witnessed", "sometimes_total",
                    "props_total", "orbit_compression",
                )
                if k in args
            },
        })

    def attach(self, checker) -> "MonitorCore":
        """Late-binds the checker handle (monitors are usually created
        BEFORE ``spawn_*`` so the very first waves are observed; the
        handle only exists after)."""
        self.checker = checker
        return self

    def _checker_done(self) -> bool:
        checker = self.checker
        try:
            return checker is not None and bool(checker.is_done())
        except Exception:  # noqa: BLE001 - watchdog gate is best effort
            return False

    # -- views --------------------------------------------------------------

    def status(self) -> Dict[str, object]:
        """The ``/status`` JSON: progress estimate + checker counts +
        the full metrics snapshot (tier/storage gauges included)."""
        out: Dict[str, object] = {
            "run_id": self.run_id,
            "uptime_s": self._clock() - self._t0,
            "progress": self.estimator.snapshot(),
        }
        checker = self.checker
        if checker is not None:
            try:
                out["checker"] = {
                    "backend": type(checker).__name__,
                    "done": checker.is_done(),
                    "state_count": checker.state_count(),
                    "unique_state_count": checker.unique_state_count(),
                    "max_depth": checker.max_depth(),
                }
            except Exception as e:  # noqa: BLE001 - mid-run races tolerated
                out["checker"] = {"error": repr(e)}
        out["metrics"] = self.registry.snapshot()
        return out

    def fleet_view(self) -> Dict[str, object]:
        """The ``/fleet`` JSON: merged per-shard totals, per-wave skew,
        and the persistent-straggler ranking (empty-shaped when no
        sharded run has emitted fleet columns yet)."""
        out = self.fleet.summary()
        out["run_id"] = self.run_id
        return out

    def prometheus(self) -> str:
        text = prometheus_text(self.registry)
        # Per-shard fleet series with shard/host labels — the exposition
        # a mesh-wide scrape joins on, next to the unlabeled families.
        from .fleet import fleet_prometheus_lines

        lines = fleet_prometheus_lines(self.fleet)
        if lines:
            text = text + "\n".join(lines) + "\n"
        return text

    def close(self) -> None:
        self.closing.set()
        self.broker.close()
        if self.watchdog is not None:
            self.watchdog.stop()
        self.tracer.remove_sink(self, close=False)


# -- shared HTTP routing (used by MonitorServer AND the Explorer) ------------


def _send(handler: BaseHTTPRequestHandler, body: bytes,
          content_type: str, code: int = 200) -> None:
    handler.send_response(code)
    handler.send_header("Content-Type", content_type)
    handler.send_header("Content-Length", str(len(body)))
    handler.end_headers()
    handler.wfile.write(body)


def handle_monitor_get(handler: BaseHTTPRequestHandler, core: MonitorCore,
                       path: str) -> bool:
    """Routes ``/metrics``, ``/status``, ``/events``, ``/fleet`` on any
    BaseHTTPRequestHandler; returns False when the path is not ours so
    the caller's own routing continues (the Explorer mounts these next
    to ``/.status``/``/.states``)."""
    if core is None:
        return False
    if path == "/metrics":
        _send(
            handler, core.prometheus().encode(),
            "text/plain; version=0.0.4; charset=utf-8",
        )
        return True
    if path == "/status":
        _send(
            handler,
            json.dumps(core.status(), default=str).encode(),
            "application/json",
        )
        return True
    if path == "/fleet":
        _send(
            handler,
            json.dumps(core.fleet_view(), default=str).encode(),
            "application/json",
        )
        return True
    if path == "/events":
        _serve_sse(handler, core)
        return True
    return False


def _serve_sse(handler: BaseHTTPRequestHandler, core: MonitorCore,
               heartbeat_s: float = 15.0) -> None:
    q = core.broker.subscribe()
    try:
        # A stalled-but-connected client (full kernel send buffer) must
        # not block this handler thread forever — it would keep its queue
        # subscribed (every publish churns sse_dropped) and survive
        # close(). A write timeout converts the stall into a caught
        # socket error and releases the subscription.
        handler.connection.settimeout(2 * heartbeat_s)
        handler.send_response(200)
        handler.send_header("Content-Type", "text/event-stream")
        handler.send_header("Cache-Control", "no-cache")
        handler.end_headers()
        # An immediate hello lets clients confirm the stream is live
        # before the first wave lands.
        handler.wfile.write(

            b"event: hello\ndata: "
            + json.dumps({"run_id": core.run_id}).encode()
            + b"\n\n"
        )
        handler.wfile.flush()
        while not core.closing.is_set():
            try:
                kind, payload = q.get(timeout=heartbeat_s)
            except queue.Empty:
                handler.wfile.write(b": keepalive\n\n")
                handler.wfile.flush()
                continue
            if kind is None:  # close sentinel
                break
            handler.wfile.write(
                f"event: {kind}\n".encode()
                + b"data: "
                + json.dumps(payload, default=str).encode()
                + b"\n\n"
            )
            handler.wfile.flush()
    except OSError:  # disconnects and write timeouts both end the stream
        pass
    finally:
        core.broker.unsubscribe(q)


class _MonitorHandler(BaseHTTPRequestHandler):
    core: MonitorCore = None

    def log_message(self, *args):  # quiet by default
        pass

    def do_GET(self):
        try:
            if handle_monitor_get(self, self.core, self.path):
                return
            if self.path in ("/", ""):
                body = json.dumps({
                    "run_id": self.core.run_id,
                    "endpoints": [
                        "/metrics", "/status", "/events", "/fleet",
                    ],
                }).encode()
                _send(self, body, "application/json")
                return
            _send(self, b"", "application/json", code=404)
        except ConnectionError:
            # Routine client disconnect mid-response (scraper timeout,
            # curl ^C) must not traceback-spam a long monitored run.
            pass


class MonitorServer:
    """The in-process live monitor: ``MonitorCore`` + an HTTP server on
    its own daemon thread. Attach to any checker::

        monitor = checker.serve_monitor(port=8790)   # or port=0: ephemeral
        ... run ...
        monitor.close()

    ``flight_recorder=True`` additionally installs a ``FlightRecorder``
    (dumping ``flight-<run_id>.json`` on crash/SIGTERM) and
    ``stall_deadline_s=N`` arms the watchdog."""

    def __init__(self, checker=None, port: int = 0, host: str = "127.0.0.1",
                 registry: MetricsRegistry = None, tracer: Tracer = None,
                 run_id: Optional[str] = None,
                 stall_deadline_s: Optional[float] = None,
                 stall_capture_dir: Optional[str] = None,
                 flight_recorder: bool = False, flight_dir: str = ".",
                 run_filter: Optional[str] = None):
        self.core = MonitorCore(
            checker=checker, registry=registry, tracer=tracer,
            run_id=run_id, stall_deadline_s=stall_deadline_s,
            stall_capture_dir=stall_capture_dir, run_filter=run_filter,
        )
        self.flight: Optional[FlightRecorder] = None
        try:
            if flight_recorder:
                self.flight = FlightRecorder(
                    run_id=self.core.run_id, out_dir=flight_dir,
                    checker=checker, registry=self.core.registry,
                    tracer=self.core.tracer,
                ).install()
            handler = type(
                "Handler", (_MonitorHandler,), {"core": self.core}
            )
            self._server = ThreadingHTTPServer((host, port), handler)
        except BaseException:
            # A failed bind (port in use) must not leak the tracer sink,
            # the watchdog thread, or installed signal/except hooks.
            self.core.close()
            if self.flight is not None:
                self.flight.uninstall()
            raise
        self._server.daemon_threads = True
        self.host, self.port = self._server.server_address[:2]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="monitor-http",
            daemon=True,
        )
        self._thread.start()
        self.core.tracer.instant(
            "monitor.started", port=self.port, run_id=self.core.run_id
        )

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def attach(self, checker) -> "MonitorServer":
        """Late-binds the checker handle (create the monitor before
        ``spawn_*`` so the first waves are observed, attach after)."""
        self.core.attach(checker)
        if self.flight is not None:
            self.flight.checker = checker
        return self

    def close(self) -> None:
        self.core.close()
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout=5.0)
        if self.flight is not None:
            self.flight.uninstall()
