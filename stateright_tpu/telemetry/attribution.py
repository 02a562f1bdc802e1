"""Wave-timeline attribution: where real-run wall-clock goes between waves.

The telemetry layer says *that* a run is slow (spans, counters, the live
monitor) and ``checker/breakdown.py`` prices the jitted stages offline —
this module attributes the wall-clock of a REAL run to the gaps between
device work. In attribution mode (opt-in: ``spawn_tpu_bfs(...,
attribution=True)`` / ``spawn_sharded_tpu_bfs(..., attribution=True)``)
each host-visible wave is fenced (``jax.block_until_ready`` at phase
boundaries) and its wall time is classified into named phases:

- ``device``      — dispatch + device compute of the wave/drain executable
- ``host_probe``  — the host tier's Bloom+run probe at the wave exit
- ``evict``       — L0→L1 evictions (incl. the merges/spills they trigger)
- ``table_grow``  — device-table rehash growth
- ``checkpoint``  — checkpoint export + pickle
- ``compile``     — rung/table-shape compiles, detected as AOT-cache
  misses at the dispatch site (the one place a compile can happen)
- ``gap``         — the residual: host bookkeeping, transfers the fences
  don't cover, dispatch idle

The invariant is that phases sum to the measured wave wall: ``gap`` is
defined as the residual, so the only way the ledger can drift is phases
OVERRUNNING the wall (clock skew, double counting) — tracked as
``overrun_s`` and asserted under ``tolerance`` (default 5%). Phases never
nest: an inner ``phase()`` opened while another is open records nothing,
so call sites can wrap helpers without auditing their callees.

**Overlapped execution** (the async pipelined wave engine,
``async_pipeline=True``): host-tier work runs on a worker thread UNDER
device compute, so its time is a new phase class — ``overlapped`` —
recorded through the thread-safe ``overlapped(name)`` window instead of
``phase(name)``. Overlapped time is deliberately NOT part of any wave
window's phase set (it is wall-clock the run never paid serially), so
the sum-to-wall invariant stays exact per wave and the 5% tolerance
check is mode-aware by construction: in overlap mode the in-window
phases are device + the few remaining serial host sections, the gap is
the residual as before, and the shadowed host time reports separately
as ``overlapped_s`` (per phase). Each overlapped window also emits a
``<prefix>.pipeline.overlapped`` trace span so ``scripts/gap_report.py``
can render the ACHIEVED overlap next to the predicted headroom.

``overlapped_s`` is worker-side host time — an UPPER bound on the
wall-clock actually saved: the fraction executed while the checker
thread was itself blocked in an epoch-barrier drain (checkpoint
boundaries, queue-empty waits) ran concurrently with an idle device,
not under compute. The realized saving is what ``utilization`` /
wall-clock deltas measure directly; compare async-off vs async-on legs
(``bench.py --async-ab``) for the ground truth.

Results surface everywhere the existing plumbing reaches: per-phase
``<prefix>.pipeline.*`` registry counters/gauges, one
``<prefix>.pipeline`` trace span per wave (args carry ``wall_ms``,
``gap_ms``, and ``<phase>_ms`` — ``scripts/trace_summary.py`` renders the
attribution table, ``scripts/gap_report.py`` the ledger + overlap
headroom), ``monitor.pipeline.*`` in ``/status`` via the monitor sink,
and per-leg ``attribution`` records in ``bench.py --attribution``.

**Overlap headroom** is the go/no-go number for the async pipelined wave
engine (ROADMAP item 2): the wall-clock a perfect overlap of the host
phases (probe/evict/checkpoint) under device compute would save —
``min(host_overlappable_s, device_s)`` — and the predicted wall under it.

The clock is injectable (tests drive a fake clock through the classifier
deterministically); ``time.perf_counter`` is the default.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from .metrics import MetricsRegistry, metrics_registry
from .trace import Tracer, get_tracer

__all__ = [
    "DEVICE_PHASES",
    "HOST_OVERLAPPABLE_PHASES",
    "PHASES",
    "WaveAttribution",
]

# The canonical phase names (call sites may add others; the ledger carries
# whatever was recorded). Order is the reporting order. Mirrored by
# scripts/trace_summary.py's PHASE_ORDER/HOST_OVERLAPPABLE — the trace
# readers must stay importable without this package (no-jax boxes).
PHASES = (
    "device",
    "wave_kernel",
    "host_probe",
    "evict",
    "table_grow",
    "checkpoint",
    "compile",
)
# Host phases an async pipelined engine could overlap under device
# compute (ROADMAP item 2) — the numerator of the headroom estimate.
# table_grow/compile are device-serial (the next wave needs their
# output), so they are NOT overlappable.
HOST_OVERLAPPABLE_PHASES = ("host_probe", "evict", "checkpoint")
# Phases that ARE device compute: "device" is the staged wave chain,
# "wave_kernel" the fused Pallas megakernel's single dispatch
# (wave_kernel="fused" — ops/pallas_wave.py). Utilization and the
# overlap-headroom denominator sum the class, so the two wave engines
# report comparable ledgers.
DEVICE_PHASES = ("device", "wave_kernel")
DEFAULT_TOLERANCE = 0.05


class _Phase:
    """One timed phase window inside (or between) waves. Non-reentrant by
    design: if another phase is already open this one records nothing
    (phases partition the wave wall; nesting would double-count)."""

    __slots__ = ("_attr", "name", "_t0", "_active")

    def __init__(self, attr: "WaveAttribution", name: str):
        self._attr = attr
        self.name = name
        self._t0 = 0.0
        self._active = False

    def __enter__(self) -> "_Phase":
        attr = self._attr
        if attr._open_phase is None:
            attr._open_phase = self
            self._active = True
            self._t0 = attr._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._active:
            attr = self._attr
            attr._open_phase = None
            attr._add_phase(self.name, attr._clock() - self._t0)


class _OverlappedPhase:
    """One host-tier window running on the async pipeline's worker
    thread, shadowed under device compute. Thread-safe (its ledger is
    lock-guarded and it never touches the wave window's ``_open_phase``
    state) and reentrant across threads by construction: every window
    records, because overlapped windows measure real concurrent work
    rather than partitioning one thread's wall. Emits a
    ``<prefix>.pipeline.overlapped`` span so trace readers see the
    achieved overlap without the registry."""

    __slots__ = ("_attr", "name", "_t0", "_span")

    def __init__(self, attr: "WaveAttribution", name: str):
        self._attr = attr
        self.name = name

    def __enter__(self) -> "_OverlappedPhase":
        attr = self._attr
        self._span = attr._tracer.span(
            f"{attr.prefix}.pipeline.overlapped", phase=self.name
        )
        self._span.__enter__()
        self._t0 = attr._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        attr = self._attr
        dt = max(0.0, attr._clock() - self._t0)
        attr._add_overlapped(self.name, dt)
        self._span.set(**{f"{self.name}_ms": dt * 1e3})
        self._span.__exit__(exc_type, exc, tb)


class _Wave:
    """One wave (or drain) window: measures wall, collects the phases
    recorded inside it, computes the residual gap on exit, and emits the
    ``<prefix>.pipeline`` trace span. Exit is idempotent so the worker's
    error path can ``abort()`` a window a crashed loop left open without
    double counting one that closed normally."""

    __slots__ = ("_attr", "kind", "phases", "_t0", "_span", "_done")

    def __init__(self, attr: "WaveAttribution", kind: str):
        self._attr = attr
        self.kind = kind
        self.phases: Dict[str, float] = {}
        self._done = False

    def __enter__(self) -> "_Wave":
        attr = self._attr
        attr._current = self
        self._span = attr._tracer.span(
            f"{attr.prefix}.pipeline", kind=self.kind
        )
        self._span.__enter__()
        self._t0 = attr._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._done:
            return
        self._done = True
        attr = self._attr
        wall = attr._clock() - self._t0
        attr._current = None
        residual = wall - sum(self.phases.values())
        gap = max(0.0, residual)
        overrun = max(0.0, -residual)
        attr._wall_s += wall
        attr._gap_s += gap
        attr._overrun_s += overrun
        if self.kind == "drain":
            attr._drains += 1
        else:
            attr._waves += 1
        attr._c_waves.inc()
        attr._c_wall.inc(wall)
        attr._c_gap.inc(gap)
        if attr._wall_s > 0:
            device = sum(
                attr._totals.get(p, 0.0) for p in DEVICE_PHASES
            )
            attr._g_util.set(device / attr._wall_s)
            attr._g_gap.set(attr._gap_s / attr._wall_s)
        self._span.set(
            wall_ms=wall * 1e3,
            gap_ms=gap * 1e3,
            **{f"{k}_ms": v * 1e3 for k, v in self.phases.items()},
        )
        self._span.__exit__(exc_type, exc, tb)


class WaveAttribution:
    """The per-run attribution engine one checker owns in attribution
    mode. ``wave()`` wraps each host-visible wave/drain window; ``phase()``
    wraps the classified sections inside it; ``fence()`` pins async device
    work into the surrounding phase. ``report()`` returns the ledger."""

    def __init__(
        self,
        prefix: str,
        clock=None,
        tracer: Tracer = None,
        registry: MetricsRegistry = None,
        tolerance: float = DEFAULT_TOLERANCE,
    ):
        self.prefix = prefix
        self._clock = clock if clock is not None else time.perf_counter
        self._tracer = tracer if tracer is not None else get_tracer()
        reg = registry if registry is not None else metrics_registry()
        self._registry = reg
        self.tolerance = tolerance
        self._totals: Dict[str, float] = {}
        # Window counts per phase: the fused wave's dispatch-overhead
        # story needs *how many* kernel dispatches a wave paid, not just
        # their seconds (one "wave_kernel" window per fused dispatch vs
        # the staged chain's per-stage XLA executables).
        self._windows: Dict[str, int] = {}
        # Phase time accrued OUTSIDE any wave window (seed/restore-time
        # checkpoint reads, the restore path's table grows): reported
        # separately so the in-wave phases + gap still sum to the wave
        # wall — folding it into _totals would silently break the
        # ledger invariant on every resumed run.
        self._outside: Dict[str, float] = {}
        self._phase_counters: Dict[str, object] = {}
        # Overlapped ledger (async pipelined engine): host-tier time the
        # worker thread spent shadowed under device compute, per phase.
        # Lock-guarded — the worker and checker threads both reach it.
        self._overlapped: Dict[str, float] = {}
        self._ov_lock = threading.Lock()
        self._ov_counters: Dict[str, object] = {}
        self._overlap_mode = False
        self._wall_s = 0.0
        self._gap_s = 0.0
        self._overrun_s = 0.0
        self._waves = 0
        self._drains = 0
        self._current: Optional[_Wave] = None
        self._open_phase: Optional[_Phase] = None
        p = f"{prefix}.pipeline"
        self._c_waves = reg.counter(f"{p}.waves")
        self._c_wall = reg.counter(f"{p}.wall_seconds")
        self._c_gap = reg.counter(f"{p}.gap_seconds")
        self._g_util = reg.gauge(f"{p}.utilization")
        self._g_gap = reg.gauge(f"{p}.gap_share")
        # Audit surface for the probabilistic machinery: the device
        # hash set's probe-chain displacement distribution (observed at
        # run end from the final table).
        self._probe_hist = reg.histogram(f"{prefix}.hashset.probe_length")
        self._probe_counts: Optional[List[int]] = None

    # -- recording ---------------------------------------------------------

    def wave(self, kind: str = "wave") -> _Wave:
        return _Wave(self, kind)

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def overlapped(self, name: str) -> _OverlappedPhase:
        """A host-tier window running on the async pipeline's worker
        thread — recorded into the separate ``overlapped`` ledger, never
        into any wave window (see the module docstring's mode-aware
        invariant note)."""
        return _OverlappedPhase(self, name)

    def set_overlap_mode(self, on: bool = True) -> None:
        """Marks the ledger as describing a pipelined run (reported as
        ``overlap_mode``): readers must not expect the host phases
        inside the wave windows — they ride ``overlapped_s``."""
        self._overlap_mode = bool(on)

    def fence(self, tree) -> None:
        """Blocks until every device array in ``tree`` is ready, so the
        surrounding phase window measures real work instead of async
        dispatch latency. Tolerates non-jax leaves and missing jax."""
        try:
            import jax

            jax.block_until_ready(tree)
        except Exception:  # noqa: BLE001 - fencing is best effort
            pass

    def _add_phase(self, name: str, dt: float) -> None:
        if dt < 0:
            dt = 0.0
        cur = self._current
        if cur is not None:
            cur.phases[name] = cur.phases.get(name, 0.0) + dt
            self._totals[name] = self._totals.get(name, 0.0) + dt
            self._windows[name] = self._windows.get(name, 0) + 1
        else:
            self._outside[name] = self._outside.get(name, 0.0) + dt
        c = self._phase_counters.get(name)
        if c is None:
            c = self._registry.counter(
                f"{self.prefix}.pipeline.{name}_seconds"
            )
            self._phase_counters[name] = c
        c.inc(dt)

    def _add_overlapped(self, name: str, dt: float) -> None:
        with self._ov_lock:
            self._overlapped[name] = self._overlapped.get(name, 0.0) + dt
            c = self._ov_counters.get(name)
            if c is None:
                c = self._registry.counter(
                    f"{self.prefix}.pipeline.overlapped.{name}_seconds"
                )
                self._ov_counters[name] = c
            total = self._ov_counters.get("__total__")
            if total is None:
                total = self._registry.counter(
                    f"{self.prefix}.pipeline.overlapped_seconds"
                )
                self._ov_counters["__total__"] = total
        # Counters carry their own locks; inc outside ours.
        c.inc(dt)
        total.inc(dt)

    def abort(self) -> None:
        """Finalizes any window a crashing loop left open (called from
        the checker worker's error path): the open phase is flushed and
        the wave closes normally, so the dying wave's ``.pipeline`` span
        still reaches the trace sinks (flight-recorder forensics) and no
        dangling ``_current``/``_open_phase`` state survives into a
        later ledger read. No-op when nothing is open."""
        phase = self._open_phase
        if phase is not None:
            phase.__exit__(None, None, None)
        cur = self._current
        if cur is not None:
            cur.__exit__(None, None, None)

    def observe_probe_lengths(self, counts) -> None:
        """Feeds the device hash set's displacement counts (index =
        probe-chain length, value = resident keys at that length) into
        the ``<prefix>.hashset.probe_length`` log2 histogram and keeps
        the exact counts for the ledger."""
        counts = [int(c) for c in counts]
        while counts and counts[-1] == 0:
            counts.pop()
        self._probe_counts = counts
        for d, c in enumerate(counts):
            if c:
                self._probe_hist.observe_many(d, c)

    # -- the ledger ---------------------------------------------------------

    def report(self) -> Dict[str, object]:
        """The phase ledger: totals, shares, the sum-to-wall invariant,
        and the overlap-headroom estimate (ROADMAP item 2's go/no-go)."""
        wall = self._wall_s
        phases = {k: v for k, v in sorted(self._totals.items())}
        device = sum(phases.get(p, 0.0) for p in DEVICE_PHASES)
        host = sum(phases.get(p, 0.0) for p in HOST_OVERLAPPABLE_PHASES)
        headroom = min(host, device)
        with self._ov_lock:
            overlapped = dict(sorted(self._overlapped.items()))
        out: Dict[str, object] = {
            "prefix": self.prefix,
            "waves": self._waves,
            "drains": self._drains,
            "wall_s": wall,
            "phases_s": phases,
            "gap_s": self._gap_s,
            "overrun_s": self._overrun_s,
            "tolerance": self.tolerance,
            "within_tolerance": (
                self._overrun_s <= self.tolerance * wall if wall else True
            ),
            "phase_share": (
                {k: v / wall for k, v in phases.items()} if wall else {}
            ),
            "phase_windows": {
                k: v for k, v in sorted(self._windows.items())
            },
            "gap_share": (self._gap_s / wall) if wall else None,
            "utilization": (device / wall) if wall else None,
            "overlap_headroom": {
                "host_overlappable_s": host,
                "device_s": device,
                "headroom_s": headroom,
                "headroom_pct": (headroom / wall) if wall else 0.0,
                "predicted_wall_s": wall - headroom,
            },
            # Overlapped execution (async pipelined engine): host time
            # shadowed under device compute — NOT in phases_s, so the
            # sum-to-wall invariant above stays exact in both modes.
            "overlap_mode": self._overlap_mode,
        }
        if overlapped or self._overlap_mode:
            out["overlapped_s"] = overlapped
            out["overlapped_total_s"] = sum(overlapped.values())
        if self._outside:
            # Phase time outside any wave window (seed/restore): real,
            # but not part of any wave's wall — reported separately so
            # the invariant above stays exact on resumed runs.
            out["outside_wave_s"] = {
                k: v for k, v in sorted(self._outside.items())
            }
        if self._probe_counts is not None:
            out["probe_length_counts"] = list(self._probe_counts)
        return out
