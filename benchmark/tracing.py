"""Reduction of a run's traces to the device's busy time and idle gaps.

Two sources, put on one clock (the host's ``perf_counter``):

- the ``jax.profiler`` trace: every operation the device ran, and the
  host's profiler annotations (the checker's ``tpu_bfs.drain`` step
  annotations and this benchmark's ``bench.spawn``/``bench.join``/
  ``bench.verdict``). A ``bench.anchor`` annotation taken at a known
  ``perf_counter_ns`` gives the offset between the two clocks;
- the checker's tracer spans (``tpu_bfs.drain``, ``tpu_bfs.wave``,
  ``tpu_bfs.compile``), recorded in memory by ``SpanRecorder``.

Busy time is the union of the intervals in which a device operation ran,
clipped to the traced window; idle time is the rest of the window. Each
idle gap is named by the most specific host span open at its midpoint.
"""

from __future__ import annotations

import glob
import os
import shutil
import time
from collections import defaultdict

# Host spans that can name an idle gap, most specific first.
GAP_LABELS = (
    "tpu_bfs.compile",
    "tpu_bfs.wave",
    "tpu_bfs.drain",
    "bench.verdict",
    "bench.spawn",
    "bench.join",
)
DEVICE_LINES = ("XLA Ops",)
TOP = 10


def union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy, lo, hi):
    """The parts of ``[lo, hi]`` that no merged ``busy`` interval covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class Labeller:
    """Names a time by the most specific host span (by ``GAP_LABELS``
    order) open at it, or "no host span"; a binary search per label."""

    def __init__(self, host_spans):
        import bisect

        self._bisect = bisect.bisect_right
        by_name = defaultdict(list)
        for name, s, e in host_spans:
            if name in GAP_LABELS:
                by_name[name].append((s, e))
        self._spans = []
        for name in GAP_LABELS:
            merged = union(by_name.get(name, ()))
            self._spans.append((name, [s for s, _ in merged], merged))

    def __call__(self, t):
        for name, starts, merged in self._spans:
            i = self._bisect(starts, t) - 1
            if i >= 0 and merged[i][1] >= t:
                return name
        return "no host span"


def op_name(hlo):
    """A device event's name is the HLO instruction's text; keep its name
    and result type (``%fusion.3 u32[8192,2]``)."""
    head, eq, rest = hlo.partition(" = ")
    if not eq:
        return hlo[:80]
    return (head + " " + rest.split("{")[0].split(" ")[0])[:80]


def reduce(device_ops, host_spans, lo, hi):
    """``device_ops`` and ``host_spans`` are ``(name, start, end)`` on one
    clock in seconds; ``[lo, hi]`` is the traced window. Returns busy and
    window seconds and the breakdown: the device operations that took most
    time, and idle time summed by the host span open in each gap."""
    busy = union(clip([(s, e) for _, s, e in device_ops], lo, hi))
    busy_s = sum(e - s for s, e in busy)
    per_op = defaultdict(float)
    for name, s, e in device_ops:
        per_op[op_name(name)] += e - s
    per_label = defaultdict(float)
    name_at = Labeller(host_spans)
    for g in gaps(busy, lo, hi):
        per_label[name_at((g[0] + g[1]) / 2)] += g[1] - g[0]
    top = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": hi - lo,
        "breakdown": {"device_ops": top(per_op), "idle_gaps": top(per_label)},
    }


class SpanRecorder:
    """A tracer sink that keeps every span in memory while attached."""

    def __init__(self):
        self.events = []

    def write_event(self, event):
        self.events.append(event)

    def attach(self):
        from stateright_tpu.telemetry import get_tracer

        get_tracer().add_sink(self)

    def detach(self):
        from stateright_tpu.telemetry import get_tracer

        get_tracer().remove_sink(self, close=False)


class DeviceTrace:
    """One ``jax.profiler`` trace of part of the window, kept in
    ``logdir`` until ``remove``."""

    def __init__(self, logdir):
        self.logdir = logdir
        self.t_from = self.t_to = None

    def start(self):
        import jax

        shutil.rmtree(self.logdir, ignore_errors=True)
        # Host annotations only (no Python function tracing): what names
        # the idle gaps, at the least cost to the host.
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 1
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.logdir, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.anchor"):
            self.anchor_ns = time.perf_counter_ns()
        self.t_from = time.perf_counter()

    def stop(self):
        import jax

        if self.t_to is None:
            self.t_to = time.perf_counter()
            jax.profiler.stop_trace()

    def read(self):
        """(device ops, host annotations) of the trace as ``(name, start,
        end)`` in perf_counter seconds."""
        from jax.profiler import ProfileData

        (path,) = glob.glob(
            os.path.join(self.logdir, "**", "*.xplane.pb"), recursive=True
        )
        pd = ProfileData.from_file(path)
        device, host, anchor = [], [], None
        for plane in pd.planes:
            is_device = plane.name.startswith("/device:")
            for line in plane.lines:
                if is_device and line.name not in DEVICE_LINES:
                    continue
                for ev in line.events:
                    rec = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    if is_device:
                        device.append(rec)
                    elif ev.name in GAP_LABELS:
                        host.append(rec)
                    elif ev.name == "bench.anchor":
                        anchor = ev.start_ns
        if anchor is None:
            raise RuntimeError("no bench.anchor annotation in the profiler trace")
        shift = self.anchor_ns - anchor
        conv = lambda evs: [(n, (s + shift) / 1e9, (e + shift) / 1e9) for n, s, e in evs]
        return conv(device), conv(host)

    def reduce(self, spans):
        device, host = self.read()
        host += [
            (e["name"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
            for e in spans if e.get("ph") == "X"
        ]
        out = reduce(device, host, self.t_from, self.t_to)
        out["t_from"], out["t_to"] = self.t_from, self.t_to
        return out

    def remove(self):
        shutil.rmtree(self.logdir, ignore_errors=True)


class StopAfter:
    """Wraps a system so the profiler stops after the first check that
    ends ``seconds`` or more after the wrapper was made."""

    def __init__(self, system, trace, seconds):
        self.system, self.trace = system, trace
        self.deadline = time.perf_counter() + seconds

    def check(self, cap):
        rec = self.system.check(cap)
        if rec.t_end >= self.deadline:
            self.trace.stop()
        return rec
