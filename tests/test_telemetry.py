"""Telemetry layer tests: instrument semantics, trace shape, golden
reporter strings, end-to-end smoke with a JSONL sink, and the always-on
overhead budget."""

import io
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from fixtures import LinearEquation
from stateright_tpu import TelemetryReporter, WriteReporter, fingerprint
from stateright_tpu.telemetry import (
    CheckTracer,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Tracer,
    chrome_trace,
    chrome_trace_from_jsonl,
    get_tracer,
    metrics_registry,
)

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- metrics registry ------------------------------------------------------


def test_counter_semantics():
    c = Counter("c")
    assert c.snapshot() == 0
    c.inc()
    c.inc(41)
    assert c.snapshot() == 42


def test_gauge_semantics():
    g = Gauge("g")
    assert g.snapshot() is None
    g.set(7)
    g.set(3.5)
    assert g.snapshot() == 3.5


def test_histogram_log2_buckets_and_stats():
    h = Histogram("h")
    for v in (1, 2, 3, 4, 1024):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["sum"] == 1034
    assert snap["min"] == 1 and snap["max"] == 1024
    assert snap["mean"] == pytest.approx(1034 / 5)
    buckets = snap["buckets_log2"]
    # 1 -> bucket 0 ((0,1]); 2 -> bucket 1; 3,4 -> bucket 2 ((2,4]);
    # 1024 = 2^10 -> bucket 10. Trailing empties elided.
    assert len(buckets) == 11
    assert buckets[0] == 1 and buckets[1] == 1 and buckets[2] == 2
    assert buckets[10] == 1


def test_histogram_nonpositive_lands_in_bucket_zero():
    h = Histogram("h")
    h.observe(0)
    h.observe(-3)
    assert h.snapshot()["buckets_log2"] == [2]


def test_registry_get_or_create_is_stable_and_kind_checked():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    reg.gauge("g").set(1)
    reg.histogram("h").observe(2)
    snap = reg.snapshot()
    assert snap["x"] == 0
    assert snap["g"] == 1
    assert snap["h"]["count"] == 1
    assert list(snap) == sorted(snap)


def test_default_registry_is_process_local_singleton():
    assert metrics_registry() is metrics_registry()


# -- tracer ----------------------------------------------------------------


def test_span_records_complete_event_with_args():
    tracer = Tracer()
    with tracer.span("work", a=1) as sp:
        sp.set(b=2)
    (ev,) = tracer.events()
    assert ev["name"] == "work"
    assert ev["ph"] == "X"
    assert ev["args"] == {"a": 1, "b": 2}
    assert ev["dur"] >= 0
    assert isinstance(ev["ts"], float)


def test_instant_and_ring_capacity():
    tracer = Tracer(ring_capacity=3)
    for i in range(5):
        tracer.instant("tick", i=i)
    events = tracer.events()
    assert len(events) == 3
    assert [e["args"]["i"] for e in events] == [2, 3, 4]
    assert all(e["ph"] == "i" for e in events)


def test_disabled_tracer_emits_nothing():
    tracer = Tracer()
    tracer.enabled = False
    with tracer.span("work") as sp:
        sp.set(a=1)
    tracer.instant("tick")
    assert tracer.events() == []


def test_spans_record_parent_and_check_across_threads():
    tracer = Tracer()
    view = CheckTracer(tracer=tracer)
    other = CheckTracer("job-7", tracer=tracer)
    assert other.check == "job-7" and view.check != other.check

    def worker():
        with view.span("remote"):
            with view.span("leaf"):
                pass

    with view.span("root") as root:
        view.root = root.id
        with view.span("child"):
            thread = threading.Thread(target=worker)
            thread.start()
            thread.join(timeout=30)
        assert not thread.is_alive()
    with other.span("job"):
        tracer.instant("tick")
    ev = {e["name"]: e for e in tracer.events()}
    assert ev["root"]["parent"] is None
    assert ev["child"]["parent"] == ev["root"]["id"]
    # A thread's first span hangs off the view's root, not the span that
    # happens to be open on the spawning thread.
    assert ev["remote"]["parent"] == ev["root"]["id"]
    assert ev["leaf"]["parent"] == ev["remote"]["id"]
    assert ev["remote"]["tid"] != ev["root"]["tid"]
    ours = ("root", "child", "remote", "leaf")
    assert len({ev[n]["id"] for n in ours}) == 4
    assert {ev[n]["args"]["check"] for n in ours} == {view.check}
    assert "run_id" not in ev["root"]["args"]
    assert ev["job"]["args"] == {"check": "job-7", "run_id": "job-7"}
    # Nesting on the plain tracer: the instant is no span; the plain
    # tracer stamps no check.
    assert ev["job"]["parent"] is None and ev["tick"]["args"] == {}


def test_profiler_bridge_follows_enablement(monkeypatch):
    import jax

    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tracer = Tracer()
    with tracer.span("on"):
        pass
    assert opened == ["on"]
    tracer.enabled = False
    with tracer.span("off"):
        pass
    with CheckTracer(tracer=tracer).span("off.view"):
        pass
    assert opened == ["on"]


def test_traced_jit_records_only_calls_that_obtain_an_executable():
    """``AotJit`` keys each call on its static arguments and the avals of
    the others, and calls the executable its ``obtain`` supplies without
    the static ones; an ``exe_span`` around a miss records where the
    executable came from."""
    import jax.numpy as jnp

    from stateright_tpu.checker.programs import AotJit
    from stateright_tpu.telemetry.executables import exe_span

    tracer = Tracer()
    exes, keys = {}, []

    def obtain(kind, key, jit_fn, args):
        keys.append(key)
        full = (kind,) + key
        if full not in exes:
            with exe_span(tracer, "t.exe", kind):
                exes[full] = jit_fn.lower(*args).compile()
        return exes[full]

    fn = AotJit(obtain, "head", lambda x, n: x[:n] + 1, static_argnums=(1,))
    with tracer.span("outer") as outer:
        assert list(fn(jnp.zeros(3), 2)) == [1, 1]
        assert list(fn(jnp.ones(3), 2)) == [2, 2]  # same key: reused
        fn(jnp.zeros(5), 2)  # new shape: a second executable
        fn(jnp.zeros(5), 1)  # new static argument: a third
        fn(jnp.zeros(5, jnp.int32), 1)  # new dtype: a fourth
    assert keys[0] == keys[1] and len(set(keys)) == 4
    assert keys[0][0] == (2,)
    spans = [e for e in tracer.events() if e["name"] == "t.exe"]
    assert len(spans) == 4
    first = spans[0]
    assert first["parent"] == outer.id
    assert first["args"]["kind"] == "head"
    assert first["args"]["source"] in ("persistent_cache", "compile")
    assert first["args"]["trace_ms"] > 0 and first["args"]["build_ms"] >= 0
    # A Python scalar is weakly typed, an int32 array is not: two keys.
    inc = AotJit(obtain, "inc", lambda x: x + 1)
    inc(1)
    inc(jnp.int32(1))
    assert keys[-1] != keys[-2]


def test_jsonl_sink_and_chrome_export(tmp_path):
    tracer = Tracer()
    path = tmp_path / "trace.jsonl"
    sink = tracer.add_sink(str(path))
    with tracer.span("outer", n=1):
        tracer.instant("inner")
    tracer.remove_sink(sink)
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 2
    parsed = [json.loads(line) for line in lines]
    # Span closes after the instant, so the instant lands first.
    assert [p["name"] for p in parsed] == ["inner", "outer"]

    trace = chrome_trace_from_jsonl(str(path))
    assert set(trace) == {"traceEvents", "displayTimeUnit"}
    assert len(trace["traceEvents"]) == 2
    span_ev = trace["traceEvents"][1]
    assert span_ev["ph"] == "X" and "dur" in span_ev and "ts" in span_ev
    assert span_ev["pid"] and span_ev["tid"]


def test_chrome_trace_from_jsonl_skips_partial_tail(tmp_path):
    path = tmp_path / "trace.jsonl"
    path.write_text('{"name": "ok", "ph": "i", "ts": 1}\n{"name": "tru')
    assert len(chrome_trace_from_jsonl(str(path))["traceEvents"]) == 1


def test_chrome_trace_wraps_default_ring():
    trace = chrome_trace([{"name": "e", "ph": "i", "ts": 0}])
    assert trace["traceEvents"][0]["name"] == "e"


# -- reporter golden strings ----------------------------------------------


def _golden_output(checker):
    out = io.StringIO()
    checker.report(WriteReporter(out))
    return out.getvalue()


def _expected_solvable_tail():
    fp = fingerprint
    expected_path = "/".join(
        str(fp(s)) for s in [(0, 0), (1, 0), (2, 0), (2, 1)]
    )
    return (
        'Discovered "solvable" example Path[3]:\n'
        "- 'IncreaseX'\n"
        "- 'IncreaseX'\n"
        "- 'IncreaseY'\n"
        f"Fingerprint path: {expected_path}\n"
    )


def test_write_reporter_strings_unchanged_with_telemetry_sink(tmp_path):
    """The golden compatibility strings must be byte-identical with a
    trace sink attached and metrics flowing."""
    sink = get_tracer().add_sink(str(tmp_path / "t.jsonl"))
    try:
        checker = LinearEquation(2, 10, 14).checker().spawn_bfs().join()
        output = _golden_output(checker)
    finally:
        get_tracer().remove_sink(sink)
    assert output.startswith("Done. states=15, unique=12, depth=4, sec=")
    assert output.endswith(_expected_solvable_tail())
    # The sink really was live during the run.
    assert (tmp_path / "t.jsonl").read_text().strip()


def test_telemetry_reporter_wraps_without_altering_inner(tmp_path):
    checker = LinearEquation(2, 10, 14).checker().spawn_bfs().join()
    plain = io.StringIO()
    checker.report(WriteReporter(plain))

    wrapped = io.StringIO()
    checker.report(
        TelemetryReporter(wrapped, inner=WriteReporter(wrapped))
    )
    wrapped_out = wrapped.getvalue()
    telemetry_at = wrapped_out.index("Telemetry ")
    inner_part = (
        wrapped_out[:telemetry_at]
        + wrapped_out[wrapped_out.index("\n", telemetry_at) + 1 :]
    )
    # Inner WriteReporter output byte-identical modulo the sec= field
    # (wall clock differs between the two report() calls).
    import re

    strip_sec = lambda s: re.sub(r"sec=\d+", "sec=_", s)  # noqa: E731
    assert strip_sec(inner_part) == strip_sec(plain.getvalue())
    telemetry_line = wrapped_out[telemetry_at:].splitlines()[0]
    snap = json.loads(telemetry_line[len("Telemetry ") :])
    assert snap["bfs.blocks"] >= 1


def test_checker_metrics_accessor():
    checker = LinearEquation(2, 10, 14).checker().spawn_bfs().join()
    assert checker.metrics() is metrics_registry()
    assert checker.metrics().snapshot()["bfs.states_generated"] >= 1


# -- end-to-end smoke: CPU BFS with tracing on ----------------------------


def test_smoke_host_bfs_trace_parses(tmp_path):
    """Tiny CPU BFS with the JSONL sink attached: the file parses, the
    Chrome export loads, and scripts/trace_summary.py renders it."""
    path = tmp_path / "bfs.jsonl"
    sink = get_tracer().add_sink(str(path))
    try:
        LinearEquation(2, 10, 14).checker().spawn_bfs().join()
    finally:
        get_tracer().remove_sink(sink)
    events = chrome_trace_from_jsonl(str(path))["traceEvents"]
    blocks = [e for e in events if e["name"] == "bfs.block"]
    assert blocks, "host BFS must emit at least one block span"
    assert blocks[-1]["args"]["generated"] >= 1

    r = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_DIR, "scripts", "trace_summary.py"),
            str(path),
            "--chrome-out",
            str(tmp_path / "bfs.chrome.json"),
        ],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert r.returncode == 0, r.stderr
    with open(tmp_path / "bfs.chrome.json") as f:
        assert json.load(f)["traceEvents"]


def test_smoke_tpu_bfs_wave_spans(tmp_path):
    """The device checker (CPU backend) must emit ≥1 span per BFS wave
    carrying frontier-size, dedup-hit-rate, and occupancy args — the
    acceptance shape for every future perf judgment."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    path = tmp_path / "tpu.jsonl"
    sink = get_tracer().add_sink(str(path))
    try:
        checker = (
            TwoPhaseSys(2)
            .checker()
            .spawn_tpu_bfs(
                frontier_capacity=1 << 6,
                table_capacity=1 << 10,
                max_drain_waves=1,  # wave-at-a-time: one span per wave
            )
            .join()
        )
    finally:
        get_tracer().remove_sink(sink)
    assert checker.unique_state_count() == 56

    events = chrome_trace_from_jsonl(str(path))["traceEvents"]
    waves = [e for e in events if e["name"] == "tpu_bfs.wave"]
    # 2pc-2 BFS has several levels; each must have produced a wave span.
    assert len(waves) >= 3
    for ev in waves:
        args = ev["args"]
        assert args["frontier"] >= 1
        assert 0.0 <= args["dedup_hit_rate"] <= 1.0
        assert 0.0 <= args["occupancy"] <= 1.0
        assert "new_unique" in args and "max_depth" in args
    # The summary table renders wave rows for these spans.
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_DIR, "scripts", "trace_summary.py"),
            str(path),
        ],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert r.returncode == 0, r.stderr
    assert "tpu_bfs.wave" in r.stdout

    reg = metrics_registry().snapshot()
    assert reg["tpu_bfs.waves"] >= len(waves)
    assert reg["tpu_bfs.hashset_occupancy"] > 0


# -- always-on overhead budget --------------------------------------------


def test_no_sink_overhead_under_budget():
    """The no-sink fast path must add <5% to a small host BFS run so the
    layer can stay always-on.

    Measured as (per-block instrumentation cost × blocks the run
    actually emitted) against the run's wall time. Direct wall-clock A/B
    of sub-second runs on this shared box swings ±20% run-to-run —
    far above the 5% budget being asserted — while the per-event cost
    over 10k iterations is stable, so this form bounds the same quantity
    without the flake (measured headroom is ~100x, not marginal)."""
    tracer = get_tracer()
    assert tracer.enabled
    reg = metrics_registry()
    blocks_before = reg.counter("bfs.blocks").snapshot()

    t0 = time.perf_counter()
    LinearEquation(2, 4, 7).checker().spawn_bfs().join()
    run_secs = time.perf_counter() - t0
    blocks = reg.counter("bfs.blocks").snapshot() - blocks_before
    assert blocks >= 1

    # One iteration = one block's full instrumentation: the span with its
    # late-bound args plus the counter/histogram bumps bfs._check_block
    # performs.
    c1, c2, c3 = (
        reg.counter("telemetry_bench.a"),
        reg.counter("telemetry_bench.b"),
        reg.counter("telemetry_bench.c"),
    )
    h = reg.histogram("telemetry_bench.h")
    n = 10_000
    t0 = time.perf_counter()
    for i in range(n):
        with tracer.span("telemetry_bench.block") as sp:
            c1.inc()
            c2.inc(1500)
            c3.inc(3000)
            h.observe(1500)
            sp.set(evaluated=1500, generated=3000, max_depth=i,
                   unique_total=i)
    per_block = (time.perf_counter() - t0) / n
    tracer.clear()  # drop the bench spam from the ring buffer

    overhead = per_block * blocks
    assert overhead < 0.05 * run_secs, (
        f"always-on telemetry overhead too high: {blocks} blocks x "
        f"{per_block * 1e6:.1f}us = {overhead * 1e3:.2f}ms on a "
        f"{run_secs * 1e3:.0f}ms run"
    )
