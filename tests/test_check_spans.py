"""Per-check tracing inside the device checker (CPU): every span of a check
carries its check id and a parent in the check's tree, executable
acquisitions are spans, checks under one AOT namespace and signature share
every program, path rebuilding counts its work, and the drain's lowered
program names its stages."""

import threading

import jax
import jax.numpy as jnp
import pytest

from stateright_tpu.models.paxos import PaxosModelCfg
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.ops.hashset import hashset_new
from stateright_tpu.telemetry import get_tracer

# Deep drains of at most 4 waves: 2pc-3 then exits to the host several
# times, so executables are obtained inside drain exits too.
SPAWN = dict(
    frontier_capacity=1 << 6,
    table_capacity=1 << 10,
    max_drain_waves=4,
    wave_dedup="sort",
    aot_cache="test_check_spans",
)
STAGES = (
    "tpu_bfs.cond",
    "tpu_bfs.expand",
    "tpu_bfs.fingerprint",
    "tpu_bfs.dedup",
    "tpu_bfs.insert",
    "tpu_bfs.compact",
    "tpu_bfs.props",
    "tpu_bfs.log",
    "tpu_bfs.ring",
)


class _Recorder:
    def __init__(self):
        self.events = []

    def write_event(self, event):
        self.events.append(event)


@pytest.fixture(scope="module")
def two_checks():
    """Two whole checks of 2pc-3 under one AOT namespace, each with its
    discovery paths rebuilt; returns [(checker, its events)]. Every
    compile is kept in the persistent cache for the module's run, so the
    second check loads what the first compiled."""
    key = "jax_persistent_cache_min_compile_time_secs"
    old = getattr(jax.config, key)
    jax.config.update(key, 0)
    rec = _Recorder()
    get_tracer().add_sink(rec)
    out = []
    try:
        for _ in range(2):
            start = len(rec.events)
            checker = TwoPhaseSys(3).checker().spawn_tpu_bfs(**SPAWN).join()
            checker.discoveries()
            out.append((checker, rec.events[start:]))
    finally:
        get_tracer().remove_sink(rec, close=False)
        jax.config.update(key, old)
    return out


def _spans(events, name=None):
    return [
        e for e in events
        if e["ph"] == "X" and (name is None or e["name"] == name)
    ]


def test_every_span_of_a_check_carries_its_id(two_checks):
    ids = []
    for checker, events in two_checks:
        check = checker._tracer.check
        spans = _spans(events)
        assert spans
        assert {e["args"]["check"] for e in spans} == {check}
        (root,) = _spans(events, "tpu_bfs.check")
        assert root["args"]["unique"] == checker.unique_state_count() == 288
        assert root["args"]["drains"] >= 2
        ids.append(check)
    assert ids[0] != ids[1]


def test_parents_nest_under_the_check(two_checks):
    checker, events = two_checks[0]
    spans = _spans(events)
    by_id = {e["id"]: e for e in spans}
    (root,) = _spans(events, "tpu_bfs.check")

    def ancestors(e):
        names = []
        while e["parent"] is not None:
            e = by_id[e["parent"]]
            names.append(e["name"])
        return names

    for e in spans:
        if e is not root:
            assert ancestors(e)[-1] == "tpu_bfs.check", e["name"]
    exe_under = {
        next(a for a in ancestors(e) if a in (
            "tpu_bfs.drain_exit", "tpu_bfs.seed", "tpu_bfs.check",
            "tpu_bfs.paths",
        ))
        for e in _spans(events, "tpu_bfs.exe")
    }
    assert {"tpu_bfs.drain_exit", "tpu_bfs.seed", "tpu_bfs.check",
            "tpu_bfs.paths"} <= exe_under
    # The final wave of a drain is consumed inside its drain exit, and the
    # table grows there (``host.drain_exit_ms`` leaves the growth out).
    for name in ("tpu_bfs.wave", "tpu_bfs.table_grow"):
        assert any(
            by_id[e["parent"]]["name"] == "tpu_bfs.drain_exit"
            for e in _spans(events, name)
        ), name
    # discoveries() ran on this (the caller's) thread, under the check.
    (paths,) = _spans(events, "tpu_bfs.paths")
    assert paths["tid"] == threading.get_ident() != root["tid"]
    assert paths["parent"] == root["id"]


def test_second_check_obtains_executables_without_compiling(two_checks):
    first, second = (events for _, events in two_checks)
    kinds = {e["args"]["kind"] for e in _spans(first, "tpu_bfs.exe")}
    assert {"drain", "init", "take", "finish", "fp_single"} <= kinds
    exes = _spans(second, "tpu_bfs.exe")
    assert {e["args"]["kind"] for e in exes} == kinds
    for e in exes:
        assert e["args"]["source"] in ("memory", "persistent_cache"), e
        assert e["args"]["trace_ms"] >= 0 and e["args"]["build_ms"] >= 0
    # Every program, of every kind, comes from the shared AOT cache: no
    # trace, no compile and no load.
    for e in exes:
        assert e["args"]["source"] == "memory", e
        assert e["args"]["trace_ms"] == 0 and e["args"]["build_ms"] == 0, e
    drains = [e for e in exes if e["args"]["kind"] == "drain"]
    # A call that reuses an executable opens no span: one acquisition per
    # (function, shape) in a check.
    assert drains and len(_spans(second, "tpu_bfs.drain")) > len(drains)


def answers(checker):
    """Counts, depth, and each discovery's path length."""
    return (
        checker.unique_state_count(),
        checker.state_count(),
        checker.max_depth(),
        {name: len(path) for name, path in checker.discoveries().items()},
    )


def test_shared_cache_check_answers_as_the_host_bfs(two_checks):
    host = TwoPhaseSys(3).checker().spawn_bfs().join()
    for checker, _ in two_checks:
        assert answers(checker) == answers(host)


def test_fps_checks_share_every_program():
    """Paxos on the fps wave, one wave a dispatch on a bucket ladder, to
    depth 9: the second check under one namespace takes ``materialize``,
    ``compact`` and the wave from the cache too, and answers as the host
    ``BfsChecker``."""
    spawn = dict(
        frontier_capacity=64, table_capacity=1 << 12, max_drain_waves=1,
        bucket_ladder=2, expand_fps=True, aot_cache="test_check_spans.fps",
    )

    def builder():
        return PaxosModelCfg(2, 3).into_model().checker().target_max_depth(9)

    rec = _Recorder()
    get_tracer().add_sink(rec)
    try:
        checks = []
        for _ in range(2):
            start = len(rec.events)
            checker = builder().spawn_tpu_bfs(**spawn).join()
            checks.append((checker, _spans(rec.events[start:], "tpu_bfs.exe")))
    finally:
        get_tracer().remove_sink(rec, close=False)
    host = builder().spawn_bfs().join()
    (first, built), (second, exes) = checks
    assert first._use_fps and len(first._buckets) > 1
    kinds = {e["args"]["kind"] for e in built}
    assert {"materialize", "compact", "wave", "init", "take"} <= kinds
    assert {e["args"]["kind"] for e in exes} == kinds
    assert {e["args"]["source"] for e in exes} == {"memory"}
    assert second._programs.cache is first._programs.cache
    assert answers(first) == answers(second) == answers(host)


def test_signatures_under_one_namespace_do_not_share(two_checks):
    rec = _Recorder()
    get_tracer().add_sink(rec)
    try:
        other = TwoPhaseSys(4).checker().spawn_tpu_bfs(**SPAWN).join()
    finally:
        get_tracer().remove_sink(rec, close=False)
    assert other.unique_state_count() == 1568
    assert other._programs.cache is not two_checks[0][0]._programs.cache
    exes = _spans(rec.events, "tpu_bfs.exe")
    assert exes and all(e["args"]["source"] != "memory" for e in exes)


def test_checker_without_aot_cache_shares_nothing(two_checks):
    spawn = {k: v for k, v in SPAWN.items() if k != "aot_cache"}
    rec = _Recorder()
    get_tracer().add_sink(rec)
    try:
        lone = TwoPhaseSys(3).checker().spawn_tpu_bfs(**spawn).join()
    finally:
        get_tracer().remove_sink(rec, close=False)
    assert lone.unique_state_count() == 288
    shared = two_checks[0][0]
    assert lone._programs.cache is not shared._programs.cache
    exes = _spans(rec.events, "tpu_bfs.exe")
    assert {"init", "drain"} <= {e["args"]["kind"] for e in exes}
    assert all(e["args"]["source"] != "memory" for e in exes)


def test_drain_exits_count_log_bytes_and_refills(two_checks):
    checker, events = two_checks[1]
    exits = _spans(events, "tpu_bfs.drain_exit")
    assert len(exits) == len(_spans(events, "tpu_bfs.drain"))
    # 16 bytes per logged state: child and parent fps, two u32 words each.
    logged = sum(e["args"]["log_bytes"] for e in exits) // 16
    assert 0 < logged < checker.unique_state_count()
    assert sum(e["args"]["refill_chunks"] for e in exits) >= 1


def test_paths_count_log_entries_and_fingerprint_calls(two_checks):
    for checker, events in two_checks:
        (paths,) = _spans(events, "tpu_bfs.paths")
        assert paths["args"]["fp_calls"] > 0
        assert 1 <= paths["args"]["log_entries"]
        assert paths["args"]["log_entries"] <= checker.unique_state_count()


INSERT_ARGS = (
    "insert_rounds",
    "insert_lanes_issued",
    "insert_lanes_live",
    "insert_narrowings",
)


def test_drains_and_waves_count_the_visited_set_insert():
    """2pc-5 on 256-lane waves: each drain, and each drain's last wave,
    carries the insert's probe rounds, lanes issued and live, and
    narrowings; the batch narrows, so fewer lanes are issued than rounds at
    the full batch width."""
    rec = _Recorder()
    get_tracer().add_sink(rec)
    try:
        checker = TwoPhaseSys(5).checker().spawn_tpu_bfs(
            frontier_capacity=256, table_capacity=1 << 12, wave_dedup="sort",
        ).join()
    finally:
        get_tracer().remove_sink(rec, close=False)
    assert checker.unique_state_count() == 8832
    assert checker.state_count() == 58146
    width = 256 * checker._A
    totals = dict.fromkeys(INSERT_ARGS, 0)
    for name in ("tpu_bfs.drain", "tpu_bfs.wave"):
        spans = _spans(rec.events, name)
        assert spans, name
        for e in spans:
            a = e["args"]
            assert set(INSERT_ARGS) <= set(a), (name, a)
            assert 0 <= a["insert_lanes_live"] <= a["insert_lanes_issued"]
            assert a["insert_lanes_issued"] <= a["insert_rounds"] * width
            for k in INSERT_ARGS:
                totals[k] += a[k]
    assert totals["insert_rounds"] > 0 and totals["insert_narrowings"] > 0
    assert totals["insert_lanes_issued"] < totals["insert_rounds"] * width


def test_drain_program_names_its_stages(two_checks):
    checker, _ = two_checks[0]
    width = checker._F_max
    drain = checker._drain_jits[width]
    args = (
        hashset_new(checker._capacity),
        checker._jit_pool_zero(checker._pool_capacity),
        jnp.int32(0),
        jnp.int32(0),
        jnp.ones((len(checker._properties),), bool),
        jnp.int32(1 << 20),
        jnp.int32(1 << 30),
    )
    text = drain.lower(*args).as_text(debug_info=True)
    assert f"@jit_tpu_bfs_drain_w{width}" in text
    for stage in STAGES:
        assert stage in text, stage
