"""Where a compiled program comes from (``checker/programs.py``): one
``Programs.obtain`` order for every engine — held, shared memory, disk
(the wave and the drain), compile — and the disk store bound to the
``aot_cache`` namespace, the sharded checker's included."""

import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stateright_tpu.checker.programs import Programs, clear_shared_aot_caches
from stateright_tpu.core.batch import BatchableModel
from stateright_tpu.core.model import Model, Property
from stateright_tpu.telemetry import Tracer, get_tracer

SOURCES = ("held", "memory", "disk", "compile", "persistent_cache")
SHARDED = dict(devices=2, frontier_per_device=8, table_capacity_per_device=64)


@pytest.fixture
def no_persistent_cache():
    """Executables the persistent compilation cache served serialize
    without their code, and the disk store refuses them: with the cache
    off, every program these checks build is compiled and saved."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        cc.reset_cache()


def _acquire(source, tmp_path):
    """Obtains one ``wave`` program a second time by the route that ends in
    ``source``; returns the second ``Programs``, the spans and the kinds
    charged to warm-up of that second acquisition, and its executable."""
    tracer, charged = Tracer(), []

    def programs(**kw):
        return Programs(
            "t", tracer, signature=("sig",),
            on_compile=lambda kind, _s: charged.append(kind), **kw,
        )

    # A constant of this run makes the program's HLO new to the
    # persistent cache; the persistent case compiles one HLO twice, each
    # time from a fresh trace.
    c = 3 if source == "persistent_cache" else uuid.uuid4().int % 1000 + 7
    args = (jnp.arange(8, dtype=jnp.int32),)

    def obtain(p):
        return p.obtain(
            "wave", (8,), jax.jit(lambda x: x * c + 1), args, width=8
        )

    ns = f"test_programs.{uuid.uuid4().hex}"
    kw = {
        "held": {},
        "memory": dict(aot_cache=ns),
        "disk": dict(aot_cache=ns, aot_store=str(tmp_path)),
        "compile": {},
        "persistent_cache": {},
    }[source]
    key = "jax_persistent_cache_min_compile_time_secs"
    old = getattr(jax.config, key)
    if source == "persistent_cache":
        jax.config.update(key, 0)
    try:
        p = programs(**kw)
        if source != "compile":
            obtain(p)
            if source == "disk":
                p.cache.clear()  # a fresh process's empty memory
            if source != "held":
                p = programs(**kw)
        start = len(tracer.events())
        del charged[:]
        exe = obtain(p)
    finally:
        jax.config.update(key, old)
    spans = [e for e in tracer.events()[start:] if e["name"] == "t.exe"]
    assert [int(v) for v in exe(*args)] == [i * c + 1 for i in range(8)]
    return p, spans, charged, exe


@pytest.mark.parametrize("source", SOURCES)
def test_obtain_takes_each_source(source, tmp_path):
    p, spans, charged, exe = _acquire(source, tmp_path)
    assert p.held == {("wave", 8)}
    assert p.cache[("wave", 8)] is exe
    if source == "held":
        # A held program is a reuse: no span, no charge.
        assert spans == [] and charged == []
        return
    (span,) = spans
    assert span["args"]["kind"] == "wave" and span["args"]["width"] == 8
    assert span["args"]["source"] == source
    compiled = source in ("compile", "persistent_cache")
    assert charged == (["wave"] if compiled else [])


def test_programs_refuse_a_store_without_a_namespace(tmp_path):
    with pytest.raises(ValueError, match="aot_store requires aot_cache"):
        Programs("t", Tracer(), signature=(), on_compile=None,
                 aot_store=str(tmp_path))


class Counter(Model, BatchableModel):
    """0 -> 1 -> ... -> n. The property ``under`` closes over ``limit``,
    which the packed arrays do not hold: two limits have one trace
    signature, and only the ``aot_cache`` namespace tells them apart."""

    def __init__(self, n, limit):
        self.n = n
        self.limit = limit

    def init_states(self):
        return [0]

    def actions(self, state, actions):
        if state < self.n:
            actions.append("inc")

    def next_state(self, state, action):
        return state + 1

    def properties(self):
        return [Property.always("under", lambda m, s: s < m.limit)]

    def packed_action_count(self):
        return 1

    def packed_init_states(self):
        return jnp.zeros((1,), jnp.uint32)

    def packed_step(self, state, action_id):
        return state + 1, state < self.n

    def packed_within_boundary(self, state):
        return jnp.bool_(True)

    def packed_conditions(self):
        return [lambda s: s < self.limit]

    def pack_state(self, host_state):
        return np.uint32(host_state)

    def unpack_state(self, packed):
        return int(packed)


def verdict(checker):
    """Each discovery's path, as states."""
    return {
        name: path.into_states()
        for name, path in checker.discoveries().items()
    }


class _Spans:
    def __init__(self):
        self.events = []

    def write_event(self, event):
        self.events.append(event)

    def exes(self, checker):
        check = checker._tracer.check
        return [
            e for e in self.events
            if e["name"] == "sharded_bfs.exe"
            and e["args"].get("check") == check
        ]


@pytest.fixture
def spans():
    sink = _Spans()
    get_tracer().add_sink(sink)
    yield sink
    get_tracer().remove_sink(sink, close=False)


def _sharded(limit, namespace, store):
    return Counter(8, limit).checker().spawn_tpu_bfs(
        aot_cache=namespace, aot_store=store, **SHARDED
    ).join()


def test_sharded_disk_entries_follow_the_namespace(tmp_path,
                                                   no_persistent_cache):
    """Two sharded checks of one model class under one disk store, with
    the in-memory caches dropped between them: the second, under its own
    namespace, must not run the first one's programs."""
    store = str(tmp_path)
    ns = uuid.uuid4().hex
    loose = _sharded(100, f"{ns}.limit100", store)
    assert loose.discoveries() == {}
    clear_shared_aot_caches()
    tight = _sharded(5, f"{ns}.limit5", store)
    host = Counter(8, 5).checker().spawn_bfs().join()
    assert verdict(tight) == verdict(host) == {"under": list(range(6))}


def test_sharded_check_loads_its_steps_from_disk(tmp_path, spans,
                                                 no_persistent_cache):
    store = str(tmp_path)
    ns = uuid.uuid4().hex
    first = _sharded(5, ns, store)
    clear_shared_aot_caches()
    second = _sharded(5, ns, store)
    steps = {
        e["args"]["kind"]: e["args"]["source"]
        for e in spans.exes(second)
        if e["args"]["kind"] in ("wave", "drain")
    }
    assert steps and set(steps.values()) == {"disk"}, steps
    host = Counter(8, 5).checker().spawn_bfs().join()
    assert verdict(first) == verdict(second) == verdict(host)


def test_sharded_checker_refuses_a_store_without_a_namespace(tmp_path):
    with pytest.raises(ValueError, match="aot_store requires aot_cache"):
        Counter(8, 5).checker().spawn_tpu_bfs(
            aot_store=str(tmp_path), **SHARDED
        )
