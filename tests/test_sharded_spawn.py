"""``spawn_tpu_bfs(devices=n)``: the sharded checker on the normal spawn
path, held to the host ``BfsChecker`` (the plain reference engine) on the
virtual CPU mesh (see conftest); its shared executables, and its spans."""

import pytest

from stateright_tpu.core.path import Path
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.parallel.sharded import ShardedTpuBfsChecker
from stateright_tpu.telemetry import get_tracer

WIDTHS = dict(frontier_per_device=64, table_capacity_per_device=1 << 12)


def answers(checker, model):
    """Counts, depth and discoveries, each discovery path replayed in the
    model from an initial state and ending where its property holds."""
    paths = checker.discoveries()
    for name, path in paths.items():
        states = path.into_states()
        assert Path.from_actions(model, states[0], path.into_actions()) == path
        assert model.property(name).condition(model, path.last_state())
    return (
        checker.unique_state_count(),
        checker.state_count(),
        checker.max_depth(),
        {name: len(path) for name, path in paths.items()},
    )


def builder(rm_count, cap):
    b = TwoPhaseSys(rm_count).checker()
    return b if cap is None else b.target_max_depth(cap)


@pytest.mark.parametrize("cap", [None, 7], ids=["whole", "depth7"])
@pytest.mark.parametrize("rm_count", [4, 5])
@pytest.mark.parametrize("devices", [2, 4])
def test_devices_match_host_bfs(devices, rm_count, cap):
    model = TwoPhaseSys(rm_count)
    host = builder(rm_count, cap).spawn_bfs().join()
    dev = builder(rm_count, cap).spawn_tpu_bfs(devices=devices, **WIDTHS).join()
    assert isinstance(dev, ShardedTpuBfsChecker)
    assert dev.worker_error() is None
    assert dev.visited_placement is not None
    assert len({d for d, _ in dev.visited_placement}) == devices
    assert answers(dev, model) == answers(host, model)


class Spans:
    def __init__(self):
        self.events = []

    def write_event(self, event):
        self.events.append(event)

    def of(self, checker):
        check = checker._tracer.check
        return [e for e in self.events if e["args"].get("check") == check]


@pytest.fixture
def spans():
    sink = Spans()
    get_tracer().add_sink(sink)
    yield sink
    get_tracer().remove_sink(sink, close=False)


def test_shared_aot_cache_builds_nothing_new(spans):
    spawn = dict(devices=4, frontier_per_device=32,
                 table_capacity_per_device=64, aot_cache="test-shared")
    first = TwoPhaseSys(4).checker().spawn_tpu_bfs(**spawn).join()
    want = answers(first, TwoPhaseSys(4))
    built = dict(first._programs.cache)
    # The table grew past 64 rows: a drain per table size, both shared.
    assert sum(1 for k in built if k[0] == "drain") >= 2
    second = TwoPhaseSys(4).checker().spawn_tpu_bfs(**spawn).join()
    assert answers(second, TwoPhaseSys(4)) == want
    cache = second._programs.cache
    assert cache is first._programs.cache and cache == built
    acquired = [e for e in spans.of(second) if e["name"] == "sharded_bfs.exe"]
    assert {e["args"]["kind"] for e in acquired} >= {"drain", "rehash"}
    assert {e["args"]["source"] for e in acquired} == {"memory"}


def test_spans_of_one_check(spans):
    checker = TwoPhaseSys(4).checker().spawn_tpu_bfs(
        devices=4, frontier_per_device=32, table_capacity_per_device=1 << 10
    ).join()
    checker.discoveries()
    mine = [e for e in spans.of(checker) if e["ph"] == "X"]
    (root,) = [e for e in mine if e["name"] == "sharded_bfs.check"]
    ids = {e["id"] for e in mine}
    for e in mine:
        assert e is root or e["parent"] in ids, e["name"]
    names = {e["name"] for e in mine}
    assert {"sharded_bfs.drain", "sharded_bfs.wave", "sharded_bfs.exe",
            "sharded_bfs.paths"} <= names
    steps = [e for e in mine
             if e["name"] in ("sharded_bfs.drain", "sharded_bfs.wave")]
    lanes = [e["args"]["exchange_lanes"] for e in steps]
    assert all(e["args"]["exchange_bytes"] == 9 * e["args"]["exchange_lanes"]
               for e in steps)
    # The unsieved exchange ships every lane of every wave to every device.
    assert sum(lanes) > 0 and sum(lanes) % (4 * 4 * 32 * checker._A) == 0
    assert sum(lanes) == checker._exchange_lanes


@pytest.mark.parametrize("arg", [
    dict(frontier_capacity=64),
    dict(hashset_impl="xla"),
    dict(mesh=None),
])
def test_one_chip_argument_with_devices_is_refused(arg):
    (name,) = arg
    with pytest.raises(ValueError, match=name):
        TwoPhaseSys(3).checker().spawn_tpu_bfs(devices=2, **arg)
