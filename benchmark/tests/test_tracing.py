"""The trace reduction on synthetic traces: the union of overlapping
device operations, idle gaps named by the host span open in them, and the
roofline arithmetic."""

import harness
import tracing
from harness import Check, load_reader


def test_union_merges_overlaps():
    assert tracing.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_busy_is_union_clipped_to_window():
    ops = [("fusion", 1.0, 3.0), ("sort", 2.0, 4.0), ("fusion", 9.0, 12.0)]
    out = tracing.reduce(ops, [], 0.0, 10.0)
    assert out["busy_s"] == 4.0  # [1, 4] and [9, 10]
    assert out["window_s"] == 10.0
    # Per-op time counts each operation's own duration, overlap included.
    assert out["breakdown"]["device_ops"] == [["fusion", 5.0], ["sort", 2.0]]


def test_gaps_are_named_by_the_most_specific_host_span():
    ops = [("fusion", 2.0, 3.0), ("fusion", 6.0, 7.0)]
    host = [
        ("bench.join", 0.0, 10.0),
        ("tpu_bfs.drain", 3.0, 5.0),  # gap [3, 6] midpoint 4.5 -> drain
        ("bench.verdict", 7.5, 10.0),  # gap [7, 10] midpoint 8.5 -> verdict
    ]
    out = tracing.reduce(ops, host, 0.0, 10.0)
    gaps = dict(map(tuple, out["breakdown"]["idle_gaps"]))
    assert gaps == {"tpu_bfs.drain": 3.0, "bench.verdict": 3.0, "bench.join": 2.0}


def test_time_without_host_span():
    name_at = tracing.Labeller([("other", 0.0, 1.0), ("bench.spawn", 2.0, 3.0)])
    assert name_at(0.5) == "no host span"
    assert name_at(2.5) == "bench.spawn"


def _cell(config):
    return harness.Cell("c", 1, config, {}, [], [])


def test_roofline_arithmetic():
    config = {"roofline": {"state_bytes": 40, "table_access_bytes": 8,
                           "log_entry_bytes": 16}}
    checks = [
        Check(None, 1.0, 3.0, unique=1000, states=5000, max_depth=3),
        Check(None, 3.0, 9.0, unique=1000, states=5000, max_depth=3),
    ]
    run = harness.Run(cell=_cell(config), checks=checks, t_start=1.0,
                      t_end=9.0, setup_s=0.0, peaks={"hbm_bytes_per_s": 1e9},
                      device={"busy_s": 1e-3, "window_s": 2.5,
                              "t_from": 0.5, "t_to": 3.5})
    # Only the first check lies inside the traced window:
    # 1000 * (2*40 + 8 + 16) + 5000 * 8 = 144,000 bytes = 144 us at 1 GB/s.
    assert abs(load_reader("wave_roofline")(run) - 14.4) < 1e-9
    assert abs(load_reader("device.idle_pct.check")(run) - 100 * (1 - 1e-3 / 2.5)) < 1e-9


def test_readers_return_nothing_without_data():
    run = harness.Run(cell=_cell({}), checks=[], t_start=0.0, t_end=1.0,
                      setup_s=0.0)
    for name in ("wave_roofline", "device.idle_pct.check",
                 "ladder.lane_fill_pct", "host.drains_per_mstate"):
        assert load_reader(name)(run) is None, name
