"""The ``insert.lane_fill_pct`` reader on hand-made spans: live lanes over
issued lanes of the insert's probe rounds, summed over the window's drain
and wave spans, and None for a program that does not count its insert."""

import pytest

import harness


def span(name, ts_ms, args=None):
    return {"name": name, "ph": "X", "ts": ts_ms * 1e3, "dur": 1e3,
            "tid": 1, "args": args or {}}


def counted(name, ts_ms, live, issued):
    return span(name, ts_ms, {"insert_rounds": 3, "insert_lanes_issued": issued,
                              "insert_lanes_live": live, "insert_narrowings": 1})


def run_of(spans):
    done = [harness.Check(None, 0.0, 1.0, 288, 288, 9) for _ in range(2)]
    return harness.Run(cell=None, checks=done, t_start=0.0, t_end=10.0,
                       setup_s=1.0, spans=spans)


def read(run):
    return harness.load_reader("insert.lane_fill_pct")(run)


def test_sums_drains_and_waves_in_the_window():
    spans = [
        counted("tpu_bfs.drain", 100, 300, 1000),
        counted("tpu_bfs.wave", 200, 20, 500),
        counted("tpu_bfs.drain", 20_000, 500, 500),  # after the window
        span("tpu_bfs.wave", 300),                    # no counters
    ]
    assert read(run_of(spans)) == pytest.approx(100 * (300 + 20) / (1000 + 500))


def test_none_without_the_counters():
    old = [span("tpu_bfs.drain", 0), span("tpu_bfs.wave", 100)]
    assert read(run_of(old)) is None
    assert read(run_of(None)) is None
