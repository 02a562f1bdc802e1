"""The ``setup.exe_hit_pct`` reader on hand-made spans: the share of the
window's ``tpu_bfs.exe`` spans whose source is ``memory``, and None for a
window without them."""

import pytest

import harness


def exe(ts_ms, source):
    return {"name": "tpu_bfs.exe", "ph": "X", "ts": ts_ms * 1e3, "dur": 1e3,
            "tid": 1, "args": {"kind": "init", "source": source}}


def run_of(spans):
    done = [harness.Check(None, 0.0, 1.0, 288, 288, 9) for _ in range(2)]
    return harness.Run(cell=None, checks=done, t_start=0.0, t_end=10.0,
                       setup_s=1.0, spans=spans)


def read(run):
    return harness.load_reader("setup.exe_hit_pct")(run)


def test_share_of_memory_sources_in_the_window():
    spans = [
        exe(100, "memory"),
        exe(200, "persistent_cache"),
        exe(300, "memory"),
        exe(400, "compile"),
        exe(500, "disk"),
        exe(-1_000, "compile"),     # set-up, before the window
        exe(20_000, "compile"),     # after the window
        {"name": "tpu_bfs.drain", "ph": "X", "ts": 600e3, "dur": 1e3,
         "tid": 1, "args": {"source": "memory"}},
    ]
    assert read(run_of(spans)) == pytest.approx(100 * 2 / 5)


def test_every_acquisition_a_hit_reads_100():
    assert read(run_of([exe(100, "memory"), exe(200, "memory")])) == 100


def test_none_without_acquisitions_in_the_window():
    assert read(run_of([exe(20_000, "memory")])) is None
    assert read(run_of([])) is None
    assert read(run_of(None)) is None
