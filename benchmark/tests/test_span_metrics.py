"""The per-check span readers (``setup.exe_ms``, ``host.drain_exit_ms``,
``verdict.paths_ms``) on hand-made spans: self time is a span less the
``tpu_bfs.exe`` spans nested in it on its thread (and, for a drain exit,
less the table's growth), and a program without the spans reads None."""

import pytest

import harness

MAIN, WORKER = 1, 2


def span(name, ts_ms, dur_ms, tid=WORKER):
    return {"name": name, "ph": "X", "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
            "tid": tid, "args": {}}


def run_of(spans, checks=2):
    done = [harness.Check(None, 0.0, 1.0, 288, 288, 9) for _ in range(checks)]
    return harness.Run(cell=None, checks=done, t_start=0.0, t_end=10.0,
                       setup_s=1.0, spans=spans)


def read(name, run):
    return harness.load_reader(name)(run)


SPANS = [
    span("tpu_bfs.check", 0, 4000),
    span("tpu_bfs.exe", 10, 30),                     # set-up, under check
    span("tpu_bfs.drain_exit", 100, 50),
    span("tpu_bfs.exe", 110, 20),                    # nested in the exit
    span("tpu_bfs.drain_exit", 300, 100),
    span("tpu_bfs.exe", 305, 1, tid=MAIN),           # other thread: not nested
    span("tpu_bfs.table_grow", 320, 60),             # growth in the exit
    span("tpu_bfs.exe", 330, 20),                    # rehash, inside the growth
    span("tpu_bfs.paths", 4100, 200, tid=MAIN),
    span("tpu_bfs.exe", 4150, 150, tid=MAIN),        # fp_single, nested
    span("tpu_bfs.check", 5000, 4000),
    span("tpu_bfs.paths", 9100, 40, tid=MAIN),
    span("tpu_bfs.exe", 20_000, 500),                # after the window
]


def test_exe_ms_sums_acquisitions_per_check():
    assert read("setup.exe_ms", run_of(SPANS)) == pytest.approx(
        (30 + 20 + 1 + 20 + 150) / 2
    )


def test_drain_exit_ms_is_mean_self_time():
    assert read("host.drain_exit_ms", run_of(SPANS)) == pytest.approx(
        ((50 - 20) + (100 - 60)) / 2
    )


def test_paths_ms_is_self_time_per_check():
    assert read("verdict.paths_ms", run_of(SPANS)) == pytest.approx(
        ((200 - 150) + 40) / 2
    )


def test_no_acquisition_reads_zero_where_checks_are_traced():
    spans = [s for s in SPANS if s["name"] != "tpu_bfs.exe"]
    assert read("setup.exe_ms", run_of(spans)) == 0


@pytest.mark.parametrize(
    "name", ["setup.exe_ms", "host.drain_exit_ms", "verdict.paths_ms"]
)
def test_none_without_the_spans(name):
    # A program that predates per-check tracing records drains and waves
    # only; a run without spans has none at all.
    old = [span("tpu_bfs.drain", 0, 100), span("tpu_bfs.wave", 100, 5)]
    assert read(name, run_of(old)) is None
    assert read(name, run_of(None)) is None
