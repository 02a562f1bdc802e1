"""The four-chip cell's readers (``exchange.bytes_per_state``,
``mesh.exe_ms``, ``mesh_roofline``) on hand-made runs, None where the spans
or the trace carry nothing to read; and the cell's factory, which refuses a
program that cannot spread a check over chips."""

import pytest

import harness
import mesh_program
from harness import Check

CONFIG = {"roofline": {"state_bytes": 40, "table_access_bytes": 8,
                       "log_entry_bytes": 16}}


def span(name, ts_ms, dur_ms, **args):
    return {"name": name, "ph": "X", "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
            "args": args}


def run_of(spans, chips=4, device=None):
    checks = [Check(None, 0.0, 4.0, 1000, 5000, 9),
              Check(None, 4.0, 9.0, 1000, 5000, 9)]
    return harness.Run(cell=harness.Cell("c", chips, CONFIG, {}, [], []),
                       checks=checks, t_start=0.0, t_end=10.0, setup_s=1.0,
                       peaks={"hbm_bytes_per_s": 1e9}, spans=spans,
                       device=device)


def read(name, run):
    return harness.load_reader(name)(run)


SPANS = [
    span("sharded_bfs.check", 0, 4000),
    span("sharded_bfs.exe", 10, 30, kind="drain", source="shared"),
    span("sharded_bfs.drain", 100, 900, exchange_lanes=3000, exchange_bytes=27000),
    span("sharded_bfs.wave", 1000, 5, exchange_lanes=0, exchange_bytes=0),
    span("sharded_bfs.check", 4000, 5000),
    span("sharded_bfs.exe", 4010, 50, kind="rehash", source="compile"),
    span("sharded_bfs.wave", 4100, 9, exchange_lanes=100, exchange_bytes=900),
    span("sharded_bfs.drain", 20_000, 900, exchange_bytes=10**9),  # after
    span("sharded_bfs.exe", 20_000, 500),                          # after
]


def test_exchange_bytes_per_state_sums_drains_and_waves():
    assert read("exchange.bytes_per_state", run_of(SPANS)) == pytest.approx(
        (27000 + 900) / 2000
    )


def test_mesh_exe_ms_sums_acquisitions_per_check():
    assert read("mesh.exe_ms", run_of(SPANS)) == pytest.approx((30 + 50) / 2)


def test_mesh_roofline_is_over_every_chip():
    device = {"busy_s": 1e-3, "window_s": 5.0, "t_from": 0.0, "t_to": 5.0}
    # Only the first check lies inside the traced window: 1000 * (2*40 + 8 +
    # 16) + 5000 * 8 = 144,000 bytes, 36 us at four chips of 1 GB/s.
    assert read("mesh_roofline", run_of([], device=device)) == pytest.approx(3.6)
    # On one chip it reads as wave_roofline.
    one = run_of([], chips=1, device=device)
    assert read("mesh_roofline", one) == pytest.approx(read("wave_roofline", one))


@pytest.mark.parametrize(
    "name", ["exchange.bytes_per_state", "mesh.exe_ms", "mesh_roofline"]
)
def test_none_without_anything_to_read(name):
    # A one-chip program's spans, a run without spans, and an empty trace.
    one_chip = [span("tpu_bfs.check", 0, 100), span("tpu_bfs.drain", 0, 50),
                span("tpu_bfs.exe", 0, 10)]
    assert read(name, run_of(one_chip)) is None
    assert read(name, run_of(None)) is None
    idle = {"busy_s": 0.0, "window_s": 5.0, "t_from": 0.0, "t_to": 5.0}
    assert read(name, run_of(None, device=idle)) is None


def test_counters_absent_read_none():
    # Drain spans of a program that counts no exchange.
    bare = [span("sharded_bfs.check", 0, 4000), span("sharded_bfs.drain", 100, 9)]
    assert read("exchange.bytes_per_state", run_of(bare)) is None
    assert read("mesh.exe_ms", run_of(bare)) == 0


def test_factory_refuses_a_program_without_devices(monkeypatch):
    from stateright_tpu.checker.builder import CheckerBuilder

    assert mesh_program.two_phase_commit(3).rm_count == 3

    def one_chip_only(self, **kwargs):
        raise AssertionError("never spawned")

    monkeypatch.setattr(CheckerBuilder, "spawn_tpu_bfs", one_chip_only)
    with pytest.raises(SystemExit, match="takes no devices"):
        mesh_program.two_phase_commit(3)
