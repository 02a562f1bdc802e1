"""bench.py's pure helpers."""

import json

import bench  # repo root is on sys.path via tests/conftest.py


def test_evaluate_pipeline_choice_flags_slower_configured():
    """The measured-policy gate (PR 7 satellite): the configured pipeline
    is flagged only when it measures >10% slower than the other one —
    both directions, noise band tolerated, degenerate inputs never flag."""
    # abd3o-shaped regression: configured fps, materialize 2.5x faster.
    assert bench.evaluate_pipeline_choice("fps", 25.0, 10.0) is True
    # configured materialize, fps faster.
    assert bench.evaluate_pipeline_choice("materialize", 10.0, 25.0) is True
    # Correctly-configured pipelines never flag.
    assert bench.evaluate_pipeline_choice("fps", 10.0, 25.0) is False
    assert bench.evaluate_pipeline_choice("materialize", 25.0, 10.0) is False
    # Inside the 10% noise band: no flag either way.
    assert bench.evaluate_pipeline_choice("fps", 10.5, 10.0) is False
    # Degenerate inputs (unsupported model, failed calibration).
    assert bench.evaluate_pipeline_choice(None, 10.0, 5.0) is False
    assert bench.evaluate_pipeline_choice("fps", None, 5.0) is False
    assert bench.evaluate_pipeline_choice("fps", 10.0, 0.0) is False


def test_pct_percentiles():
    """The service leg's stdlib percentile: linear interpolation,
    None-safe, empty-safe."""
    assert bench._pct([], 50) is None
    assert bench._pct([None, None], 99) is None
    assert bench._pct([3.0], 99) == 3.0
    assert bench._pct([1.0, 2.0, 3.0, 4.0], 50) == 2.5
    assert bench._pct([1.0, None, 3.0], 50) == 2.0
    assert bench._pct([1.0, 2.0, 3.0, 4.0], 100) == 4.0
    assert bench._pct([1.0, 2.0, 3.0, 4.0], 0) == 1.0


def test_service_report_round_trip(tmp_path):
    """scripts/service_report.py reads a bench --service record and
    emits the shared --json summary shape."""
    import io
    import sys

    sys.path.insert(
        0, str(__import__("pathlib").Path(bench.__file__).parent / "scripts")
    )
    import service_report

    record = {
        "metric": "service aggregate unique states/sec",
        "value": 1234.5,
        "device": "cpu",
        "model": "2pc-5",
        "jobs": 2,
        "quantum_s": 0.5,
        "batch_rate": 1300.0,
        "single_job_rate": 1250.0,
        "service_overhead_pct": 3.8,
        "aggregate_states_per_s": 1234.5,
        "concurrent_wall_s": 14.3,
        "p50_ttfv_s": 0.5,
        "p99_ttfv_s": 0.9,
        "preempts_total": 3,
        "jobs_zero_compile": 1,
        "per_job": [
            {"job_id": "job-1", "tenant": "t0", "unique": 8832,
             "ttfv_s": 0.4, "wall_s": 7.0, "queued_s": 0.01,
             "active_s": 6.0, "preempts": 2, "slices": 3,
             "rate": 1250.0, "compile_s": 2.0},
            {"job_id": "job-2", "tenant": "t1", "unique": 8832,
             "ttfv_s": 0.6, "wall_s": 9.0, "queued_s": 0.02,
             "active_s": 6.1, "preempts": 1, "slices": 2,
             "rate": 1240.0, "compile_s": 0.0},
        ],
    }
    path = tmp_path / "BENCH_r10.json"
    path.write_text("garbage line\n" + json.dumps(record) + "\n")
    loaded = service_report.load_record(str(path))
    assert loaded["per_job"][1]["compile_s"] == 0.0
    summary = service_report.summarize(loaded)
    assert summary["p99_ttfv_s"] == 0.9
    assert summary["jobs_zero_compile"] == 1
    out = io.StringIO()
    service_report.render(summary, out=out)
    text = out.getvalue()
    assert "p99  0.900s" in text
    assert "job-2" in text
    # Missing record is a clean nonzero exit, not a traceback.
    empty = tmp_path / "empty.json"
    empty.write_text("{}\n")
    assert service_report.main([str(empty), "--json"]) == 2


def test_device_peak_is_an_exact_lookup():
    """The roofline's peak comes from the exact device_kind: v5e's kind
    as JAX reports it resolves, a prefix or an unknown TPU raises, and
    a non-TPU backend has no peak."""
    import pytest

    from stateright_tpu.checker.breakdown import device_peak

    assert device_peak("tpu", "TPU v5 lite")["hbm_gbps"] == 819.0
    with pytest.raises(KeyError, match="TPU v5 lite pod"):
        device_peak("tpu", "TPU v5 lite pod")
    with pytest.raises(KeyError):
        device_peak("tpu", "TPU v9")
    assert device_peak("cpu", "cpu") is None
