"""The chaos suite: deterministic fault injection (utils/faults.py)
through the self-healing service layer.

The acceptance bar (ISSUE 13): for every injected fault class — host
probe, spill ENOSPC, pipeline-worker death, device-wave raise,
checkpoint write, stall — the job recovers via checkpointed retry and
its verdict (counts, depths, discoveries, golden reporter) is
bit-identical to the fault-free run; a packed tenant's blast radius is
exactly itself; and a kill-and-recover(service_dir) resumes a zoo job
bit-identically from its last durable checkpoint.

Budget notes: every service test reuses the suite's 2pc spawn shape
(frontier 16 / table 4096, one shared AOT namespace) so the persistent
compile cache keeps incarnations cheap, and the fault-free baseline is
computed once per module.
"""

import io
import os
import re
import threading
import time

import pytest

from stateright_tpu import WriteReporter
from stateright_tpu.checker.pipeline import PipelinePoisonedError
from stateright_tpu.checker.tpu import min_admissible_hbm_budget_mib
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.service import (
    CheckService,
    JobHandle,
    QueueFullError,
    RetryPolicy,
)
from stateright_tpu.utils.faults import (
    DeviceWaveFault,
    FaultInjector,
    FaultSpec,
    SpillFault,
    classify_fault,
    inject,
    seeded_specs,
    tenant_fault_of,
)

SPAWN_2PC = {
    "frontier_capacity": 16,
    "table_capacity": 1 << 12,
    "max_drain_waves": 2,
    # Same SHAPES as tests/test_service.py (the persistent jax compile
    # cache keys on the HLO, so lowerings stay warm across the suite)
    # but a DISTINCT in-process AOT namespace: sharing "t-svc" would
    # pre-warm test_service's executables and break its timing-shaped
    # assumption that a cold 2pc-4 job outlives one 0.75s quantum.
    "aot_cache": "t-flt",
}
UNIQUE_2PC3 = 288


def _golden(text_or_checker):
    if isinstance(text_or_checker, str):
        text = text_or_checker
    else:
        out = io.StringIO()
        text_or_checker.report(WriteReporter(out))
        text = out.getvalue()
    return re.sub(r"sec=\d+", "sec=_", text)


def _service(**kw):
    kw.setdefault("quantum_s", 5.0)
    kw.setdefault("default_spawn", dict(SPAWN_2PC))
    return CheckService(**kw)


@pytest.fixture(scope="module")
def baseline():
    """Fault-free 2pc-3 verdicts: in-core and out-of-core (the same
    numbers — that is the PR 5 guarantee — but captured separately so
    golden comparisons stay apples-to-apples)."""
    svc = _service()
    try:
        r = svc.submit(
            model_name="2pc", model_args={"rm_count": 3}
        ).result(timeout=300)
    finally:
        svc.close()
    return r


# -- the injector itself -----------------------------------------------------


def test_injector_fires_at_exact_hit_indices():
    inj = FaultInjector(FaultSpec("device.wave", at=2))
    inj.fire("device.wave")
    inj.fire("device.wave")
    with pytest.raises(DeviceWaveFault):
        inj.fire("device.wave")
    inj.fire("device.wave")  # count=1: only hit index 2 faults
    assert inj.hits("device.wave") == 4
    assert inj.triggered() == 1


def test_injector_tenant_filter_counts_only_matching_hits():
    inj = FaultInjector(FaultSpec("storage.host_probe", at=1, tenant="b"))
    inj.fire("storage.host_probe", tenant="a")  # not counted for the spec
    inj.fire("storage.host_probe", tenant="b")  # b hit 0
    with pytest.raises(Exception):
        inj.fire("storage.host_probe", tenant="b")  # b hit 1 -> fault
    assert inj.triggered() == 1


def test_classify_fault_walks_cause_chains():
    assert classify_fault(SpillFault()) == "spill"
    assert classify_fault(OSError(28, "No space left on device")) == "spill"
    inner = DeviceWaveFault()
    outer = RuntimeError("wrapped")
    outer.__cause__ = inner
    assert classify_fault(outer) == "device_wave"
    poisoned = PipelinePoisonedError(ValueError("worker died"))
    assert classify_fault(poisoned) == "pipeline_worker"
    assert classify_fault(ValueError("x")) == "unknown"
    assert tenant_fault_of(outer) is None


def test_seeded_specs_are_reproducible():
    sites = ["device.wave", "storage.host_probe", "storage.spill"]
    a = seeded_specs(1234, sites)
    b = seeded_specs(1234, sites)
    assert [(s.site, s.at) for s in a] == [(s.site, s.at) for s in b]
    c = seeded_specs(99, sites)
    assert [(s.site, s.at) for s in a] != [(s.site, s.at) for s in c]
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec("no.such.site")
    with pytest.raises(ValueError, match="stall_s"):
        FaultSpec("wave.stall")


def test_retry_policy_filter_and_backoff():
    p = RetryPolicy(max_retries=2, backoff_s=0.5, backoff_factor=2.0,
                    max_backoff_s=10.0, retry_on={"device_wave"})
    assert p.allows("device_wave", 0) and p.allows("device_wave", 1)
    assert not p.allows("device_wave", 2)
    assert not p.allows("spill", 0)
    assert p.delay_s(0) == 0.5 and p.delay_s(1) == 1.0
    assert RetryPolicy.from_dict(p.to_dict()).to_dict() == p.to_dict()


# -- per-fault-class recovery: bit-identical verdicts ------------------------


def test_device_wave_fault_retries_bit_identical(baseline):
    svc = _service()
    try:
        with inject(FaultSpec("device.wave", at=1)) as inj:
            h = svc.submit(model_name="2pc", model_args={"rm_count": 3})
            r = h.result(timeout=300)
        assert inj.triggered() == 1
        st = h.status()
        assert st["retries"] == 1
        assert st["faults"][0]["class"] == "device_wave"
        assert r["unique"] == baseline["unique"]
        assert r["states"] == baseline["states"]
        assert r["max_depth"] == baseline["max_depth"]
        assert set(r["discoveries"]) == set(baseline["discoveries"])
        assert _golden(r["report"]) == _golden(baseline["report"])
    finally:
        svc.close()


def test_host_probe_and_spill_faults_retry_bit_identical(
    baseline, tmp_path
):
    """Out-of-core 2pc-3 under the minimum budget: a host-probe death
    and a spill ENOSPC each fault the slice, the retry recovers, and
    the verdict matches the fault-free run exactly."""
    budget = min_admissible_hbm_budget_mib(TwoPhaseSys(3), 16)
    cases = [
        ("storage.host_probe", "host_probe", {}),
        (
            "storage.spill",
            "spill",
            {
                "host_budget_mib": 0.0001,
                "spill_dir": str(tmp_path / "spill"),
            },
        ),
    ]
    for site, klass, extra_spawn in cases:
        svc = _service()
        try:
            with inject(FaultSpec(site, at=0)) as inj:
                h = svc.submit(
                    model_name="2pc", model_args={"rm_count": 3},
                    hbm_budget_mib=budget, spawn=extra_spawn or None,
                )
                r = h.result(timeout=300)
            assert inj.triggered() == 1, site
            st = h.status()
            assert st["retries"] == 1, (site, st["faults"])
            assert st["faults"][0]["class"] == klass
            assert r["unique"] == baseline["unique"], site
            assert _golden(r["report"]) == _golden(baseline["report"])
        finally:
            svc.close()


def test_checkpoint_write_fault_retries_bit_identical(baseline, tmp_path):
    svc = _service()
    try:
        with inject(FaultSpec("checkpoint.write", at=0)) as inj:
            h = svc.submit(
                model_name="2pc", model_args={"rm_count": 3},
                spawn={
                    "checkpoint_path": str(tmp_path / "c.ckpt"),
                    "checkpoint_every_chunks": 1,
                },
            )
            r = h.result(timeout=300)
        assert inj.triggered() == 1
        st = h.status()
        assert st["retries"] == 1
        assert st["faults"][0]["class"] == "checkpoint_write"
        assert r["unique"] == baseline["unique"]
        assert _golden(r["report"]) == _golden(baseline["report"])
    finally:
        svc.close()


def test_stall_watchdog_auto_preempts_and_recovers(baseline):
    """A wedged wave (injected 1.2s sleep) trips the service stall
    watchdog, whose default action hook auto-preempts: the job suspends
    at its next yield point, retries, and finishes exactly."""
    svc = _service(packing=False, stall_deadline_s=0.3, quantum_s=30.0)
    try:
        with inject(
            FaultSpec("wave.stall", at=2, stall_s=1.2)
        ) as inj:
            h = svc.submit(model_name="2pc", model_args={"rm_count": 3})
            r = h.result(timeout=300)
        assert inj.triggered() == 1
        st = h.status()
        assert st["stall_preempts"] == 1
        assert st["preempts"] >= 1
        assert r["unique"] == baseline["unique"]
        assert _golden(r["report"]) == _golden(baseline["report"])
    finally:
        svc.close()


def test_pipeline_worker_death_fault_retries_bit_identical(baseline):
    """Async-pipeline worker death at the SERVICE level: the poisoned
    pipeline surfaces as the worker error, classifies as
    pipeline_worker, and the retry recovers exactly."""
    budget = min_admissible_hbm_budget_mib(TwoPhaseSys(3), 16)
    svc = _service()
    try:
        with inject(FaultSpec("pipeline.worker", at=1)) as inj:
            h = svc.submit(
                model_name="2pc", model_args={"rm_count": 3},
                hbm_budget_mib=budget,
                spawn={"async_pipeline": True},
            )
            r = h.result(timeout=300)
        assert inj.triggered() == 1
        st = h.status()
        assert st["retries"] == 1
        assert st["faults"][0]["class"] == "pipeline_worker"
        assert r["unique"] == baseline["unique"]
        assert _golden(r["report"]) == _golden(baseline["report"])
    finally:
        svc.close()


def test_retry_resumes_from_snapshot_not_scratch():
    """The checkpointed-retry contract: a fault on a RESUMED slice
    hands the pre-slice payload back, so the retry re-explores only
    from the last good wave boundary. Driven directly through
    _run_slice with the scheduler parked, for determinism."""
    svc = _service(packing=False, quantum_s=30.0)
    # Park the scheduler thread (close-without-jobs), then drive slices
    # on this thread: deterministic, no racing picker.
    svc._closing.set()
    svc._wake()
    svc._scheduler.join(timeout=30)
    svc._closing.clear()
    try:
        h = svc.submit(model_name="2pc", model_args={"rm_count": 4})
        job = svc.job(h.job_id)
        # Slice 1: run a bit, preempt -> suspended payload.
        t = threading.Thread(target=svc._run_slice, args=(job,))
        t.start()
        deadline = time.monotonic() + 60
        while svc._active_checker is None and time.monotonic() < deadline:
            time.sleep(0.002)
        checker = svc._active_checker
        assert checker is not None, "slice never spawned"
        checker.request_preempt()
        t.join(timeout=180)
        assert job.state == "suspended", job.state
        resumed_payload = job.payload
        assert resumed_payload is not None
        mid_unique = resumed_payload["unique_count"]
        # Slice 2: resumes from the payload, faults on its first wave.
        with inject(FaultSpec("device.wave", at=0)):
            svc._run_slice(job)
        assert job.state == "faulted", job.state
        # The snapshot (the suspended payload) came back — the retry
        # will NOT start from scratch.
        assert job.payload is not None
        assert job.payload["unique_count"] == mid_unique
        # Slice 3: the retry completes exactly.
        job.not_before = None
        svc._run_slice(job)
        assert job.state == "done", (job.state, job.error)
        assert job.result["unique"] == 1568
        assert job.retries == 1
    finally:
        svc.close()


def test_quarantine_after_exhausted_retries():
    svc = _service()
    try:
        with inject(FaultSpec("device.wave", at=0, count=10 ** 6)):
            h = svc.submit(
                model_name="2pc", model_args={"rm_count": 3},
                retry_policy=RetryPolicy(max_retries=1, backoff_s=0.01),
            )
            with pytest.raises(RuntimeError, match="quarantined"):
                h.result(timeout=300)
        st = h.status()
        assert st["state"] == "quarantined"
        assert st["retries"] == 1
        assert len(st["faults"]) == 2
        # The flight dump carries the forensics: history + traceback.
        assert st["flight"]["fault_class"] == "device_wave"
        assert "DeviceWaveFault" in st["flight"]["traceback"]
        assert st["error_traceback"] is not None
    finally:
        svc.close()


def test_no_retry_policy_fails_first_fault_with_traceback():
    svc = _service(retry_policy=None)
    try:
        with inject(FaultSpec("device.wave", at=0)):
            h = svc.submit(model_name="2pc", model_args={"rm_count": 3})
            with pytest.raises(RuntimeError, match="failed"):
                h.result(timeout=300)
        st = h.status()
        assert st["state"] == "failed"
        assert st["retries"] == 0
        # Satellite: the formatted traceback (not just repr) survives
        # into the status/HTTP view and the flight dump.
        assert "DeviceWaveFault" in st["error_traceback"]
        assert "Traceback" in st["error_traceback"]
        assert st["flight"]["traceback"] == st["error_traceback"]
    finally:
        svc.close()


# -- pack-local blast radius -------------------------------------------------


def test_pack_fault_blast_radius_is_one_tenant(baseline):
    """4 packed tenants, one injected per-tenant verdict fault: the 3
    survivors complete with ZERO preemptions, the faulted tenant is
    lane-dropped with its rolled-back payload slice and its solo retry
    matches the solo baseline bit-identically. The quantum outlasts the
    test's own timeout: a slice that ends while the faulted tenant's solo
    retry waits preempts the pack for the schedule, not for the fault."""
    svc = _service(quantum_s=300.0)
    try:
        with inject(
            FaultSpec("pack.tenant.verdict", tenant="blast-2", at=0)
        ) as inj:
            handles = {
                jid: svc.submit(
                    model_name="2pc", model_args={"rm_count": 3},
                    job_id=jid,
                )
                for jid in (
                    "blast-0", "blast-1", "blast-2", "blast-3"
                )
            }
            results = {
                jid: h.result(timeout=300)
                for jid, h in handles.items()
            }
        assert inj.triggered() == 1
        for jid, r in results.items():
            assert r["unique"] == baseline["unique"], jid
            assert _golden(r["report"]) == _golden(baseline["report"])
        stats = {jid: h.status() for jid, h in handles.items()}
        faulted = stats.pop("blast-2")
        assert faulted["retries"] == 1
        assert faulted["faults"][0]["class"] == "pack_tenant"
        for jid, st in stats.items():
            # Survivors never preempted, never faulted — the blast
            # radius was exactly the faulted tenant.
            assert st["preempts"] == 0, (jid, st)
            assert st["retries"] == 0 and not st["faults"], (jid, st)
            assert st["packed"] is True, jid
    finally:
        svc.close()


def test_two_tenants_faulting_same_wave_both_drop_no_livelock(baseline):
    """Regression (review finding): when one wave faults SEVERAL
    tenants, every flagged tenant must be rolled back and dropped —
    leaving one resident would exclude it from scheduling while still
    counting it live, spinning the pack loop forever. Both faulted
    tenants retry, the survivor finishes untouched."""
    svc = _service()
    try:
        with inject(
            FaultSpec("pack.tenant.verdict", tenant="multi-0", at=0),
            FaultSpec("pack.tenant.verdict", tenant="multi-1", at=0),
        ) as inj:
            handles = {
                jid: svc.submit(
                    model_name="2pc", model_args={"rm_count": 3},
                    job_id=jid,
                )
                for jid in ("multi-0", "multi-1", "multi-2")
            }
            results = {
                jid: h.result(timeout=120)
                for jid, h in handles.items()
            }
        assert inj.triggered() == 2
        for jid, r in results.items():
            assert r["unique"] == baseline["unique"], jid
            assert _golden(r["report"]) == _golden(baseline["report"])
        stats = {jid: h.status() for jid, h in handles.items()}
        assert stats["multi-0"]["retries"] == 1
        assert stats["multi-1"]["retries"] == 1
        assert stats["multi-2"]["retries"] == 0
        assert stats["multi-2"]["preempts"] == 0
    finally:
        svc.close()


def test_pack_engine_fault_retries_all_members_solo(baseline):
    """A non-attributable engine fault (device wave raise under the
    shared dispatch) suspends every member and retries them solo — no
    job is failed, every verdict stays exact."""
    svc = _service()
    try:
        with inject(FaultSpec("device.wave", at=1)) as inj:
            handles = [
                svc.submit(model_name="2pc", model_args={"rm_count": 3})
                for _ in range(2)
            ]
            results = [h.result(timeout=300) for h in handles]
        assert inj.triggered() == 1
        for r in results:
            assert r["unique"] == baseline["unique"]
            assert _golden(r["report"]) == _golden(baseline["report"])
        # At least one member rode the fault->solo-retry path.
        assert any(h.status()["retries"] >= 1 for h in handles)
        for h in handles:
            st = h.status()
            if st["retries"]:
                assert st["packable"] is False
                assert "solo" in st["packable_reason"]
    finally:
        svc.close()


# -- durable recovery --------------------------------------------------------


def test_durable_recovery_resumes_bit_identical(baseline, tmp_path):
    """Kill-and-recover: a suspended zoo job's durable checkpoint +
    journal rebuild the queue after a 'crash' (close + fresh service),
    the finished-job record is reconstructed, an unjournalable job is
    surfaced durable:false, and the resumed job's verdict is
    bit-identical."""
    d = str(tmp_path / "svc")
    svc = _service(service_dir=d)
    try:
        done = svc.submit(
            model_name="2pc", model_args={"rm_count": 3},
            job_id="rec-done",
        )
        r_done = done.result(timeout=300)
        assert svc.job("rec-done").durable is True
        # Non-journalable: a custom model instance.
        custom = svc.submit(model=TwoPhaseSys(3), job_id="rec-custom")
        assert custom.status()["durable"] is False
        custom.result(timeout=300)
        # A job interrupted mid-run: close() preempts and flushes its
        # durable checkpoint.
        mid = svc.submit(
            model_name="2pc", model_args={"rm_count": 4},
            job_id="rec-mid",
        )
        deadline = time.monotonic() + 60
        while (
            svc.job("rec-mid").state == "queued"
            and time.monotonic() < deadline
        ):
            time.sleep(0.005)
        time.sleep(0.5)
    finally:
        out = svc.close()
    assert out["closed"] is True
    assert svc.job("rec-mid").state == "suspended"
    assert os.path.exists(os.path.join(d, "jobs", "rec-mid.ckpt"))

    svc2 = CheckService.recover(
        d, quantum_s=5.0, default_spawn=dict(SPAWN_2PC)
    )
    try:
        # Finished-job record reconstructed (bit-identity evidence
        # included).
        j_done = svc2.job("rec-done")
        assert j_done.state == "done"
        assert j_done.result["unique"] == r_done["unique"]
        assert _golden(j_done.result["report"]) == _golden(
            r_done["report"]
        )
        # The resumed job completes from its last durable checkpoint.
        r_mid = JobHandle(svc2.job("rec-mid"), svc2).result(timeout=300)
        assert r_mid["unique"] == 1568
    finally:
        svc2.close()
    # Bit-identity of the recovered run vs a fault-free one.
    svc3 = _service()
    try:
        rb = svc3.submit(
            model_name="2pc", model_args={"rm_count": 4}
        ).result(timeout=300)
    finally:
        svc3.close()
    assert r_mid["states"] == rb["states"]
    assert r_mid["max_depth"] == rb["max_depth"]
    assert _golden(r_mid["report"]) == _golden(rb["report"])


def test_recover_surfaces_lost_nondurable_job(tmp_path):
    """An UNFINISHED durable:false job must come back as an honest
    failed record, not vanish."""
    import json

    d = str(tmp_path / "svc")
    os.makedirs(os.path.join(d, "jobs"), exist_ok=True)
    with open(os.path.join(d, "journal.jsonl"), "w") as f:
        f.write(json.dumps({
            "ev": "submit", "t": 0.0, "job_id": "lost-1",
            "durable": False, "spec": None,
        }) + "\n")
    svc = CheckService.recover(d, default_spawn=dict(SPAWN_2PC))
    try:
        j = svc.job("lost-1")
        assert j is not None and j.state == "failed"
        assert "durable: false" in j.error
    finally:
        svc.close()


# -- graceful degradation ----------------------------------------------------


def test_recover_bypasses_admission_bound(tmp_path):
    """Regression (review finding): replaying more journaled jobs than
    max_queued_jobs must not abort recovery with QueueFullError — the
    jobs were already admitted before the crash."""
    import json

    d = str(tmp_path / "svc")
    os.makedirs(os.path.join(d, "jobs"), exist_ok=True)
    with open(os.path.join(d, "journal.jsonl"), "w") as f:
        for i in range(3):
            f.write(json.dumps({
                "ev": "submit", "t": 0.0, "job_id": f"rb-{i}",
                "durable": True,
                "spec": {"model_name": "2pc",
                         "model_args": {"rm_count": 3}},
            }) + "\n")
    svc = CheckService.recover(
        d, max_queued_jobs=1, default_spawn=dict(SPAWN_2PC),
        quantum_s=5.0,
    )
    try:
        for i in range(3):
            job = svc.job(f"rb-{i}")
            assert job is not None and job.state != "failed", (
                i, job and job.error
            )
        # The bound still applies to NEW submissions.
        with pytest.raises(QueueFullError):
            svc.submit(model_name="2pc", model_args={"rm_count": 3})
        for i in range(3):
            JobHandle(svc.job(f"rb-{i}"), svc).cancel()
    finally:
        svc.close()


def test_timeout_on_nonpreemptible_backend_keeps_finished_verdict():
    """Regression (review finding): a non-preemptible slice that blows
    its timeout but RUNS TO COMPLETION keeps its verdict — the deadline
    could not cut the slice, and discarding a finished result would
    make the outcome depend on preempt-attempt ordering."""
    svc = CheckService(
        quantum_s=30.0, packing=False, spawn_method="spawn_bfs",
        default_spawn={},
    )
    try:
        h = svc.submit(
            model_name="2pc", model_args={"rm_count": 3},
            timeout_s=0.001,
        )
        r = h.result(timeout=300)
        assert r["unique"] == UNIQUE_2PC3
        assert h.status()["state"] == "done"
        assert h.status()["preemptible"] is False
    finally:
        svc.close()


def test_bounded_admission_queue():
    svc = _service(max_queued_jobs=2, quantum_s=0.5)
    try:
        h1 = svc.submit(model_name="2pc", model_args={"rm_count": 4})
        h2 = svc.submit(model_name="2pc", model_args={"rm_count": 4})
        with pytest.raises(QueueFullError, match="queue full"):
            svc.submit(model_name="2pc", model_args={"rm_count": 4})
        h1.cancel()
        h2.cancel()
    finally:
        svc.close()


def test_timeout_fails_with_partial_progress_evidence():
    svc = _service(packing=False, quantum_s=30.0)
    try:
        h = svc.submit(
            model_name="2pc", model_args={"rm_count": 5}, timeout_s=1.0
        )
        with pytest.raises(RuntimeError, match="timeout"):
            h.result(timeout=300)
        st = h.status()
        assert st["state"] == "failed"
        flight = st["flight"]
        assert flight["reason"] == "timeout"
        # Partial-progress evidence: the digest shows how far it got.
        assert flight["partial_progress"] is not None
        assert flight["partial_progress"]["unique_state_count"] > 0
    finally:
        svc.close()


# -- pipeline poison hygiene (satellite) -------------------------------------


def test_pipeline_poison_typed_error_no_hang_no_held_lock():
    """Injected worker death: the checker surfaces a typed
    PipelinePoisonedError carrying the original exception, the
    close/drain path terminates (no hang), and the tiered store's
    RLock is released."""
    budget = min_admissible_hbm_budget_mib(TwoPhaseSys(3), 16)
    checker = (
        TwoPhaseSys(3)
        .checker()
        .spawn_tpu_bfs(
            frontier_capacity=16, table_capacity=1 << 12,
            hbm_budget_mib=budget, async_pipeline=True,
            aot_cache="t-flt",
        )
    )
    with inject(FaultSpec("pipeline.worker", at=1)) as inj:
        for t in checker.handles():
            t.join(timeout=180)
            assert not t.is_alive(), "worker hung after poisoning"
    assert inj.triggered() == 1
    err = checker.worker_error()
    assert err is not None
    # Typed poison with the original worker exception in the chain.
    chain = []
    e = err
    while e is not None:
        chain.append(e)
        e = e.__cause__ or e.__context__
    assert any(isinstance(x, PipelinePoisonedError) for x in chain), chain
    assert classify_fault(err) == "pipeline_worker"
    poisoned = next(
        x for x in chain if isinstance(x, PipelinePoisonedError)
    )
    assert poisoned.cause is not None
    # The tiered store's merge fence is NOT left held.
    assert checker._tier._fence.acquire(timeout=2.0)
    checker._tier._fence.release()
    # The pipeline worker thread exited (close() ran, did not hang).
    assert not checker._pipe._thread.is_alive()


def test_fault_metric_families_are_hygiene_clean():
    """fault.* / retry.* / service.* families (dynamic per-class and
    per-site names included) export as distinct, grammar-legal
    Prometheus series."""
    from stateright_tpu.telemetry import metrics_registry
    from stateright_tpu.telemetry.server import registry_hygiene_problems

    reg = metrics_registry()
    # Ensure the dynamic names exist even if no chaos test ran first.
    for cls in ("host_probe", "spill", "pipeline_worker", "device_wave",
                "checkpoint_write", "pack_tenant", "unknown"):
        reg.counter(f"fault.by_class.{cls}")
    for site in ("storage.host_probe", "storage.spill", "device.wave"):
        reg.counter(f"fault.injected.{site}")
    reg.counter("service.recovery.jobs_resumed")
    problems = [
        p
        for p in registry_hygiene_problems(reg)
        if "fault" in p or "retry" in p or "service" in p
    ]
    assert problems == []


def test_close_reports_stuck_scheduler():
    """close(timeout=) must detect a scheduler that failed to join and
    say so (return value + service.close.stuck metric) instead of
    pretending the close succeeded."""
    from stateright_tpu.telemetry import metrics_registry

    svc = CheckService()
    # Park the real scheduler, then substitute a wedged stand-in.
    svc._closing.set()
    svc._wake()
    svc._scheduler.join(timeout=30)
    release = threading.Event()
    svc._scheduler = threading.Thread(target=release.wait, daemon=True)
    svc._scheduler.start()
    before = metrics_registry().snapshot().get("service.close.stuck", 0)
    out = svc.close(timeout=0.1)
    assert out == {"closed": False, "stuck": True}
    after = metrics_registry().snapshot().get("service.close.stuck", 0)
    assert after == before + 1
    release.set()


# -- device-liveness edge store under adversity (ISSUE 14) -------------------


def _live_graph():
    """A lasso-bearing graph with enough edges to force mid-run edge
    evictions under a tiny device store."""
    from test_device_liveness import PackedDGraph

    return PackedDGraph(
        [2 * i for i in range(24)] + [2],  # long even chain closing a cycle
        [0, 46],
    )


_LIVE_SPAWN = {
    "frontier_capacity": 16,
    "table_capacity": 1 << 10,
    "liveness": "device",
    # Minimum legal capacity (F·(A+1) rows): every couple of waves
    # evicts, so the injected fault lands MID-eviction, mid-run.
    "edge_log_capacity": 64,
    "aot_cache": "t-flt-live",
}


@pytest.fixture(scope="module")
def live_baseline():
    svc = _service()
    try:
        r = svc.submit(_live_graph, spawn=dict(_LIVE_SPAWN)).result(
            timeout=300
        )
    finally:
        svc.close()
    assert r["liveness"]["mode"] == "device"
    assert "odd" in r["discoveries"]
    return r


def test_liveness_edge_evict_fault_retries_bit_identical(live_baseline):
    """A fault mid-edge-eviction (the liveness.edge_evict seam inside
    LivenessEdgeStore.absorb) faults the slice; the checkpointed retry
    recovers and the device-liveness verdict is bit-identical to the
    fault-free run — a dropped edge store must never decay into a
    silent 'absence'."""
    svc = _service()
    try:
        with inject(FaultSpec("liveness.edge_evict", at=1)) as inj:
            h = svc.submit(_live_graph, spawn=dict(_LIVE_SPAWN))
            r = h.result(timeout=300)
        assert inj.triggered() == 1
        st = h.status()
        assert st["retries"] == 1
        assert st["faults"][0]["class"] == "liveness_evict"
        assert st["liveness_mode"] == "device"
        assert r["unique"] == live_baseline["unique"]
        assert set(r["discoveries"]) == set(live_baseline["discoveries"])
        assert (
            r["liveness"]["outcomes"]["odd"]["verdict"] == "counterexample"
        )
        assert _golden(r["report"]) == _golden(live_baseline["report"])
    finally:
        svc.close()


def test_liveness_survives_stall_preempt_resume(live_baseline):
    """Preempt mid-exploration (stall-watchdog auto-preempt), resume:
    the edge log rides the v3 payload intact and the resumed run's
    device verdict matches the uninterrupted one exactly."""
    svc = _service(packing=False, stall_deadline_s=0.3, quantum_s=30.0)
    try:
        with inject(FaultSpec("wave.stall", at=2, stall_s=1.2)) as inj:
            h = svc.submit(_live_graph, spawn=dict(_LIVE_SPAWN))
            r = h.result(timeout=300)
        assert inj.triggered() == 1
        st = h.status()
        assert st["stall_preempts"] == 1
        assert st["preempts"] >= 1
        assert r["unique"] == live_baseline["unique"]
        assert set(r["discoveries"]) == set(live_baseline["discoveries"])
        # The edge relation accumulated across BOTH incarnations (the
        # resumed store starts from the payload, not from scratch).
        assert (
            r["liveness"]["edge_store"]["edges_logged"]
            >= live_baseline["liveness"]["edge_store"]["edges_logged"]
        )
        assert _golden(r["report"]) == _golden(live_baseline["report"])
    finally:
        svc.close()


def test_liveness_metric_family_is_hygiene_clean():
    from stateright_tpu.telemetry import metrics_registry
    from stateright_tpu.telemetry.server import registry_hygiene_problems

    reg = metrics_registry()
    reg.counter("fault.by_class.liveness_evict")
    reg.counter("fault.injected.liveness.edge_evict")
    reg.counter("liveness.inconclusive")
    reg.counter("liveness.skipped_crashed_run")
    problems = [
        p for p in registry_hygiene_problems(reg) if "liveness" in p
    ]
    assert problems == []
