"""The checking service: scheduling, preemptive multiplexing, the shared
AOT cache, per-run telemetry scoping, and the HTTP front-end."""

import io
import json
import re
import time
import urllib.error
import urllib.request

import pytest

from stateright_tpu import WriteReporter
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.service import CheckService, ServiceServer
from stateright_tpu.telemetry import metrics_registry
from stateright_tpu.utils.faults import FaultSpec, inject

# Shapes shared with the rest of the suite, so the persistent compile
# cache keeps these tests cheap.
SPAWN_2PC = {
    "frontier_capacity": 16,
    "table_capacity": 1 << 12,
    "max_drain_waves": 2,
    # One shared AOT namespace for the module (the signature separates
    # the 2pc-3 and 2pc-4 configurations): incarnations never re-trace.
    "aot_cache": "t-svc",
}
UNIQUE_2PC3 = 288
UNIQUE_2PC4 = 1568


def _golden(checker_or_text):
    if isinstance(checker_or_text, str):
        text = checker_or_text
    else:
        out = io.StringIO()
        checker_or_text.report(WriteReporter(out))
        text = out.getvalue()
    return re.sub(r"sec=\d+", "sec=_", text)


@pytest.fixture
def service():
    # The quantum must exceed the resume overhead (respawn + restore,
    # ~1s cold on this CPU backend) or slices make no progress and the
    # tests churn; the service default (1.0s) reflects the same rule.
    svc = CheckService(quantum_s=0.75, default_spawn=dict(SPAWN_2PC))
    yield svc
    svc.close()


def test_single_job_full_verdict(service):
    handle = service.submit(model_name="2pc", model_args={"rm_count": 3})
    result = handle.result(timeout=180)
    assert result["unique"] == UNIQUE_2PC3
    assert result["properties_hold"] is True
    assert "Done." in result["report"]
    assert set(result["discoveries"]) == {
        "abort agreement", "commit agreement",
    }
    status = handle.status()
    assert status["state"] == "done"
    lat = status["latency"]
    # The latency fields the bench and the HTTP API surface.
    assert lat["wall_s"] is not None and lat["wall_s"] > 0
    assert lat["queued_s"] is not None
    assert lat["ttfv_s"] is not None  # 2pc's sometimes props discover


def test_concurrent_jobs_preempt_and_stay_exact():
    """The TIME-SLICE path (packing disabled — PR 12's packer would
    co-schedule these): two equal-priority contending jobs round-robin
    the device at wave granularity; both verdicts match the batch path
    exactly and their golden reports agree with each other (identical
    workload). Packed co-scheduling of the same pair is covered by
    tests/test_packed_tenancy.py.

    Contention does not hang on the machine's speed: every pass of a
    job's drain loop is held 50 ms (an injected ``wave.stall``), and the
    service's clock counts only those holds. A quantum is then 15 passes
    of the running job, however long its respawn and restore take, and a
    2pc-4 job makes more passes than that, so neither job can finish
    inside its first slice and every slice makes progress."""
    stall_s = 0.05
    inj = None

    def clock():
        return 0.0 if inj is None else inj.hits("wave.stall") * stall_s

    svc = CheckService(
        quantum_s=0.75, default_spawn=dict(SPAWN_2PC), packing=False,
        clock=clock,
    )
    try:
        with inject(
            FaultSpec("wave.stall", count=1 << 30, stall_s=stall_s)
        ) as inj:
            h1 = svc.submit(model_name="2pc", model_args={"rm_count": 4})
            h2 = svc.submit(model_name="2pc", model_args={"rm_count": 4})
            r1 = h1.result(timeout=300)
            r2 = h2.result(timeout=300)
        assert r1["unique"] == UNIQUE_2PC4
        assert r2["unique"] == UNIQUE_2PC4
        assert _golden(r1["report"]) == _golden(r2["report"])
        # Each job's passes alone outlast a quantum.
        assert inj.hits("wave.stall") * stall_s > 2 * 2 * svc.quantum_s
        # Contention existed, so at least one job was preempted mid-run —
        # and its result is still exact (the bit-identical guarantee under
        # real scheduling, not just the direct-API test).
        assert h1.status()["preempts"] + h2.status()["preempts"] >= 1
    finally:
        svc.close()


def test_high_priority_job_overtakes_running_low():
    """A high-priority arrival preempts the running low-priority job at
    its next quantum and completes first. Dedicated short-quantum
    service: with a warm AOT cache a 2pc-4 job can finish inside the
    fixture's 0.75s quantum, and a job that completes its first slice
    is (correctly) never preempted."""
    svc = CheckService(quantum_s=0.15, default_spawn=dict(SPAWN_2PC))
    try:
        low = svc.submit(model_name="2pc", model_args={"rm_count": 4})
        deadline = time.monotonic() + 60
        while (
            svc.job(low.job_id).state == "queued"
            and time.monotonic() < deadline
        ):
            time.sleep(0.005)
        high = svc.submit(
            model_name="2pc", model_args={"rm_count": 3}, priority=5
        )
        assert high.result(timeout=180)["unique"] == UNIQUE_2PC3
        assert svc.job(low.job_id).finished_t is None or (
            svc.job(high.job_id).finished_t
            <= svc.job(low.job_id).finished_t
        )
        assert low.result(timeout=300)["unique"] == UNIQUE_2PC4
    finally:
        svc.close()


def test_second_job_shares_aot_cache_zero_compiles(service):
    """The acceptance criterion: two jobs of the same wave shape share
    the AOT rung cache — the later job's attribution ledger records
    ZERO compile phases (in-wave and outside-wave both)."""
    from stateright_tpu.checker.programs import clear_shared_aot_caches

    # The cache is process-global (that's the point — it outlives
    # service instances); clear it so THIS test's first job provably
    # pays the compiles its second job then skips.
    clear_shared_aot_caches()
    h1 = service.submit(
        model_name="2pc", model_args={"rm_count": 3},
        spawn={"attribution": True},
    )
    h1.result(timeout=180)
    h2 = service.submit(
        model_name="2pc", model_args={"rm_count": 3},
        spawn={"attribution": True},
    )
    r2 = h2.result(timeout=180)
    attr = r2["attribution"]
    assert attr["phases_s"].get("compile", 0.0) == 0.0
    assert (attr.get("outside_wave_s") or {}).get("compile", 0.0) == 0.0
    # compile_s_total spans every incarnation via the run registry — the
    # honest cross-preemption evidence bench.py counts.
    assert r2["compile_s_total"] == 0.0
    # The first job did compile (it built the cache the second one rode).
    r1 = h1.status()["result"]
    a1 = r1["attribution"]
    compiled = a1["phases_s"].get("compile", 0.0) + (
        a1.get("outside_wave_s") or {}
    ).get("compile", 0.0)
    assert compiled > 0.0
    assert r1["compile_s_total"] > 0.0
    assert r1["unique"] == r2["unique"] == UNIQUE_2PC3


def test_zoo_aliases_share_one_aot_namespace(service):
    """"2pc" and "two_phase_commit" are the same factory; their jobs
    must land in one AOT namespace (aliases never recompile)."""
    ns = []
    for name in ("2pc", "two_phase_commit"):
        h = service.submit(model_name=name, model_args={"rm_count": 3})
        ns.append(service.job(h.job_id).aot_namespace)
        h.cancel()
    assert ns[0] == ns[1]


def test_cancel_running_job(service):
    victim = service.submit(model_name="2pc", model_args={"rm_count": 4})
    # Let it actually start, then cancel mid-run.
    deadline = time.monotonic() + 60
    while (
        service.job(victim.job_id).state == "queued"
        and time.monotonic() < deadline
    ):
        time.sleep(0.005)
    assert victim.cancel() is True
    with pytest.raises(RuntimeError, match="cancelled"):
        victim.result(timeout=120)
    assert victim.status()["state"] == "cancelled"
    # The device frees up for the next tenant.
    after = service.submit(model_name="2pc", model_args={"rm_count": 3})
    assert after.result(timeout=180)["unique"] == UNIQUE_2PC3


def test_per_tenant_hbm_budget(service):
    """A tenant's hbm_budget_mib flows to the tiered store: the job
    completes exactly despite forced L0 evictions, and its own (run-
    scoped) registry records them."""
    import math

    actions = TwoPhaseSys(4).packed_action_count()
    rows = 1 << math.ceil(math.log2(16 * actions / 0.55 + 1))
    budget = ((rows + 128) * 8) / (1 << 20)
    handle = service.submit(
        model_name="2pc", model_args={"rm_count": 4},
        hbm_budget_mib=budget, tenant="small-tenant",
    )
    result = handle.result(timeout=300)
    assert result["unique"] == UNIQUE_2PC4
    job = service.job(handle.job_id)
    snap = metrics_registry(job.run_id).snapshot()
    assert snap.get("tpu_bfs.storage.evictions", 0) >= 1


def test_submit_validation(service):
    with pytest.raises(ValueError, match="unknown model"):
        service.submit(model_name="nope")
    with pytest.raises(ValueError, match="model_name"):
        service.submit()
    with pytest.raises(ValueError, match="unknown options"):
        service.submit(model_name="2pc", options={"bogus": 1})
    # Scheduling inputs are coerced at submit time: a string deadline
    # from an HTTP body must be rejected HERE, not TypeError the
    # scheduler thread mid-sort (which would hang every job).
    with pytest.raises(ValueError, match="deadline_s"):
        service.submit(model_name="2pc", deadline_s="soon")
    with pytest.raises(ValueError, match="hbm_budget_mib"):
        service.submit(model_name="2pc", hbm_budget_mib="lots")


def test_quantum_preempts_only_when_peer_would_be_picked():
    """The quantum-expiry guard compares real reschedule order: a
    finite-deadline job keeps the device over a deadline-less peer (it
    would be re-picked anyway — preempting is pure churn), while an
    earlier-deadline or higher-priority peer does preempt."""
    from stateright_tpu.service.jobs import CheckJob

    svc = CheckService(quantum_s=0.1)
    try:
        def add(jid, seq, **kw):
            job = CheckJob(jid, lambda: None, seq=seq, **kw)
            svc._jobs[jid] = job
            return job

        edf = add("edf", 0, deadline_s=60.0)
        edf.state = "running"
        plain = add("plain", 1)
        # plain would NOT be picked over edf's re-entry -> no preempt.
        assert svc._should_preempt_for_peer(edf) is False
        # An earlier-deadline peer would be picked -> preempt.
        add("sooner", 2, deadline_s=1.0)
        assert svc._should_preempt_for_peer(edf) is True
        # A higher-priority peer preempts a deadline-less runner; a
        # lower-priority one never does.
        plain.state = "running"
        del svc._jobs["edf"], svc._jobs["sooner"]
        add("low", 3, priority=-1)
        assert svc._should_preempt_for_peer(plain) is False
        add("high", 4, priority=1)
        assert svc._should_preempt_for_peer(plain) is True
    finally:
        svc.close()


def test_finished_job_retention(service):
    """Terminal jobs (and their run registries) beyond the cap are
    evicted oldest-first; live handles keep answering."""
    from stateright_tpu.telemetry.metrics import run_registries

    service.max_finished_jobs = 1
    h1 = service.submit(model_name="2pc", model_args={"rm_count": 3})
    r1 = h1.result(timeout=180)
    h2 = service.submit(model_name="2pc", model_args={"rm_count": 3})
    h2.result(timeout=180)
    deadline = time.monotonic() + 10
    while service.job(h1.job_id) is not None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert service.job(h1.job_id) is None, "oldest finished job evicted"
    assert service.job(h2.job_id) is not None
    assert h1.job_id not in run_registries(), "registry discarded"
    # The handle still works — it holds the job, not the index entry.
    assert h1.status()["state"] == "done"
    assert r1["unique"] == UNIQUE_2PC3


# -- per-run telemetry scoping (the namespacing satellite) -------------------


def test_run_scoped_registries_do_not_collide():
    """Two checkers in one process with distinct run_ids each count
    their own waves/uniques; the default registry sees neither."""
    base = metrics_registry().snapshot().get("tpu_bfs.states_unique", 0)
    a = (
        TwoPhaseSys(3)
        .checker()
        .spawn_tpu_bfs(run_id="iso-a", **SPAWN_2PC)
        .join()
    )
    b = (
        TwoPhaseSys(4)
        .checker()
        .spawn_tpu_bfs(run_id="iso-b", **SPAWN_2PC)
        .join()
    )
    snap_a = metrics_registry("iso-a").snapshot()
    snap_b = metrics_registry("iso-b").snapshot()
    assert snap_a["tpu_bfs.states_unique"] == UNIQUE_2PC3
    assert snap_b["tpu_bfs.states_unique"] == UNIQUE_2PC4
    assert a.metrics() is metrics_registry("iso-a")
    assert b.metrics() is metrics_registry("iso-b")
    after = metrics_registry().snapshot().get("tpu_bfs.states_unique", 0)
    assert after == base, "run-scoped checkers must not touch the default"


def test_monitor_core_run_filter():
    """MonitorCore(run_filter=...) selects one run's wave stream; the
    unfiltered core aggregates every run."""
    from stateright_tpu.telemetry.server import MonitorCore

    selected = MonitorCore(run_filter="run-a", registry=metrics_registry("mcrf"))
    aggregate = MonitorCore(registry=metrics_registry("mcrf2"))
    try:
        for run, n_new in (("run-a", 5), ("run-b", 7)):
            event = {
                "ph": "X", "name": "tpu_bfs.wave", "dur": 1000.0,
                "args": {"new_unique": n_new, "generated": n_new,
                         "run_id": run},
            }
            selected.write_event(event)
            aggregate.write_event(event)
        assert selected.estimator.unique_total == 5
        assert aggregate.estimator.unique_total == 12
    finally:
        selected.close()
        aggregate.close()


def test_run_scoped_tracer_stamps_spans():
    from stateright_tpu.telemetry import get_tracer

    tracer = get_tracer("stamp-test")
    with tracer.span("x.wave", foo=1):
        pass
    ev = [e for e in tracer.events() if e["name"] == "x.wave"][-1]
    assert ev["args"]["run_id"] == "stamp-test"
    assert ev["args"]["foo"] == 1


# -- HTTP front-end ----------------------------------------------------------


def _http_json(url, data=None):
    req = urllib.request.Request(
        url,
        data=data,
        headers={"Content-Type": "application/json"} if data else {},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.load(resp)


def test_http_front_end():
    with ServiceServer(
        quantum_s=0.75, default_spawn=dict(SPAWN_2PC)
    ) as server:
        # Submit two concurrent jobs over HTTP (the CI smoke shape).
        ids = []
        for _ in range(2):
            resp = _http_json(
                server.url + "/jobs",
                json.dumps(
                    {"model": "2pc", "model_args": {"rm_count": 3}}
                ).encode(),
            )
            assert resp["state"] in ("queued", "running")
            ids.append(resp["job_id"])
        deadline = time.monotonic() + 240
        done = {}
        while len(done) < 2 and time.monotonic() < deadline:
            for jid in ids:
                st = _http_json(f"{server.url}/jobs/{jid}")
                if st["state"] in ("done", "failed", "cancelled"):
                    done[jid] = st
            time.sleep(0.1)
        assert len(done) == 2, "jobs did not finish in time"
        for st in done.values():
            assert st["state"] == "done"
            assert st["result"]["unique"] == UNIQUE_2PC3
            assert st["result"]["properties_hold"] is True
            lat = st["latency"]
            assert lat["wall_s"] is not None
            assert lat["ttfv_s"] is not None

        # Job list (the UI panel feed).
        listing = _http_json(server.url + "/jobs")
        assert {j["job_id"] for j in listing["jobs"]} >= set(ids)

        # Per-job metrics: that run's registry, labeled with its run_id.
        text = (
            urllib.request.urlopen(
                f"{server.url}/jobs/{ids[0]}/metrics", timeout=30
            )
            .read()
            .decode()
        )
        assert f'run_id="{ids[0]}"' in text
        # Packed jobs carry their per-tenant lane accounting; a job that
        # fell back to time-slicing carries the solo wave family. Either
        # way the per-run registry is populated and labeled.
        assert (
            "stateright_pack_tenant_states_unique_total" in text
            or "stateright_tpu_bfs_states_unique_total" in text
        )

        # Aggregate /metrics exports every run under its label, with at
        # most ONE TYPE line per metric family (spec-valid exposition —
        # strict parsers reject duplicates).
        agg = (
            urllib.request.urlopen(server.url + "/metrics", timeout=30)
            .read()
            .decode()
        )
        for jid in ids:
            assert f'run_id="{jid}"' in agg
        type_lines = [
            line for line in agg.splitlines() if line.startswith("# TYPE ")
        ]
        assert len(type_lines) == len(set(type_lines))

        # The /jobs listing is the summary view: scalar verdicts only,
        # no report text / ledgers (the UI polls it every ~2s).
        listed = _http_json(server.url + "/jobs")["jobs"]
        for j in listed:
            if isinstance(j.get("result"), dict):
                assert "report" not in j["result"]
                assert "attribution" not in j["result"]

        # Unknown model / unknown job surface as HTTP errors.
        with pytest.raises(urllib.error.HTTPError) as err:
            _http_json(
                server.url + "/jobs", json.dumps({"model": "nope"}).encode()
            )
        assert err.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as err:
            _http_json(server.url + "/jobs/absent")
        assert err.value.code == 404
        # Bare "/jobs/" (trailing slash) is a clean 404, not a dropped
        # connection from an unhandled IndexError.
        with pytest.raises(urllib.error.HTTPError) as err:
            _http_json(server.url + "/jobs/")
        assert err.value.code == 404

        # Dangerous spawn kwargs are refused over HTTP: resume_from
        # would pickle.load a server-side path of the client's choosing.
        for bad_body in (
            {"model": "2pc", "spawn": {"resume_from": "/tmp/evil.pkl"}},
            {"model": "2pc", "spawn": {"checkpoint_path": "/tmp/x"}},
            {"model": "2pc", "spawn": 5},
            {"model": "2pc", "model_args": 5},
            {"model": "2pc", "priority": [1]},
        ):
            with pytest.raises(urllib.error.HTTPError) as err:
                _http_json(
                    server.url + "/jobs", json.dumps(bad_body).encode()
                )
            assert err.value.code == 400

        # The UI page (with the jobs panel markup) serves from /.
        page = (
            urllib.request.urlopen(server.url + "/", timeout=30)
            .read()
            .decode()
        )
        assert "jobs-panel" in page

        # Cancel over HTTP: submit a bigger job and kill it.
        resp = _http_json(
            server.url + "/jobs",
            json.dumps(
                {"model": "2pc", "model_args": {"rm_count": 4}}
            ).encode(),
        )
        jid = resp["job_id"]
        out = _http_json(f"{server.url}/jobs/{jid}/cancel", b"")
        assert out["cancelled"] is True
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            st = _http_json(f"{server.url}/jobs/{jid}")
            if st["state"] in ("done", "failed", "cancelled"):
                break
            time.sleep(0.05)
        assert st["state"] == "cancelled"


@pytest.mark.parametrize(
    "body,code,needle",
    [
        # Unknown zoo name: structured 400 naming the zoo.
        ({"model": "not-a-model"}, 400, "unknown model"),
        # Inadmissible budget: rejected at admission, not mid-run.
        (
            {"model": "2pc", "hbm_budget_mib": 0.0001},
            400,
            "rejected at admission",
        ),
        # Non-numeric deadline: coerced at submit, 400 with the reason.
        ({"model": "2pc", "deadline_s": "soon"}, 400, "deadline_s"),
        # Bad retry policy shape.
        ({"model": "2pc", "retry": "always"}, 400, "retry"),
        # Full admission queue: 429 + Retry-After (graceful
        # degradation, not a client error).
        ({"model": "2pc", "model_args": {"rm_count": 4}}, 429, "full"),
    ],
)
def test_http_admission_errors_are_structured(body, code, needle):
    """Every admission failure over HTTP is a structured JSON error
    with the right status — including 429 for a full queue."""
    with ServiceServer(
        quantum_s=0.5,
        default_spawn=dict(SPAWN_2PC),
        max_queued_jobs=1,
    ) as server:
        filler = None
        if code == 429:
            # Occupy the single queue slot first.
            filler = _http_json(
                server.url + "/jobs",
                json.dumps(
                    {"model": "2pc", "model_args": {"rm_count": 4}}
                ).encode(),
            )
        with pytest.raises(urllib.error.HTTPError) as err:
            _http_json(server.url + "/jobs", json.dumps(body).encode())
        assert err.value.code == code
        payload = json.loads(err.value.read().decode())
        assert needle in payload["error"]
        if code == 429:
            assert err.value.headers.get("Retry-After") is not None
            assert payload["retry_after_s"] > 0
        if filler is not None:
            _http_json(
                f"{server.url}/jobs/{filler['job_id']}/cancel", b""
            )
