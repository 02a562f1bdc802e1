"""Round benchmark: TPU BFS throughput on the reference bench workloads.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Primary workload: exhaustive check of the 7-RM two-phase-commit model
(296,448 unique states — the scaled-up version of the reference's
``2pc check N`` bench config, ``/root/reference/bench.sh:27``) on the
``TpuBfsChecker`` device backend. Baseline: the host ``BfsChecker`` on the
same model, rate-sampled with a state-count cap so the bench stays fast;
the reference itself publishes no absolute numbers (BASELINE.md).

Secondary legs cover every BASELINE.md measurement config: paxos 2c/3s
with the linearizability history checked on device per wave (reference
flagship, ``examples/paxos.rs:325``), ``increment_lock`` with 4 threads
(``examples/increment_lock.rs:97-106``), the 3-client ordered ABD
register (``bench.sh:31-34``), the BASELINE.md 5-node lossy Raft as a
time-to-counterexample run on its intentionally-falsifiable ``eventually
"stable leader"`` property, and the
north-star ``paxos check 3`` config (1.19M states).

Each leg runs in its own subprocess with its own timeout, one at a time,
and the parent never imports JAX: a chip belongs to one process at a
time. A leg that finds no TPU fails, and so does the bench; there is no
retry on the CPU. ``--cpu`` runs every leg on the CPU backend, for tests
only, and its output says ``"device": "cpu"``. Diagnostics go to stderr;
stdout carries only the JSON line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO_DIR = os.path.dirname(os.path.abspath(__file__))
# Per-run scratch of the legs: spill directories and flight-recorder dumps.
RUNTIME_DIR = os.path.join(REPO_DIR, ".runtime")

RM_COUNT = 7
EXPECTED_UNIQUE = 296_448
HOST_CAP = 30_000
LEG_TIMEOUT_S = {
    "smoke": 120,
    "2pc": 720,
    "paxos": 600,
    "ilock": 300,
    "abd3o": 600,
    "raft5": 600,
    "paxos3": 900,
    "scr4": 900,
}


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def _leg_backend(cpu: bool):
    """A leg child's first step, before anything imports JAX: select the
    backend, enable the shared compile cache, and refuse a device run
    that finds no TPU."""
    if cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from stateright_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    device = jax.devices()[0]
    if not cpu and device.platform != "tpu":
        raise SystemExit(
            f"no TPU: JAX found {device.platform} ({device.device_kind}); "
            "pass --cpu for a CPU test run"
        )


def _leg_specs():
    """One spec per leg: model factory, builder tweaks, spawn kwargs, and
    the pinned oracle count. The shared skeleton in ``_run_leg`` does the
    rest (optional host baseline, count assert, rate computation)."""
    from stateright_tpu.actor import Network
    from stateright_tpu.models.increment import IncrementLock
    from stateright_tpu.models.linearizable_register import AbdModelCfg
    from stateright_tpu.models.paxos import PaxosModelCfg
    from stateright_tpu.models.raft import RaftModelCfg
    from stateright_tpu.models.single_copy_register import SingleCopyModelCfg
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    return {
        # Smoke leg: 2pc-5 — 8,832 states, warm in seconds. Not part of
        # the bench rotation (its steady-state window is too short to be
        # a rate claim); advisory by design.
        "smoke": dict(
            model=lambda: TwoPhaseSys(5),
            spawn=dict(frontier_capacity=1 << 10, table_capacity=1 << 15),
            expected=8_832,
            advisory=True,
        ),
        "2pc": dict(
            model=lambda: TwoPhaseSys(RM_COUNT),
            spawn=dict(
                frontier_capacity=1 << 13,
                table_capacity=1 << 20,
                drain_log_factor=48,
            ),
            expected=EXPECTED_UNIQUE,
            host_baseline=True,
        ),
        # Paxos BFS frontiers are narrow (hundreds of states); a small
        # fixed wave width wastes far fewer masked lanes (measured 3.4x
        # steady-state vs 2048 lanes on the CPU backend).
        "paxos": dict(
            model=lambda: PaxosModelCfg(2, 3).into_model(),
            spawn=dict(frontier_capacity=1 << 9, table_capacity=1 << 16),
            expected=16_668,
        ),
        # The north-star workload (BASELINE.md: `paxos check 3`): 3
        # clients / 3 servers with the linearizability history checked on
        # device per wave; the property HOLDS, so this is a full-space
        # traversal. Count pinned from a full TpuBfsChecker (device-path)
        # run executed on the CPU backend (862s).
        "paxos3": dict(
            model=lambda: PaxosModelCfg(3, 3, envelope_capacity=24).into_model(),
            spawn=dict(
                frontier_capacity=1 << 11,
                table_capacity=1 << 21,
                drain_log_factor=32,
            ),
            expected=1_194_428,
        ),
        # BASELINE.md measurement config: `increment_lock` with 4 threads
        # (always-mutex; the "sum" ALWAYS property holds). Tiny space, so
        # the number is dominated by warmup — reported for config coverage,
        # with the steady-state rate computed net of warmup like the rest.
        # 257 states is warmup-dominated: the rate swings ±70% run-to-run
        # (4,786/s vs 2,847/s measured in round 4), so it is marked
        # advisory (VERDICT r04 #6) — the leg exists for BASELINE.md
        # config coverage, not as a throughput claim.
        "ilock": dict(
            model=lambda: IncrementLock(4),
            spawn=dict(frontier_capacity=1 << 6, table_capacity=1 << 10),
            expected=257,
            advisory=True,
        ),
        # BASELINE.md measurement config: `linearizable-register check 3
        # ordered` — 3 ABD clients / 2 servers over per-pair FIFO flows,
        # linearizability history checked on device per wave. Oracle pinned
        # by test_ordered_abd_3_clients_bench_family_parity
        # (tests/test_packed_ordered_crash.py).
        # flow_capacity=2 is measured-exact for the 2-server quorum (see
        # AbdModelCfg) and this leg's count assert pins it.
        "abd3o": dict(
            model=lambda: AbdModelCfg(
                3,
                2,
                network=Network.new_ordered(),
                envelope_capacity=12,
                flow_capacity=2,
            ).into_model(),
            spawn=dict(frontier_capacity=1 << 11, table_capacity=1 << 17),
            expected=46_516,
        ),
        # The reference bench-suite row `single-copy-register check 4`
        # (/root/reference/bench.sh:29): 4 register clients against one
        # non-replicated server, linearizability history (the 81-node
        # C=4 DP) checked on device per wave. Count pinned by this
        # framework's first completed run (round 4, 137s CPU; the r03
        # rehearsal exceeded an hour) — the reference publishes no count
        # for this config, so the oracle guards determinism and
        # regression, not cross-engine parity.
        "scr4": dict(
            model=lambda: SingleCopyModelCfg(
                4, 1, envelope_capacity=12
            ).into_model(),
            spawn=dict(
                frontier_capacity=1 << 12,
                table_capacity=1 << 22,
                drain_log_factor=32,
            ),
            expected=400_233,
        ),
        # BASELINE.md asks for time-to-counterexample: raft-5's
        # ``eventually "stable leader"`` is intentionally falsifiable, so
        # this leg runs the model with ONLY that property retained and
        # measures wall time until the checker records its discovery and
        # early-exits (the previous depth-7 slice measured compile + ramp,
        # not a BASELINE metric). Unique-at-exit is deterministic for the
        # strict-FIFO single-device drain but not asserted — the metric is
        # the discovery, not the count.
        "raft5": dict(
            model=lambda: RaftModelCfg(
                server_count=5, max_term=1, lossy=True
            )
            .into_model()
            .retain_properties("stable leader"),
            spawn=dict(frontier_capacity=1 << 11, table_capacity=1 << 21),
            expect_discovery="stable leader",
            check_properties=False,
        ),
    }


def _run_leg(leg: str, pin_cpu: bool):
    """Child entry: runs one leg, prints its result dict as a JSON line."""
    _leg_backend(pin_cpu)
    import jax

    device = jax.devices()[0]
    log(f"[{leg}] device: {device.platform} ({device})")
    # Measurement-regime label (VERDICT r04 #7): in-bench legs share the
    # box with sibling legs' host baselines and caches and measure 4-15%
    # below solo runs; the number should say which regime produced it.
    out = {
        "device": device.platform,
        "run_mode": "in_bench" if "--in-bench" in sys.argv else "solo",
    }
    # Telemetry trace sink (--trace-out): every wave/drain span this leg's
    # checker emits streams to the JSONL file; the path rides the leg
    # result so the bench JSON says where the trace landed
    # (scripts/trace_summary.py renders it; chrome_trace_from_jsonl
    # exports Perfetto-loadable JSON).
    trace_path = _parse_trace_out()
    if trace_path is not None:
        from stateright_tpu.telemetry import get_tracer

        get_tracer().add_sink(trace_path)
        out["trace_path"] = trace_path

    specs = _leg_specs()
    if leg not in specs:
        raise ValueError(f"unknown leg {leg!r} (have: {sorted(specs)})")
    spec = specs[leg]
    spec["spawn"]["wave_dedup"] = _dedup_for(spec, device.platform)
    out["wave_dedup"] = spec["spawn"]["wave_dedup"]
    # Out-of-core mode (BENCH_r06 trajectory): ``--hbm-budget-mib N``
    # runs every leg with the tiered visited store so the spill/merge
    # overhead is quantifiable against the unbounded r05 numbers.
    budget = _parse_float_flag("--hbm-budget-mib")
    host_budget = _parse_float_flag("--host-budget-mib")
    if budget is not None:
        spec["spawn"]["hbm_budget_mib"] = budget
        if host_budget is not None:
            spec["spawn"]["host_budget_mib"] = host_budget
            spec["spawn"]["spill_dir"] = os.path.join(
                RUNTIME_DIR, f"spill_{leg}"
            )
        out["hbm_budget_mib"] = budget
    # Wave-timeline attribution (--attribution): fences each wave and
    # classifies wall into phases (telemetry/attribution.py). Opt-in:
    # the fences serialize dispatch, so the timed rate measures the
    # attributed regime — the per-leg record says so.
    if "--attribution" in sys.argv:
        spec["spawn"]["attribution"] = True
        out["attribution_enabled"] = True
    # Async pipelined wave engine (--async-pipeline): wave N's host-tier
    # probe/evict/checkpoint overlap wave N+1's device dispatch on a
    # host worker thread. Results bit-identical; the per-leg attribution
    # record gains the overlapped ledger. See bench.py --async-ab for
    # the dedicated on/off comparison leg.
    if "--async-pipeline" in sys.argv:
        spec["spawn"]["async_pipeline"] = True
        out["async_pipeline"] = True
    # State-space cartography (--coverage): the in-wave coverage
    # reductions (telemetry/coverage.py) ride the run; the per-leg
    # record carries the full report (actions/properties/shape/vacuity).
    # Results stay bit-identical — only the extra per-wave vector pull
    # changes pacing.
    if "--coverage" in sys.argv:
        spec["spawn"]["coverage"] = True
        out["coverage_enabled"] = True
    if spec.get("host_baseline") and "--no-host-baseline" not in sys.argv:
        t0 = time.time()
        host = (
            spec["model"]()
            .checker()
            .target_state_count(HOST_CAP)
            .spawn_bfs()
            .join()
        )
        host_dt = time.time() - t0
        out["host_rate"] = host.unique_state_count() / host_dt
        log(
            f"[{leg}] host BfsChecker: {host.unique_state_count()} unique "
            f"in {host_dt:.2f}s = {out['host_rate']:,.0f}/s (capped)"
        )

    t0 = time.time()
    builder = spec["model"]().checker()
    builder = spec.get("builder", lambda b: b)(builder)
    # Live monitoring (--monitor-port): /metrics (Prometheus), /status
    # (JSON progress + ETA band), /events (SSE wave stream) served
    # concurrently with the check; the flight recorder rides along so a
    # SIGTERM'd (timed-out) leg leaves flight-<run_id>.json forensics,
    # and --stall-deadline-s arms the no-wave watchdog.
    # Created BEFORE spawn (the documented pattern): the worker thread
    # can finish waves of a short leg before a late-attached sink would
    # see them, skewing the wave/ETA counters.
    monitor = None
    monitor_port = _parse_float_flag("--monitor-port")
    stall_deadline_s = _parse_float_flag("--stall-deadline-s")
    if monitor_port is not None:
        from stateright_tpu.telemetry.server import MonitorServer

        os.makedirs(RUNTIME_DIR, exist_ok=True)
        monitor = MonitorServer(
            port=int(monitor_port),
            run_id=f"{leg}-{os.getpid()}",
            stall_deadline_s=stall_deadline_s,
            flight_recorder=True,
            flight_dir=RUNTIME_DIR,
        )
        out["monitor_port"] = monitor.port
        log(f"[{leg}] monitor serving at {monitor.url}")
    checker = None
    try:
        # Spawn inside the try: a spawn-time failure (bad knob, device
        # init) must still flight-dump and close the monitor below, not
        # leak its server thread / watchdog / tracer sink.
        checker = builder.spawn_tpu_bfs(**spec["spawn"])
        if monitor is not None:
            monitor.attach(checker)
        checker.join()
        dt = time.time() - t0
    finally:
        if monitor is not None:
            # A worker error propagates AFTER this finally uninstalls the
            # excepthook — and a main-thread exception reaches the hook
            # only after monitor.close() has restored the original one —
            # so the crash dump must happen here or a crashed monitored
            # leg would leave no flight file at all.
            werr = checker.worker_error() if checker is not None else None
            exc = None
            if werr is not None:
                exc = ("worker_error", (type(werr), werr, werr.__traceback__))
            else:
                inflight = sys.exc_info()
                if inflight[0] is not None:
                    exc = ("exception", inflight)
            if exc is not None and monitor.flight is not None:
                try:
                    monitor.flight.dump(exc[0], exc=exc[1])
                except Exception as dump_err:  # noqa: BLE001
                    # A failed dump (disk full — plausibly what killed
                    # the run) must not supersede the real error.
                    log(f"[{leg}] flight dump failed: {dump_err!r}")
            monitor.close()
    err = checker.worker_error()
    if err is not None:
        raise err
    expected = spec.get("expected")
    if expected is not None and checker.unique_state_count() != expected:
        raise AssertionError(
            f"{leg} count mismatch: "
            f"{checker.unique_state_count()} != {expected}"
        )
    if spec.get("check_properties", True):
        checker.assert_properties()
    warmup = checker.warmup_seconds or 0.0
    unique = checker.unique_state_count()
    out.update(
        unique=unique,
        wall_s=dt,
        warmup_s=warmup,
        rate=unique / max(dt - warmup, 1e-9),
    )
    if spec.get("advisory"):
        # Sub-second steady windows are not rate claims (VERDICT r04 #6).
        out["advisory"] = True
    # expand_fps as a measured policy: one calibration wave per pipeline
    # AFTER the timed run (its jits must not pollute the leg timing) but
    # BEFORE the telemetry snapshot (the mismatch counter rides it).
    # --no-calibrate skips it.
    if "--no-calibrate" not in sys.argv:
        out["pipeline_choice"] = _calibrate_pipeline(leg, spec, checker)
    # Leg-level observability: the wave/occupancy counters the run left in
    # the registry (scalar instruments only — histograms ride the trace).
    snap = checker.metrics().snapshot()
    out["telemetry"] = {
        k: v for k, v in snap.items() if not isinstance(v, dict)
    }
    # Occupancy-adaptive dispatch record (BENCH_r06+ trajectory): the
    # per-rung dispatch histogram, the run's last frontier fill /
    # compaction ratio, and whether buffer donation was active.
    out["bucket_dispatch"] = {
        k.rsplit(".", 1)[1]: v
        for k, v in snap.items()
        if ".bucket_dispatch." in k
    }
    out["frontier_fill"] = snap.get("tpu_bfs.frontier_fill")
    out["compaction_ratio"] = snap.get("tpu_bfs.compaction_ratio")
    out["donation"] = bool(getattr(checker, "donation_enabled", False))
    # Out-of-core record: spill/merge counters, peak per-tier occupancy,
    # and the effective compression ratio — zeros/absent on unbounded
    # runs, the r06-vs-r05 overhead evidence on budgeted ones.
    tier = getattr(checker, "_tier", None)
    if tier is not None:
        out["storage"] = tier.instruments.bench_stats()
    # Attribution record: the phase ledger + overlap headroom (the
    # go/no-go number for the async pipelined wave engine).
    attribution = checker.attribution_report()
    if attribution is not None:
        out["attribution"] = attribution
    # Coverage record: the state-space cartography + vacuity verdict
    # (scripts/coverage_report.py renders the same data from the trace).
    cov = checker.coverage_report()
    if cov is not None:
        out["coverage"] = cov
    want = spec.get("expect_discovery")
    if want is not None:
        path = checker.discoveries().get(want)
        if path is None:
            raise AssertionError(f"{leg}: no discovery for {want!r}")
        # Time-to-counterexample net of compile warmup: the BASELINE.md
        # metric for the falsifiable-liveness leg.
        out["ttc_s"] = max(dt - warmup, 0.0)
        out["counterexample_len"] = len(path.into_actions())
    log(
        f"[{leg}] {out.get('unique')} unique in {out.get('wall_s'):.2f}s "
        f"wall ({out.get('warmup_s'):.2f}s warmup) = "
        f"{out.get('rate'):,.0f}/s steady-state"
        + (f"; ttc={out['ttc_s']:.2f}s" if "ttc_s" in out else "")
    )
    print(json.dumps(out))


# Configured-vs-measured pipeline mismatch threshold: the configured
# pipeline must be >10% slower than the measured winner before the bench
# flags it — sub-10% deltas on this shared box are noise, while the
# regressions that motivated the policy (VERDICT r05: abd3o 2.5x, scr4
# 26%) clear it comfortably.
PIPELINE_MISMATCH_FACTOR = 1.10


def evaluate_pipeline_choice(
    configured, fps_ms, materialize_ms, factor=PIPELINE_MISMATCH_FACTOR
):
    """True when the CONFIGURED expansion pipeline measured more than
    ``factor``× slower than the other one — the silent-regression
    condition the calibration wave exists to surface. Pure so the gate
    is unit-testable without a jax run."""
    if configured not in ("fps", "materialize"):
        return False
    if not fps_ms or not materialize_ms:
        return False
    mine = fps_ms if configured == "fps" else materialize_ms
    other = materialize_ms if configured == "fps" else fps_ms
    return mine > factor * other


def _calibrate_pipeline(leg, spec, checker):
    """Times one calibration wave per expansion pipeline for this leg's
    model (breakdown.measure_pipeline_choice), records the configured
    pipeline next to both timings, and warns (stderr +
    ``bench.pipeline_mismatch`` counter — it rides the leg's telemetry
    snapshot) when the configured one is measurably slower. Never fatal:
    a failed calibration returns its error instead of killing the leg."""
    configured = getattr(checker, "pipeline", None)
    try:
        from stateright_tpu.checker.breakdown import measure_pipeline_choice

        res = measure_pipeline_choice(
            spec["model"](),
            frontier_capacity=min(
                spec["spawn"].get("frontier_capacity", 1 << 10), 1 << 10
            ),
            table_capacity=min(
                spec["spawn"].get("table_capacity", 1 << 16), 1 << 18
            ),
            wave_dedup=spec["spawn"].get("wave_dedup"),
        )
    except Exception as e:  # noqa: BLE001 - calibration is advisory
        return {"configured": configured, "error": repr(e)}
    res["configured"] = configured
    if res.get("supported"):
        mismatch = evaluate_pipeline_choice(
            configured, res.get("fps_ms"), res.get("materialize_ms")
        )
        res["mismatch"] = mismatch
        if mismatch:
            from stateright_tpu.telemetry import metrics_registry

            metrics_registry().counter("bench.pipeline_mismatch").inc()
            log(
                f"[{leg}] WARNING: configured pipeline {configured!r} "
                f"measured slower than {res['measured_faster']!r} "
                f"(fps {res['fps_ms']}ms vs materialize "
                f"{res['materialize_ms']}ms) — pass "
                f"expand_fps={configured != 'fps'} to spawn_tpu_bfs or "
                "update the leg spec"
            )
    return res


def _dedup_for(spec, platform: str) -> str:
    """Wave-dedup resolution shared by the timed legs and the breakdown
    attribution (which must describe the same pipeline): CLI ``--dedup``
    override > an explicit value in the leg spec > the library's shared
    backend default (``checker.tpu.default_wave_dedup`` — the one place
    the policy lives)."""
    override = _parse_dedup_flag()
    if override is not None:
        return override
    explicit = spec["spawn"].get("wave_dedup")
    if explicit is not None:
        return explicit
    from stateright_tpu.checker.tpu import default_wave_dedup

    return default_wave_dedup(platform)


def _run_breakdown(leg: str, pin_cpu: bool):
    """Child entry: per-wave stage cost attribution for one leg's model
    (VERDICT r03 #1b — the judgeability half of the TPU datapoint). Runs
    AFTER the timed legs so its stage-split jits never pollute leg
    timings; prints one JSON line."""
    _leg_backend(pin_cpu)
    import jax

    from stateright_tpu.checker.breakdown import measure_wave_breakdown

    spec = _leg_specs()[leg]
    # Attribute the SAME dedup pipeline the timed legs run on this
    # backend — stage numbers for a pipeline the rate never executed
    # would mislead the next round.
    dedup = _dedup_for(spec, jax.devices()[0].platform)
    out = measure_wave_breakdown(
        spec["model"](),
        frontier_capacity=spec["spawn"].get("frontier_capacity", 1 << 11),
        table_capacity=spec["spawn"].get("table_capacity", 1 << 20),
        wave_dedup=dedup,
    )
    print(json.dumps(out))


def _parse_trace_out():
    """``--trace-out PATH`` (both forms): attach the telemetry JSONL sink.
    In the parent PATH is a base; each leg child gets ``PATH.<leg>.jsonl``
    so per-leg traces never interleave across subprocesses."""
    for i, arg in enumerate(sys.argv):
        if arg == "--trace-out":
            if i + 1 >= len(sys.argv) or sys.argv[i + 1].startswith("--"):
                raise SystemExit("--trace-out requires a path")
            return sys.argv[i + 1]
        if arg.startswith("--trace-out="):
            return arg.split("=", 1)[1]
    return None


def _trace_out_args(leg: str):
    base = _parse_trace_out()
    if base is None:
        return ()
    return ("--trace-out", f"{base}.{leg}.jsonl")


def _parse_float_flag(flag: str):
    """``--flag N`` / ``--flag=N`` parsed as float (explicit error on a
    missing or non-numeric value), or None when absent."""
    for i, arg in enumerate(sys.argv):
        value = None
        if arg == flag:
            if i + 1 >= len(sys.argv) or sys.argv[i + 1].startswith("--"):
                raise SystemExit(f"{flag} requires a numeric value")
            value = sys.argv[i + 1]
        elif arg.startswith(flag + "="):
            value = arg.split("=", 1)[1]
        if value is not None:
            try:
                return float(value)
            except ValueError:
                raise SystemExit(f"{flag} requires a numeric value")
    return None


def _budget_override_args():
    """Parent-level out-of-core and monitor flags must reach every leg
    child (the same silently-no-op hazard ``--dedup`` had). The monitor
    port is shared safely: legs run sequentially, one child at a time."""
    args = []
    for flag in (
        "--hbm-budget-mib",
        "--host-budget-mib",
        "--monitor-port",
        "--stall-deadline-s",
    ):
        value = _parse_float_flag(flag)
        if value is not None:
            args += [flag, str(value)]
    # Boolean flags forwarded verbatim (same silently-no-op hazard).
    for flag in (
        "--attribution", "--coverage", "--no-calibrate",
        "--async-pipeline",
    ):
        if flag in sys.argv:
            args.append(flag)
    return tuple(args)


def _parse_dedup_flag():
    """The one place ``--dedup`` is parsed (both forms, explicit error on
    a missing value — a trailing ``--dedup`` must not IndexError the
    whole bench and ``--dedup=X`` must not silently no-op)."""
    for i, arg in enumerate(sys.argv):
        if arg == "--dedup":
            if i + 1 >= len(sys.argv) or sys.argv[i + 1].startswith("--"):
                raise SystemExit("--dedup requires a value (sort|scatter)")
            return sys.argv[i + 1]
        if arg.startswith("--dedup="):
            return arg.split("=", 1)[1]
    return None


def _dedup_override_args():
    """A parent-level ``--dedup X`` must reach every child (legs and
    breakdowns) or the override silently no-ops while appearing accepted
    (advisor finding, round 4)."""
    value = _parse_dedup_flag()
    return ("--dedup", value) if value is not None else ()


def _child_json(argv, timeout_s: float, label: str):
    """Runs one bench child; returns the JSON dict from its last stdout
    line, or None (timeout/crash/garbage). The shared leg-child protocol:
    stderr inherits the parent's stream so diagnostics (and OOM reports)
    surface live instead of dying with the child."""
    try:
        r = subprocess.run(argv, timeout=timeout_s, stdout=subprocess.PIPE)
    except subprocess.TimeoutExpired:
        log(f"[{label}] timed out after {timeout_s}s")
        return None
    lines = r.stdout.decode().strip().splitlines()
    if r.returncode == 0 and lines:
        try:
            return json.loads(lines[-1])
        except json.JSONDecodeError:
            pass
    log(f"[{label}] failed (rc={r.returncode})")
    return None


def _leg_subprocess(leg: str, pin_cpu: bool, extra=()):
    """Runs one leg in a child; returns its result dict or None."""
    argv = [
        sys.executable, __file__, "--leg", leg, "--in-bench",
        *_dedup_override_args(), *_budget_override_args(),
        *_trace_out_args(leg),
        *extra,
    ]
    # The CPU backend is slower than the chip; its test runs get headroom.
    timeout_s = LEG_TIMEOUT_S[leg] * (3 if pin_cpu else 1)
    if pin_cpu:
        argv.append("--cpu")
    return _child_json(argv, timeout_s, leg)


def _validate_flag_combos():
    """Fail dependent-flag combos up front, before any work: in
    full-bench mode a bad combo would otherwise be forwarded to every
    leg child, each failing on its own (same must-not-no-op rule as
    ``--dedup``: a flag the user asked for that silently never arms is
    worse than an error)."""
    for flag, needs in (
        ("--stall-deadline-s", "--monitor-port"),
        ("--host-budget-mib", "--hbm-budget-mib"),
    ):
        if (
            _parse_float_flag(flag) is not None
            and _parse_float_flag(needs) is None
        ):
            raise SystemExit(f"{flag} requires {needs}")


SERVICE_LEG_TIMEOUT_S = 1500


def _pct(values, p):
    """Linear-interpolated percentile (None-safe: None values dropped;
    empty -> None). Stdlib-only so the record never depends on numpy."""
    vals = sorted(v for v in values if v is not None)
    if not vals:
        return None
    if len(vals) == 1:
        return vals[0]
    pos = (p / 100.0) * (len(vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def _run_service_leg(pin_cpu: bool, packed: bool = False):
    """Child entry: the checking-as-a-service latency leg (BENCH_r10+;
    ``packed=True`` is the BENCH_r12+ tenant-packed variant).

    Three phases on the 2pc-N workload (its ``sometimes`` agreement
    properties make time-to-first-violation/witness a real latency
    signal while the ``always`` property keeps the run exhaustive):

    1. a batch ``spawn_tpu_bfs`` reference run (the throughput yardstick),
    2. one job through ``CheckService`` (service overhead must stay
       within 10% of the batch path),
    3. >= 4 concurrent jobs. Time-sliced mode (``--service``): a
       sub-second quantum; per-job submit->first-discovery latency
       (p50/p99), aggregate states/s, preemption counts, and the
       shared-AOT-cache evidence (jobs with zero compile phases in
       their attribution ledgers). Packed mode (``--service-packed``,
       default 8 jobs): the same fleet co-scheduled into shared waves —
       the ROADMAP gate is aggregate states/s within 15% of the
       single-job rate with ZERO preemptions, plus the lane-occupancy
       evidence (``pack.lanes_live / pack.lanes_dispatched``).
    """
    _leg_backend(pin_cpu)
    import jax

    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.service import CheckService

    device = jax.devices()[0]
    log(f"[service] device: {device.platform} ({device})")
    jobs_n = int(
        _parse_float_flag("--service-jobs") or (8 if packed else 4)
    )
    quantum = _parse_float_flag("--service-quantum") or 0.5
    rm = int(_parse_float_flag("--service-rm") or 5)
    spawn = dict(frontier_capacity=1 << 10, table_capacity=1 << 15)
    out = {
        "device": device.platform,
        "model": f"2pc-{rm}",
        "jobs": jobs_n,
        "quantum_s": quantum,
        "packed": packed,
    }

    # 1. Batch reference (the normal spawn path, identical capacities).
    t0 = time.time()
    batch = TwoPhaseSys(rm).checker().spawn_tpu_bfs(**spawn).join()
    wall = time.time() - t0
    warm = batch.warmup_seconds or 0.0
    expected = batch.unique_state_count()
    out["expected_unique"] = expected
    out["batch_rate"] = expected / max(wall - warm, 1e-9)
    log(f"[service] batch: {expected} unique, {out['batch_rate']:,.0f}/s")

    # No service_dir here: the concurrent phase measures time-sliced
    # throughput with purely in-memory AOT sharing (the r10-comparable
    # metric), and a persistent dir would let later jobs reseed from
    # the single-job phase's finished run. Disk-plane accounting —
    # per-job aot_cache.* counters — lives in the warm-start sub-leg
    # below; per_job rows here record "aot": null, which readers take
    # as "no disk store attached".
    svc = CheckService(
        quantum_s=quantum, default_spawn=spawn,
        packing=packed, max_pack_tenants=max(8, jobs_n),
    )
    try:
        # 2. Single job: no contention, so no preemption — the measured
        # delta vs batch is pure service overhead (scheduler polling).
        h = svc.submit(model_name="2pc", model_args={"rm_count": rm})
        res = h.result(timeout=SERVICE_LEG_TIMEOUT_S / 2)
        if res["unique"] != expected:
            raise AssertionError(
                f"service single-job count mismatch: "
                f"{res['unique']} != {expected}"
            )
        out["single_job_rate"] = res["rate"]
        out["service_overhead_pct"] = 100.0 * (
            1.0 - res["rate"] / out["batch_rate"]
        )
        log(
            f"[service] single job: {res['rate']:,.0f}/s "
            f"({out['service_overhead_pct']:+.1f}% vs batch)"
        )

        # 3. Concurrent load. Time-sliced mode: attribution per job so
        # the ledger proves the AOT-cache sharing (compile-free jobs)
        # and shows preempt overhead as checkpoint phases. Packed mode:
        # no spawn overrides (they would disqualify packing) — the
        # engine's lane counters carry the occupancy evidence instead,
        # isolated in a freshly-reset default registry.
        if packed:
            from stateright_tpu.telemetry import metrics_registry

            metrics_registry().reset()
        t0 = time.time()
        handles = [
            svc.submit(
                model_name="2pc",
                model_args={"rm_count": rm},
                spawn=None if packed else {"attribution": True},
                tenant=f"tenant-{i}",
            )
            for i in range(jobs_n)
        ]
        for h in handles:
            h.result(timeout=SERVICE_LEG_TIMEOUT_S)
        wall = time.time() - t0
        per_job, ttfvs, zero_compile, total_unique = [], [], 0, 0
        for h in handles:
            st = h.status()
            r = st["result"]
            if r["unique"] != expected:
                raise AssertionError(
                    f"{st['job_id']} count mismatch: "
                    f"{r['unique']} != {expected}"
                )
            total_unique += r["unique"]
            lat = st["latency"]
            ttfvs.append(lat["ttfv_s"])
            attr = r.get("attribution") or {}
            # compile_s_total spans every incarnation of a preempted job
            # (the per-run registry accumulates across resumes); the
            # final-ledger sum is the fallback for old records. Packed
            # jobs have no per-job ledger — their honest compile figure
            # is the engine compile time accrued while resident
            # (warmup_s), zero when the pack executables were warm.
            compile_s = r.get("compile_s_total")
            if compile_s is None and packed:
                compile_s = r.get("warmup_s", 0.0)
            if compile_s is None:
                compile_s = attr.get("phases_s", {}).get("compile", 0.0)
                compile_s += (attr.get("outside_wave_s") or {}).get(
                    "compile", 0.0
                )
            if compile_s == 0.0:
                zero_compile += 1
            per_job.append(
                {
                    "job_id": st["job_id"],
                    "tenant": st["tenant"],
                    "unique": r["unique"],
                    "ttfv_s": lat["ttfv_s"],
                    "wall_s": lat["wall_s"],
                    "active_s": lat["active_s"],
                    "queued_s": lat["queued_s"],
                    "preempts": st["preempts"],
                    "slices": st["slices"],
                    "packed": st.get("packed", False),
                    # Fault-tolerance evidence (PR 13): a healthy bench
                    # run shows zeros; a chaos leg shows the recovery.
                    # (A quarantined job would have raised at result()
                    # above, so this is False here by construction —
                    # recorded anyway so report readers key on a real
                    # field.)
                    "retries": st.get("retries", 0),
                    "faults": len(st.get("faults") or []),
                    "quarantined": st.get("state") == "quarantined",
                    # Liveness honesty (ISSUE 14): how `eventually`
                    # verdicts were produced, and downgrades.
                    "liveness_mode": st.get("liveness_mode"),
                    "liveness_reason": st.get("liveness_reason"),
                    # Verification mode (ISSUE 15): exhaustive | swarm.
                    "mode": st.get("mode", "exhaustive"),
                    "rate": r["rate"],
                    "compile_s": compile_s,
                    # Warm-start evidence (ISSUE 19): per-job disk-AOT
                    # counters (absent without a service_dir) and the
                    # seeded flag — report readers distinguish disk hits
                    # from in-memory hits by these.
                    "warm_start": bool(st.get("warm_start")),
                    "aot": r.get("aot"),
                }
            )
        out["aggregate_states_per_s"] = total_unique / wall
        out["service_rate"] = out["aggregate_states_per_s"]
        out["concurrent_wall_s"] = wall
        out["p50_ttfv_s"] = _pct(ttfvs, 50)
        out["p99_ttfv_s"] = _pct(ttfvs, 99)
        out["preempts_total"] = sum(j["preempts"] for j in per_job)
        out["retries_total"] = sum(j["retries"] for j in per_job)
        out["faults_total"] = sum(j["faults"] for j in per_job)
        out["jobs_zero_compile"] = zero_compile
        out["per_job"] = per_job
        # Steady-state aggregate (compile excluded — the same window
        # single_job_rate is measured over, so the two are comparable;
        # the wall-clock aggregate above stays the conservative
        # headline). Pack compiles are one shared wall for every
        # member, so the fleet's compile time is the per-job max.
        compile_wall = max(
            (j["compile_s"] for j in per_job), default=0.0
        )
        out["aggregate_steady_states_per_s"] = total_unique / max(
            wall - compile_wall, 1e-9
        )
        out["aggregate_vs_single_pct"] = 100.0 * (
            out["aggregate_steady_states_per_s"] / out["single_job_rate"]
            - 1.0
        )
        if packed:
            # Lane-occupancy evidence from the engine's counters (the
            # registry was reset just before the fleet was submitted,
            # so these cover exactly the packed phase).
            snap = metrics_registry().snapshot()
            live = snap.get("pack.lanes_live", 0)
            dispatched = snap.get("pack.lanes_dispatched", 0)
            out["pack"] = {
                "waves": snap.get("pack.waves", 0),
                "lanes_live": live,
                "lanes_dispatched": dispatched,
                "lane_fill": (live / dispatched) if dispatched else None,
                "packed_jobs": sum(
                    1 for j in per_job if j.get("packed")
                ),
            }
        def fmt_s(v):
            # ttfv percentiles are None when no job ever discovered a
            # property — the log line must not crash a leg whose
            # throughput/preemption data is complete.
            return "n/a" if v is None else f"{v:.2f}s"

        log(
            f"[service] {jobs_n} concurrent: "
            f"{out['aggregate_states_per_s']:,.0f}/s aggregate, "
            f"ttfv p50={fmt_s(out['p50_ttfv_s'])} "
            f"p99={fmt_s(out['p99_ttfv_s'])}, "
            f"{out['preempts_total']} preempts, "
            f"{zero_compile}/{jobs_n} jobs compile-free"
        )
        # The service's rolling SLO ledger (service/slo.py): per-mode
        # ttfv/verdict percentiles + queue/compile/explore decomposition
        # over everything this leg served — service_report.py renders it
        # as the SLO table.
        out["slo"] = svc.slo.snapshot()
    finally:
        svc.close()

    # 4. Warm-start sub-leg (ISSUE 19): the same shape served from a
    # persistent service_dir across an emulated process restart, with
    # the two planes measured SEPARATELY. The executable plane uses
    # ``target_max_depth`` jobs (a target beyond 2pc's true depth, so
    # the space is explored in full but the job stays out of the seed
    # plane): cold-cold compiles and writes the disk AOT store, a
    # resident resubmit gives the warm ttfv, and the post-restart run
    # must be served compile-free off disk — that cold-vs-warm pair is
    # the headline ``cold_over_warm_pct``. The seed plane uses plain
    # full runs: the first writes the finished-run seed, the
    # post-restart resubmit must reseed (O(verify), near-zero explore).
    # True cross-process isolation is covered by tests/test_warmstart.py
    # and the tier-1 smoke; the bench emulates the restart in-process
    # (clear_shared_aot_caches) so one child carries the whole record.
    # CPU-advisory like every latency number here.
    if not packed:
        import shutil
        import tempfile

        from stateright_tpu.checker.programs import clear_shared_aot_caches

        wdir = tempfile.mkdtemp(prefix="bench-warmstart-")

        # The sub-leg must measure THIS repo's disk-AOT plane, so jax's
        # own persistent compilation cache is off for its window: an
        # executable XLA served from a warm cache would turn the
        # cold-process run into a cache load — measuring the cache, not
        # the store.
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc,
        )

        def _xla_cache(enabled):
            jax.config.update("jax_enable_compilation_cache", enabled)
            _cc.reset_cache()

        xla_prev = jax.config.jax_enable_compilation_cache
        clear_shared_aot_caches()  # drop exes compiled under the old cache
        _xla_cache(False)

        def _one(svc_ws, target=False):
            h = svc_ws.submit(
                model_name="2pc", model_args={"rm_count": rm},
                options={"target_max_depth": 64} if target else None,
            )
            r = h.result(timeout=SERVICE_LEG_TIMEOUT_S / 2)
            st = h.status()
            lat = st["latency"]
            return {
                "ttfv_s": lat["ttfv_s"],
                "wall_s": lat["wall_s"],
                "warmup_s": r["warmup_s"],
                "warm_start": bool(st.get("warm_start")),
                "aot": r.get("aot"),
                "unique": r["unique"],
            }

        try:
            svc_ws = CheckService(
                quantum_s=quantum, default_spawn=spawn,
                packing=False, service_dir=wdir,
            )
            reps = 3  # medians: single-shot ttfv is noise-dominated
            try:
                first = _one(svc_ws, target=True)   # cold-cold: compiles
                warm_rows = [
                    _one(svc_ws, target=True) for _ in range(reps)
                ]  # resident warm resubmits
                _one(svc_ws)  # plain full run: writes the seed
            finally:
                svc_ws.close()
            cold_rows, pool_waits = [], []
            pool_aot = {}
            reseed_row = None
            for i in range(reps):
                clear_shared_aot_caches()  # emulate a process restart
                # The intended cold-process flow: the warm pool
                # pre-loads this shape's executables at service start
                # (from the disk AOT store when present — no compile),
                # so the first real job pays neither compile nor
                # deserialize. The wait-to-ready is recorded.
                t_pool = time.time()
                svc_ws = CheckService(
                    quantum_s=quantum, default_spawn=spawn,
                    packing=False, service_dir=wdir,
                    warm_pool=[("2pc", {"rm_count": rm})],
                )
                try:
                    deadline = time.time() + 120.0
                    while time.time() < deadline and any(
                        e["state"] == "pending"
                        for e in svc_ws.warm_pool_status.values()
                    ):
                        time.sleep(0.05)
                    pool_waits.append(time.time() - t_pool)
                    if i == 0:
                        # Disk-plane evidence lives in the POOL job's
                        # registry (it did the load); the measured job
                        # then finds everything warm in memory.
                        from stateright_tpu.telemetry import (
                            metrics_registry as _mreg,
                        )

                        pool_jid = next(
                            (e.get("job_id")
                             for e in svc_ws.warm_pool_status.values()),
                            None,
                        )
                        pool_aot = {
                            k: v
                            for k, v in (_mreg(pool_jid).snapshot()
                                         if pool_jid else {}).items()
                            if k.startswith("aot_cache.")
                        }
                    cold_rows.append(_one(svc_ws, target=True))
                    if i == reps - 1:
                        reseed_row = _one(svc_ws)  # seeded resubmission
                finally:
                    svc_ws.close()
            warm_row, cold_row = warm_rows[0], cold_rows[0]
            warm_ttfv = _pct(
                [r["ttfv_s"] or r["wall_s"] for r in warm_rows], 50
            )
            cold_ttfv = _pct(
                [r["ttfv_s"] or r["wall_s"] for r in cold_rows], 50
            )
            out["warmstart"] = {
                "process_emulated": True,
                "first_ttfv_s": first["ttfv_s"] or first["wall_s"],
                "first_warmup_s": first["warmup_s"],
                "warm_ttfv_s": warm_ttfv,
                "cold_ttfv_s": cold_ttfv,
                "cold_over_warm_pct": (
                    100.0 * (cold_ttfv / warm_ttfv - 1.0)
                    if warm_ttfv and cold_ttfv is not None
                    else None
                ),
                "cold_warmup_s": cold_row["warmup_s"],
                "cold_pool_wait_s": _pct(pool_waits, 50),
                "cold_pool_aot": pool_aot,
                "cold_aot": cold_row["aot"],
                # Seed plane: the post-restart plain resubmit.
                "seeded": reseed_row["warm_start"],
                "seeded_ttfv_s": (
                    reseed_row["ttfv_s"] or reseed_row["wall_s"]
                ),
                "cpu_advisory": device.platform == "cpu",
            }
            log(
                f"[service] warm-start: first={out['warmstart']['first_ttfv_s']:.2f}s "
                f"warm={warm_ttfv:.3f}s cold-process={cold_ttfv:.3f}s "
                f"({out['warmstart']['cold_over_warm_pct']:+.1f}%, pool "
                f"disk hits {pool_aot.get('aot_cache.disk_hit', 0)}, "
                f"pool wait {out['warmstart']['cold_pool_wait_s']:.2f}s, "
                f"cold warmup={cold_row['warmup_s']:.2f}s); reseed "
                f"{out['warmstart']['seeded_ttfv_s']:.3f}s "
                f"(seeded={reseed_row['warm_start']})"
            )
        finally:
            _xla_cache(xla_prev)
            shutil.rmtree(wdir, ignore_errors=True)
    print(json.dumps(out))


def _run_slo_leg(pin_cpu: bool):
    """Child entry: the end-to-end SLO attribution leg (BENCH_r18).

    Drives a job fleet through every verification mode and records the
    service's rolling SLO ledger (``service/slo.py``): per-mode p50/p99
    ttfv + verdict latency, the queue/compile/explore ttfv
    decomposition (clamped to partition ttfv exactly — the record
    asserts the partition holds within 5%), and burn rates against the
    leg's targets.

    Two service phases on 2pc-N (its ``sometimes`` properties make ttfv
    a real signal):

    1. unpacked service: ``jobs_n`` exhaustive then ``jobs_n`` swarm
       jobs — the ``exhaustive`` / ``swarm`` mode rows;
    2. tenant-packed service: a plain fleet co-scheduled into shared
       waves — the ``packed`` mode row (a packed slice's mode wins over
       its base mode in the ledger).
    """
    _leg_backend(pin_cpu)
    import jax

    from stateright_tpu.service import CheckService

    device = jax.devices()[0]
    log(f"[slo] device: {device.platform} ({device})")
    rm = int(_parse_float_flag("--service-rm") or 4)
    jobs_n = int(_parse_float_flag("--service-jobs") or 3)
    spawn = dict(frontier_capacity=1 << 10, table_capacity=1 << 15)
    # Deliberately loose targets: a healthy bench leg should show burn
    # rates near zero — the gauges' existence is what the record
    # demonstrates, not a tuned objective.
    targets = {"ttfv_s": 120.0, "verdict_s": 600.0, "objective": 0.9}
    out = {
        "device": device.platform,
        "model": f"2pc-{rm}",
        "jobs_per_mode": jobs_n,
        "slo_targets": targets,
    }

    # Phase 1: unpacked — exhaustive and swarm rows. packing defaults
    # ON, and a packed slice's mode wins in the ledger, so it must be
    # forced off here or every row would land under "packed".
    svc = CheckService(
        default_spawn=spawn, packing=False, slo_targets=targets
    )
    try:
        for mode in ("exhaustive", "swarm"):
            # Swarm jobs need a stop bound at admission (a holding
            # property is never "discovered"); exhaustive jobs stop at
            # fixpoint on their own.
            options = (
                {"target_state_count": 10_000} if mode == "swarm" else {}
            )
            handles = [
                svc.submit(
                    model_name="2pc",
                    model_args={"rm_count": rm},
                    options=options,
                    mode=mode,
                    seed=i,
                )
                for i in range(jobs_n)
            ]
            for h in handles:
                h.result(timeout=SERVICE_LEG_TIMEOUT_S)
            log(f"[slo] {jobs_n} {mode} jobs served")
        snap_unpacked = svc.slo.snapshot()
    finally:
        svc.close()

    # Phase 2: packed — plain fleet, co-scheduled (spawn overrides
    # would disqualify packing, so none are passed).
    svc = CheckService(
        default_spawn=spawn, packing=True,
        max_pack_tenants=max(8, jobs_n), slo_targets=targets,
    )
    try:
        handles = [
            svc.submit(
                model_name="2pc",
                model_args={"rm_count": rm},
                tenant=f"tenant-{i}",
            )
            for i in range(max(2, jobs_n))
        ]
        for h in handles:
            h.result(timeout=SERVICE_LEG_TIMEOUT_S)
        log(f"[slo] {max(2, jobs_n)} packed jobs served")
        snap_packed = svc.slo.snapshot()
    finally:
        svc.close()

    # One merged snapshot: each mode row comes from the service that
    # actually served that mode (the two ledgers are disjoint by
    # construction — phase 1 never packs, phase 2 only packs).
    slo = dict(snap_unpacked)
    slo["modes"] = {
        m: (
            snap_packed["modes"][m]
            if snap_packed["modes"][m]["jobs"] > 0
            else snap_unpacked["modes"][m]
        )
        for m in snap_unpacked["modes"]
    }
    out["slo"] = slo

    # Acceptance evidence: the decomposition partitions ttfv within 5%
    # per mode (exactly, by construction — recorded so the check is a
    # number in the record, not a claim in a docstring).
    partitions = {}
    for mode, view in slo["modes"].items():
        last = (view.get("last") or {}).get("decomposition")
        if last:
            gap = abs(
                last["queue_s"] + last["compile_s"] + last["explore_s"]
                - last["ttfv_s"]
            )
            partitions[mode] = gap <= 0.05 * max(last["ttfv_s"], 1e-9)
    out["decomposition_partitions"] = partitions

    def fmt_s(v):
        return "n/a" if v is None else f"{v:.2f}s"

    for mode, view in slo["modes"].items():
        if view["jobs"]:
            log(
                f"[slo] {mode}: {view['jobs']} jobs, ttfv "
                f"p50={fmt_s(view['ttfv']['p50_s'])} "
                f"p99={fmt_s(view['ttfv']['p99_s'])}, verdict "
                f"p50={fmt_s(view['verdict']['p50_s'])}"
            )
    print(json.dumps(out))


def _main_slo():
    """Parent entry for ``bench.py --slo``: runs the SLO leg in a child
    (timeout isolation) and writes ``BENCH_r18.json`` (override with
    ``--slo-out PATH``), printing the same record as the one JSON
    line. Render with ``scripts/slo_report.py`` or compare the
    trajectory with ``scripts/bench_compare.py --slo``."""
    passthrough = []
    for flag in ("--service-jobs", "--service-rm"):
        value = _parse_float_flag(flag)
        if value is not None:
            passthrough += [flag, str(value)]

    def run(pin_cpu):
        argv = [sys.executable, __file__, "--slo-leg", *passthrough]
        if pin_cpu:
            argv.append("--cpu")
        return _child_json(
            argv, SERVICE_LEG_TIMEOUT_S * (3 if pin_cpu else 1), "slo"
        )

    rec = run(pin_cpu="--cpu" in sys.argv)
    if rec is None:
        print(
            json.dumps(
                {
                    "metric": "service SLO ttfv p50 (per-mode ledger)",
                    "value": 0,
                    "unit": "seconds",
                    "error": "slo leg failed",
                }
            )
        )
        sys.exit(1)
    packed_p50 = (
        rec["slo"]["modes"].get("packed", {}).get("ttfv", {}).get("p50_s")
    )
    record = {
        "metric": "service SLO ttfv p50 (packed mode, queue/compile/"
        "explore attributed)",
        "value": round(packed_p50, 3) if packed_p50 is not None else 0,
        "unit": "seconds",
        **rec,
    }
    out_path = None
    for i, arg in enumerate(sys.argv):
        if arg == "--slo-out" and i + 1 < len(sys.argv):
            out_path = sys.argv[i + 1]
        elif arg.startswith("--slo-out="):
            out_path = arg.split("=", 1)[1]
    if out_path is None:
        out_path = os.path.join(REPO_DIR, "BENCH_r18.json")
    with open(out_path, "w") as f:
        # One JSON line, like every BENCH_r* record (the line-oriented
        # readers — slo_report, bench_compare — scan for it).
        f.write(json.dumps(record) + "\n")
    log(f"[slo] record written to {out_path}")
    print(json.dumps(record))


CONFORMANCE_TIMEOUT_S = 1800


def _run_conformance_leg(pin_cpu: bool):
    """Child entry: the conformance-plane throughput legs (BENCH_r20).

    (a) **replay**: traces/sec through the vmapped trace replayer at
        batch sizes 1/64/1024 (one jitted ``vmap(lax.scan)`` dispatch
        per batch) — the batching win is the headline: a 1024-lane
        batch must amortize dispatch overhead that dominates at
        batch=1.
    (b) **audit**: histories/sec through the batched device
        linearizability tester at the same batch sizes.
    (c) **divergence-rate sweep**: replay throughput at 0/10/50%
        divergent lanes — the kernel is branchless (a diverged lane
        keeps riding the scan, masked), so throughput must be flat in
        the divergence rate; a slope would mean divergence handling
        re-introduced per-lane control flow.

    Warm convention: every shape dispatches twice, the first run pays
    the compile (recorded as *_cold_s), the second is the steady-state
    headline — the number a resident service's warm pool serves.
    """
    _leg_backend(pin_cpu)
    import jax

    import random as _random

    from stateright_tpu.conformance import (
        audit_batch,
        mutate_trace,
        random_history,
        random_walk_trace,
        replay_batch,
    )
    from stateright_tpu.service.zoo import aot_namespace, default_zoo

    device = jax.devices()[0]
    log(f"[conformance] device: {device.platform} ({device})")
    model_name = "increment_lock"
    model = default_zoo()[model_name]()
    ns = aot_namespace(model_name, {})
    rng = _random.Random(20)
    T = 16
    batches = (1, 64, 1024)

    # One pool of distinct seeded walks, replicated (fresh ids) up to
    # the largest batch: verdict work is per-lane, so replication keeps
    # generation cheap without making lanes degenerate.
    walk_pool = [
        random_walk_trace(
            model, rng, T, rec_id=f"w{i}", model_name=model_name
        )
        for i in range(32)
    ]
    divergent_pool = []
    for rec in walk_pool:
        mut = mutate_trace(model, rng, rec)
        if mut is not None:
            divergent_pool.append(mut)
    assert divergent_pool, "no mutation sites in the walk pool"

    def trace_batch(n, divergent_fraction=0.0):
        out = []
        n_div = int(round(n * divergent_fraction))
        for i in range(n):
            src = (
                divergent_pool[i % len(divergent_pool)]
                if i < n_div else walk_pool[i % len(walk_pool)]
            )
            out.append(dict(src, id=f"{src['id']}-{i}"))
        return out

    def time_replay(recs, lanes):
        def once():
            t0 = time.perf_counter()
            verdicts = replay_batch(recs, model, ns, T, lanes=lanes)
            return verdicts, time.perf_counter() - t0

        _v, cold = once()
        verdicts, warm = once()
        return verdicts, warm, cold

    out = {
        "device": device.platform,
        "model": model_name,
        "trace_steps": T,
        "replay": {},
        "audit": {},
        "divergence_sweep": {},
    }

    # (a) replay throughput vs batch size.
    for n in batches:
        recs = trace_batch(n)
        verdicts, warm, cold = time_replay(recs, lanes=n)
        assert all(v["conforms"] for v in verdicts)
        rate = n / max(warm, 1e-9)
        out["replay"][str(n)] = {
            "traces_per_s": rate, "warm_s": warm, "cold_s": cold,
        }
        log(
            f"[conformance] replay batch={n}: {rate:,.0f} traces/s "
            f"(warm {warm * 1e3:.1f}ms, cold {cold:.2f}s)"
        )
    b1 = out["replay"]["1"]["traces_per_s"]
    bmax = out["replay"][str(batches[-1])]["traces_per_s"]
    out["replay_batch_amortization"] = bmax / max(b1, 1e-9)

    # (b) audit throughput vs batch size (one shape bucket: the
    # register C=2/O=2 linearizability grid).
    hist_pool = [
        random_history(
            rng, spec="register", semantics="linearizability",
            threads=2, ops_per_thread=2,
            mode=("clean", "random")[i % 2], rec_id=f"h{i}",
        )
        for i in range(64)
    ]
    # Replication must preserve the bucket: drop the occasional
    # off-shape history (a tail op left in flight can reduce O).
    from stateright_tpu.conformance import bucket_key

    key0 = bucket_key(hist_pool[0])
    hist_pool = [h for h in hist_pool if bucket_key(h) == key0]
    for n in batches:
        recs = [
            dict(hist_pool[i % len(hist_pool)], id=f"h{i}-{n}")
            for i in range(n)
        ]

        def once():
            t0 = time.perf_counter()
            verdicts = audit_batch(recs)
            return verdicts, time.perf_counter() - t0

        _v, cold = once()
        verdicts, warm = once()
        assert all("refused" not in v for v in verdicts)
        rate = n / max(warm, 1e-9)
        out["audit"][str(n)] = {
            "histories_per_s": rate, "warm_s": warm, "cold_s": cold,
        }
        log(
            f"[conformance] audit batch={n}: {rate:,.0f} histories/s "
            f"(warm {warm * 1e3:.1f}ms, cold {cold:.2f}s)"
        )

    # (c) divergence-rate sweep at the largest batch: branchless lanes
    # => flat throughput.
    n = batches[-1]
    for frac in (0.0, 0.1, 0.5):
        recs = trace_batch(n, divergent_fraction=frac)
        verdicts, warm, _cold = time_replay(recs, lanes=n)
        n_div = sum(1 for v in verdicts if not v["conforms"])
        assert n_div == int(round(n * frac)), (n_div, frac)
        rate = n / max(warm, 1e-9)
        out["divergence_sweep"][f"{int(frac * 100)}pct"] = {
            "traces_per_s": rate, "divergent_lanes": n_div,
        }
        log(
            f"[conformance] divergence {int(frac * 100)}%: "
            f"{rate:,.0f} traces/s"
        )
    rates = [
        v["traces_per_s"] for v in out["divergence_sweep"].values()
    ]
    out["divergence_flatness"] = min(rates) / max(max(rates), 1e-9)
    print(json.dumps(out))


def _main_conformance():
    """Parent entry for ``bench.py --conformance``: runs the
    conformance throughput legs in a child (timeout isolation) and writes
    ``BENCH_r20.json`` (override with ``--conformance-out PATH``),
    printing the same record as the one JSON line. Render the
    trajectory with ``scripts/bench_compare.py --conformance``."""
    def run(pin_cpu):
        argv = [sys.executable, __file__, "--conformance-leg"]
        if pin_cpu:
            argv.append("--cpu")
        return _child_json(
            argv, CONFORMANCE_TIMEOUT_S * (3 if pin_cpu else 1),
            "conformance",
        )

    rec = run(pin_cpu="--cpu" in sys.argv)
    if rec is None:
        print(
            json.dumps(
                {
                    "metric": "conformance replay throughput "
                    "(1024-lane batch)",
                    "value": 0,
                    "unit": "traces/sec",
                    "error": "conformance leg failed",
                }
            )
        )
        sys.exit(1)
    headline = rec["replay"]["1024"]["traces_per_s"]
    record = {
        "metric": "conformance replay throughput (1024-lane batch, "
        "vmapped trace replayer)",
        "value": round(headline, 1),
        "unit": "traces/sec",
        "conformance": rec,
    }
    if rec.get("divergence_flatness", 1.0) < 0.5:
        log(
            "[conformance] WARNING: throughput is not flat in the "
            f"divergence rate (min/max {rec['divergence_flatness']:.2f})"
        )
    out_path = None
    for i, arg in enumerate(sys.argv):
        if arg == "--conformance-out" and i + 1 < len(sys.argv):
            out_path = sys.argv[i + 1]
        elif arg.startswith("--conformance-out="):
            out_path = arg.split("=", 1)[1]
    if out_path is None:
        out_path = os.path.join(REPO_DIR, "BENCH_r20.json")
    with open(out_path, "w") as f:
        # One JSON line, like every BENCH_r* record (the line-oriented
        # readers scan for the "conformance" key).
        f.write(json.dumps(record) + "\n")
    log(f"[conformance] record written to {out_path}")
    print(json.dumps(record))


ASYNC_AB_TIMEOUT_S = 1800


def _run_async_ab_leg(pin_cpu: bool):
    """Child entry: the async-pipeline A/B (BENCH_r11+, ROADMAP item 3's
    acceptance gate). One out-of-core 2pc-N run twice with the SAME
    spawn config — async_pipeline off, then on — both with attribution
    ledgers. Asserts bit-identical results (counts, depths,
    discoveries, golden reporter) and records per-leg rate, realized
    pipeline utilization, the async-off ledger's PREDICTED utilization
    under perfect overlap (the PR-7 headroom estimate), and the
    async-on worker's achieved overlap — the instrument closing its
    own loop. Config mirrors tests/test_storage_equivalence.py's
    acceptance run (frontier 16 forces multiple L0 evictions)."""
    import io
    import re

    _leg_backend(pin_cpu)
    import jax

    from stateright_tpu import WriteReporter
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.telemetry import metrics_registry

    device = jax.devices()[0]
    log(f"[async_ab] device: {device.platform} ({device})")
    rm = int(_parse_float_flag("--ab-rm") or 5)
    model = TwoPhaseSys(rm)
    frontier = 16
    budget = _parse_float_flag("--hbm-budget-mib")
    if budget is None:
        # The smallest admissible budget for this frontier: maximum
        # eviction pressure (THE shared definition — it tracks the
        # checker's load factor and table layout by construction).
        from stateright_tpu.checker.tpu import (
            min_admissible_hbm_budget_mib,
        )

        budget = min_admissible_hbm_budget_mib(model, frontier)
    spawn = dict(
        frontier_capacity=frontier,
        table_capacity=1 << 14,
        hbm_budget_mib=budget,
        attribution=True,
    )
    out = {
        "device": device.platform,
        "model": f"2pc-{rm}",
        "hbm_budget_mib": budget,
        # CPU boxes make the rate half of this leg noise; the
        # utilization delta is the claim (see tier1.yml note). Keyed
        # "async_advisory" so bench_compare's trajectory gate reads it
        # as the advisory flag of the "async" headline leg.
        "async_advisory": device.platform == "cpu",
    }

    def golden(checker):
        sink = io.StringIO()
        checker.report(WriteReporter(sink))
        return re.sub(r"sec=\d+", "sec=_", sink.getvalue())

    legs = {}
    goldens = {}
    for name, async_on in (("async_off", False), ("async_on", True)):
        metrics_registry().reset()
        t0 = time.time()
        checker = (
            TwoPhaseSys(rm)
            .checker()
            .spawn_tpu_bfs(**spawn, async_pipeline=async_on)
            .join()
        )
        wall = time.time() - t0
        warm = checker.warmup_seconds or 0.0
        rep = checker.attribution_report()
        snap = checker.metrics().snapshot()
        leg = {
            "unique": checker.unique_state_count(),
            "states": checker.state_count(),
            "max_depth": checker.max_depth(),
            "wall_s": wall,
            "warmup_s": warm,
            "rate": checker.unique_state_count() / max(wall - warm, 1e-9),
            "utilization": rep.get("utilization"),
            "monitor_utilization_gauge": snap.get(
                "tpu_bfs.pipeline.utilization"
            ),
            "evictions": snap.get("tpu_bfs.storage.evictions"),
            "attribution": rep,
        }
        if async_on:
            leg["overlapped_total_s"] = rep.get("overlapped_total_s")
        if not leg["evictions"]:
            # The leg's whole claim is out-of-core overlap; a budget
            # that never bound (e.g. the load-factor arithmetic above
            # drifting from checker/tpu._MAX_LOAD) would silently
            # compare two in-core runs and report a ~0 delta as if the
            # acceptance gate ran.
            raise AssertionError(
                f"async A/B {name} leg recorded no L0 evictions — the "
                f"hbm budget ({budget} MiB) never bound; the leg is "
                "not measuring the out-of-core pipeline"
            )
        legs[name] = leg
        goldens[name] = golden(checker)
        log(
            f"[async_ab] {name}: {leg['unique']} unique, "
            f"{leg['rate']:,.0f}/s, utilization="
            f"{(leg['utilization'] or 0.0):.3f}"
        )
    identical = (
        legs["async_off"]["unique"] == legs["async_on"]["unique"]
        and legs["async_off"]["states"] == legs["async_on"]["states"]
        and legs["async_off"]["max_depth"] == legs["async_on"]["max_depth"]
        and goldens["async_off"] == goldens["async_on"]
    )
    out["bit_identical"] = identical
    if not identical:
        raise AssertionError(
            "async-on leg diverged from async-off: "
            f"{ {k: (v['unique'], v['states'], v['max_depth']) for k, v in legs.items()} }"
        )
    off_att = legs["async_off"]["attribution"]
    oh = off_att.get("overlap_headroom") or {}
    device_s = (off_att.get("phases_s") or {}).get("device")
    predicted_wall = oh.get("predicted_wall_s")
    out["predicted_utilization"] = (
        device_s / predicted_wall
        if device_s is not None and predicted_wall
        else None
    )
    out["utilization_delta"] = (
        (legs["async_on"]["utilization"] or 0.0)
        - (legs["async_off"]["utilization"] or 0.0)
    )
    out["async_off"] = legs["async_off"]
    out["async_on"] = legs["async_on"]
    print(json.dumps(out))


MEGAKERNEL_TIMEOUT_S = 1800


def _run_megakernel_leg(pin_cpu: bool):
    """Child entry: the fused-wave megakernel A/B (BENCH_r16). Two zoo
    models — 2pc-N (full passing sweep) and the shallow sharded_kv
    torn-write violation — each run twice with the SAME spawn config:
    ``wave_kernel="staged"`` (with ``wave_dedup="sort"``, the discipline
    the fused sweep implements) then ``wave_kernel="fused"``, both with
    attribution ledgers and ``max_drain_waves=1`` so every wave goes
    through the per-wave engine and the ledger prices each dispatch.
    Asserts bit-identical results (counts, depths, golden reporter —
    including the sharded_kv violation trace) and records per-leg
    ``utilization``, ``gap_share``, and ``phase_windows`` (the staged
    chain's ``device`` windows vs the fused path's single
    ``wave_kernel`` dispatch per wave). On CPU the fused kernel runs
    under the Pallas interpreter — utilization is advisory there (the
    interpreter pays a python-loop tax XLA compute doesn't), while the
    gap_share drop (fewer host/dispatch seams per wave) holds on every
    backend."""
    import io
    import re

    _leg_backend(pin_cpu)
    import jax

    from stateright_tpu import WriteReporter
    from stateright_tpu.models.sharded_kv import ShardedKv
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.telemetry import metrics_registry

    device = jax.devices()[0]
    log(f"[megakernel] device: {device.platform} ({device})")
    rm = int(_parse_float_flag("--mega-rm") or 5)
    spawn = dict(
        frontier_capacity=1024,
        table_capacity=1 << 14,
        attribution=True,
        max_drain_waves=1,
    )
    primary = f"2pc-{rm}"
    models = (
        (primary, lambda: TwoPhaseSys(rm)),
        (
            "sharded_kv(2x2 shallow torn-write)",
            lambda: ShardedKv(2, 2, 1, guarded=False),
        ),
    )
    out = {
        "device": device.platform,
        # CPU boxes run the fused kernel under the Pallas interpreter:
        # wall/utilization are that interpreter's cost, not the
        # megakernel's — the gap_share drop is the portable claim.
        "advisory": device.platform == "cpu",
        "models": {},
    }

    def golden(checker):
        sink = io.StringIO()
        checker.report(WriteReporter(sink))
        return re.sub(r"sec=\d+", "sec=_", sink.getvalue())

    for mname, make in models:
        legs = {}
        goldens = {}
        for leg, kw in (
            ("staged", dict(wave_dedup="sort")),
            ("fused", dict(wave_kernel="fused")),
        ):
            metrics_registry().reset()
            t0 = time.time()
            checker = (
                make().checker().spawn_tpu_bfs(**spawn, **kw).join()
            )
            wall = time.time() - t0
            warm = checker.warmup_seconds or 0.0
            rep = checker.attribution_report()
            legs[leg] = {
                "unique": checker.unique_state_count(),
                "states": checker.state_count(),
                "max_depth": checker.max_depth(),
                "wall_s": wall,
                "warmup_s": warm,
                "rate": checker.unique_state_count()
                / max(wall - warm, 1e-9),
                "utilization": rep.get("utilization"),
                "gap_share": rep.get("gap_share"),
                "phase_windows": rep.get("phase_windows"),
                "attribution": rep,
            }
            goldens[leg] = golden(checker)
            log(
                f"[megakernel] {mname} {leg}: "
                f"{legs[leg]['unique']} unique, "
                f"utilization={(legs[leg]['utilization'] or 0.0):.3f}, "
                f"gap_share={(legs[leg]['gap_share'] or 0.0):.3f}"
            )
        identical = (
            legs["staged"]["unique"] == legs["fused"]["unique"]
            and legs["staged"]["states"] == legs["fused"]["states"]
            and legs["staged"]["max_depth"] == legs["fused"]["max_depth"]
            and goldens["staged"] == goldens["fused"]
        )
        if not identical:
            raise AssertionError(
                f"fused leg diverged from staged on {mname}: "
                f"{ {k: (v['unique'], v['states'], v['max_depth']) for k, v in legs.items()} }"
            )
        rec = {
            "bit_identical": True,
            "staged": legs["staged"],
            "fused": legs["fused"],
            "utilization_delta": (
                (legs["fused"]["utilization"] or 0.0)
                - (legs["staged"]["utilization"] or 0.0)
            ),
            "gap_share_delta": (
                (legs["fused"]["gap_share"] or 0.0)
                - (legs["staged"]["gap_share"] or 0.0)
            ),
        }
        if rec["gap_share_delta"] >= 0:
            log(
                f"[megakernel] WARNING: {mname} fused gap_share did not "
                f"drop ({rec['gap_share_delta']:+.3f})"
            )
        if rec["utilization_delta"] <= 0 and not out["advisory"]:
            log(
                f"[megakernel] WARNING: {mname} fused utilization did "
                f"not rise ({rec['utilization_delta']:+.3f})"
            )
        out["models"][mname] = rec
    prim = out["models"][primary]
    out["bit_identical"] = all(
        r["bit_identical"] for r in out["models"].values()
    )
    out["gap_share_delta"] = prim["gap_share_delta"]
    out["utilization_delta"] = prim["utilization_delta"]
    print(json.dumps(out))


LIVENESS_TIMEOUT_S = 1200


class _LevelDag:
    """The absence-certification workload (BENCH_r14): a wide, shallow
    DAG — every maximal path ends at a terminal ``level == L`` state
    where the ``eventually "done"`` condition finally holds. No cycles,
    no condition-false terminal ⇒ NO counterexample, so certifying
    absence costs the FULL condition-false region on the host post-pass
    (one Python ``actions``+``next_state`` re-expansion per false
    state) but only the trim fixpoint on the device path (~L peel
    rounds — the ≥5× headline). Width/level tuned to ~74K states.

    Host states are ACTOR-SHAPED on purpose — a (level, field-tuple,
    message-frozenset) record, not a bare int — so the host pass pays
    the per-state construction + hashing cost real models pay (the
    ``checker/liveness.py`` docstring's thousands-to-tens-of-thousands
    states/s bracket); a bare-int encoding would flatter the host pass
    ~30× below any workload anyone actually checks. The packed side is
    the same u32 codec either way (``pack_state`` strips the
    deterministic garnish), so the two paths explore the identical
    region."""

    W = 1 << 13
    WB = 13  # bit-width of the value field
    L = 20

    def _mk(self, level, value):
        bits = tuple((value >> i) & 1 for i in range(self.WB))
        msgs = frozenset((i, b) for i, b in enumerate(bits) if b)
        return (level, bits, msgs)

    def _value(self, state):
        return sum(b << i for i, b in enumerate(state[1]))

    def init_states(self):
        return [self._mk(0, 0)]

    def within_boundary(self, state):
        return True

    def actions(self, state, actions):
        if state[0] < self.L:
            actions.extend((0, 1))

    def next_state(self, state, action):
        level = state[0]
        value = (2 * self._value(state) + action + level) % self.W
        return self._mk(level + 1, value)

    def properties(self):
        from stateright_tpu import Property

        return [
            Property.eventually("done", lambda _m, s: s[0] == self.L)
        ]

    # -- packed protocol ---------------------------------------------------

    def packed_action_count(self):
        return 2

    def packed_init_states(self):
        import jax.numpy as jnp

        return {"s": jnp.zeros((1,), jnp.uint32)}

    def packed_step(self, state, action_id):
        import jax.numpy as jnp

        s = state["s"]
        W = jnp.uint32(self.W)
        level, value = s // W, s % W
        valid = level < jnp.uint32(self.L)
        nxt = (level + 1) * W + (
            2 * value + action_id.astype(jnp.uint32) + level
        ) % W
        return {"s": jnp.where(valid, nxt, s)}, valid

    def packed_conditions(self):
        import jax.numpy as jnp

        return [lambda st: (st["s"] // jnp.uint32(self.W)) == self.L]

    def pack_state(self, host_state):
        import numpy as np

        return {
            "s": np.uint32(host_state[0] * self.W + self._value(host_state))
        }

    def unpack_state(self, packed):
        s = int(packed["s"])
        return self._mk(s // self.W, s % self.W)


def _run_liveness_leg(pin_cpu: bool):
    """Child entry: the device-liveness legs (BENCH_r14).

    (a) raft-3 check-live config (lossy, stable-leader): the
        ``liveness="device"`` run must produce a REAL counterexample —
        the soundness headline — with the analysis cost recorded.
    (b) absence certification at equal state count: the _LevelDag
        region, certified absent by the device trim/reach pass vs the
        host post-pass exhausting the same condition-false region —
        the ≥5× wall-clock claim (advisory outside the acceptance
        gate, like every timing on a shared box)."""
    _leg_backend(pin_cpu)
    import jax

    from stateright_tpu.checker.liveness import find_eventually_lasso
    from stateright_tpu.core.batch import BatchableModel
    from stateright_tpu.core.model import Model
    from stateright_tpu.models.raft import RaftModelCfg

    device = jax.devices()[0]
    log(f"[liveness] device: {device.platform} ({device})")

    # (a) raft-3 check-live, device path.
    raft = (
        RaftModelCfg(server_count=3, max_term=1, lossy=True)
        .into_model()
        .retain_properties("stable leader")
    )
    t0 = time.perf_counter()
    ck = (
        raft.checker()
        .spawn_tpu_bfs(
            frontier_capacity=1 << 10, table_capacity=1 << 14,
            liveness="device",
        )
        .join()
    )
    raft_wall = time.perf_counter() - t0
    found = ck.discoveries()
    assert "stable leader" in found, "device path missed the raft-3 lasso"
    path = found["stable leader"]
    prop = raft.properties()[0]
    assert not any(prop.condition(raft, s) for s in path.into_states())
    raft_rec = {
        "unique": ck.unique_state_count(),
        "wall_s": raft_wall,
        "warmup_s": ck.warmup_seconds,
        "certificate_len": len(path),
        "liveness": ck.liveness_report(),
    }
    log(
        f"[liveness] raft-3 check-live: counterexample len "
        f"{len(path)} over {ck.unique_state_count()} states in "
        f"{raft_wall:.1f}s"
    )

    # (b) absence certification, equal state count both ways.
    class _Dag(_LevelDag, Model, BatchableModel):
        pass

    dag = _Dag()
    t0 = time.perf_counter()
    dev = (
        dag.checker()
        .spawn_tpu_bfs(
            frontier_capacity=1 << 12, table_capacity=1 << 17,
            liveness="device",
        )
        .join()
    )
    dev_wall = time.perf_counter() - t0
    outcome = dev._live_outcomes["done"]
    assert outcome["verdict"] == "absent", outcome
    analysis_cold_s = outcome["seconds"]
    # Steady-state analysis (the bench-wide warmup convention): the
    # first pass pays the trim/reach kernel compiles — one-time per
    # padded shape class — so the headline is the re-run, with the
    # cold number recorded alongside.
    from stateright_tpu.checker.device_liveness import analyze_liveness

    t0 = time.perf_counter()
    _paths, warm_outcomes = analyze_liveness(
        dag, dag.properties(), dev._ebit, dev._live_store,
        dev._host_fp, set(),
    )
    analysis_s = time.perf_counter() - t0
    assert warm_outcomes["done"]["verdict"] == "absent"

    host_model = _Dag()
    t0 = time.perf_counter()
    host_verdict = find_eventually_lasso(
        host_model, host_model.properties()[0]
    )
    host_pass_s = time.perf_counter() - t0
    assert host_verdict is None
    speedup = host_pass_s / max(analysis_s, 1e-9)
    log(
        f"[liveness] absence @ {dev.unique_state_count()} states: "
        f"device analysis {analysis_s:.2f}s vs host post-pass "
        f"{host_pass_s:.2f}s ({speedup:.1f}x)"
    )

    record = {
        "metric": "device-liveness absence certification vs host "
        "post-pass (equal state count)",
        "value": round(speedup, 1),
        "unit": "x host post-pass",
        "device": device.platform,
        "advisory": device.platform == "cpu",
        "raft3_check_live": raft_rec,
        "absence": {
            "states": dev.unique_state_count(),
            "device_analysis_s": analysis_s,
            "device_analysis_cold_s": analysis_cold_s,
            "device_wall_s": dev_wall,
            "device_warmup_s": dev.warmup_seconds,
            "host_pass_s": host_pass_s,
            "speedup": speedup,
            "trim_rounds": outcome.get("trim_rounds"),
            "edges": outcome.get("edges"),
            "liveness": dev.liveness_report(),
        },
    }
    print(json.dumps(record))


SWARM_TIMEOUT_S = 1200


def _run_swarm_leg(pin_cpu: bool):
    """Child entry: the swarm-verification legs (BENCH_r15).

    (a) raft-3 check-live time-to-first-violation: the exhaustive
        path must enumerate + run the liveness analysis before it can
        produce the `stable leader` counterexample; the swarm's
        randomized walks hit a leaderless cycle in a fraction of that
        wall — the headline ttfv speedup.
    (b) 2pc-3 witness hunt: swarm vs exhaustive wall to both
        `sometimes` examples (the easy-workload sanity leg; 2pc-3 on
        purpose — see the inline note on conjunctive witnesses).
    (c) sharded_kv at S=4/K=8/V=3 (~10^14 states — beyond the tiered
        store): walk-steps/s, the unique-coverage sample, and the
        `no torn writes` violation exhaustive checking cannot reach.
    """
    _leg_backend(pin_cpu)
    import jax

    from stateright_tpu.models.raft import RaftModelCfg
    from stateright_tpu.models.sharded_kv import ShardedKv
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    device = jax.devices()[0]
    log(f"[swarm] device: {device.platform} ({device})")

    def swarm_run(model, seed, **kw):
        t0 = time.perf_counter()
        ck = model.checker().spawn_swarm(seed=seed, **kw).join()
        wall = time.perf_counter() - t0
        assert ck.worker_error() is None
        return ck, wall

    # The bench-wide warmup convention: every path runs twice under a
    # shared AOT cache — the first run pays the compiles (recorded as
    # *_cold_s), the second is the steady-state headline. A resident
    # service amortizes compiles across jobs (checker/swarm.py's wave
    # cache / checker/tpu.py's shared_aot_cache), so the warm number is
    # the one production traffic sees.
    # (a) raft-3 check-live: swarm vs exhaustive ttfv.
    def raft():
        return (
            RaftModelCfg(server_count=3, max_term=1, lossy=True)
            .into_model()
            .retain_properties("stable leader")
        )

    def exhaustive_raft():
        t0 = time.perf_counter()
        ck = (
            raft()
            .checker()
            .spawn_tpu_bfs(
                frontier_capacity=1 << 10, table_capacity=1 << 14,
                liveness="device", aot_cache="bench:raft3-live",
            )
            .join()
        )
        assert "stable leader" in ck.discoveries()
        return ck, time.perf_counter() - t0

    ex, exhaustive_cold = exhaustive_raft()
    _ex_warm, exhaustive_ttfv = exhaustive_raft()

    # One model INSTANCE per swarm leg, reused across the cold and warm
    # runs: the swarm wave cache pins models by identity, so a fresh
    # model per run would make the "warm" number pay the compile again
    # (the exhaustive side's shared_aot_cache is signature-keyed and
    # doesn't care).
    raft_model = raft()

    def swarm_raft():
        return swarm_run(
            raft_model, seed=7, lanes=512, wave_steps=64,
            max_trace_len=128, sample_capacity=1 << 15,
            sample_stride=8, aot_cache="bench:raft3-swarm",
        )

    sw, swarm_cold = swarm_raft()
    assert "stable leader" in sw.discoveries(), "swarm missed the lasso"
    _sw_warm, swarm_ttfv = swarm_raft()
    speedup = exhaustive_ttfv / max(swarm_ttfv, 1e-9)
    log(
        f"[swarm] raft-3 check-live ttfv (warm): swarm "
        f"{swarm_ttfv:.2f}s vs exhaustive {exhaustive_ttfv:.2f}s "
        f"({speedup:.1f}x; cold {swarm_cold:.1f}s vs "
        f"{exhaustive_cold:.1f}s)"
    )
    raft_rec = {
        "swarm_ttfv_s": swarm_ttfv,
        "swarm_ttfv_cold_s": swarm_cold,
        "exhaustive_ttfv_s": exhaustive_ttfv,
        "exhaustive_ttfv_cold_s": exhaustive_cold,
        "speedup": speedup,
        "swarm_walk_steps": sw.state_count(),
        "swarm_sample": sw.coverage_estimate(),
        "exhaustive_unique": ex.unique_state_count(),
    }

    # (b) 2pc-3 witness hunt (warm both ways, same convention). 2pc-3
    # on purpose: the all-N-commit witness needs ~3N coordinated steps
    # with abort actions competing at every one, so its per-walk hit
    # probability falls exponentially in N — at N>=4 uniform walks need
    # minutes where BFS needs seconds. That asymmetry is recorded here
    # honestly (the README table: rare coordinated witnesses and
    # certified absence are exhaustive territory; deep violations are
    # the swarm's — leg (c)).
    def exhaustive_2pc():
        t0 = time.perf_counter()
        ck = (
            TwoPhaseSys(3)
            .checker()
            .spawn_tpu_bfs(
                frontier_capacity=1 << 9, table_capacity=1 << 13,
                aot_cache="bench:2pc3",
            )
            .join()
        )
        return ck, time.perf_counter() - t0

    ex2, ex2_cold = exhaustive_2pc()
    _ex2w, ex2_wall = exhaustive_2pc()

    two_pc_model = TwoPhaseSys(3)

    def swarm_2pc():
        # 2pc's holding `consistent` always-property is never
        # "discovered", so (reference simulation semantics) the run
        # only ends at the walk budget — witness ttfv is measured by
        # polling the discovery names and preempting once both landed.
        t0 = time.perf_counter()
        ck = (
            two_pc_model
            .checker()
            .target_state_count(50_000_000)
            .spawn_swarm(
                seed=11, lanes=512, wave_steps=64,
                max_trace_len=64, sample_capacity=1 << 15,
                sample_stride=4, aot_cache="bench:2pc3-swarm",
            )
        )
        ttfv = None
        while not ck.is_done():
            if {"abort agreement", "commit agreement"} <= set(
                ck._discovery_names()
            ):
                ttfv = time.perf_counter() - t0
                ck.request_preempt()
                break
            time.sleep(0.02)
        ck.join()
        assert ck.worker_error() is None
        assert ttfv is not None, "swarm missed the 2pc-3 witnesses"
        return ck, ttfv

    sw2, sw2_cold = swarm_2pc()
    _sw2w, sw2_wall = swarm_2pc()
    two_pc_rec = {
        "model": "2pc-3",
        "swarm_wall_s": sw2_wall,
        "swarm_wall_cold_s": sw2_cold,
        "exhaustive_wall_s": ex2_wall,
        "exhaustive_wall_cold_s": ex2_cold,
        "swarm_walk_steps": sw2.state_count(),
        "swarm_sample": sw2.coverage_estimate(),
        "exhaustive_unique": ex2.unique_state_count(),
        "note": "conjunctive sometimes-witnesses get exponentially "
        "rare under uniform walks as N grows (2pc-5 takes minutes "
        "where BFS takes seconds) — rare coordinated witnesses are "
        "exhaustive territory; the swarm's is deep violations",
    }
    log(
        f"[swarm] 2pc-3 witnesses (warm): swarm {sw2_wall:.2f}s vs "
        f"exhaustive {ex2_wall:.2f}s"
    )

    # (c) the too-big-to-enumerate leg (~10^14 upper bound) and the
    # HEADLINE ttfv A/B: the deep `no total tear` violation sits >= 16
    # actions from init — the breadth-first frontier explodes long
    # before that depth, so the exhaustive run gets a generous wall
    # budget and is honestly preempted when it blows it; the swarm
    # reaches the depth in one walk.
    def deep_kv():
        return ShardedKv(4, 8, 3, retain=("no total tear",))

    EXHAUSTIVE_BUDGET_S = 60.0
    t0 = time.perf_counter()
    ex3 = deep_kv().checker().spawn_tpu_bfs(
        frontier_capacity=1 << 10, table_capacity=1 << 18,
    )
    ex3_found = None
    while not ex3.is_done():
        if "no total tear" in ex3._discovery_names():
            ex3_found = time.perf_counter() - t0
            break
        if time.perf_counter() - t0 > EXHAUSTIVE_BUDGET_S:
            ex3.request_preempt()
            break
        time.sleep(0.05)
    ex3.join()
    if ex3_found is None and "no total tear" in ex3._discovery_names():
        ex3_found = time.perf_counter() - t0
    ex3_states = ex3.unique_state_count()
    ex3_depth = ex3.max_depth()

    deep_model = deep_kv()

    def swarm_deep():
        return swarm_run(
            deep_model, seed=3, lanes=1024, wave_steps=128,
            max_trace_len=128, sample_capacity=1 << 17,
            sample_stride=8, aot_cache="bench:kv-deep",
        )

    sw3, sw3_cold = swarm_deep()
    assert "no total tear" in sw3._discoveries_fps, (
        "swarm missed the deep torn-write violation"
    )
    _sw3w, sw3_wall = swarm_deep()
    steps_per_s = sw3.state_count() / max(
        sw3_cold - (sw3.warmup_seconds or 0.0), 1e-9
    )
    # The honest headline: exhaustive ttfv when it found it, else the
    # budget it burned without finding it (a LOWER bound on its ttfv).
    ex3_ttfv_bound = (
        ex3_found if ex3_found is not None else EXHAUSTIVE_BUDGET_S
    )
    deep_speedup = ex3_ttfv_bound / max(sw3_wall, 1e-9)
    kv_rec = {
        "model": "sharded_kv(shards=4, keys=8, max_version=3)",
        "state_space_upper_bound": "~1e14",
        "violation": "no total tear (every key torn; depth >= 16)",
        "swarm_ttfv_s": sw3_wall,
        "swarm_ttfv_cold_s": sw3_cold,
        "exhaustive_found": ex3_found is not None,
        "exhaustive_ttfv_s": ex3_found,
        "exhaustive_budget_s": EXHAUSTIVE_BUDGET_S,
        "exhaustive_states_explored": ex3_states,
        "exhaustive_max_depth": ex3_depth,
        "speedup_lower_bound": deep_speedup,
        "ttfv_s": sw3_wall,
        "walk_steps": sw3.state_count(),
        "walk_steps_per_s": steps_per_s,
        "warmup_s": sw3.warmup_seconds,
        "sample": sw3.coverage_estimate(),
        "violation_len": len(sw3._discoveries_fps["no total tear"]),
    }
    log(
        f"[swarm] sharded_kv 4x8 deep violation: swarm ttfv "
        f"{sw3_wall:.2f}s vs exhaustive "
        + (
            f"{ex3_found:.2f}s"
            if ex3_found is not None
            else f"NOT FOUND in {EXHAUSTIVE_BUDGET_S:.0f}s "
            f"({ex3_states:,} states to depth {ex3_depth})"
        )
        + f" (>= {deep_speedup:.0f}x); {steps_per_s:,.0f} walk-steps/s"
    )

    record = {
        "metric": "swarm time-to-first-violation vs exhaustive "
        "(sharded_kv deep torn-write, exhaustive wall-budgeted)",
        "value": round(deep_speedup, 1),
        "unit": "x exhaustive ttfv (lower bound)",
        "device": device.platform,
        "advisory": device.platform == "cpu",
        "swarm": {
            "raft3_check_live": raft_rec,
            "two_phase": two_pc_rec,
            "sharded_kv": kv_rec,
        },
    }
    print(json.dumps(record))


def _main_swarm():
    """Parent entry for ``bench.py --swarm``: runs the swarm legs in a
    child (timeout isolation) and prints the one BENCH-record JSON line
    (BENCH_r15.json; render with ``scripts/bench_compare.py
    --swarm``)."""
    def run(pin_cpu):
        argv = [sys.executable, __file__, "--swarm-leg"]
        if pin_cpu:
            argv.append("--cpu")
        return _child_json(
            argv, SWARM_TIMEOUT_S * (3 if pin_cpu else 1), "swarm"
        )

    rec = run(pin_cpu="--cpu" in sys.argv)
    if rec is None:
        print(
            json.dumps(
                {
                    "metric": "swarm time-to-first-violation vs "
                    "exhaustive (sharded_kv deep torn-write, "
                    "exhaustive wall-budgeted)",
                    "value": 0,
                    "unit": "x exhaustive ttfv (lower bound)",
                    "error": "swarm leg failed",
                }
            )
        )
        sys.exit(1)
    if rec.get("value", 0) < 1:
        log(
            f"[swarm] WARNING: swarm ttfv {rec.get('value')}x did not "
            "beat exhaustive"
        )
    print(json.dumps(rec))


def _main_liveness():
    """Parent entry for ``bench.py --liveness``: runs the liveness legs
    in a child (timeout isolation) and prints the one BENCH-record JSON
    line (BENCH_r14.json)."""
    def run(pin_cpu):
        argv = [sys.executable, __file__, "--liveness-leg"]
        if pin_cpu:
            argv.append("--cpu")
        return _child_json(
            argv, LIVENESS_TIMEOUT_S * (3 if pin_cpu else 1), "liveness"
        )

    rec = run(pin_cpu="--cpu" in sys.argv)
    if rec is None:
        print(
            json.dumps(
                {
                    "metric": "device-liveness absence certification "
                    "vs host post-pass (equal state count)",
                    "value": 0,
                    "unit": "x host post-pass",
                    "error": "liveness leg failed",
                }
            )
        )
        sys.exit(1)
    if rec.get("value", 0) < 5:
        log(
            f"[liveness] WARNING: absence-certification speedup "
            f"{rec.get('value')}x below the 5x bar"
        )
    print(json.dumps(rec))


def _main_async_ab():
    """Parent entry for ``bench.py --async-ab``: runs the A/B leg in a
    child (timeout isolation) and prints the one BENCH-record JSON line
    (render it with ``scripts/bench_compare.py --ab-async``)."""
    passthrough = []
    for flag in ("--ab-rm", "--hbm-budget-mib"):
        value = _parse_float_flag(flag)
        if value is not None:
            passthrough += [flag, str(value)]

    def run(pin_cpu):
        argv = [sys.executable, __file__, "--async-ab-leg", *passthrough]
        if pin_cpu:
            argv.append("--cpu")
        return _child_json(
            argv, ASYNC_AB_TIMEOUT_S * (3 if pin_cpu else 1), "async_ab"
        )

    rec = run(pin_cpu="--cpu" in sys.argv)
    if rec is None:
        print(
            json.dumps(
                {
                    "metric": "async pipeline A/B "
                    "(out-of-core 2pc, async on vs off)",
                    "value": 0,
                    "unit": "unique states/sec",
                    "error": "async A/B leg failed",
                }
            )
        )
        sys.exit(1)
    line = {
        "metric": "async pipeline A/B "
        f"(out-of-core {rec['model']}, async on vs off)",
        "value": round(rec["async_on"]["rate"], 1),
        "unit": "unique states/sec",
        **rec,
    }
    print(json.dumps(line))


def _main_megakernel():
    """Parent entry for ``bench.py --megakernel``: runs the fused-wave
    A/B leg in a child (timeout isolation) and prints the one BENCH-record
    JSON line (BENCH_r16.json; render with ``scripts/bench_compare.py
    --megakernel``)."""
    passthrough = []
    value = _parse_float_flag("--mega-rm")
    if value is not None:
        passthrough += ["--mega-rm", str(value)]

    def run(pin_cpu):
        argv = [sys.executable, __file__, "--megakernel-leg", *passthrough]
        if pin_cpu:
            argv.append("--cpu")
        return _child_json(
            argv, MEGAKERNEL_TIMEOUT_S * (3 if pin_cpu else 1), "megakernel"
        )

    rec = run(pin_cpu="--cpu" in sys.argv)
    if rec is None:
        print(
            json.dumps(
                {
                    "metric": "fused wave megakernel A/B "
                    "(2pc + sharded_kv shallow, staged vs fused)",
                    "value": 0,
                    "unit": "gap_share delta (fused - staged)",
                    "error": "megakernel leg failed",
                }
            )
        )
        sys.exit(1)
    line = {
        "metric": "fused wave megakernel A/B "
        "(2pc + sharded_kv shallow, staged vs fused)",
        "value": round(rec["gap_share_delta"], 4),
        "unit": "gap_share delta (fused - staged)",
        **rec,
    }
    print(json.dumps(line))


MULTICHIP_TIMEOUT_S = 700
MULTICHIP_SHARD_COUNTS = (1, 2, 4, 8)


def _parse_int_flag(flag):
    v = _parse_float_flag(flag)
    return None if v is None else int(v)


def _run_multichip_leg(pin_cpu: bool):
    """Child entry for ``--multichip-leg``: one sharded 2pc-5 run at
    ``--shards N`` with the sieve on or off (``--sieve 0|1``), printing
    counts + steady-state rate + the comms ledger as a JSON line. The
    parent A/Bs these for the MULTICHIP scaling record."""
    shards = _parse_int_flag("--shards") or 8
    sieve = bool(_parse_int_flag("--sieve"))
    if pin_cpu:
        # Virtual shard pool BEFORE backend init: the CPU multichip legs
        # model a pod slice with 8 single-core devices.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )
    _leg_backend(pin_cpu)
    import jax

    import numpy as np
    from jax.sharding import Mesh

    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.telemetry import metrics_registry

    devices = jax.devices()
    if shards > len(devices):
        print(json.dumps({"skipped": f"{shards} shards > {len(devices)}"}))
        return
    mesh = Mesh(np.array(devices[:shards]), ("fp",))
    log(
        f"[multichip] {shards} shard(s) on {devices[0].platform}, "
        f"sieve={'on' if sieve else 'off'}"
    )
    t0 = time.time()
    checker = (
        TwoPhaseSys(5)
        .checker()
        .spawn_sharded_tpu_bfs(
            mesh=mesh,
            frontier_per_device=max(8, 512 // shards),
            table_capacity_per_device=1 << 14,
            sieve=sieve,
        )
        .join()
    )
    wall = time.time() - t0
    err = checker.worker_error()
    if err is not None:
        raise RuntimeError(f"multichip leg failed: {err}")
    warmup = checker.warmup_seconds or 0.0
    unique = checker.unique_state_count()
    snap = metrics_registry().snapshot()
    waves = int(snap.get("sharded_bfs.waves", 0)) or 1
    lanes = int(snap.get("sharded_bfs.comms.lanes_shipped", 0))
    comms = {
        "lanes_shipped": lanes,
        "bytes_shipped": int(snap.get("sharded_bfs.comms.bytes_shipped", 0)),
        "lanes_per_wave": round(lanes / waves, 1),
        "sieve_kill_rate": snap.get("sharded_bfs.comms.sieve.kill_rate", 0.0),
        "bloom_probe_total": int(
            snap.get("sharded_bfs.comms.sieve.bloom_probe_total", 0)
        ),
        "bloom_fp_total": int(
            snap.get("sharded_bfs.comms.sieve.bloom_fp_total", 0)
        ),
        "rung_dispatch": {
            k.rsplit(".", 1)[1]: int(v)
            for k, v in snap.items()
            if k.startswith("sharded_bfs.comms.rung_dispatch.")
        },
    }
    # Fleet skew forensics (MULTICHIP_r07+): the per-shard imbalance
    # evidence — cumulative per-shard gauges, run-total skew, and the
    # EWMA straggler call — plus the fold's own measured overhead (the
    # <5% budget is a recorded number, not an assertion on faith).
    fleet = {
        "waves": int(snap.get("sharded_bfs.fleet.waves", 0)),
        "overhead_s": round(
            snap.get("sharded_bfs.fleet.overhead_seconds", 0.0), 4
        ),
        "straggler_shard": int(
            snap.get("sharded_bfs.fleet.straggler.shard", -1)
        ),
        "straggler_score": round(
            snap.get("sharded_bfs.fleet.straggler.score", 0.0), 3
        ),
        "straggler_persistence": round(
            snap.get("sharded_bfs.fleet.straggler.persistence", 0.0), 3
        ),
        "skew": {
            k.split(".fleet.skew.", 1)[1]: round(v, 3)
            for k, v in snap.items()
            if k.startswith("sharded_bfs.fleet.skew.")
        },
        "insert_load_per_shard": [
            snap.get(f"sharded_bfs.fleet.shard.{d}.insert_load", 0.0)
            for d in range(shards)
        ],
    }
    print(
        json.dumps(
            {
                "shards": shards,
                "sieve": sieve,
                "device": devices[0].platform,
                "unique": unique,
                "states": checker.state_count(),
                "depth": checker.max_depth(),
                "waves": waves,
                "wall_s": round(wall, 2),
                "warmup_s": round(warmup, 2),
                "rate": round(unique / max(wall - warmup, 1e-9), 1),
                "comms": comms,
                "fleet": fleet,
            }
        )
    )


def _main_multichip():
    """Parent entry for ``bench.py --multichip``: the MULTICHIP_r07
    scaling record — states/s vs shard count with a sieve on/off A/B at
    every width, bit-identity gated (identical counts/depths or the
    record says so, with fleet skew forensics per leg from r07 on).
    Writes ``MULTICHIP_r07.json`` (override with
    ``--multichip-out PATH``) with the legacy dryrun keys
    (``n_devices``/``rc``/``ok``/``skipped``/``tail``) plus the curve,
    and prints the same record as the one JSON line."""
    def run(shards, sieve, pin_cpu):
        argv = [
            sys.executable, __file__, "--multichip-leg",
            "--shards", str(shards), "--sieve", str(int(sieve)),
        ]
        if pin_cpu:
            argv.append("--cpu")
        return _child_json(
            argv,
            MULTICHIP_TIMEOUT_S * (3 if pin_cpu else 1),
            f"multichip-{shards}{'s' if sieve else ''}",
        )

    curve = []
    errors = []
    for shards in MULTICHIP_SHARD_COUNTS:
        pair = {}
        for sieve in (False, True):
            rec = run(shards, sieve, pin_cpu="--cpu" in sys.argv)
            if rec is None or rec.get("skipped"):
                errors.append(
                    f"{shards}-shard sieve={'on' if sieve else 'off'} leg "
                    f"missing"
                )
                continue
            pair["on" if sieve else "off"] = rec
        if not pair:
            continue
        point = {"n_shards": shards}
        for key, rec in pair.items():
            point[f"sieve_{key}"] = rec
        if "on" in pair and "off" in pair:
            identical = all(
                pair["on"][k] == pair["off"][k]
                for k in ("unique", "states", "depth")
            )
            point["bit_identical"] = identical
            if not identical:
                errors.append(f"{shards}-shard sieve A/B results diverge")
            off_lanes = pair["off"]["comms"]["lanes_per_wave"]
            on_lanes = pair["on"]["comms"]["lanes_per_wave"]
            if off_lanes:
                point["lane_reduction_x"] = round(
                    off_lanes / max(on_lanes, 1e-9), 2
                )
        curve.append(point)

    ok = bool(curve) and not errors
    record = {
        # Legacy dryrun-multichip keys first: the series readers
        # (bench_compare --multichip) key on these across r01..r06.
        "n_devices": max(
            (p["n_shards"] for p in curve), default=0
        ),
        "rc": 0 if ok else 1,
        "ok": ok,
        "skipped": False,
        "tail": "; ".join(errors),
        "metric": "sharded states/s vs shard count "
        "(2pc-5, sieve on/off A/B, bit-identity gated)",
        "unit": "unique states/sec",
        "value": (
            curve[-1].get("sieve_on", {}).get("rate", 0) if curve else 0
        ),
        "curve": curve,
    }
    out_path = None
    for i, arg in enumerate(sys.argv):
        if arg == "--multichip-out" and i + 1 < len(sys.argv):
            out_path = sys.argv[i + 1]
        elif arg.startswith("--multichip-out="):
            out_path = arg.split("=", 1)[1]
    if out_path is None:
        out_path = os.path.join(REPO_DIR, "MULTICHIP_r07.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    log(f"[multichip] record written to {out_path}")
    print(json.dumps(record))
    if not ok:
        sys.exit(1)


def _main_service(packed: bool = False):
    """Parent entry for ``bench.py --service`` / ``--service-packed``:
    runs the service leg in a child (timeout isolation, like every other
    leg) and prints the one BENCH-record JSON line."""
    leg_flag = "--service-packed-leg" if packed else "--service-leg"
    label = "service-packed" if packed else "service"
    passthrough = []
    for flag in ("--service-jobs", "--service-quantum", "--service-rm"):
        value = _parse_float_flag(flag)
        if value is not None:
            passthrough += [flag, str(value)]

    def run(pin_cpu):
        argv = [sys.executable, __file__, leg_flag, *passthrough]
        if pin_cpu:
            argv.append("--cpu")
        return _child_json(
            argv, SERVICE_LEG_TIMEOUT_S * (3 if pin_cpu else 1), label
        )

    rec = run(pin_cpu="--cpu" in sys.argv)
    kind = "CheckService packed" if packed else "CheckService"
    if rec is None:
        print(
            json.dumps(
                {
                    "metric": "service aggregate unique states/sec "
                    f"({kind}, concurrent 2pc)",
                    "value": 0,
                    "unit": "unique states/sec",
                    "error": "service leg failed",
                }
            )
        )
        sys.exit(1)
    line = {
        "metric": "service aggregate unique states/sec "
        f"({kind}, {rec['jobs']} concurrent {rec['model']})",
        "value": round(rec["aggregate_states_per_s"], 1),
        "unit": "unique states/sec",
        **rec,
    }
    # ``--service-out PATH`` persists the record as a BENCH_r* file
    # (one JSON line, like --slo-out) so the warm-start sub-leg's
    # cold-vs-warm figures land in the trajectory.
    out_path = None
    for i, arg in enumerate(sys.argv):
        if arg == "--service-out" and i + 1 < len(sys.argv):
            out_path = sys.argv[i + 1]
        elif arg.startswith("--service-out="):
            out_path = arg.split("=", 1)[1]
    if out_path is not None:
        with open(out_path, "w") as f:
            f.write(json.dumps(line) + "\n")
        log(f"[{label}] record written to {out_path}")
    print(json.dumps(line))


def main():
    _validate_flag_combos()
    if "--service-packed-leg" in sys.argv:
        return _run_service_leg("--cpu" in sys.argv, packed=True)
    if "--service-leg" in sys.argv:
        return _run_service_leg("--cpu" in sys.argv)
    if "--service-packed" in sys.argv:
        return _main_service(packed=True)
    if "--slo-leg" in sys.argv:
        return _run_slo_leg("--cpu" in sys.argv)
    if "--slo" in sys.argv:
        return _main_slo()
    if "--conformance-leg" in sys.argv:
        return _run_conformance_leg("--cpu" in sys.argv)
    if "--conformance" in sys.argv:
        return _main_conformance()
    if "--service" in sys.argv:
        return _main_service()
    if "--async-ab-leg" in sys.argv:
        return _run_async_ab_leg("--cpu" in sys.argv)
    if "--async-ab" in sys.argv:
        return _main_async_ab()
    if "--megakernel-leg" in sys.argv:
        return _run_megakernel_leg("--cpu" in sys.argv)
    if "--megakernel" in sys.argv:
        return _main_megakernel()
    if "--multichip-leg" in sys.argv:
        return _run_multichip_leg("--cpu" in sys.argv)
    if "--multichip" in sys.argv:
        return _main_multichip()
    if "--liveness-leg" in sys.argv:
        return _run_liveness_leg("--cpu" in sys.argv)
    if "--liveness" in sys.argv:
        return _main_liveness()
    if "--swarm-leg" in sys.argv:
        return _run_swarm_leg("--cpu" in sys.argv)
    if "--swarm" in sys.argv:
        return _main_swarm()
    if "--breakdown" in sys.argv:
        return _run_breakdown(
            sys.argv[sys.argv.index("--breakdown") + 1], "--cpu" in sys.argv
        )
    if "--leg" in sys.argv:
        return _run_leg(
            sys.argv[sys.argv.index("--leg") + 1], "--cpu" in sys.argv
        )

    _main_benched()


def _main_benched():
    cpu = "--cpu" in sys.argv
    results = {}
    for leg in ("2pc", "paxos", "ilock", "abd3o", "raft5", "paxos3", "scr4"):
        res = _leg_subprocess(leg, pin_cpu=cpu)
        if res is None:
            # No retry elsewhere: a leg that failed on its backend is a
            # failed bench, reported under the failing leg's own name.
            name = f"2pc-{RM_COUNT}" if leg == "2pc" else leg
            print(
                json.dumps(
                    {
                        "metric": f"{name} exhaustive unique "
                        "states/sec (TpuBfs)",
                        "value": 0,
                        "unit": "unique states/sec",
                        "vs_baseline": 0,
                        "error": f"{leg} leg failed",
                    }
                )
            )
            sys.exit(1)
        results[leg] = res

    primary = results["2pc"]
    line = {
        "metric": f"2pc-{RM_COUNT} exhaustive unique states/sec (TpuBfs)",
        "value": round(primary["rate"], 1),
        "unit": "unique states/sec",
        "vs_baseline": round(primary["rate"] / primary["host_rate"], 3),
        # The denominator is this repo's own pure-Python host BfsChecker —
        # NOT the reference's Rust engine. The reference publishes no
        # absolute numbers (BASELINE.md) and this image has no Rust
        # toolchain to measure one, so the only defensible reference-engine
        # figure is the one implied by the driver's own north-star
        # arithmetic: >=50M states/s at >=20x the 32-thread Rust
        # BfsChecker implies ~2.5M states/s for the Rust engine on paxos.
        "baseline": "host BfsChecker (pure Python), same model, capped run"
        " — NOT the reference Rust engine",
        "ref_engine_estimate": {
            "states_per_sec": 2_500_000,
            "basis": "implied by BASELINE.md north-star (50M/s at 20x the"
            " 32-thread Rust BfsChecker); not measured — no Rust"
            " toolchain on this image, reference publishes no figures."
            " vs_baseline does NOT claim a win over the Rust engine.",
        },
        "unique_states": primary["unique"],
        "wall_s": round(primary["wall_s"], 2),
        "warmup_s": round(primary["warmup_s"], 2),
        "device": primary["device"],
    }
    line["run_mode"] = primary.get("run_mode", "in_bench")
    # Occupancy-adaptive dispatch trajectory (BENCH_r06+): the primary
    # leg's bucket histogram + frontier fill + donation status ride the
    # headline line, per-leg ones ride the loop below.
    if primary.get("bucket_dispatch"):
        line["bucket_dispatch"] = primary["bucket_dispatch"]
    if primary.get("frontier_fill") is not None:
        line["frontier_fill"] = round(primary["frontier_fill"], 4)
    line["donation"] = primary.get("donation", False)
    if primary.get("storage"):
        line["storage"] = primary["storage"]
    if primary.get("hbm_budget_mib") is not None:
        line["hbm_budget_mib"] = primary["hbm_budget_mib"]
    if primary.get("attribution"):
        line["attribution"] = primary["attribution"]
    if primary.get("coverage"):
        line["coverage"] = primary["coverage"]
    if primary.get("pipeline_choice"):
        line["pipeline_choice"] = primary["pipeline_choice"]
    for leg in ("paxos", "ilock", "abd3o", "raft5", "paxos3", "scr4"):
        if leg in results:
            line[f"{leg}_rate"] = round(results[leg]["rate"], 1)
            line[f"{leg}_unique"] = results[leg]["unique"]
            line[f"{leg}_wall_s"] = round(results[leg]["wall_s"], 2)
            line[f"{leg}_device"] = results[leg]["device"]
            if results[leg].get("bucket_dispatch"):
                line[f"{leg}_bucket_dispatch"] = results[leg][
                    "bucket_dispatch"
                ]
            if results[leg].get("frontier_fill") is not None:
                line[f"{leg}_frontier_fill"] = round(
                    results[leg]["frontier_fill"], 4
                )
            if results[leg].get("advisory"):
                line[f"{leg}_advisory"] = True
            if "ttc_s" in results[leg]:
                line[f"{leg}_ttc_s"] = round(results[leg]["ttc_s"], 2)
            if results[leg].get("storage"):
                line[f"{leg}_storage"] = results[leg]["storage"]
            if results[leg].get("attribution"):
                line[f"{leg}_attribution"] = results[leg]["attribution"]
            if results[leg].get("coverage"):
                line[f"{leg}_coverage"] = results[leg]["coverage"]
            if results[leg].get("pipeline_choice"):
                line[f"{leg}_pipeline_choice"] = results[leg][
                    "pipeline_choice"
                ]

    # Judgeability (VERDICT r03 #1b): per-wave stage attribution + roofline
    # for the headline leg and the predicate-heavy ABD leg, run after the
    # timed legs. Each is its own subprocess so a hung breakdown costs
    # its own timeout only.
    for leg in ("2pc", "abd3o", "paxos3"):
        argv = [
            sys.executable, __file__, "--breakdown", leg,
            *_dedup_override_args(),
        ]
        if cpu:
            argv.append("--cpu")
        try:
            r = subprocess.run(argv, timeout=600, stdout=subprocess.PIPE)
            if r.returncode == 0 and r.stdout.strip():
                line[f"breakdown_{leg}"] = json.loads(
                    r.stdout.decode().strip().splitlines()[-1]
                )
        except (subprocess.TimeoutExpired, json.JSONDecodeError):
            log(f"[breakdown {leg}] failed or timed out")

    print(json.dumps(line))


if __name__ == "__main__":
    main()
