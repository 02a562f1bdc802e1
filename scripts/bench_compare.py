#!/usr/bin/env python
"""Diff bench trajectory files into a per-leg delta table with a
regression gate.

    python scripts/bench_compare.py BENCH_r02.json BENCH_r03.json
    python scripts/bench_compare.py BENCH_r*.json            # trajectory
    python scripts/bench_compare.py BENCH_r03.json /tmp/leg.json \
        --as-leg smoke --threshold 0.25

With exactly two inputs, prints old-vs-new per-leg rates and exits
nonzero iff any shared leg's rate regresses past ``--threshold``
(fraction, default 0.10) — the CI-checkable gate the bench trajectory
never had. With more inputs, prints the whole trajectory (legs x files;
no gate). Legs the bench marks advisory (``<leg>_advisory``: sub-second
steady windows, not rate claims) are shown but never gate.

Accepted input shapes, sniffed per file:

- a ``BENCH_r*.json`` wrapper (``{"parsed": {...}, "tail": "..."}``) —
  uses ``parsed`` when present, else regex-salvages rates out of the
  ``tail`` (which may be truncated mid-line: killed benches tear it);
- the raw ``bench.py`` output line itself (``{"metric": ..., "value":
  ..., "<leg>_rate": ...}``);
- a single leg child's JSON line (``{"rate": ..., "unique": ...}``) —
  named via ``--as-leg`` (default: the file stem).

Stdlib-only: trajectory files outlive the runs that wrote them and must
stay comparable on boxes without jax.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import re
import sys

# The primary 2pc leg rides the headline "value" field; every other leg
# is "<leg>_rate". Salvage both shapes straight out of (possibly torn)
# text so a truncated tail still yields every complete key it carries.
# The delimiter lookahead is load-bearing: a tail torn mid-number
# ('"value": 123' from '"value": 123456.7') must be DROPPED, not
# salvaged as a rate that is wrong by orders of magnitude.
_LEG_RATE_RE = re.compile(
    r'"([A-Za-z0-9_]+)_rate"\s*:\s*([0-9.eE+-]+)(?=[,}\s])'
)
_VALUE_RE = re.compile(r'"value"\s*:\s*([0-9.eE+-]+)(?=[,}\s])')
_ADVISORY_RE = re.compile(r'"([A-Za-z0-9_]+)_advisory"\s*:\s*true')
_METRIC_LEG_RE = re.compile(r'"metric"\s*:\s*"([A-Za-z0-9_]+)')

PRIMARY_LEG = "2pc"


def _primary_leg_of(metric) -> str:
    """The leg the headline "value" belongs to: the metric string's
    leading word ("2pc-7 exhaustive ..." -> "2pc", "service aggregate
    ..." -> "service"). Attributing a service-bench aggregate to the
    2pc leg would poison the trajectory gate with an apples-to-oranges
    regression."""
    if not metric:
        return PRIMARY_LEG
    head = re.match(r"[A-Za-z0-9_]+", str(metric))
    return head.group(0) if head else PRIMARY_LEG


def _rates_from_text(text):
    rates, advisory = {}, set()
    m = _VALUE_RE.search(text)
    if m:
        metric = _METRIC_LEG_RE.search(text)
        try:
            rates[
                _primary_leg_of(metric.group(1) if metric else None)
            ] = float(m.group(1))
        except ValueError:
            pass  # interleaved-write garbage ('1.23.4'): DROP, don't die
    for leg, value in _LEG_RATE_RE.findall(text):
        if leg == "host":  # host_rate is the baseline, not a leg
            continue
        try:
            rates[leg] = float(value)
        except ValueError:
            pass
    for (leg,) in (m.groups() for m in _ADVISORY_RE.finditer(text)):
        advisory.add(leg)
    return rates, advisory


def _rates_from_line(line: dict):
    rates, advisory = {}, set()
    if "value" in line:
        try:
            rates[_primary_leg_of(line.get("metric"))] = float(
                line["value"]
            )
        except (TypeError, ValueError):
            pass  # null/garbage from a torn or hand-edited file: DROP
    for key, value in line.items():
        if key.endswith("_rate") and key != "host_rate":
            try:
                rates[key[: -len("_rate")]] = float(value)
            except (TypeError, ValueError):
                pass
        if key.endswith("_advisory") and value:
            advisory.add(key[: -len("_advisory")])
    return rates, advisory


def load_rates(path, as_leg=None):
    """``(rates {leg: states/s}, advisory legs, note)`` for one file."""
    with open(path) as f:
        text = f.read()
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        rates, advisory = _rates_from_text(text)
        return rates, advisory, "unparseable JSON; regex salvage"
    if isinstance(obj, dict) and ("tail" in obj or "parsed" in obj):
        parsed = obj.get("parsed")
        if isinstance(parsed, dict):
            rates, advisory = _rates_from_line(parsed)
            return rates, advisory, None
        rates, advisory = _rates_from_text(obj.get("tail") or "")
        return rates, advisory, "no parsed line; salvaged from tail"
    if isinstance(obj, dict) and ("value" in obj or "metric" in obj):
        rates, advisory = _rates_from_line(obj)
        return rates, advisory, None
    if isinstance(obj, dict) and "rate" in obj:
        leg = as_leg or os.path.splitext(os.path.basename(path))[0]
        advisory = {leg} if obj.get("advisory") else set()
        try:
            return {leg: float(obj["rate"])}, advisory, None
        except (TypeError, ValueError):
            return {}, set(), "null/garbage rate; dropped"
    return {}, set(), "unrecognized shape"


def compare(old, new, threshold, out=sys.stdout):
    """Old-vs-new delta table; returns the legs breaching the gate.

    A non-advisory leg present in old but MISSING from new gates too —
    a leg that crashed entirely is worse than one that merely slowed —
    but only when the files share at least one leg (zero overlap means
    the inputs aren't comparable trajectories, e.g. a bench line vs a
    single fresh leg: table only, caller warned), and only when the new
    side was fully parsed: in a torn-tail salvage a missing key is
    indistinguishable from truncation, so absence there cannot convict."""
    old_rates, old_adv, _ = old
    new_rates, new_adv, new_note = new
    legs = sorted(set(old_rates) | set(new_rates))
    comparable = bool(set(old_rates) & set(new_rates))
    new_complete = new_note is None
    breaches = []
    header = (
        f"{'leg':<10} {'old /s':>12} {'new /s':>12} {'delta':>8}  flag"
    )
    out.write(header + "\n" + "-" * len(header) + "\n")
    for leg in legs:
        a, b = old_rates.get(leg), new_rates.get(leg)
        if a is None or b is None:
            dropped = b is None
            gates = (
                dropped and comparable and new_complete
                and leg not in old_adv
            )
            if gates:
                breaches.append(leg)
            flag = (
                "DROPPED (gate)" if gates
                else "(dropped?)" if dropped and not new_complete
                else "(dropped)" if dropped
                else "(new leg)"
            )
            out.write(
                f"{leg:<10} {_fmt(a):>12} {_fmt(b):>12} {'':>8}  {flag}\n"
            )
            continue
        delta = (b - a) / a if a else 0.0
        advisory = leg in old_adv or leg in new_adv
        breached = delta < -threshold and not advisory
        if breached:
            breaches.append(leg)
        flag = (
            "REGRESSION" if breached
            else "advisory" if advisory and delta < -threshold
            else ""
        )
        out.write(
            f"{leg:<10} {a:>12,.1f} {b:>12,.1f} {delta:>+7.1%}  {flag}\n"
        )
    if not comparable:
        print(
            "warning: no shared legs between the two inputs; "
            "nothing gated",
            file=sys.stderr,
        )
    return breaches


def trajectory(loaded, out=sys.stdout):
    """Legs x files rate table over the whole trajectory (no gate)."""
    names = [os.path.basename(p) for p, _ in loaded]
    legs = sorted({leg for _, (rates, _, _) in loaded for leg in rates})
    width = max(12, max((len(n) for n in names), default=12) + 1)
    out.write(f"{'leg':<10}" + "".join(f"{n:>{width}}" for n in names) + "\n")
    for leg in legs:
        row = f"{leg:<10}"
        for _, (rates, _, _) in loaded:
            value = rates.get(leg)
            row += f"{_fmt(value):>{width}}"
        out.write(row + "\n")


def _fmt(value):
    return f"{value:,.1f}" if value is not None else "-"


def _leg_utilization(leg):
    """(realized, predicted-under-full-overlap) from one A/B leg's
    attribution record; (None, None) when the ledger is absent."""
    att = leg.get("attribution") or {}
    realized = att.get("utilization")
    oh = att.get("overlap_headroom") or {}
    device = (att.get("phases_s") or {}).get("device")
    predicted_wall = oh.get("predicted_wall_s")
    predicted = (
        device / predicted_wall
        if device is not None and predicted_wall
        else None
    )
    return realized, predicted


def service_trajectory(paths, out=sys.stdout):
    """Concurrent-throughput trajectory across service bench records
    (r10 time-sliced -> r12 tenant-packed): aggregate states/s, its
    ratio to the single-job rate (the "concurrency tax"), ttfv
    latencies, preempt counts, and lane fill where the record carries
    pack accounting. Renders every file that holds a ``per_job``
    record; exits nonzero when fewer than two do (nothing to compare)."""
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)))
    )
    from service_report import load_record

    rows = []
    for path in paths:
        rec = load_record(path)
        if rec is None:
            print(f"note: {path}: no service record", file=sys.stderr)
            continue
        agg = rec.get("aggregate_states_per_s")
        steady = rec.get("aggregate_steady_states_per_s", agg)
        single = rec.get("single_job_rate")
        pack = rec.get("pack") or {}
        rows.append(
            {
                "name": os.path.basename(path),
                "mode": "packed" if rec.get("packed") else "sliced",
                "jobs": rec.get("jobs"),
                "aggregate": agg,
                "steady": steady,
                "ratio": (
                    steady / single if steady and single else None
                ),
                "p50": rec.get("p50_ttfv_s"),
                "p99": rec.get("p99_ttfv_s"),
                "preempts": rec.get("preempts_total"),
                "lane_fill": pack.get("lane_fill"),
            }
        )
    if len(rows) < 2:
        print(
            "error: need >= 2 files with service records "
            "(bench.py --service / --service-packed output)",
            file=sys.stderr,
        )
        return 2
    header = (
        f"{'record':<16} {'mode':>7} {'jobs':>5} {'agg/s':>10} "
        f"{'steady/s':>10} {'vs-1job':>8} {'p50ttfv':>8} {'p99ttfv':>8} "
        f"{'preempts':>8} {'lanefill':>8}\n"
    )
    out.write(header)
    out.write("-" * (len(header) - 1) + "\n")

    def cell(v, spec="{:,.1f}"):
        return "-" if v is None else spec.format(v)

    for r in rows:
        out.write(
            f"{r['name']:<16} {r['mode']:>7} {str(r['jobs']):>5} "
            f"{cell(r['aggregate']):>10} {cell(r['steady']):>10} "
            f"{cell(r['ratio'], '{:.2f}x'):>8} "
            f"{cell(r['p50'], '{:.2f}s'):>8} "
            f"{cell(r['p99'], '{:.2f}s'):>8} "
            f"{str(r['preempts']):>8} "
            f"{cell(r['lane_fill'], '{:.2f}'):>8}\n"
        )
    first, last = rows[0], rows[-1]
    if first["aggregate"] and last["aggregate"]:
        out.write(
            f"\nconcurrent aggregate {first['name']} -> {last['name']}: "
            f"{first['aggregate']:,.1f} -> {last['aggregate']:,.1f} "
            f"states/s ({last['aggregate'] / first['aggregate']:.2f}x)\n"
        )
    return 0


def ab_async_report(path, out=sys.stdout):
    """The async-pipeline A/B table from one ``bench.py --async-ab``
    record (BENCH_r11+): rate and pipeline-utilization deltas between
    the async-off and async-on legs, with the async-off ledger's
    PREDICTED utilization (the PR-7 headroom estimate) next to the
    async-on leg's REALIZED one — the instrument closing its own loop.
    Always advisory (exit 0 when both legs parsed): CPU boxes make
    rate claims noise; the bit-identical assert lives in the bench
    child itself."""
    with open(path) as f:
        obj = json.load(f)
    rec = obj.get("parsed") if isinstance(obj, dict) and "parsed" in obj \
        else obj
    if not isinstance(rec, dict):
        print(f"error: {path}: no parsed A/B record", file=sys.stderr)
        return 2
    off, on = rec.get("async_off"), rec.get("async_on")
    if not off or not on:
        print(
            f"error: {path}: record carries no async_off/async_on legs "
            "(produce one with bench.py --async-ab)",
            file=sys.stderr,
        )
        return 2
    u_off, predicted = _leg_utilization(off)
    u_on, _ = _leg_utilization(on)
    header = (
        f"{'':<14} {'async off':>12} {'async on':>12} {'delta':>8}"
    )
    out.write(header + "\n" + "-" * len(header) + "\n")
    r_off, r_on = off.get("rate"), on.get("rate")
    rate_delta = (
        f"{(r_on - r_off) / r_off:+.1%}" if r_off and r_on else ""
    )
    out.write(
        f"{'states/s':<14} {_fmt(r_off):>12} {_fmt(r_on):>12} "
        f"{rate_delta:>8}\n"
    )
    def pct(v):
        return f"{100.0 * v:.1f}%" if v is not None else "-"
    util_delta = (
        f"{100.0 * (u_on - u_off):+.1f}pp"
        if u_on is not None and u_off is not None
        else ""
    )
    out.write(
        f"{'utilization':<14} {pct(u_off):>12} {pct(u_on):>12} "
        f"{util_delta:>8}\n"
    )
    out.write(
        f"{'predicted':<14} {pct(predicted):>12} {'(realized ^)':>12}\n"
    )
    overlapped = on.get("overlapped_total_s")
    if overlapped is not None:
        out.write(
            f"achieved overlap: {overlapped:.2f}s host work run on the "
            "pipeline worker (upper bound on wall saved; the realized "
            "saving is the rate/utilization delta above)\n"
        )
    if rec.get("bit_identical") is not None:
        out.write(f"bit-identical: {rec['bit_identical']}\n")
    return 0


def megakernel_report(path, out=sys.stdout):
    """The fused-wave megakernel A/B table from one ``bench.py
    --megakernel`` record (BENCH_r16): per-model staged-vs-fused
    utilization, gap share, dispatch windows per wave (the staged
    chain's ``device`` windows vs the fused path's single
    ``wave_kernel`` dispatch), and rate. Always advisory (exit 0 when
    the record parsed): on CPU the fused kernel runs under the Pallas
    interpreter, so wall/utilization are the interpreter's cost — the
    bit-identical assert lives in the bench child itself."""
    with open(path) as f:
        obj = json.load(f)
    rec = obj.get("parsed") if isinstance(obj, dict) and "parsed" in obj \
        else obj
    if not isinstance(rec, dict) or "models" not in rec:
        print(
            f"error: {path}: no megakernel A/B record (produce one with "
            "bench.py --megakernel)",
            file=sys.stderr,
        )
        return 2
    out.write(
        f"fused wave megakernel A/B ({rec.get('device')}"
        + (", advisory" if rec.get("advisory") else "")
        + ")\n"
    )

    def pct(v):
        return f"{100.0 * v:.1f}%" if v is not None else "-"

    def windows(leg):
        w = leg.get("phase_windows") or {}
        n = w.get("wave_kernel", w.get("device"))
        waves = (leg.get("attribution") or {}).get("waves")
        if n is None or not waves:
            return "-"
        return f"{n}/{waves}w"

    for mname, m in rec["models"].items():
        staged, fused = m.get("staged") or {}, m.get("fused") or {}
        out.write(f"\n{mname}\n")
        header = (
            f"{'':<14} {'staged':>12} {'fused':>12} {'delta':>9}"
        )
        out.write(header + "\n" + "-" * len(header) + "\n")
        u_s, u_f = staged.get("utilization"), fused.get("utilization")
        u_delta = (
            f"{100.0 * (u_f - u_s):+.1f}pp"
            if u_s is not None and u_f is not None
            else ""
        )
        out.write(
            f"{'utilization':<14} {pct(u_s):>12} {pct(u_f):>12} "
            f"{u_delta:>9}\n"
        )
        g_s, g_f = staged.get("gap_share"), fused.get("gap_share")
        g_delta = (
            f"{100.0 * (g_f - g_s):+.1f}pp"
            if g_s is not None and g_f is not None
            else ""
        )
        out.write(
            f"{'gap share':<14} {pct(g_s):>12} {pct(g_f):>12} "
            f"{g_delta:>9}\n"
        )
        out.write(
            f"{'dispatches':<14} {windows(staged):>12} "
            f"{windows(fused):>12}\n"
        )
        r_s, r_f = staged.get("rate"), fused.get("rate")
        rate_delta = (
            f"{(r_f - r_s) / r_s:+.1%}" if r_s and r_f else ""
        )
        out.write(
            f"{'states/s':<14} {_fmt(r_s):>12} {_fmt(r_f):>12} "
            f"{rate_delta:>9}\n"
        )
        if m.get("bit_identical") is not None:
            out.write(f"bit-identical: {m['bit_identical']}\n")
    return 0


def swarm_report(path, out=sys.stdout):
    """The swarm-verification table from one ``bench.py --swarm``
    record (BENCH_r15): per-leg time-to-first-violation (swarm vs
    exhaustive where exhaustive exists), walk throughput, and the
    honest unique-coverage sample. Always advisory (exit 0 when the
    record parsed): wall-clock claims are noise on shared CPU boxes;
    the determinism asserts live in the bench child and the tier-1
    suite."""
    with open(path) as f:
        rec = None
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict) and "swarm" in obj:
                rec = obj
    if rec is None:
        print(
            f"error: {path}: no swarm record found (produce one with "
            "bench.py --swarm)",
            file=sys.stderr,
        )
        return 2
    sw = rec["swarm"]
    out.write(
        f"swarm verification ({rec.get('device')}"
        + (", advisory" if rec.get("advisory") else "")
        + ")\n\n"
    )
    header = (
        f"{'leg':<24} {'swarm ttfv':>11} {'exhaustive':>11} "
        f"{'speedup':>8} {'sample uniq':>12}"
    )
    out.write(header + "\n" + "-" * len(header) + "\n")

    def sample_cell(leg):
        s = leg.get("swarm_sample") or leg.get("sample") or {}
        u = s.get("unique_sample")
        if u is None:
            return "-"
        return ("≥" if s.get("saturated") else "") + f"{u:,}"

    raft = sw.get("raft3_check_live") or {}
    out.write(
        f"{'raft-3 check-live':<24} "
        f"{_fmt(raft.get('swarm_ttfv_s')) + 's':>11} "
        f"{_fmt(raft.get('exhaustive_ttfv_s')) + 's':>11} "
        f"{_fmt(raft.get('speedup')) + 'x':>8} "
        f"{sample_cell(raft):>12}\n"
    )
    two = sw.get("two_phase") or sw.get("two_phase_5") or {}
    two_label = f"{two.get('model', '2pc')} witnesses"
    out.write(
        f"{two_label:<24} "
        f"{_fmt(two.get('swarm_wall_s')) + 's':>11} "
        f"{_fmt(two.get('exhaustive_wall_s')) + 's':>11} "
        f"{'':>8} {sample_cell(two):>12}\n"
    )
    kv = sw.get("sharded_kv") or {}
    if kv.get("exhaustive_found"):
        ex_cell = _fmt(kv.get("exhaustive_ttfv_s")) + "s"
        sp_cell = _fmt(kv.get("speedup_lower_bound")) + "x"
    else:
        budget = kv.get("exhaustive_budget_s")
        bound = kv.get("speedup_lower_bound")
        ex_cell = f">{budget:.0f}s" if budget is not None else "-"
        sp_cell = f">={bound:.0f}x" if bound is not None else "-"
    out.write(
        f"{'sharded_kv 4x8 (~1e14)':<24} "
        f"{_fmt(kv.get('ttfv_s')) + 's':>11} "
        f"{ex_cell:>11} {sp_cell:>8} {sample_cell(kv):>12}\n"
    )
    if not kv.get("exhaustive_found"):
        out.write(
            f"  (exhaustive explored "
            f"{kv.get('exhaustive_states_explored', 0):,} states to "
            f"depth {kv.get('exhaustive_max_depth')} inside its wall "
            "budget without reaching the violation)\n"
        )
    if kv.get("walk_steps_per_s") is not None:
        out.write(
            f"\nwalk throughput: {kv['walk_steps_per_s']:,.0f} "
            f"walk-steps/s over {kv.get('walk_steps', 0):,} steps "
            f"(violation: {kv.get('violation')!r} at depth "
            f"{kv.get('violation_len')})\n"
        )
    return 0


def conformance_report(path, out=sys.stdout):
    """The conformance-plane throughput table from one ``bench.py
    --conformance`` record (BENCH_r20): replay traces/sec and audit
    histories/sec vs batch size (the batching-amortization story), and
    the divergence-rate sweep (flat = the replay kernel stayed
    branchless). Always advisory (exit 0 when the record parsed):
    wall-clock claims are noise on shared CPU boxes; the bit-identity
    asserts live in the parity suite."""
    with open(path) as f:
        rec = None
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict) and "conformance" in obj:
                rec = obj
    if rec is None:
        print(
            f"error: {path}: no conformance record found (produce one "
            "with bench.py --conformance)",
            file=sys.stderr,
        )
        return 2
    conf = rec["conformance"]
    out.write(
        f"conformance plane ({conf.get('device')}, "
        f"{conf.get('model')} traces, T={conf.get('trace_steps')})\n\n"
    )
    header = (
        f"{'batch':>6} {'replay traces/s':>16} {'warm':>9} "
        f"{'cold':>7} {'audit hist/s':>13} {'warm':>9}"
    )
    out.write(header + "\n" + "-" * len(header) + "\n")
    batches = sorted(
        set(conf.get("replay") or {}) | set(conf.get("audit") or {}),
        key=int,
    )
    for b in batches:
        rp = (conf.get("replay") or {}).get(b) or {}
        au = (conf.get("audit") or {}).get(b) or {}

        def ms(v):
            return "-" if v is None else f"{v * 1e3:,.1f}ms"

        out.write(
            f"{b:>6} {_fmt(rp.get('traces_per_s')):>16} "
            f"{ms(rp.get('warm_s')):>9} "
            f"{_fmt(rp.get('cold_s')) + 's':>7} "
            f"{_fmt(au.get('histories_per_s')):>13} "
            f"{ms(au.get('warm_s')):>9}\n"
        )
    amort = conf.get("replay_batch_amortization")
    if amort is not None:
        out.write(
            f"\nbatch amortization: {amort:,.0f}x traces/s at the "
            "widest batch vs batch=1\n"
        )
    sweep = conf.get("divergence_sweep") or {}
    if sweep:
        out.write("\ndivergence-rate sweep (widest batch)\n")
        for label, v in sweep.items():
            out.write(
                f"  {label:>6}: {_fmt(v.get('traces_per_s')):>12} "
                f"traces/s ({v.get('divergent_lanes', 0):,} divergent "
                "lanes)\n"
            )
        flat = conf.get("divergence_flatness")
        if flat is not None:
            out.write(
                f"  flatness (min/max): {flat:.2f} "
                "(~1.0 = branchless, rate-independent)\n"
            )
    return 0


def multichip_trajectory(paths, out=sys.stdout):
    """The pod-scale sharding trajectory across ``MULTICHIP_r*.json``
    records (r01 dryruns -> r06 sieve A/B scaling curve): one summary
    row per file keyed on the legacy dryrun fields (``n_devices`` /
    ``rc`` / ``ok`` / ``skipped`` / ``tail``), then the newest record's
    shard-count curve when it carries one. A file absent from the
    series renders as a ``(missing)`` row instead of aborting — the
    early points of a trajectory outlive the boxes that wrote them, and
    one lost file must not hide the rest. Exits nonzero only when no
    input loads at all."""
    rows = []
    for path in paths:
        name = os.path.basename(path)
        try:
            with open(path) as f:
                rec = json.load(f)
        except OSError:
            rows.append((name, None, "(missing)"))
            continue
        except json.JSONDecodeError:
            rows.append((name, None, "(unparseable)"))
            continue
        if not isinstance(rec, dict) or "n_devices" not in rec:
            rows.append((name, None, "(no multichip record)"))
            continue
        rows.append((name, rec, None))
    if not any(rec is not None for _, rec, _ in rows):
        print(
            "error: no readable MULTICHIP record among inputs",
            file=sys.stderr,
        )
        return 2
    header = (
        f"{'record':<20} {'devices':>8} {'verdict':>8} {'states/s':>10}"
        "  note"
    )
    out.write(header + "\n" + "-" * len(header) + "\n")
    newest_curve = None
    for name, rec, note in rows:
        if rec is None:
            out.write(f"{name:<20} {'-':>8} {'-':>8} {'-':>10}  {note}\n")
            continue
        verdict = (
            "skipped" if rec.get("skipped")
            else "ok" if rec.get("ok")
            else f"rc={rec.get('rc')}"
        )
        value = rec.get("value")
        rate = value if isinstance(value, (int, float)) and value else None
        tail = (rec.get("tail") or "").strip()
        tail_note = "" if rec.get("ok") else tail.splitlines()[-1][:44] \
            if tail else ""
        out.write(
            f"{name:<20} {str(rec.get('n_devices', '-')):>8} "
            f"{verdict:>8} {_fmt(rate):>10}  {tail_note}\n"
        )
        if isinstance(rec.get("curve"), list) and rec["curve"]:
            newest_curve = (name, rec["curve"])
    if newest_curve is None:
        out.write(
            "\n(no record carries a scaling curve yet — produce one "
            "with bench.py --multichip)\n"
        )
        return 0
    name, curve = newest_curve
    out.write(f"\nscaling curve ({name}): sieve off vs on per width\n")
    header = (
        f"{'shards':>6} {'off /s':>10} {'on /s':>10} {'bit-id':>7} "
        f"{'lanes/wave':>16} {'reduction':>10} {'kill':>6} {'fp':>9}"
    )
    out.write(header + "\n" + "-" * len(header) + "\n")
    for point in curve:
        off = point.get("sieve_off") or {}
        on = point.get("sieve_on") or {}
        coff, con = off.get("comms") or {}, on.get("comms") or {}
        ident = point.get("bit_identical")
        lanes = (
            f"{coff['lanes_per_wave']:,.0f}->{con['lanes_per_wave']:,.0f}"
            if "lanes_per_wave" in coff and "lanes_per_wave" in con
            else "-"
        )
        reduction = point.get("lane_reduction_x")
        kill = con.get("sieve_kill_rate")
        probes, fps = con.get("bloom_probe_total"), con.get("bloom_fp_total")
        fp_cell = f"{fps}/{probes}" if probes else "-"
        out.write(
            f"{str(point.get('n_shards', '-')):>6} "
            f"{_fmt(off.get('rate')):>10} {_fmt(on.get('rate')):>10} "
            f"{'yes' if ident else '-' if ident is None else 'NO':>7} "
            f"{lanes:>16} "
            f"{(str(reduction) + 'x') if reduction is not None else '-':>10} "
            f"{f'{kill:.0%}' if kill is not None else '-':>6} "
            f"{fp_cell:>9}\n"
        )
    diverged = [
        str(p.get("n_shards"))
        for p in curve
        if p.get("bit_identical") is False
    ]
    if diverged:
        out.write(
            f"\nBIT-IDENTITY BROKEN at shard widths: {', '.join(diverged)}"
            " — the sieve changed results; gate before trusting rates\n"
        )
        return 1
    return 0


def slo_trajectory(paths, out=sys.stdout):
    """The serving-latency trajectory across service bench records
    (r10 time-sliced -> r12 tenant-packed -> r18 SLO ledger): one row
    per file with its best-available ttfv evidence — the full
    queue/compile/explore decomposition where the record carries an
    ``slo`` block (BENCH_r18+), the bare p50/p99 ttfv where it only has
    the legacy service keys (r10/r12), the swarm ttfv where only a
    swarm record exists (r15). A file absent from the series renders as
    a ``(missing)`` row instead of aborting — matching ``--multichip``:
    early trajectory points outlive the boxes that wrote them, and one
    lost file must not hide the rest. Exits nonzero only when no input
    loads at all. After the table, the newest ``slo`` block renders
    per-mode."""
    rows = []
    newest_slo = None
    for path in paths:
        name = os.path.basename(path)
        try:
            with open(path) as f:
                text = f.read()
        except OSError:
            rows.append((name, None, "(missing)"))
            continue
        rec = None
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict):
                rec = obj
        if rec is None:
            rows.append((name, None, "(unparseable)"))
            continue
        slo = rec.get("slo")
        if isinstance(slo, dict) and slo.get("modes"):
            modes = {
                m: v
                for m, v in slo["modes"].items()
                if (v.get("jobs") or 0) > 0
            }
            view = modes.get("packed") or (
                next(iter(modes.values())) if modes else None
            )
            if view is None:
                rows.append((name, None, "(empty slo ledger)"))
                continue
            d = view.get("decomposition") or {}
            comp = view.get("compile") or {}
            rows.append((name, {
                "source": "slo ledger",
                "jobs": sum(v.get("jobs", 0) for v in modes.values()),
                "p50": view["ttfv"].get("p50_s"),
                "p99": view["ttfv"].get("p99_s"),
                "queue": (d.get("queue_s") or {}).get("p50_s"),
                "compile": (d.get("compile_s") or {}).get("p50_s"),
                "explore": (d.get("explore_s") or {}).get("p50_s"),
                # Compile-share columns (r19 warm-start records): the
                # per-job compile-seconds percentiles and the fraction
                # of served jobs that never compiled; None on r18.
                "comp_p50": comp.get("p50_s"),
                "comp_p99": comp.get("p99_s"),
                "comp_free": comp.get("free_fraction"),
            }, None))
            newest_slo = (name, slo)
        elif "p50_ttfv_s" in rec:
            rows.append((name, {
                "source": "packed" if rec.get("packed") else "sliced",
                "jobs": rec.get("jobs"),
                "p50": rec.get("p50_ttfv_s"),
                "p99": rec.get("p99_ttfv_s"),
                "queue": None, "compile": None, "explore": None,
            }, None))
        elif isinstance(rec.get("swarm"), dict):
            raft = rec["swarm"].get("raft3_check_live") or {}
            rows.append((name, {
                "source": "swarm",
                "jobs": None,
                "p50": raft.get("swarm_ttfv_s"),
                "p99": None,
                "queue": None, "compile": None, "explore": None,
            }, None))
        else:
            rows.append((name, None, "(no ttfv data)"))
    if not any(r is not None for _, r, _ in rows):
        print(
            "error: no readable ttfv/SLO record among inputs",
            file=sys.stderr,
        )
        return 2

    def cell(v, spec="{:.3f}"):
        return "-" if v is None else spec.format(v)

    header = (
        f"{'record':<18} {'source':>11} {'jobs':>5} {'ttfv p50':>9} "
        f"{'ttfv p99':>9} {'queue':>8} {'compile':>8} {'explore':>8}"
        "  note"
    )
    out.write(header + "\n" + "-" * len(header) + "\n")
    for name, r, note in rows:
        if r is None:
            out.write(
                f"{name:<18} {'-':>11} {'-':>5} {'-':>9} {'-':>9} "
                f"{'-':>8} {'-':>8} {'-':>8}  {note}\n"
            )
            continue
        out.write(
            f"{name:<18} {r['source']:>11} {str(r['jobs'] or '-'):>5} "
            f"{cell(r['p50']):>9} {cell(r['p99']):>9} "
            f"{cell(r['queue']):>8} {cell(r['compile']):>8} "
            f"{cell(r['explore']):>8}\n"
        )
    if newest_slo is None:
        out.write(
            "\n(no record carries an SLO ledger yet — produce one with "
            "bench.py --slo)\n"
        )
        return 0
    name, slo = newest_slo
    targets = slo.get("targets") or {}
    tgt = (
        ", ".join(f"{k} <= {v}s" for k, v in sorted(targets.items()))
        if targets
        else "none"
    )
    out.write(
        f"\nper-mode ledger ({name}; targets: {tgt})\n"
    )
    header = (
        f"{'mode':<12} {'jobs':>5} {'ttfv p50':>9} {'ttfv p99':>9} "
        f"{'queue':>8} {'compile':>8} {'explore':>8} {'burn':>12}"
    )
    out.write(header + "\n" + "-" * len(header) + "\n")
    for mode, view in slo["modes"].items():
        if not (view.get("jobs") or 0):
            continue
        d = view.get("decomposition") or {}
        burn = view.get("burn_rate") or {}
        burn_cell = (
            ", ".join(f"{k} {v:.1f}x" for k, v in sorted(burn.items()))
            if burn
            else "-"
        )
        out.write(
            f"{mode:<12} {view.get('jobs', 0):>5} "
            f"{cell(view['ttfv'].get('p50_s')):>9} "
            f"{cell(view['ttfv'].get('p99_s')):>9} "
            f"{cell((d.get('queue_s') or {}).get('p50_s')):>8} "
            f"{cell((d.get('compile_s') or {}).get('p50_s')):>8} "
            f"{cell((d.get('explore_s') or {}).get('p50_s')):>8} "
            f"{burn_cell:>12}\n"
        )
    # Compile-share delta between the two newest SLO-ledger records
    # (r18 -> r19): what the warm-start plane bought in per-job compile
    # seconds and compile-free-job fraction.
    ledger_rows = [
        (n, r) for n, r, _ in rows
        if r is not None and r.get("source") == "slo ledger"
    ]
    if len(ledger_rows) >= 2:
        (old_name, old), (new_name, new) = ledger_rows[-2], ledger_rows[-1]
        out.write(f"\ncompile share ({old_name} -> {new_name})\n")

        def pct_cell(v):
            return "-" if v is None else f"{v:.0%}"

        for label, key, fmt in (
            ("compile p50 (s)", "comp_p50", cell),
            ("compile p99 (s)", "comp_p99", cell),
            ("compile-free jobs", "comp_free", pct_cell),
        ):
            ov, nv = old.get(key), new.get(key)
            delta = ""
            if ov is not None and nv is not None and key != "comp_free":
                delta = f"  ({nv - ov:+.3f}s)"
            elif ov is not None and nv is not None:
                delta = f"  ({(nv - ov) * 100:+.0f}pp)"
            out.write(
                f"  {label:<18} {fmt(ov):>9} -> {fmt(nv):>9}{delta}\n"
            )
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Per-leg rate deltas between bench trajectory files, "
        "with a regression threshold gate."
    )
    parser.add_argument("files", nargs="+", help="BENCH_r*.json (or raw "
                        "bench/leg JSON lines); 2 = gated diff, 3+ = "
                        "trajectory table")
    parser.add_argument(
        "--threshold", type=float, default=None,
        help="max tolerated fractional rate drop per leg (default 0.10); "
        "exceeded => exit 1. With 3+ files the trajectory table prints "
        "and an explicit --threshold gates the newest step",
    )
    parser.add_argument(
        "--legs", help="comma-separated leg filter (default: all)"
    )
    parser.add_argument(
        "--as-leg",
        help="leg name for bare single-leg result files (bench.py --leg "
        "output); default: the file stem",
    )
    parser.add_argument(
        "--ab-async", action="store_true",
        help="render the async-pipeline A/B table (rate + predicted vs "
        "realized utilization) from one bench.py --async-ab record",
    )
    parser.add_argument(
        "--megakernel", action="store_true",
        help="render the fused-wave megakernel A/B table (per-model "
        "staged vs fused utilization, gap share, dispatch windows) from "
        "one bench.py --megakernel record",
    )
    parser.add_argument(
        "--swarm", action="store_true",
        help="render the swarm-verification table (ttfv vs exhaustive, "
        "walk throughput, coverage sample) from one bench.py --swarm "
        "record",
    )
    parser.add_argument(
        "--conformance", action="store_true",
        help="render the conformance-plane throughput table (replay "
        "traces/s and audit histories/s vs batch size, divergence-rate "
        "sweep) from one bench.py --conformance record",
    )
    parser.add_argument(
        "--multichip", action="store_true",
        help="render the pod-scale sharding trajectory across "
        "MULTICHIP_r*.json records (per-file verdicts, then the newest "
        "sieve A/B scaling curve); missing files render as rows, not "
        "errors",
    )
    parser.add_argument(
        "--slo", action="store_true",
        help="render the serving-latency trajectory across service "
        "bench records (r10/r12 ttfv -> r18 SLO ledger with "
        "queue/compile/explore decomposition); missing files render as "
        "rows, not errors",
    )
    parser.add_argument(
        "--service-trajectory", action="store_true",
        help="render the concurrent-throughput trajectory across "
        "service bench records (time-sliced r10 vs tenant-packed r12+: "
        "aggregate, ratio to single-job rate, ttfv, preempts, lane "
        "fill)",
    )
    args = parser.parse_args(argv)

    if args.multichip:
        return multichip_trajectory(args.files)

    if args.slo:
        return slo_trajectory(args.files)

    if args.service_trajectory:
        return service_trajectory(args.files)

    if args.megakernel:
        if len(args.files) != 1:
            print(
                "error: --megakernel takes exactly one bench record",
                file=sys.stderr,
            )
            return 2
        try:
            return megakernel_report(args.files[0])
        except (OSError, json.JSONDecodeError) as e:
            print(f"error: {args.files[0]}: {e}", file=sys.stderr)
            return 2

    if args.conformance:
        if len(args.files) != 1:
            print(
                "error: --conformance takes exactly one bench record",
                file=sys.stderr,
            )
            return 2
        try:
            return conformance_report(args.files[0])
        except (OSError, json.JSONDecodeError) as e:
            print(f"error: {args.files[0]}: {e}", file=sys.stderr)
            return 2

    if args.swarm:
        if len(args.files) != 1:
            print(
                "error: --swarm takes exactly one bench record",
                file=sys.stderr,
            )
            return 2
        try:
            return swarm_report(args.files[0])
        except (OSError, json.JSONDecodeError) as e:
            print(f"error: {args.files[0]}: {e}", file=sys.stderr)
            return 2

    if args.ab_async:
        if len(args.files) != 1:
            print(
                "error: --ab-async takes exactly one bench record",
                file=sys.stderr,
            )
            return 2
        try:
            return ab_async_report(args.files[0])
        except (OSError, json.JSONDecodeError) as e:
            print(f"error: {args.files[0]}: {e}", file=sys.stderr)
            return 2

    loaded = []
    for path in args.files:
        try:
            rates, advisory, note = load_rates(path, as_leg=args.as_leg)
        except OSError as e:
            print(f"error: {path}: {e}", file=sys.stderr)
            return 2
        if note:
            print(f"note: {path}: {note}", file=sys.stderr)
        if not rates:
            print(f"error: {path}: no leg rates found", file=sys.stderr)
            return 2
        if args.legs:
            keep = set(args.legs.split(","))
            rates = {k: v for k, v in rates.items() if k in keep}
            if not rates:
                # A typo'd filter must not turn the gate vacuously green.
                print(
                    f"error: {path}: --legs {args.legs!r} matches no leg",
                    file=sys.stderr,
                )
                return 2
        loaded.append((path, (rates, advisory, note)))

    threshold = 0.10 if args.threshold is None else args.threshold

    def gate(base, cand, base_path, out=sys.stdout):
        breaches = compare(base, cand, threshold=threshold, out=out)
        if breaches:
            print(
                f"REGRESSION: {', '.join(breaches)} regressed past "
                f"{threshold:.0%} (or vanished) vs {base_path}",
                file=sys.stderr,
            )
            return 1
        return 0

    if len(loaded) == 2:
        return gate(loaded[0][1], loaded[1][1], loaded[0][0])
    trajectory(loaded)
    if args.threshold is not None:
        # An explicit threshold must never be a silent no-op: gate the
        # newest step of the trajectory (table already printed above).
        if len(loaded) < 2:
            print(
                "error: --threshold needs at least two files to gate "
                "(usage error, not a regression)",
                file=sys.stderr,
            )
            return 2
        return gate(
            loaded[-2][1], loaded[-1][1], loaded[-2][0], out=io.StringIO()
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
