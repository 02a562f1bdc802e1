#!/usr/bin/env python
"""Phase ledger + overlap headroom from an attribution-mode trace JSONL.

    python scripts/gap_report.py TRACE.jsonl [--json]

Reads the JSONL sink an attribution-mode run produced (``bench.py
--attribution --trace-out ...``, or any checker spawned with
``attribution=True`` plus ``get_tracer().add_sink(path)``) and renders,
per checker prefix, the wave-timeline phase ledger the
``<prefix>.pipeline`` spans carry: total wall, per-phase milliseconds and
shares (device compute, host Bloom+run probe, evict/merge/spill,
table growth, checkpoint, compile, residual dispatch gap), and the
**overlap headroom** — the wall-clock a perfect async overlap of the host
phases under device compute would reclaim, the go/no-go number for the
pipelined wave engine (ROADMAP item 2):

    headroom  = min(host_probe + evict + checkpoint, device)
    predicted = wall - headroom

When the trace came from an ``async_pipeline=True`` run, the worker's
``<prefix>.pipeline.overlapped`` spans carry the host time actually
shadowed under device compute; the report then renders the ACHIEVED
overlap next to the prediction — realized utilization (device/wall)
against the utilization a perfect overlap of this ledger's own host
phases would produce — closing the loop the PR-7 headroom estimate
opened. Compare an async-off and an async-on leg of the same model with
``scripts/bench_compare.py --ab-async`` for the per-leg
predicted-vs-realized delta.

``--json`` emits the ledgers as one JSON object instead of the tables
(machine-readable; the tests consume it). The event loader, the
``.pipeline``-span aggregation, and the phase lists are shared with
``trace_summary.py`` (same directory) — stdlib-only, like every trace
reader here: trace files outlive the runs that wrote them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from trace_summary import (  # noqa: E402
    DEVICE_PHASES,
    HOST_OVERLAPPABLE,
    PHASE_ORDER,
    attribution_rows,
    load_events,
)


def collect_ledgers(events):
    """Per-prefix ledgers from the shared ``.pipeline`` aggregation:
    ``{prefix: {"waves": N, "wall_ms": W, "phases_ms": {...}}}`` where
    ``gap`` rides phases_ms like any other phase."""
    ledgers = {}
    for name, g in attribution_rows(events).items():
        prefix = name[: -len(".pipeline")]
        ledgers[prefix] = {
            "waves": g["waves"],
            "wall_ms": g["wall_ms"],
            "phases_ms": dict(g["phases"]),
        }
    return ledgers


def collect_overlapped(events):
    """Per-prefix ACHIEVED-overlap sums from the async worker's
    ``<prefix>.pipeline.overlapped`` spans: ``{prefix: {phase: ms}}``.
    Empty for synchronous (async-off) traces."""
    out = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "")
        if not name.endswith(".pipeline.overlapped"):
            continue
        prefix = name[: -len(".pipeline.overlapped")]
        args = ev.get("args") or {}
        phase = args.get("phase") or "overlapped"
        d = out.setdefault(prefix, {})
        d[phase] = d.get(phase, 0.0) + float(ev.get("dur", 0.0)) / 1e3
    return out


# Cross-shard exchange args the sharded wave spans carry (sieve-and-
# compact routing, PR-17): summed into the per-prefix comms ledger.
COMMS_KEYS = (
    "comms_probes",
    "comms_killed",
    "comms_bloom_probes",
    "comms_bloom_hits",
    "comms_bloom_fps",
    "exchange_lanes",
    "exchange_bytes",
)


def collect_comms(events):
    """Per-prefix exchange-ledger sums from the wave/drain spans that
    carry ``exchange_*`` (and ``comms_*``) args: ``{prefix: {key:
    total}}``. Empty for single-device traces and for sharded runs whose
    exchange shipped nothing (a zero-lane trace has no ledger to
    render)."""
    out = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        if "exchange_lanes" not in args:
            continue
        prefix = ev.get("name", "").rsplit(".", 1)[0]
        led = out.setdefault(prefix, dict.fromkeys(COMMS_KEYS, 0))
        for key in COMMS_KEYS:
            led[key] += int(args.get(key, 0) or 0)
    return out


def comms_block(c):
    """The derived-rate view of one comms ledger (the ``--json`` shape):
    raw sums plus sieve kill rate and the OBSERVED Bloom FP rate — the
    audit number to hold against the filter's design bound."""
    probes, killed = c["comms_probes"], c["comms_killed"]
    bloom_probes, bloom_fps = c["comms_bloom_probes"], c["comms_bloom_fps"]
    return {
        **c,
        "sieve_kill_rate": (killed / probes) if probes else None,
        "bloom_fp_rate_observed": (
            bloom_fps / bloom_probes if bloom_probes else None
        ),
    }


def print_comms(prefix, c, out=sys.stdout):
    out.write(
        f"comms ledger: {prefix} — {c['exchange_lanes']:,} lanes / "
        f"{c['exchange_bytes']:,} bytes shipped cross-shard\n"
    )
    probes, killed = c["comms_probes"], c["comms_killed"]
    if probes:
        out.write(
            f"  sieve: {killed:,}/{probes:,} candidate lanes killed "
            f"pre-exchange ({100.0 * killed / probes:.1f}%)\n"
        )
    bloom_probes, bloom_fps = c["comms_bloom_probes"], c["comms_bloom_fps"]
    if bloom_probes:
        out.write(
            f"  bloom audit: {bloom_fps:,}/{bloom_probes:,} observed "
            f"false positives ({100.0 * bloom_fps / bloom_probes:.3f}%)\n"
        )
    out.write("\n")


# Per-shard fleet columns the sharded wave/drain spans carry
# (telemetry/fleet.py FLEET_COLS — kept in sync by the tier-1 fleet
# report test). Stdlib fold: trace files outlive the runs (and the
# numpy installs) that wrote them.
FLEET_KEYS = (
    "live_lanes",
    "generated",
    "fresh",
    "insert_load",
    "overflow",
    "routed",
    "sieve_hits",
    "probe_ms",
    "evict_ms",
    "evict_bytes",
)


def collect_fleet(events):
    """Per-prefix per-shard sums + slowest-wave tallies from the spans
    carrying ``fleet_*`` columns: ``{prefix: {"shards": n, "hosts": h,
    "waves": W, "cost_waves": C, "totals": {col: [per-shard]},
    "slowest": [per-shard]}}``. Empty for non-fleet traces."""
    out = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        n = args.get("fleet_shards")
        if not n:
            continue
        n = int(n)
        prefix = ev.get("name", "").rsplit(".", 1)[0]
        f = out.setdefault(prefix, {
            "shards": n,
            "hosts": int(args.get("fleet_hosts") or 1),
            "waves": 0,
            "cost_waves": 0,
            "totals": {k: [0.0] * n for k in FLEET_KEYS},
            "slowest": [0] * n,
        })
        try:
            f["waves"] += max(1, int(args.get("waves") or 1))
        except (TypeError, ValueError):
            f["waves"] += 1
        rows = {}
        for key in FLEET_KEYS:
            col = args.get(f"fleet_{key}")
            if isinstance(col, list) and len(col) == n:
                rows[key] = [float(x) for x in col]
                tot = f["totals"][key]
                for d, x in enumerate(rows[key]):
                    tot[d] += float(x)
        # The wave's cost vector: host tier wall when any shard paid one
        # (time dominates), owner-side insert load otherwise — the same
        # straggler definition as the live fold.
        host = [
            rows.get("probe_ms", [0.0] * n)[d]
            + rows.get("evict_ms", [0.0] * n)[d]
            for d in range(n)
        ]
        cost = host if sum(host) > 0 else rows.get(
            "insert_load", rows.get("live_lanes", [0.0] * n)
        )
        if sum(cost) > 0:
            f["cost_waves"] += 1
            f["slowest"][cost.index(max(cost))] += 1
    return out


def _skew(values):
    mean = sum(values) / len(values) if values else 0.0
    if mean <= 0.0:
        return None
    var = sum((v - mean) ** 2 for v in values) / len(values)
    return {
        "max_over_mean": max(values) / mean,
        "cv": var ** 0.5 / mean,
    }


def fleet_block(f):
    """The derived skew/straggler view of one fleet fold (the
    ``--json`` shape): per-shard totals, run-total skew per column, and
    the slowest shards ranked by summed cost."""
    n = f["shards"]
    per_host = max(1, n // max(1, f["hosts"]))
    host_ms = [
        f["totals"]["probe_ms"][d] + f["totals"]["evict_ms"][d]
        for d in range(n)
    ]
    cost = host_ms if sum(host_ms) > 0 else f["totals"]["insert_load"]
    total_cost = sum(cost) or 1.0
    order = sorted(range(n), key=lambda d: -cost[d])
    stragglers = [
        {
            "shard": d,
            "host": d // per_host,
            "share": cost[d] / total_cost,
            "score": n * cost[d] / total_cost,
            "slowest_waves": f["slowest"][d],
            "persistence": (
                f["slowest"][d] / f["cost_waves"]
                if f["cost_waves"]
                else 0.0
            ),
        }
        for d in order[:2]
    ]
    return {
        "shards": n,
        "hosts": f["hosts"],
        "waves": f["waves"],
        "per_shard": [
            {
                "shard": d,
                "host": d // per_host,
                **{k: f["totals"][k][d] for k in FLEET_KEYS},
            }
            for d in range(n)
        ],
        "skew": {
            k: s
            for k in ("live_lanes", "fresh", "insert_load", "probe_ms")
            if (s := _skew(f["totals"][k])) is not None
        },
        "stragglers": stragglers,
    }


def print_fleet(prefix, f, out=sys.stdout):
    b = fleet_block(f)
    out.write(
        f"fleet skew: {prefix} — {b['shards']} shards / "
        f"{b['hosts']} host(s), {b['waves']} waves\n"
    )
    cols = ("live_lanes", "fresh", "insert_load", "probe_ms", "evict_ms")
    header = "  " + f"{'shard':>5}" + "".join(
        f"{c:>13}" for c in cols
    )
    out.write(header + "\n")
    out.write("  " + "-" * (len(header) - 2) + "\n")
    for row in b["per_shard"]:
        out.write(
            f"  {row['shard']:>5}"
            + "".join(f"{row[c]:>13,.1f}" for c in cols)
            + "\n"
        )
    for col, s in b["skew"].items():
        out.write(
            f"  skew[{col}]: max/mean {s['max_over_mean']:.2f}, "
            f"cv {s['cv']:.2f}\n"
        )
    for i, st in enumerate(b["stragglers"]):
        out.write(
            f"  {'straggler' if i == 0 else 'runner-up'}: shard "
            f"{st['shard']} (host {st['host']}) — {100 * st['share']:.1f}% "
            f"of cost, slowest in {st['slowest_waves']}/"
            f"{f['cost_waves']} waves\n"
        )
    out.write("\n")


def overlap_headroom(led):
    """The headroom block for one ledger: always non-null (zero host
    phases => zero headroom, predicted == measured)."""
    phases = led["phases_ms"]
    wall = led["wall_ms"]
    device = sum(phases.get(p, 0.0) for p in DEVICE_PHASES)
    host = sum(phases.get(p, 0.0) for p in HOST_OVERLAPPABLE)
    headroom = min(host, device)
    return {
        "host_overlappable_ms": host,
        "device_ms": device,
        "headroom_ms": headroom,
        "headroom_pct": (100.0 * headroom / wall) if wall else 0.0,
        "predicted_wall_ms": wall - headroom,
    }


def utilization_block(led, overlapped_ms=None):
    """Predicted vs realized utilization for one ledger. ``realized`` is
    what the run measured (device share of wall); ``predicted`` is the
    utilization a perfect overlap of this ledger's own host phases
    would produce. On an async-on trace, ``achieved_overlap_ms`` is the
    host time executed on the pipeline worker — an upper bound on the
    wall actually saved (fractions spent while the checker was blocked
    at an epoch barrier ran against an idle device)."""
    wall = led["wall_ms"]
    oh = overlap_headroom(led)
    predicted_wall = oh["predicted_wall_ms"]
    block = {
        "realized": (oh["device_ms"] / wall) if wall else None,
        "predicted_under_full_overlap": (
            oh["device_ms"] / predicted_wall if predicted_wall else None
        ),
    }
    if overlapped_ms:
        block["achieved_overlap_ms"] = dict(sorted(overlapped_ms.items()))
        block["achieved_overlap_total_ms"] = sum(overlapped_ms.values())
    return block


def _phase_rows(phases_ms):
    known = [p for p in PHASE_ORDER if p in phases_ms]
    extra = sorted(p for p in phases_ms if p not in PHASE_ORDER)
    return known + extra


def print_ledger(prefix, led, overlapped_ms=None, out=sys.stdout):
    wall = led["wall_ms"]
    out.write(
        f"phase ledger: {prefix} ({led['waves']} waves, "
        f"{wall:.1f} ms wall)\n"
    )
    header = f"  {'phase':<12} {'ms':>10} {'share':>7}"
    out.write(header + "\n")
    out.write("  " + "-" * (len(header) - 2) + "\n")
    for phase in _phase_rows(led["phases_ms"]):
        ms = led["phases_ms"][phase]
        share = 100.0 * ms / wall if wall else 0.0
        mark = " *" if phase in HOST_OVERLAPPABLE else ""
        out.write(f"  {phase:<12} {ms:>10.2f} {share:>6.1f}%{mark}\n")
    oh = overlap_headroom(led)
    out.write(
        "  (* host phases an async pipelined engine could overlap)\n"
        f"overlap headroom: {oh['headroom_ms']:.1f} ms "
        f"({oh['headroom_pct']:.1f}% of wall) — predicted wall under "
        f"perfect host/device overlap: {oh['predicted_wall_ms']:.1f} ms\n"
    )
    util = utilization_block(led, overlapped_ms)
    realized = util["realized"]
    predicted = util["predicted_under_full_overlap"]
    out.write(
        f"utilization: realized {100.0 * (realized or 0.0):.1f}% vs "
        f"{100.0 * (predicted or 0.0):.1f}% predicted under full "
        "overlap"
    )
    if overlapped_ms:
        per_phase = " ".join(
            f"{p}={ms:.1f}ms"
            for p, ms in sorted(overlapped_ms.items())
        )
        out.write(
            f"\nachieved overlap (async pipeline): "
            f"{util['achieved_overlap_total_ms']:.1f} ms host work run "
            f"on the pipeline worker ({per_phase}) — an upper bound on "
            "wall saved (barrier-stalled fractions included); the "
            "realized saving is the utilization/wall delta"
        )
    out.write("\n\n")


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Phase ledger + overlap headroom from an "
        "attribution-mode trace JSONL."
    )
    parser.add_argument("trace", help="JSONL trace file (telemetry sink)")
    parser.add_argument(
        "--json", action="store_true",
        help="emit the ledgers as JSON instead of tables",
    )
    parser.add_argument(
        "--fleet", action="store_true",
        help="also render the per-shard fleet skew / straggler view "
        "(sharded runs with fleet=True)",
    )
    args = parser.parse_args(argv)

    events = load_events(args.trace)
    ledgers = collect_ledgers(events)
    overlapped = collect_overlapped(events)
    comms = collect_comms(events)
    fleet = collect_fleet(events) if args.fleet else {}
    if not ledgers and not fleet:
        hint = (
            " or fleet columns" if args.fleet else ""
        )
        print(
            f"no .pipeline attribution spans{hint} in {args.trace} — was "
            "the run spawned with attribution=True?",
            file=sys.stderr,
        )
        return 1
    if args.json:
        out = {
            prefix: {
                **led,
                "overlap_headroom": overlap_headroom(led),
                "utilization": utilization_block(
                    led, overlapped.get(prefix)
                ),
                **(
                    {"overlapped_ms": overlapped[prefix]}
                    if prefix in overlapped
                    else {}
                ),
                **(
                    {"comms": comms_block(comms[prefix])}
                    if prefix in comms
                    else {}
                ),
            }
            for prefix, led in sorted(ledgers.items())
        }
        for prefix, f in sorted(fleet.items()):
            out.setdefault(prefix, {})["fleet"] = fleet_block(f)
        json.dump(out, sys.stdout, indent=2)
        sys.stdout.write("\n")
        return 0
    for prefix, led in sorted(ledgers.items()):
        print_ledger(prefix, led, overlapped.get(prefix))
        if prefix in comms:
            print_comms(prefix, comms[prefix])
    for prefix, f in sorted(fleet.items()):
        print_fleet(prefix, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
