#!/usr/bin/env python
"""Chip smoke: the device checker's main path, once, on a TPU.

    python chip_smoke.py              # one chip: 2pc check 7, paxos check 3
    python chip_smoke.py --chips 4    # four chips: sharded 2pc check 7 only

Each phase runs a model to completion through the public API
(``checker().spawn_tpu_bfs(...)``, with ``devices=4`` for the sharded
checker over a four-device mesh) with
the bench legs' own spawn arguments (``bench._leg_specs``), asserts the
exact unique-state count the leg pins and every property verdict, and
prints one ``phase {...}`` line: the device, the counts, max depth, wall
seconds and set-up seconds (compilation of the first drain included).
These lines are a smoke check, not a benchmark.

The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or on any count, verdict or exception, the script exits
non-zero and prints no such line. All work runs in this one process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# --chips 4: the sharded path's per-device widths.
SHARDED_FRONTIER_PER_DEVICE = 1 << 11
SHARDED_TABLE_PER_DEVICE = 1 << 18


def device_info():
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def run_phase(name, model, spawn, expected):
    """Run ``model`` to completion, assert ``expected`` unique states and
    every property, print the phase line, and return the checker."""
    builder = model.checker()
    t0 = time.perf_counter()
    checker = builder.spawn_tpu_bfs(**spawn).join()
    wall = time.perf_counter() - t0
    unique = checker.unique_state_count()
    if unique != expected:
        raise AssertionError(f"{name}: {unique} unique states != {expected}")
    checker.assert_properties()
    print(
        "phase "
        + json.dumps(
            {
                "phase": name,
                "device": device_info(),
                "unique": unique,
                "states": checker.state_count(),
                "max_depth": checker.max_depth(),
                "wall_s": wall,
                "setup_s": checker.warmup_seconds,
            }
        ),
        flush=True,
    )
    return checker


def phase_leg(leg, name):
    """One bench leg, run as the bench runs it, held to its pinned count."""
    from bench import _leg_specs

    spec = _leg_specs()[leg]
    run_phase(name, spec["model"](), spec["spawn"], spec["expected"])


def phase_sharded_2pc(n_devices):
    from bench import _leg_specs

    spec = _leg_specs()["2pc"]
    checker = run_phase(
        f"2pc check 7 sharded over {n_devices} devices",
        spec["model"](),
        dict(
            devices=n_devices,
            frontier_per_device=SHARDED_FRONTIER_PER_DEVICE,
            table_capacity_per_device=SHARDED_TABLE_PER_DEVICE,
        ),
        spec["expected"],
    )
    placement = checker.visited_placement
    devices = {device.id for device, _shape in placement}
    print(
        "placement "
        + json.dumps(
            {
                "visited_shards": [
                    {"device": device.id, "shape": list(shape)}
                    for device, shape in placement
                ]
            }
        ),
        flush=True,
    )
    if len(devices) != n_devices:
        raise AssertionError(
            f"visited set on devices {sorted(devices)}, want {n_devices} "
            "distinct"
        )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the sharded path over a 4-device mesh",
    )
    args = parser.parse_args(argv)

    from stateright_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    info = device_info()
    if info["platform"] != "tpu":
        print(f"no TPU: JAX found {info}", file=sys.stderr)
        return 2
    if info["count"] < args.chips:
        print(f"need {args.chips} chips, JAX found {info}", file=sys.stderr)
        return 2

    from stateright_tpu.native import (
        NativeFingerprintStore,
        make_fingerprint_store,
    )

    native = isinstance(make_fingerprint_store(16), NativeFingerprintStore)
    print("fp_store " + json.dumps({"native": native}), flush=True)

    if args.chips == 4:
        phase_sharded_2pc(4)
    else:
        phase_leg("2pc", "2pc check 7")
        phase_leg("paxos3", "paxos check 3")
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
