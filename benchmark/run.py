"""Benchmark of the device model checker: whole checks back to back.

    python3 benchmark/run.py --workload 2pc7.full --seed 7 --seconds 30 --trace 0

One process holds one chip. Set-up builds the cell's model, runs one
warm-up check of every depth cap the cell's traffic uses (so every drain
and wave executable the window needs is compiled or loaded from the
persistent cache), and ends where the window starts. The window runs
whole checks, spawn to verdict with the discovery paths rebuilt, in
blocks of every depth cap of the traffic once, until the first block that
ends at or after ``--seconds``. After it, the device's
peak memory is read, every check's answer is compared with the plain
reference (``reference/``), and one JSON line is printed last on stdout.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics: the tracer's spans over the whole window, and a
profiler trace of its first checks (``TRACE_SECONDS``) reduced by
``tracing.py``. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")
# The profiler covers the window's checks until the first that ends this
# many seconds after the window starts.
TRACE_SECONDS = 5.0


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def device_info(jax):
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def main(argv=None):
    args = parse(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, CHECKOUT)
    import harness

    cell = harness.load_cell(args.workload)
    # The compile cache lives in this checkout, whatever the environment
    # says, so two checkouts never share compiled programs. Every compile
    # is kept, however short, so that only a checkout's first run compiles.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    info = device_info(jax)
    if info["platform"] != "tpu" or info["count"] < cell.chips:
        print(f"need {cell.chips} TPU chip(s); JAX found {info}", file=sys.stderr)
        return 3
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"].get(info["kind"])
    if peaks is None:
        print(f"no peaks for device kind {info['kind']!r} in peaks.json",
              file=sys.stderr)
        return 3
    return measure(args, cell, info, peaks)


def measure(args, cell, info, peaks, system=None):
    """Set-up, window, comparison and result line of one run. ``system``
    replaces the program under test (the tests pass a broken one)."""
    import jax

    import harness

    counter = harness.CompileCounter()
    if system is None:
        system = harness.Program(cell.config, namespace=cell.name)
    caps = harness.depth_caps(cell.traffic, args.seed)
    for cap in sorted(set(cell.traffic["depth_caps"]), key=harness.cap_key):
        system.check(cap)
    setup_s = time.perf_counter() - T_PROCESS

    spans = trace = None
    if args.trace:
        import tracing

        spans = tracing.SpanRecorder()
        trace = tracing.DeviceTrace(os.path.join(CHECKOUT, ".bench_trace"))
        spans.attach()
        trace.start()
        system = tracing.StopAfter(system, trace, TRACE_SECONDS)
    counter.on = True
    checks, t0, t1 = harness.run_window(
        system, caps, args.seconds, len(cell.traffic["depth_caps"])
    )
    counter.on = False
    device = dict(info)
    if trace is not None:
        trace.stop()
        spans.detach()
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    del system
    gc.collect()

    run = harness.Run(
        cell=cell, checks=checks, t_start=t0, t_end=t1, setup_s=setup_s,
        memory_peak_bytes=device["memory_peak_bytes"], peaks=peaks,
    )
    breakdown = None
    if trace is not None:
        run.spans = spans.events
        run.device = trace.reduce(run.spans)
        device["busy_s"] = run.device["busy_s"]
        device["window_s"] = run.device["window_s"]
        breakdown = run.device["breakdown"]
        trace.remove()

    reference = harness.load_reference(cell.config, cell.bench_dir)
    compared, failed = harness.compare(cell.config, checks, reference)
    correct = harness.is_correct(compared)

    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = harness.load_reader(m["name"], cell.bench_dir)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    print(
        f"window: {len(checks)} checks in {run.window_s:.3f} s, "
        f"{counter.compiles} compiles and {counter.cache_loads} cache loads",
        file=sys.stderr,
    )
    for name, (value, limit) in compared.items():
        rel = ">=" if name == "checked" else "<="
        print(f"compare {name}: {value} (limit {rel} {limit})", file=sys.stderr)
    result = {
        "correct": correct,
        "attempted": len(checks),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {
        name: {"value": value, "limit": limit}
        for name, (value, limit) in compared.items()
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
