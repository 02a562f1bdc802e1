"""The benchmark's parts that know no particular cell.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; both
are data files found by name:

- ``configs/<config>.json``: how to build the model under test
  (``program.factory`` as ``module:callable`` with ``args``/``kwargs`` and
  an optional ``then`` method), its ``spawn`` arguments, its plain
  reference (``reference.module`` under ``reference/`` with ``args``), the
  byte constants of the roofline, and the reference's pinned answers per
  depth cap (``pins``, keyed by the cap or ``"none"``);
- ``traffic/<traffic>.json``: the ``depth_caps`` a window's checks use.
  Checks run back to back in blocks; each block is every cap once, in an
  order drawn from the seed, so every seed gives the same mix of work;
- ``metrics/<metric>.py``: one reader per metric, ``read(run)``, which
  returns a number or None when there is nothing to read.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import random
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def _read_json(path):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str = HERE


def load_cell(name, root=CHECKOUT, bench_dir=HERE):
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its
    configuration and traffic files read, and the metrics it reports."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if name in m.get("workloads", [name] if m["moves"] in reported else [])
    ]
    return Cell(name, w["chips"], config, traffic, e2e, per_layer, bench_dir)


def depth_caps(traffic, seed):
    """The endless sequence of depth caps (None: no cap) for one run:
    blocks of every cap in ``traffic["depth_caps"]`` once, each block in
    an order drawn from ``seed``."""
    caps = list(traffic["depth_caps"])
    rng = random.Random(seed)
    while True:
        block = caps[:]
        rng.shuffle(block)
        yield from block


def cap_key(cap):
    return "none" if cap is None else str(cap)


def load_reader(name, bench_dir=HERE):
    """``read`` of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(config, bench_dir=HERE):
    ref = config["reference"]
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)
    mod = importlib.import_module("reference." + ref["module"])
    return mod.build(**ref.get("args", {}))


# -- the system under test ---------------------------------------------------


@dataclass
class Check:
    """One whole check, spawn to verdict, on the host clock."""

    cap: Optional[int]
    t_spawn: float
    t_end: float
    unique: int
    states: int
    max_depth: int
    discoveries: Dict[str, list] = field(default_factory=dict)
    error: Optional[str] = None


def build_model(config):
    prog = config["program"]
    module, _, attr = prog["factory"].partition(":")
    obj = getattr(importlib.import_module(module), attr)(
        *prog.get("args", []), **prog.get("kwargs", {})
    )
    if prog.get("then"):
        obj = getattr(obj, prog["then"])()
    return obj


class Program:
    """Runs checks of one configuration through the public API, sharing
    compiled drains and waves across checks under one ``aot_cache``
    namespace, as a resident checking service does."""

    def __init__(self, config, namespace):
        self.model = build_model(config)
        self.spawn = dict(config["spawn"])
        self.namespace = namespace

    @staticmethod
    def _span(name):
        """A profiler annotation: ``tracing.py`` names idle gaps by it."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    def check(self, cap):
        """One check, spawn to verdict. A check that raises is an answer
        that never came: it is recorded with its error, not retried."""
        t0 = time.perf_counter()
        try:
            with self._span("bench.spawn"):
                builder = self.model.checker()
                if cap is not None:
                    builder = builder.target_max_depth(cap)
                checker = builder.spawn_tpu_bfs(
                    **self.spawn, aot_cache=self.namespace
                )
            with self._span("bench.join"):
                checker.join()
            with self._span("bench.verdict"):
                found = checker.discoveries()
                rec = Check(
                    cap=cap,
                    t_spawn=t0,
                    t_end=0.0,
                    unique=checker.unique_state_count(),
                    states=checker.state_count(),
                    max_depth=checker.max_depth(),
                    discoveries={k: p.into_states() for k, p in found.items()},
                )
        except Exception as e:  # noqa: BLE001 - recorded, judged by compare()
            rec = Check(cap, t0, 0.0, -1, -1, -1, error=repr(e))
        rec.t_end = time.perf_counter()
        return rec


def run_window(system, caps, seconds, block):
    """Checks back to back until the first block of ``block`` checks that
    ends at or after ``seconds``; returns the checks and the window's
    (start, end). Closing on whole blocks gives every seed the same work."""
    checks = []
    t0 = time.perf_counter()
    while True:
        rec = system.check(next(caps))
        checks.append(rec)
        print(f"check cap={rec.cap} unique={rec.unique} "
              f"seconds={rec.t_end - rec.t_spawn:.4f}", file=sys.stderr)
        if len(checks) % block == 0 and rec.t_end - t0 >= seconds:
            return checks, t0, rec.t_end


# -- correctness -------------------------------------------------------------


def compare(config, checks, reference):
    """Each check's answer against the reference's pinned answer at its
    depth cap, and each discovery path replayed in the reference.

    Returns ``({name: (value, limit)}, failed)``: every limit is exact (0)
    but ``checked``, which must be at least 1; ``failed`` counts the checks
    with any difference."""
    from reference.bfs import replay

    pins = config["pins"]
    worst = dict(errors=0, unique_off=0, states_off=0, depth_off=0,
                 verdict_off=0, path_bad=0, path_len_off=0)
    failed = 0
    for c in checks:
        if c.error is not None:
            worst["errors"] += 1
            failed += 1
            print(f"check at cap {c.cap} raised {c.error}", file=sys.stderr)
            continue
        pin = pins[cap_key(c.cap)]
        want = pin["discoveries"]
        off = dict(
            unique_off=abs(c.unique - pin["unique"]),
            states_off=abs(c.states - pin["states"]),
            depth_off=abs(c.max_depth - pin["max_depth"]),
            verdict_off=int(set(c.discoveries) != set(want)),
            path_bad=0,
            path_len_off=0,
        )
        for name, states in c.discoveries.items():
            try:
                why = replay(reference, [reference.from_program(s) for s in states], name)
            except (AttributeError, TypeError, ValueError, KeyError) as e:
                why = f"unreadable path: {e!r}"
            if why is not None:
                off["path_bad"] += 1
                print(f"discovery {name!r} at cap {c.cap}: {why}", file=sys.stderr)
            if name in want:
                off["path_len_off"] = max(
                    off["path_len_off"], abs(len(states) - want[name])
                )
        for k in ("verdict_off", "path_bad"):
            worst[k] += off[k]
        for k in ("unique_off", "states_off", "depth_off", "path_len_off"):
            worst[k] = max(worst[k], off[k])
        failed += any(off.values())
    out = {k: (v, 0) for k, v in worst.items()}
    out["checked"] = (len(checks), 1)
    return out, failed


def is_correct(compared):
    return all(
        (v >= lim) if name == "checked" else (v <= lim)
        for name, (v, lim) in compared.items()
    )


# -- compiles ------------------------------------------------------------------


class CompileCounter:
    """Counts, while ``on``, each executable JAX builds (``requests``: a
    compile or a load from the persistent cache) and how many of those
    came from the cache (``cache_loads``), from ``jax.monitoring`` events."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.on = False
        self.requests = 0
        self.cache_loads = 0
        from jax._src import monitoring

        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    @property
    def compiles(self):
        return self.requests - self.cache_loads

    def _event(self, name, **_):
        if self.on and name == self.CACHE_HIT:
            self.cache_loads += 1

    def _duration(self, name, _secs, **_):
        if self.on and name == self.BUILD:
            self.requests += 1


# -- run record read by the metric readers ------------------------------------


@dataclass
class Run:
    """What a metric reader may read: the window's checks, its start and
    end, set-up seconds, the device's peak bytes, the configuration, the
    chip's peaks, and with ``--trace 1`` the tracer's spans (perf_counter
    microseconds) and the device trace summary."""

    cell: Cell
    checks: List[Check]
    t_start: float
    t_end: float
    setup_s: float
    memory_peak_bytes: Optional[int] = None
    peaks: Optional[dict] = None
    spans: Optional[List[dict]] = None
    device: Optional[dict] = None

    @property
    def window_s(self):
        return self.t_end - self.t_start

    def window_spans(self, name):
        lo, hi = self.t_start * 1e6, self.t_end * 1e6
        return [
            e for e in self.spans or ()
            if e.get("name") == name and e.get("ph") == "X" and lo <= e["ts"] <= hi
        ]

