"""The four-chip cell's layout on 2pc check 3, run on four virtual CPU
devices: ``configs/2pc7x4.json`` with 3 resource managers and small widths,
every check sharded over the four devices and compared with the plain
reference. JAX reads the device count once, so the run is a child process
with its own ``XLA_FLAGS``."""

import json
import os
import re
import subprocess
import sys

from cells import PIN_2PC3, write_tree
from harness import CHECKOUT, HERE

CHILD = r"""
import argparse, json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import harness, run
cell = harness.load_cell("2pc3.sharded4", root=sys.argv[3], bench_dir=sys.argv[4])
info = {"platform": "cpu", "kind": "cpu", "count": 4}
for trace in (0, 1):
    args = argparse.Namespace(workload=cell.name, seed=2**33 + 25, seconds=1.0,
                              trace=trace)
    assert run.measure(args, cell, info, {"hbm_bytes_per_s": 819e9}) == 0
"""


def write_mesh_tree(root):
    """``write_tree``'s 2pc3 tree with the cell ``2pc3.sharded4``: the
    ``2pc7x4`` configuration at 3 resource managers on 4 devices."""
    write_tree(root, metrics=[
        {"name": name, "unit": "u", "better": "lower", "source": "program_span",
         "layer": "l", "moves": "states_per_s", "workloads": ["2pc3.sharded4"]}
        for name in ("exchange.bytes_per_state", "mesh.exe_ms")
    ])
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(HERE, "configs", "2pc7x4.json")) as f:
        cfg = json.load(f)
    cfg.update(name="2pc3x4", rm_count=3, pins=PIN_2PC3)
    cfg["program"]["args"] = [3]
    cfg["reference"]["args"] = {"rm_count": 3}
    cfg["spawn"].update(frontier_per_device=32, table_capacity_per_device=256)
    with open(os.path.join(bench, "configs", "2pc3x4.json"), "w") as f:
        json.dump(cfg, f)
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["configs"].append(dict(spec["configs"][0], name="2pc3x4",
                                file="benchmark/configs/2pc3x4.json"))
    spec["workloads"] = [dict(name="2pc3.sharded4", config="2pc3x4",
                              traffic="full", chips=4, why="test")]
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    return bench


def test_sharded_cell_is_correct_on_four_cpu_devices(tmp_path):
    bench = write_mesh_tree(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", CHILD, HERE, CHECKOUT, str(tmp_path), bench],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    plain, traced = (json.loads(line) for line in out.stdout.splitlines()
                     if line.startswith("{"))
    for line in (plain, traced):
        assert line["correct"] is True and line["failed"] == 0
        assert all(v["value"] == 0 for k, v in line["compared"].items()
                   if k != "checked")
    assert set(plain["metrics"]) == {"states_per_s", "setup_s"}
    assert traced["metrics"]["exchange.bytes_per_state"]["value"] > 0
    assert traced["metrics"]["mesh.exe_ms"]["value"] >= 0
    # Both windows: every executable came from the shared AOT cache.
    assert re.findall(r"checks in [0-9.]+ s, (\d+) compiles", out.stderr) == [
        "0", "0"]
