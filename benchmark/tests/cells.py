"""A benchmark tree of one small cell, written into a temporary directory:
2pc check 3 (288 states) with the 2pc7 configuration's layout, so the
harness runs on the CPU in seconds."""

import json
import os

from harness import HERE, load_cell

PIN_2PC3 = {
    "none": {
        "unique": 288,
        "states": 1146,
        "max_depth": 11,
        "discoveries": {"abort agreement": 4, "commit agreement": 11},
    }
}


def write_tree(root, factory="stateright_tpu.models.two_phase_commit:TwoPhaseSys",
               metrics=None):
    """``root/BENCHMARK.json`` with the cell ``2pc3.full`` and its files
    under ``root/benchmark``; returns the loaded cell."""
    bench = os.path.join(root, "benchmark")
    for sub in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    with open(os.path.join(HERE, "configs", "2pc7.json")) as f:
        cfg = json.load(f)
    cfg.update(name="2pc3", rm_count=3, pins=PIN_2PC3)
    cfg["program"] = {"factory": factory, "args": [3]}
    cfg["reference"]["args"] = {"rm_count": 3}
    cfg["spawn"] = {"frontier_capacity": 64, "table_capacity": 1024}
    with open(os.path.join(bench, "configs", "2pc3.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "full.json"), "w") as f:
        json.dump({"depth_caps": [None]}, f)
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [
        dict(spec["configs"][0], name="2pc3", file="benchmark/configs/2pc3.json")
    ]
    spec["workloads"] = [
        dict(name="2pc3.full", config="2pc3", traffic="full", chips=1, why="test")
    ]
    spec["end_to_end"] = [
        {k: v for k, v in m.items() if k != "workloads"}
        for m in spec["end_to_end"] if m["name"] in ("states_per_s", "setup_s")
    ]
    spec["per_layer"] = metrics or []
    for m in spec["per_layer"]:
        src = os.path.join(HERE, "metrics", m["name"] + ".py")
        if os.path.exists(src):
            with open(src) as f, open(os.path.join(bench, "metrics", m["name"] + ".py"), "w") as g:
                g.write(f.read())
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    for name in ("states_per_s", "setup_s"):
        with open(os.path.join(HERE, "metrics", name + ".py")) as f, open(
            os.path.join(bench, "metrics", name + ".py"), "w"
        ) as g:
            g.write(f.read())
    return load_cell("2pc3.full", root=str(root), bench_dir=bench)
