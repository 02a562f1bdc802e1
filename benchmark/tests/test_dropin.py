"""A configuration, a traffic mix and a metric dropped into a tree are
found by their names, with no edit to any file already there."""

import json
import os

import harness
from cells import write_tree


def test_new_files_are_found_by_name(tmp_path):
    write_tree(tmp_path)
    bench = tmp_path / "benchmark"
    (bench / "traffic" / "deep.json").write_text(json.dumps({"depth_caps": [6, 7]}))
    (bench / "metrics" / "checks_per_s.py").write_text(
        "def read(run):\n    return len(run.checks) / run.window_s\n"
    )
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append(
        dict(name="2pc3.deep", config="2pc3", traffic="deep", chips=1, why="t")
    )
    spec["per_layer"].append(dict(
        name="checks_per_s", unit="checks/s", better="higher",
        source="host_clock", layer="job entry", moves="states_per_s",
    ))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("2pc3.deep", root=str(tmp_path), bench_dir=str(bench))
    assert cell.traffic["depth_caps"] == [6, 7]
    assert cell.config["name"] == "2pc3"
    # No ``workloads`` key: reported wherever its end-to-end metric is.
    assert [m["name"] for m in cell.per_layer] == ["checks_per_s"]
    run = harness.Run(cell=cell, checks=[None, None], t_start=0.0, t_end=4.0,
                      setup_s=0.0)
    assert harness.load_reader("checks_per_s", cell.bench_dir)(run) == 0.5


def test_shipped_cells_resolve():
    with open(os.path.join(os.path.dirname(harness.HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        for cap in cell.traffic["depth_caps"]:
            assert harness.cap_key(cap) in cell.config["pins"], (w["name"], cap)
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_reader(m["name"]))
