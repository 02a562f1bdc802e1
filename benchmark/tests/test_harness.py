"""The harness on 2pc check 3, driven through its functions on the CPU
(the look for a chip is skipped): whole checks in a window, each answer
compared with the plain reference, one JSON result line."""

import argparse
import json

import harness
import run
from cells import write_tree
from reference import paxos, two_phase_commit
from reference.bfs import bfs

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"hbm_bytes_per_s": 819e9}


def result_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def measure(cell, capsys, seconds=1.0, trace=0, system=None):
    args = argparse.Namespace(workload=cell.name, seed=2**33 + 17,
                              seconds=seconds, trace=trace)
    assert run.measure(args, cell, CPU, PEAKS, system=system) == 0
    return result_line(capsys)


def test_window_of_whole_checks_is_correct(tmp_path, capsys):
    cell = write_tree(tmp_path)
    out = measure(cell, capsys)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"states_per_s", "setup_s"}
    assert out["metrics"]["states_per_s"]["value"] > 0
    assert list(out)[-1] == "compared"
    assert out["compared"]["unique_off"] == {"value": 0, "limit": 0}


def test_traced_run_reports_device_window(tmp_path, capsys):
    cell = write_tree(tmp_path, metrics=[{
        "name": "host.drains_per_mstate", "unit": "drains/Mstate",
        "better": "lower", "source": "program_span", "layer": "host loop",
        "moves": "states_per_s", "workloads": ["2pc3.full"],
    }])
    out = measure(cell, capsys, trace=1)
    assert out["correct"] is True
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["metrics"]["host.drains_per_mstate"]["value"] > 0


def test_depth_caps_are_seeded_blocks():
    traffic = {"depth_caps": [8, 9, 10, 11]}
    a = harness.depth_caps(traffic, 2**40 + 3)
    b = harness.depth_caps(traffic, 2**40 + 3)
    c = harness.depth_caps(traffic, 5)
    first = [next(a) for _ in range(40)]
    assert first == [next(b) for _ in range(40)]
    other = [next(c) for _ in range(40)]
    for seq in (first, other):
        for i in range(0, 40, 4):
            assert sorted(seq[i:i + 4]) == [8, 9, 10, 11]
    assert first != other


def test_reference_counts_at_small_sizes():
    # Upstream's and this repository's known counts.
    assert bfs(two_phase_commit.build(3))["unique"] == 288
    assert bfs(two_phase_commit.build(5))["unique"] == 8832
    assert bfs(paxos.build(2, 3))["unique"] == 16668
    r = bfs(paxos.build(3, 3), depth_cap=8)
    assert (r["unique"], r["states"], r["max_depth"]) == (1969, 3279, 8)


def test_pinned_answers_match_the_reference():
    # The configuration files' pins at the caps cheap enough for a test.
    for name in ("paxos3",):
        cfg = json.load(open(f"{harness.HERE}/configs/{name}.json"))
        model = harness.load_reference(cfg)
        for cap in ("8", "9", "10", "11"):
            r = bfs(model, depth_cap=int(cap))
            assert r == cfg["pins"][cap], cap
