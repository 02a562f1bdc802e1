"""scripts/bench_compare.py: loader shapes (BENCH wrapper, raw bench
line, bare leg line, torn tail), the regression gate's exit codes, and
the trajectory table."""

import json
import os
import subprocess
import sys

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO_DIR, "scripts", "bench_compare.py")

sys.path.insert(0, os.path.join(REPO_DIR, "scripts"))

from bench_compare import load_rates  # noqa: E402


def _bench_line(value, **leg_rates):
    line = {"metric": "2pc-7 exhaustive", "value": value,
            "unit": "unique states/sec", "host_rate": 1234.5}
    for leg, rate in leg_rates.items():
        line[f"{leg}_rate"] = rate
    return line


def _wrapper(path, line, parsed=True):
    text = json.dumps(line)
    record = {
        "n": 6, "cmd": "python bench.py", "rc": 0,
        "tail": text + "\n",
        "parsed": line if parsed else None,
    }
    path.write_text(json.dumps(record))
    return str(path)


def _run(*argv):
    return subprocess.run(
        [sys.executable, SCRIPT, *argv],
        capture_output=True, text=True, timeout=60,
    )


def test_load_rates_from_wrapper_parsed(tmp_path):
    path = _wrapper(
        tmp_path / "a.json",
        _bench_line(9000.0, paxos=5000.0, ilock=100.0),
    )
    rates, advisory, note = load_rates(path)
    assert rates == {"2pc": 9000.0, "paxos": 5000.0, "ilock": 100.0}
    assert note is None
    assert "host" not in rates  # host_rate is the baseline, not a leg


def test_load_rates_salvages_truncated_tail(tmp_path):
    """A killed bench tears the tail mid-line; every complete key it
    still carries must be salvaged (a killed run's record keeps
    parsed=null and a 2000-char tail starting mid-JSON)."""
    text = json.dumps(_bench_line(9000.0, paxos=5000.0, scr4=8863.0))
    record = {"n": 5, "rc": 0, "parsed": None,
              "tail": text[len(text) // 2:]}  # torn: keeps the late keys
    path = tmp_path / "torn.json"
    path.write_text(json.dumps(record))
    rates, _, note = load_rates(str(path))
    assert "scr4" in rates and rates["scr4"] == 8863.0
    assert note is not None  # salvage is flagged to stderr


def test_load_rates_bare_leg_line(tmp_path):
    path = tmp_path / "smoke.json"
    path.write_text(json.dumps({"rate": 4321.0, "unique": 8832,
                                "device": "cpu", "advisory": True}))
    rates, advisory, _ = load_rates(str(path), as_leg="smoke")
    assert rates == {"smoke": 4321.0}
    assert advisory == {"smoke"}


def test_gate_passes_within_threshold(tmp_path):
    old = _wrapper(tmp_path / "old.json", _bench_line(9000.0, paxos=5000.0))
    new = _wrapper(tmp_path / "new.json", _bench_line(8500.0, paxos=5100.0))
    r = _run(old, new, "--threshold", "0.10")  # 2pc -5.6%: inside
    assert r.returncode == 0, r.stderr
    assert "REGRESSION" not in r.stdout


def test_gate_exits_nonzero_on_breach(tmp_path):
    old = _wrapper(tmp_path / "old.json", _bench_line(9000.0, paxos=5000.0))
    new = _wrapper(tmp_path / "new.json", _bench_line(7000.0, paxos=5100.0))
    r = _run(old, new, "--threshold", "0.10")  # 2pc -22%: breach
    assert r.returncode == 1
    assert "REGRESSION" in r.stderr
    assert "2pc" in r.stderr


def test_advisory_legs_never_gate(tmp_path):
    old_line = _bench_line(9000.0, ilock=4786.0)
    old_line["ilock_advisory"] = True
    new_line = _bench_line(9000.0, ilock=2847.0)  # -40%, but advisory
    new_line["ilock_advisory"] = True
    old = _wrapper(tmp_path / "old.json", old_line)
    new = _wrapper(tmp_path / "new.json", new_line)
    r = _run(old, new, "--threshold", "0.10")
    assert r.returncode == 0, r.stderr + r.stdout
    assert "advisory" in r.stdout


def test_dropped_leg_gates_and_new_leg_does_not(tmp_path):
    """A leg that vanished from the new file is a gate breach (a crashed
    leg is worse than a slow one); a brand-new leg is not."""
    old = _wrapper(tmp_path / "old.json", _bench_line(9000.0, paxos=5000.0))
    new = _wrapper(tmp_path / "new.json", _bench_line(8900.0, scr4=8000.0))
    r = _run(old, new)
    assert r.returncode == 1
    assert "DROPPED (gate)" in r.stdout  # paxos missing from new
    assert "(new leg)" in r.stdout  # scr4 missing from old
    assert "paxos" in r.stderr
    r = _run(old, new, "--legs", "2pc,paxos,scr4")
    assert "2pc" in r.stdout
    r = _run(old, new, "--legs", "2pc")
    assert r.returncode == 0  # the shared leg alone is within threshold
    assert "paxos" not in r.stdout and "scr4" not in r.stdout


def test_no_shared_legs_is_table_only(tmp_path):
    """Zero overlap (e.g. a fresh single-leg file vs a full bench line)
    is not a comparable trajectory: table + warning, no gate."""
    old = _wrapper(tmp_path / "old.json", _bench_line(9000.0))
    new = tmp_path / "smoke.json"
    new.write_text(json.dumps({"rate": 4321.0, "unique": 8832}))
    r = _run(old, str(new), "--as-leg", "smoke")
    assert r.returncode == 0, r.stderr + r.stdout
    assert "no shared legs" in r.stderr


def test_legs_filter_typo_errors_instead_of_vacuous_pass(tmp_path):
    old = _wrapper(tmp_path / "old.json", _bench_line(9000.0))
    new = _wrapper(tmp_path / "new.json", _bench_line(100.0))  # -98.9%
    r = _run(old, new, "--legs", "2pc5")  # typo'd leg name
    assert r.returncode == 2
    assert "matches no leg" in r.stderr


def test_trajectory_table_over_three_files(tmp_path):
    paths = [
        _wrapper(tmp_path / f"r{i}.json", _bench_line(1000.0 * i))
        for i in (1, 2, 3)
    ]
    r = _run(*paths)
    assert r.returncode == 0
    assert "r1.json" in r.stdout and "r3.json" in r.stdout
    assert "3,000.0" in r.stdout


def test_real_trajectory_files_compare():
    """The committed BENCH_r02 vs r03 (wrapper records with stdout
    tails) must load and diff without a gate breach at a loose
    threshold — the CPU-cheap verify-recipe invocation."""
    r = _run(
        os.path.join(REPO_DIR, "BENCH_r02.json"),
        os.path.join(REPO_DIR, "BENCH_r03.json"),
        "--threshold", "0.9",
    )
    assert r.returncode == 0, r.stderr + r.stdout
    assert "leg" in r.stdout


def test_unreadable_input_exits_two(tmp_path):
    path = tmp_path / "empty.json"
    path.write_text("{}")
    r = _run(str(path), str(path))
    assert r.returncode == 2
    assert "no leg rates" in r.stderr
