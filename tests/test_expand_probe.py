"""``scripts/expand_probe.py`` on the CPU: with the CPU as both reference
and device every candidate agrees, and a wrong fingerprint is caught and
named by its action."""

import os
import sys

import pytest

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_DIR, "scripts"))

import expand_probe  # noqa: E402
from bench import _leg_specs  # noqa: E402


@pytest.fixture(scope="module")
def paxos():
    spec = _leg_specs()["paxos"]
    model = spec["model"]()
    ref = expand_probe.reference(model, 8)
    assert int(ref["unique"]) == spec["expected"]
    return model, ref


def test_probe_agrees_on_both_waves(paxos):
    model, ref = paxos
    results = expand_probe.compare(model, ref)
    assert [r["wave"] for r in results] == ["packed_expand", "packed_expand_fps"]
    for r in results:
        assert r["candidates"] > 0
        assert r["valid_mismatch"] == r["fp_mismatch"] == 0, r


def test_probe_names_a_wrong_fingerprint(paxos):
    model, ref = paxos
    ref = dict(ref)
    i, a = (int(x) for x in zip(*ref["r_cv"].nonzero()).__next__())
    ref["r_lo"] = ref["r_lo"].copy()
    ref["r_lo"][i, a] ^= 1
    for r in expand_probe.compare(model, ref):
        assert r["fp_mismatch"] == 1
        assert r["first_bad"] == [[i, a]]
        assert r["per_action"][a] == 1


def test_probe_main_round_trips_its_reference(tmp_path, capsys):
    path = str(tmp_path / "ref.npz")
    args = ["--leg", "paxos", "--sample", "64", "--ref", path]
    assert expand_probe.main(args) == 0
    assert os.path.exists(path)
    assert expand_probe.main(args) == 0
    out = capsys.readouterr().out
    assert out.count("reference ") == 1  # the second run loaded it
    assert out.count("probe ") == 4
