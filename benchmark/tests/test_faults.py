"""A run with the timed path broken underneath comes out not correct: a
step that returns its state unchanged, half of each batch left out, and an
answer altered where it is produced. (The cells run on one chip, so there
is no exchange between chips to leave out.)"""

import pytest

from cells import write_tree
from test_harness import measure


@pytest.mark.parametrize("fault", ["StuckStep", "HalfBatch", "AlteredAnswer"])
def test_broken_path_is_not_correct(tmp_path, capsys, fault):
    cell = write_tree(tmp_path, factory=f"faulty:{fault}")
    out = measure(cell, capsys, seconds=0.5)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
