"""The control (the reference with the FIFO frontier broken, put in the
program's place) comes out not correct on the paxos3.d15 cell, at the
cell's own size (a warm-up check and one window check)."""

import json

import control


def test_fifo_control_is_not_correct(capsys):
    assert control.main(["--workload", "paxos3.d15", "--seed", str(2**32 + 9),
                         "--seconds", "1", "--break", "fifo"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False
    assert out["compared"]["path_len_off"]["value"] >= 1
