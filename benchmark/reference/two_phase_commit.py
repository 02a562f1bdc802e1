"""Plain reference of two-phase commit (upstream ``examples/2pc.rs``).

A state is ``(rm, tm, prepared, msgs)``: each resource manager's phase,
the transaction manager's phase, the TM's prepared flags, and the message
set as a sorted tuple. Everything is a plain tuple, so equality and
``repr`` are canonical.
"""

from __future__ import annotations

WORKING, PREPARED, COMMITTED, ABORTED = "Working", "Prepared", "Committed", "Aborted"
INIT = "Init"
COMMIT, ABORT = ("Commit",), ("Abort",)


def _with(t, i, v):
    return t[:i] + (v,) + t[i + 1:]


def _add(msgs, m):
    return msgs if m in msgs else tuple(sorted(msgs + (m,), key=repr))


class TwoPhaseCommit:
    def __init__(self, rm_count):
        self.n = rm_count

    def init_states(self):
        return [((WORKING,) * self.n, INIT, (False,) * self.n, ())]

    def successors(self, s):
        rm, tm, prepared, msgs = s
        out = []
        if tm == INIT and all(prepared):
            out.append((rm, COMMITTED, prepared, _add(msgs, COMMIT)))
        if tm == INIT:
            out.append((rm, ABORTED, prepared, _add(msgs, ABORT)))
        for r in range(self.n):
            if tm == INIT and ("Prepared", r) in msgs:
                out.append((rm, tm, _with(prepared, r, True), msgs))
            if rm[r] == WORKING:
                out.append(
                    (_with(rm, r, PREPARED), tm, prepared,
                     _add(msgs, ("Prepared", r)))
                )
                out.append((_with(rm, r, ABORTED), tm, prepared, msgs))
            if COMMIT in msgs:
                out.append((_with(rm, r, COMMITTED), tm, prepared, msgs))
            if ABORT in msgs:
                out.append((_with(rm, r, ABORTED), tm, prepared, msgs))
        return out

    def properties(self):
        return [
            ("abort agreement", "sometimes",
             lambda s: all(x == ABORTED for x in s[0])),
            ("commit agreement", "sometimes",
             lambda s: all(x == COMMITTED for x in s[0])),
            ("consistent", "always",
             lambda s: not (ABORTED in s[0] and COMMITTED in s[0])),
        ]

    def from_program(self, st):
        """The reference state of a state the checker under test reports
        (read by attribute name: ``rm_state``, ``tm_state``,
        ``tm_prepared``, ``msgs``). A reference state is returned as is."""
        if isinstance(st, tuple):
            return st
        msgs = tuple(sorted((tuple(m) for m in st.msgs), key=repr))
        return (tuple(st.rm_state), st.tm_state, tuple(st.tm_prepared), msgs)


def build(rm_count):
    return TwoPhaseCommit(rm_count)
