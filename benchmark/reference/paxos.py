"""Plain reference of single-decree Paxos with register clients (upstream
``examples/paxos.rs``), checked for linearizability.

Servers ``0 .. S-1`` run Paxos; clients ``S .. S+C-1`` each Put one value
and then Get, round-robining servers. The network is unordered and does
not duplicate: a multiset of envelopes, where a delivery removes one copy.
A delivery that changes nothing (no new actor state, nothing sent) is not
a transition. The history is the linearizability tester's state:
completed operations per client, each with the index of every other
client's last completed operation when it was invoked, the operations in
flight, and whether the history is well formed.

A system state is ``(actors, network, history)`` built only from tuples,
ints, strings, bools and None, kept in one canonical order, so equality
and ``repr`` are canonical.

Messages: ``("Put", req, value)``, ``("Get", req)``, ``("PutOk", req)``,
``("GetOk", req, value)``, and the internal ``("Prepare", ballot)``,
``("Prepared", ballot, last_accepted)``, ``("Accept", ballot, proposal)``,
``("Accepted", ballot)``, ``("Decided", ballot, proposal)``. A ballot is
``(round, leader)``, a proposal ``(req, requester, value)``.
"""

from __future__ import annotations

from functools import lru_cache

DEFAULT = "\x00"  # the register's initial value


def _canon(items):
    return tuple(sorted(items, key=repr))


class Paxos:
    def __init__(self, client_count, server_count):
        self.c = client_count
        self.s = server_count
        self.majority = server_count // 2 + 1

    # -- actors -----------------------------------------------------------

    def _server_msg(self, me, st, src, msg):
        """(new state or None, sends) for a server receiving ``msg``."""
        ballot, proposal, prepares, accepts, accepted, decided = st
        peers = [p for p in range(self.s) if p != me]
        kind = msg[0]
        if decided:
            if kind == "Get":
                return None, [(src, ("GetOk", msg[1], accepted[1][2]))]
            return None, []
        if kind == "Put" and proposal is None:
            b = (ballot[0] + 1, me)
            return (
                (b, (msg[1], src, msg[2]), ((me, accepted),), (), accepted,
                 False),
                [(p, ("Prepare", b)) for p in peers],
            )
        if kind == "Prepare" and ballot < msg[1]:
            return (
                (msg[1], proposal, prepares, accepts, accepted, False),
                [(src, ("Prepared", msg[1], accepted))],
            )
        if kind == "Prepared" and msg[1] == ballot:
            got = dict(prepares)
            got[src] = msg[2]
            new_prepares = tuple(sorted(got.items()))
            sends = []
            if len(new_prepares) == self.majority:
                best = max(got.values(), key=lambda a: (0,) if a is None else (1, a))
                if best is not None:
                    proposal = best[1]
                accepted = (ballot, proposal)
                accepts = (me,)
                sends = [(p, ("Accept", ballot, proposal)) for p in peers]
            return (
                (ballot, proposal, new_prepares, accepts, accepted, False),
                sends,
            )
        if kind == "Accept" and ballot <= msg[1]:
            return (
                (msg[1], proposal, prepares, accepts, (msg[1], msg[2]), False),
                [(src, ("Accepted", msg[1]))],
            )
        if kind == "Accepted" and msg[1] == ballot:
            new_accepts = tuple(sorted(set(accepts) | {src}))
            sends = []
            if len(new_accepts) == self.majority:
                decided = True
                sends = [(p, ("Decided", ballot, proposal)) for p in peers]
                sends.append((proposal[1], ("PutOk", proposal[0])))
            return (
                (ballot, proposal, prepares, new_accepts, accepted, decided),
                sends,
            )
        if kind == "Decided":
            return (
                (msg[1], proposal, prepares, accepts, (msg[1], msg[2]), True),
                [],
            )
        return None, []

    def _client_msg(self, me, st, src, msg):
        awaiting, ops = st
        if awaiting is None:
            return None, []
        if msg[0] == "PutOk" and msg[1] == awaiting:
            req = (ops + 1) * me
            dst = (me + ops) % self.s
            # One Put per client, then a Get.
            return (req, ops + 1), [(dst, ("Get", req))]
        if msg[0] == "GetOk" and msg[1] == awaiting:
            return (None, ops + 1), []
        return None, []

    # -- history ----------------------------------------------------------

    @staticmethod
    def _invoke(hist, t, op):
        done, flight, valid = hist
        if not valid:
            return hist
        flying = dict(flight)
        if t in flying:
            return (done, flight, False)
        last = tuple((u, len(h) - 1) for u, h in done if u != t and h)
        flying[t] = (last, op)
        done_d = dict(done)
        done_d.setdefault(t, ())
        return (tuple(sorted(done_d.items())), tuple(sorted(flying.items())),
                True)

    @staticmethod
    def _return(hist, t, ret):
        done, flight, valid = hist
        if not valid:
            return hist
        flying = dict(flight)
        if t not in flying:
            return (done, flight, False)
        last, op = flying.pop(t)
        done_d = dict(done)
        done_d[t] = done_d.get(t, ()) + ((last, op, ret),)
        return (tuple(sorted(done_d.items())), tuple(sorted(flying.items())),
                True)

    @staticmethod
    def _record_out(hist, src, msg):
        if msg[0] == "Put":
            return Paxos._invoke(hist, src, ("Write", msg[2]))
        if msg[0] == "Get":
            return Paxos._invoke(hist, src, ("Read",))
        return hist

    @staticmethod
    def _record_in(hist, dst, msg):
        if msg[0] == "PutOk":
            return Paxos._return(hist, dst, ("WriteOk",))
        if msg[0] == "GetOk":
            return Paxos._return(hist, dst, ("ReadOk", msg[2]))
        return hist

    # -- the system -------------------------------------------------------

    def _send(self, net, hist, src, sends):
        net = dict(net)
        for dst, msg in sends:
            hist = self._record_out(hist, src, msg)
            env = (src, dst, msg)
            net[env] = net.get(env, 0) + 1
        return net, hist

    def init_states(self):
        actors = []
        net, hist = {}, ((), (), True)
        for i in range(self.s):
            actors.append(((0, 0), None, (), (), None, False))
        for i in range(self.s, self.s + self.c):
            value = chr(ord("A") + i - self.s)
            actors.append((i, 1))
            net, hist = self._send(net, hist, i, [(i % self.s, ("Put", i, value))])
        return [(tuple(actors), _canon(net.items()), hist)]

    def successors(self, state):
        actors, net, hist = state
        out = []
        for env, count in net:
            src, dst, msg = env
            if dst < self.s:
                new, sends = self._server_msg(dst, actors[dst], src, msg)
            else:
                new, sends = self._client_msg(dst, actors[dst], src, msg)
            if new is None and not sends:
                continue
            h = self._record_in(hist, dst, msg)
            n = dict(net)
            if count == 1:
                del n[env]
            else:
                n[env] = count - 1
            n, h = self._send(n, h, dst, sends)
            a = actors if new is None else actors[:dst] + (new,) + actors[dst + 1:]
            out.append((a, _canon(n.items()), h))
        return out

    def properties(self):
        def value_chosen(s):
            return any(
                env[2][0] == "GetOk" and env[2][2] != DEFAULT for env, _ in s[1]
            )

        return [
            ("linearizable", "always", lambda s: linearizable(s[2])),
            ("value chosen", "sometimes", value_chosen),
        ]

    # -- states reported by the checker under test --------------------------

    def from_program(self, st):
        """The reference state of a state the checker under test reports,
        read by attribute and class name only: ``actor_states`` (server
        ``ballot``/``proposal``/``prepares``/``accepts``/``accepted``/
        ``is_decided``; client ``awaiting``/``op_count``), ``network.data``
        (envelope -> count, an envelope's ``src``/``dst``/``msg``) and the
        linearizability tester's ``history_by_thread``,
        ``in_flight_by_thread`` and ``is_valid_history``. A reference
        state is returned as is."""
        if isinstance(st, tuple):
            return st
        actors = []
        for i, a in enumerate(st.actor_states):
            if i < self.s:
                actors.append((
                    _tup(a.ballot), _tup(a.proposal), _tup(a.prepares),
                    tuple(sorted(int(x) for x in a.accepts)),
                    _tup(a.accepted), bool(a.is_decided),
                ))
            else:
                actors.append((a.awaiting, a.op_count))
        net = _canon(
            ((int(e.src), int(e.dst), _msg(e.msg)), c)
            for e, c in st.network.data.items()
        )
        h = st.history
        done = tuple(sorted(
            (int(t), tuple((_tup(c), _tup(op), _tup(r)) for c, op, r in ops))
            for t, ops in h.history_by_thread.items()
        ))
        flight = tuple(sorted(
            (int(t), (_tup(c), _tup(op))) for t, (c, op) in h.in_flight_by_thread.items()
        ))
        return (tuple(actors), net, (done, flight, bool(h.is_valid_history)))


def _tup(x):
    """Nested tuples of ints/strs/bools/None; integer-like ids become ints."""
    if x is None or isinstance(x, (bool, str)):
        return x
    if isinstance(x, int):
        return int(x)
    if isinstance(x, (tuple, list)):
        return tuple(_tup(v) for v in x)
    return int(x)


def _msg(m):
    name = type(m).__name__
    if name == "Internal":
        return _tup(m.msg)
    if name == "Put":
        return ("Put", m.request_id, m.value)
    if name == "Get":
        return ("Get", m.request_id)
    if name == "PutOk":
        return ("PutOk", m.request_id)
    if name == "GetOk":
        return ("GetOk", m.request_id, m.value)
    raise ValueError(f"unknown message {m!r}")


@lru_cache(maxsize=1 << 16)
def linearizable(hist):
    """Whether a register history has a linearization: a total order of
    the completed operations, plus any subset of those in flight, that
    respects real time (an operation follows every operation another
    client had completed when it was invoked) and register semantics."""
    done, flight, valid = hist
    if not valid:
        return False
    ops = dict(done)
    threads = tuple(t for t, _ in done)
    flying = dict(flight)

    @lru_cache(maxsize=None)
    def search(value, consumed, pending):
        pos = dict(zip(threads, consumed))
        if all(pos[t] == len(ops[t]) for t in threads):
            return True

        def blocked(last):
            return any(pos.get(u, 0) <= idx for u, idx in last)

        for i, t in enumerate(threads):
            if pos[t] < len(ops[t]):
                last, op, ret = ops[t][pos[t]]
                if blocked(last):
                    continue
                if op[0] == "Write":
                    if ret != ("WriteOk",):
                        continue
                    nxt = op[1]
                elif ret == ("ReadOk", value):
                    nxt = value
                else:
                    continue
                c2 = consumed[:i] + (consumed[i] + 1,) + consumed[i + 1:]
                if search(nxt, c2, pending):
                    return True
            elif t in pending:
                last, op = flying[t]
                if blocked(last):
                    continue
                nxt = op[1] if op[0] == "Write" else value
                if search(nxt, consumed, pending - {t}):
                    return True
        return False

    return search(DEFAULT, (0,) * len(threads), frozenset(flying))


def build(client_count, server_count):
    return Paxos(client_count, server_count)
