"""Device-side linearizability: bounded-width packed histories + a traceable
serialization-search predicate.

The host ``LinearizabilityTester`` (``semantics/linearizability.py``, ported
from ``/root/reference/src/semantics/linearizability.rs:57-312``) is
recursive and pointer-heavy — infeasible to trace. SURVEY §7's "hard parts"
names the alternative implemented here: *bound op counts and precompute
serializability tables*. For register-protocol workloads every client thread
performs a statically bounded number of operations (``put_count`` Puts then
one Get), so

- the tester state packs into a fixed-width u32 vector: per thread, a
  completed-op count plus ``O`` op slots ``[kind, value, constraint[C]]``
  where ``constraint[p]`` records peer ``p``'s completed-op count at
  invocation time (the host's ``completed_map`` real-time constraint in
  dense form), with slot ``j == count`` holding the in-flight op if any;
- the Wing&Gong search becomes a *data-parallel scan over a precomputed
  interleaving table*: every program-order-respecting interleaving of the
  per-thread op streams (a multinomial — e.g. 6 for 2 threads × 2 ops),
  crossed with the 2^C choices of which in-flight ops to linearize. Each
  (interleaving, inclusion) lane replays the register semantics and the
  real-time constraints with masks; the history is linearizable iff any
  lane validates. All shapes are static, so the whole predicate fuses into
  the wave kernel — no host round trip, unlike the reference where this
  check dominates the hot loop (SURVEY §2.4).

Encoding invariants (bijective with the host tester for reachable register
histories — exact-count parity depends on it):
- ``hist[0]``: ``is_valid_history`` (1/0). Invalid histories freeze.
- thread ``c`` occupies ``1 + c*TW .. 1 + (c+1)*TW`` with
  ``TW = 1 + O*(2+C)``: count word, then op slots in program order.
- op kinds: 0 = absent, 1 = write (value = written char), 2 = read
  (value = returned char for completed reads, 0 while in flight).
- an empty ``history_by_thread`` entry on the host co-occurs with an
  in-flight op, so "thread ever invoked" is recoverable from the slots.
"""

from __future__ import annotations

from itertools import product
from typing import List, Sequence

import numpy as np

from .register import READ, ReadOk, Register, Write, WRITE_OK
from .linearizability import LinearizabilityTester


def _interleavings(C: int, O: int) -> np.ndarray:
    """All orderings of C streams × O slots that respect stream order:
    (S, M) arrays of thread ids and occurrence indexes, M = C*O."""
    M = C * O
    seqs: List[List[int]] = []

    def rec(prefix, used):
        if len(prefix) == M:
            seqs.append(list(prefix))
            return
        for t in range(C):
            if used[t] < O:
                used[t] += 1
                prefix.append(t)
                rec(prefix, used)
                prefix.pop()
                used[t] -= 1

    rec([], [0] * C)
    seq_t = np.array(seqs, np.int32)
    seq_j = np.zeros_like(seq_t)
    for s in range(seq_t.shape[0]):
        occ = [0] * C
        for pos in range(M):
            t = int(seq_t[s, pos])
            seq_j[s, pos] = occ[t]
            occ[t] += 1
    return seq_t, seq_j


class PackedRegisterLinearizability:
    """Packs ``LinearizabilityTester(Register(default))`` histories for
    ``thread_ids`` client threads with at most ``ops_per_thread`` operations
    each, and builds the traceable hooks + predicate."""

    def __init__(
        self,
        thread_ids: Sequence,
        ops_per_thread: int,
        default_value: str,
    ):
        self.thread_ids = [int(t) for t in thread_ids]
        self.C = len(self.thread_ids)
        self.O = ops_per_thread
        self.default_value = default_value
        self.TW = 1 + self.O * (2 + self.C)
        self.width = 1 + self.C * self.TW
        self._dense = {t: c for c, t in enumerate(self.thread_ids)}

    # -- host <-> packed ----------------------------------------------------

    def pack(self, tester: LinearizabilityTester) -> np.ndarray:
        C, O = self.C, self.O
        out = np.zeros((self.width,), np.uint32)
        out[0] = 1 if tester.is_valid_history else 0

        def constraint_vec(completed_map):
            vec = np.zeros((C,), np.uint32)
            for peer, last_idx in completed_map:
                vec[self._dense[int(peer)]] = last_idx + 1
            return vec

        def slot_base(c, j):
            return 1 + c * self.TW + 1 + j * (2 + C)

        for t, entries in tester.history_by_thread.items():
            c = self._dense[int(t)]
            if len(entries) > O:
                raise ValueError(
                    f"thread {t} has {len(entries)} completed ops; "
                    f"ops_per_thread={O} is too small"
                )
            out[1 + c * self.TW] = len(entries)
            for j, (completed_map, op, ret) in enumerate(entries):
                b = slot_base(c, j)
                if op[0] == "Write":
                    out[b] = 1
                    out[b + 1] = ord(op[1])
                else:  # READ; ret = ReadOk(value)
                    out[b] = 2
                    out[b + 1] = ord(ret[1])
                out[b + 2 : b + 2 + C] = constraint_vec(completed_map)
        for t, (completed_map, op) in tester.in_flight_by_thread.items():
            c = self._dense[int(t)]
            j = int(out[1 + c * self.TW])
            if j >= O:
                raise ValueError(
                    f"thread {t} in-flight op overflows ops_per_thread={O}"
                )
            b = slot_base(c, j)
            if op[0] == "Write":
                out[b] = 1
                out[b + 1] = ord(op[1])
            else:
                out[b] = 2
            out[b + 2 : b + 2 + C] = constraint_vec(completed_map)
        return out

    def unpack(self, vec: np.ndarray) -> LinearizabilityTester:
        C, O = self.C, self.O
        vec = np.asarray(vec)
        tester = LinearizabilityTester(Register(self.default_value))
        tester.is_valid_history = bool(vec[0])

        def read_slot(c, j):
            b = 1 + c * self.TW + 1 + j * (2 + C)
            kind = int(vec[b])
            value = int(vec[b + 1])
            constr = vec[b + 2 : b + 2 + C]
            completed_map = tuple(
                sorted(
                    (self.thread_ids[p], int(constr[p]) - 1)
                    for p in range(C)
                    if constr[p] > 0
                )
            )
            return kind, value, completed_map

        from ..actor.actor import Id

        for c, t in enumerate(self.thread_ids):
            tid = Id(t)
            count = int(vec[1 + c * self.TW])
            entries = []
            for j in range(count):
                kind, value, completed_map = read_slot(c, j)
                if kind == 1:
                    entries.append((completed_map, Write(chr(value)), WRITE_OK))
                else:
                    entries.append((completed_map, READ, ReadOk(chr(value))))
            in_flight = None
            if count < O:
                kind, value, completed_map = read_slot(c, count)
                if kind == 1:
                    in_flight = (completed_map, Write(chr(value)))
                elif kind == 2:
                    in_flight = (completed_map, READ)
            if entries or in_flight is not None:
                tester.history_by_thread[tid] = entries
            if in_flight is not None:
                tester.in_flight_by_thread[tid] = in_flight
        return tester

    # -- traceable structure helpers ---------------------------------------

    def _split(self, hist):
        """(valid, counts (C,), slots (C, O, 2+C)) views of the flat vector."""
        C, O = self.C, self.O
        valid = hist[0]
        body = hist[1:].reshape(C, self.TW)
        counts = body[:, 0]
        slots = body[:, 1:].reshape(C, O, 2 + C)
        return valid, counts, slots

    def _join(self, valid, counts, slots):
        import jax.numpy as jnp

        C = self.C
        body = jnp.concatenate(
            [counts[:, None], slots.reshape(C, -1)], axis=1
        )
        return jnp.concatenate([valid[None], body.reshape(-1)])

    # -- traceable recording hooks ------------------------------------------

    def on_invoke(self, hist, c, kind, value, active):
        """Records an invocation by dense thread ``c`` (traced scalar).
        Mirrors host ``on_invoke``: double-in-flight invalidates the
        history; the constraint vector snapshots peer completed counts."""
        import jax.numpy as jnp

        C, O = self.C, self.O
        valid, counts, slots = self._split(hist)
        cnt = counts[c]
        j = jnp.clip(cnt, 0, O - 1).astype(jnp.int32)
        in_flight = slots[c, j, 0] != 0
        overflow = cnt >= O
        bad = in_flight | overflow
        constr = counts.at[c].set(0)
        new_slot = jnp.concatenate(
            [
                jnp.stack([kind.astype(jnp.uint32), value.astype(jnp.uint32)]),
                constr.astype(jnp.uint32),
            ]
        )
        live = active & (valid == 1)
        apply = live & ~bad
        slots = slots.at[c, j].set(
            jnp.where(apply, new_slot, slots[c, j])
        )
        valid = jnp.where(live & bad, jnp.uint32(0), valid)
        return self._join(valid, counts, slots)

    def on_return(self, hist, c, ret_value, active):
        """Records a return for dense thread ``c``: completes the in-flight
        op (reads store the returned value); a return with no in-flight op
        invalidates the history (host ``on_return``)."""
        import jax.numpy as jnp

        C, O = self.C, self.O
        valid, counts, slots = self._split(hist)
        cnt = counts[c]
        j = jnp.clip(cnt, 0, O - 1).astype(jnp.int32)
        kind = slots[c, j, 0]
        has_inflight = (kind != 0) & (cnt < O)
        live = active & (valid == 1)
        apply = live & has_inflight
        is_read = kind == 2
        new_value = jnp.where(
            is_read, ret_value.astype(jnp.uint32), slots[c, j, 1]
        )
        slots = slots.at[c, j, 1].set(jnp.where(apply, new_value, slots[c, j, 1]))
        counts = counts.at[c].add(jnp.where(apply, jnp.uint32(1), jnp.uint32(0)))
        valid = jnp.where(live & ~has_inflight, jnp.uint32(0), valid)
        return self._join(valid, counts, slots)

    # -- the traceable predicate --------------------------------------------

    def predicate(self, real_time: bool = True):
        """Builds ``fn(hist) -> bool``: True iff a serialization exists.
        vmap over state batches; everything is static-shaped.

        ``real_time=False`` drops the recorded real-time constraints and
        decides *sequential consistency* instead (host analog:
        ``SequentialConsistencyTester`` — same search minus
        ``_violates_real_time``). The packed encoding is unchanged; the
        constraint words are simply ignored, so one packed batch can be
        audited under either criterion.

        Implementation: dynamic programming over *consumption vectors*
        instead of enumerating the multinomial × 2^C lane grid
        (``predicate_lanes``, kept for cross-checking). A node is the
        vector of per-thread consumed-op counts — the shared prefix class
        of every interleaving that consumed those ops in any order. Per
        node the DP carries a bitmask over the value universe (default +
        each slot's written/observed value): bit i set iff some
        program-order-respecting prefix reaches this node with register
        value ``U[i]``. Transitions consume thread ``t``'s next op — the
        slot index is the node's own count, so ALL indexing is static (no
        device gathers, unlike the lane grid). Real-time constraints
        depend only on the consumed-count vector, so they are exact per
        node; in-flight inclusion needs no 2^C factor because acceptance
        allows stopping at any node that consumed every COMPLETED op.
        Node count is ``(O+1)^C`` vs ``(C*O)!/(O!)^C * 2^C`` lanes — for
        3 clients × 2 ops: 27 nodes vs 720 lanes; for 4 clients: 81 vs
        40,320. Exactness is pinned against the host Wing&Gong tester on
        every reachable state (tests/test_packed_history.py) and against
        the lane grid on random histories. Reference hot spot this
        replaces: ``/root/reference/src/semantics/linearizability.rs:
        179-284`` (the recursive search the reference re-runs per state).
        """
        import jax
        import jax.numpy as jnp

        C, O = self.C, self.O
        nodes = sorted(
            product(range(O + 1), repeat=C), key=lambda c: (sum(c), c)
        )
        node_idx = {c: i for i, c in enumerate(nodes)}
        default = np.uint32(ord(self.default_value))
        V = 1 + C * O  # value universe: default + one per op slot
        if V > 32:
            # The value-set bitmask is one u32 per DP node; silent bit
            # wraparound would yield wrong verdicts. (The lane grid made
            # such configs unreachable — (C*O)! lanes — so only the DP
            # can even be asked.)
            raise ValueError(
                f"packed linearizability supports at most 31 ops total "
                f"({C} clients x {O} ops = {C * O}); widen the DP value "
                "mask to u64 pairs to go further"
            )
        BITS = jnp.asarray((1 << np.arange(V)).astype(np.uint32))

        def fn(hist):
            # Names the DP's operations in device traces.
            with jax.named_scope("history"):
                valid, counts, slots = self._split(hist)
                U = jnp.concatenate(
                    [
                        jnp.full((1,), default, jnp.uint32),
                        slots[:, :, 1].reshape(-1).astype(jnp.uint32),
                    ]
                )

                def eq_bits(v):
                    return jnp.where(U == v, BITS, jnp.uint32(0)).sum()

                EB = [
                    [eq_bits(slots[t, j, 1]) for j in range(O)]
                    for t in range(C)
                ]
                masks = [jnp.uint32(0)] * len(nodes)
                masks[0] = eq_bits(jnp.uint32(default))
                accept = jnp.bool_(False)
                for i, c in enumerate(nodes):
                    m = masks[i]
                    done = jnp.bool_(True)
                    for t in range(C):
                        done &= jnp.uint32(c[t]) >= counts[t]
                    accept |= done & (m != 0)
                    for t in range(C):
                        j = c[t]
                        if j >= O:
                            continue
                        succ = node_idx[c[:t] + (j + 1,) + c[t + 1 :]]
                        kind = slots[t, j, 0]
                        constr = slots[t, j, 2:]
                        completed = jnp.uint32(j) < counts[t]
                        inflight = (jnp.uint32(j) == counts[t]) & (kind != 0)
                        present = completed | inflight
                        cvec = jnp.asarray(np.array(c, np.uint32))
                        rt_ok = (
                            (cvec >= constr).all() if real_time
                            else jnp.bool_(True)
                        )
                        eb = EB[t][j]
                        write_m = jnp.where(m != 0, eb, jnp.uint32(0))
                        # In-flight reads generate their return: no constraint.
                        read_m = jnp.where(completed, m & eb, m)
                        m_next = jnp.where(
                            kind == 1, write_m, jnp.where(kind == 2, read_m, m)
                        )
                        contrib = jnp.where(
                            present & rt_ok, m_next, jnp.uint32(0)
                        )
                        masks[succ] = masks[succ] | contrib
                return (valid == 1) & accept

        return fn

    def predicate_lanes(self, real_time: bool = True):
        """The original lane-grid predicate (every interleaving × every
        in-flight inclusion as an independent lane) — superseded by the
        consumption-vector DP above, kept as an independent oracle for
        equivalence tests. ``real_time=False`` decides sequential
        consistency (constraint words ignored), mirroring
        ``predicate``."""
        import jax
        import jax.numpy as jnp

        C, O = self.C, self.O
        M = C * O
        seq_t, seq_j = _interleavings(C, O)
        S = seq_t.shape[0]
        masks = np.array(list(product([0, 1], repeat=C)), np.uint32)
        K = masks.shape[0]
        # The (S*K, ...) lane grid: interleaving × in-flight inclusion.
        SEQ_T = jnp.asarray(np.repeat(seq_t, K, axis=0))
        SEQ_J = jnp.asarray(np.repeat(seq_j, K, axis=0))
        MASKS = jnp.asarray(np.tile(masks, (S, 1)))
        default = np.uint32(ord(self.default_value))

        def lane(seq_t_row, seq_j_row, inc, counts, slots):
            val = jnp.uint32(default)
            ok = jnp.bool_(True)
            consumed = jnp.zeros((C,), jnp.uint32)
            for pos in range(M):  # static unroll; M is small
                t = seq_t_row[pos]
                j = seq_j_row[pos]
                kind = slots[t, j, 0]
                v = slots[t, j, 1]
                constr = slots[t, j, 2:]
                completed = j.astype(jnp.uint32) < counts[t]
                inflight = (
                    (j.astype(jnp.uint32) == counts[t])
                    & (kind != 0)
                    & (inc[t] == 1)
                )
                present = completed | inflight
                rt_ok = (
                    (consumed >= constr).all() if real_time
                    else jnp.bool_(True)
                )
                ok &= ~present | rt_ok
                # Register semantics: completed reads must observe the
                # current value; writes update it; in-flight ops generate
                # their return, so they are always valid.
                ok &= ~(present & completed & (kind == 2)) | (val == v)
                val = jnp.where(present & (kind == 1), v, val)
                consumed = consumed.at[t].add(present.astype(jnp.uint32))
            return ok

        def fn(hist):
            valid, counts, slots = self._split(hist)
            ok = jax.vmap(lambda st, sj, m: lane(st, sj, m, counts, slots))(
                SEQ_T, SEQ_J, MASKS
            )
            return (valid == 1) & ok.any()

        return fn
