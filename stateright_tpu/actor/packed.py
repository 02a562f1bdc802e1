"""Packed actor systems: staging ``ActorModel`` transitions onto the TPU.

The host ``ActorModel`` (``stateright_tpu.actor.model``) enumerates
data-dependent action sets and runs arbitrary Python actor callbacks — the
reference's design (``/root/reference/src/actor/model.rs:214-649``), which
cannot be traced. This module provides the fixed-width staged equivalent
(SURVEY §2.2 names ``ActorModel`` "the prime candidate for the fixed-width
staged transition function"):

- **actor rows**: per-actor state packs into a ``(N, R)`` u32 matrix;
- **network table**: unordered nets use a bounded ``(E,)``-slot envelope
  table (src, dst, msg words, count); identical envelope multisets
  fingerprint identically because the fingerprint view reduces the table
  to an order-insensitive multiset digest (the host hashes networks
  order-insensitively; the commutative digest is the device analog — no
  per-transition sort). Ordered nets use per directed-pair FIFO queues
  ``(N², Q, W)`` with the head always at index 0 (shift-on-consume keeps
  the arrays canonical) — the device analog of the reference's
  ``BTreeMap<(src,dst), VecDeque>`` flows
  (``/root/reference/src/actor/network.rs:46-68``);
- **timers**: one bitmask word per actor;
- **crash faults**: a ``(N,)`` crashed vector when ``max_crashes`` is set;
  excluded from fingerprints via ``packed_fingerprint_view`` to mirror the
  host state hash (reference ``src/actor/model_state.rs:86-97``);
- **dense actions**: Deliver ids (``E`` slots, or ``N²`` flow heads for
  ordered) + Drop ids (lossy only) + ``N×T`` Timeout ids + ``N`` Crash ids
  (when ``max_crashes > 0``), each with a traceable guard;
- **auxiliary history**: codecs with ``history_width > 0`` carry a packed
  history vector updated by traceable ``record_msg_in``/``record_msg_out``
  twins (see ``semantics/packed_linearizability.py``);
- **actor callbacks**: each actor type supplies jax-traceable
  ``on_msg``/``on_timeout`` kernels via an ``ActorPackedCodec``;
  heterogeneous systems dispatch with ``lax.switch``.

The transition semantics mirror the host model exactly — no-op pruning
(``is_no_op``/``is_no_op_with_timer``), deliver-before-send network
effects, fired-timer clearing before command processing — so packed and
host checkers agree on exact state counts (the parity test contract).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

import numpy as np

from ..core.batch import BatchableModel
from .actor import Id, Out
from .model import ActorModel
from .model_state import ActorModelState
from .network import (
    Envelope,
    Network,
    ORDERED,
    UNORDERED_DUPLICATING,
    UNORDERED_NONDUPLICATING,
)
from .timers import Timers


class ActorPackedCodec:
    """Model-specific packing contract consumed by ``PackedActorModel``.

    Widths are static; the traceable kernels receive/return u32 arrays:

    - ``on_msg`` branch (one per actor type):
      ``fn(id, row, src, msg) -> (row', sends, set_bits, cancel_bits, changed)``
      with ``id``/``src`` scalar i32, ``row`` ``(R,)`` u32, ``msg`` ``(W,)``
      u32, ``sends`` ``(S, 1+W)`` u32 (column 0 = destination id, or
      ``SEND_NONE`` for unused rows), timer masks scalar u32, ``changed``
      scalar bool (the analog of returning a new state vs ``None``).
    - ``on_timeout`` branch: ``fn(id, row, timer_id) -> same``.
    """

    SEND_NONE = np.uint32(0xFFFFFFFF)

    msg_width: int
    state_width: int
    # timer value -> bit index by position; immutable default (a mutable
    # class-level list would be shared across every codec subclass).
    timer_values: Sequence[Any] = ()
    send_capacity: int
    # Auxiliary history support (the reference's ``H`` type param,
    # ``/root/reference/src/actor/model.rs:23-55``): 0 means "no history".
    # A codec with ``history_width > 0`` packs the model's history into a
    # ``(history_width,)`` u32 vector that rides in the packed state (and
    # therefore the device fingerprint — history distinguishes states, so
    # it must), and supplies the two traceable hooks mirroring the host's
    # ``record_msg_in`` / ``record_msg_out``.
    history_width: int = 0

    # -- host <-> packed conversions --------------------------------------

    def pack_actor_state(self, actor_index: int, state) -> np.ndarray:
        raise NotImplementedError

    def unpack_actor_state(self, actor_index: int, row: np.ndarray):
        raise NotImplementedError

    def pack_msg(self, msg) -> np.ndarray:
        raise NotImplementedError

    def unpack_msg(self, vec: np.ndarray):
        raise NotImplementedError

    def pack_history(self, history) -> np.ndarray:
        raise NotImplementedError

    def unpack_history(self, vec: np.ndarray):
        raise NotImplementedError

    # -- traceable history hooks (history_width > 0 only) -------------------

    def history_on_deliver(self, model, hist, src, dst, msg):
        """``record_msg_in`` analog: applied on Deliver with the envelope
        being delivered, BEFORE send commands are processed (host order)."""
        raise NotImplementedError

    def history_on_send(self, model, hist, src, dst, msg):
        """``record_msg_out`` analog: applied per Send command, in command
        order, to the already-updated history."""
        raise NotImplementedError

    # -- traceable kernels -------------------------------------------------

    def actor_type_id(self, actor_index: int, actor) -> int:
        return 0

    def on_msg_branches(self, model) -> List[Callable]:
        raise NotImplementedError

    def on_timeout_branches(self, model) -> List[Callable]:
        """Timer-free codecs (empty ``timer_values``) may return []."""
        return []

    # -- traceable symmetry hooks (device symmetry reduction only) ----------

    def rewrite_actor_row(self, model, row, old_to_new):
        """Rewrites embedded actor ids inside one packed state row under a
        permutation (``old_to_new[i]`` = the new id of actor ``i``) — the
        traceable twin of the host ``rewrite_value`` recursion over the
        actor state. The default is the identity: correct ONLY for rows
        with no embedded ids. Codecs whose rows carry ids (votes, leader
        hints, …) must override, or device symmetry counts will diverge
        from the host orbit counts (the parity tests' contract)."""
        return row

    def rewrite_msg_ids(self, model, vec, old_to_new):
        """Same, for embedded ids inside one packed message vector."""
        return vec

    # -- traceable model hooks ---------------------------------------------

    def packed_conditions(self, model) -> List[Callable]:
        raise NotImplementedError

    def packed_within_boundary(self, model, state) -> Any:
        import jax.numpy as jnp

        return jnp.bool_(True)

    def packed_row_within_boundary(self, model, row) -> Any:
        """Per-row boundary check for the fps expansion path. Must satisfy
        ``packed_within_boundary(state) == all rows pass this`` — the fps
        wave checks only the row a transition changed (the parent's other
        rows were admitted already, so the check is inductive). Codecs
        overriding ``packed_within_boundary`` with a per-row predicate
        (e.g. Raft's term cap) MUST override this consistently; boundary
        predicates that are not per-row decompositions cannot use the fps
        path."""
        import jax.numpy as jnp

        return jnp.bool_(True)


class PackedActorModel(ActorModel, BatchableModel):
    """An ``ActorModel`` that additionally implements the packed protocol.

    Build it exactly like an ``ActorModel`` (``.actor()``,
    ``.init_network()``, ``.property()``, …) and attach a codec; the packed
    side is validated lazily on first use so host-only checking of
    unsupported configurations still works.
    """

    def __init__(self, codec: ActorPackedCodec, cfg=None, init_history=None):
        super().__init__(cfg=cfg, init_history=init_history)
        self.codec = codec
        self.envelope_capacity = 32
        self.flow_capacity = 8
        self.flow_pairs = None

    def with_envelope_capacity(self, capacity: int) -> "PackedActorModel":
        """Sets the network table's slot count (unordered networks). Must
        upper-bound the reachable distinct-envelope count: overflowing
        transitions are pruned, which the exact-count parity tests surface
        as a mismatch."""
        self.envelope_capacity = capacity
        return self

    def with_flow_capacity(self, capacity: int) -> "PackedActorModel":
        """Sets the per-flow FIFO depth (ordered networks); analogous
        overflow semantics to ``with_envelope_capacity``."""
        self.flow_capacity = capacity
        return self

    def with_flow_pairs(self, pairs) -> "PackedActorModel":
        """Restricts ordered-network flows to the given directed
        ``(src, dst)`` pairs. The flow arrays and the deliver/drop action
        grid then scale with ``len(pairs)`` instead of ``N^2`` — on the
        3-client ordered ABD register this shrinks the packed state ~4x
        (the N^2 flow table is ~87% of its words, mostly structurally
        dead: register clients never message clients, nobody messages
        itself). A device-side send outside the set behaves as a
        zero-capacity flow (transition pruned — the same boundary
        semantics as ``with_flow_capacity`` overflow, surfaced by the
        exact-count parity tests); host packing of such a state raises.
        Incompatible with full-group ``packed_symmetry`` (the pair set is
        generally not closed under S_N; ``packed_symmetry`` raises)."""
        pairs = [(int(a), int(b)) for a, b in pairs]
        if len(set(pairs)) != len(pairs):
            raise ValueError("flow_pairs contains duplicates")
        self.flow_pairs = pairs
        return self

    def _pair_tables(self):
        """(lookup, src, dst) numpy tables for ordered flows: ``lookup``
        maps ``src*N + dst`` to the flow index (-1 = excluded pair);
        ``src``/``dst`` invert it per flow index. Identity layout when
        ``flow_pairs`` is unset."""
        N = self._N
        if self.flow_pairs is None:
            idx = np.arange(N * N, dtype=np.int32)
            return idx, (idx // N).astype(np.int32), (idx % N).astype(np.int32)
        lookup = np.full((N * N,), -1, np.int32)
        src = np.zeros((len(self.flow_pairs),), np.int32)
        dst = np.zeros_like(src)
        for k, (a, b) in enumerate(self.flow_pairs):
            if not (0 <= a < N and 0 <= b < N):
                raise ValueError(f"flow pair {(a, b)} out of range for N={N}")
            lookup[a * N + b] = k
            src[k], dst[k] = a, b
        return lookup, src, dst

    # -- validation --------------------------------------------------------

    def _packed_check(self):
        if self.init_history is not None and not self.codec.history_width:
            raise NotImplementedError(
                "this codec does not pack auxiliary history (declare "
                "history_width and the history hooks to stage it on device)"
            )
        # Non-empty initial networks need no special staging: host
        # ``init_states`` seeds the ``ActorModelState`` network from
        # ``init_network`` (reference ``src/actor/model.rs:96-100``) plus
        # on-start sends, and ``pack_state`` packs whatever the state's
        # network holds — envelope table and FIFO flows alike. Capacity
        # overflow surfaces as the usual ``ValueError`` at packing time.

    # -- static shape helpers ----------------------------------------------

    @property
    def _N(self) -> int:
        return len(self.actors_list)

    @property
    def _E(self) -> int:
        return self.envelope_capacity

    @property
    def _Q(self) -> int:
        return self.flow_capacity

    @property
    def _P(self) -> int:
        """Directed flow pair count (ordered networks): all ``N^2`` pairs
        laid out as ``src * N + dst``, or the restricted ``flow_pairs``
        list's length."""
        if self.flow_pairs is not None:
            return len(self.flow_pairs)
        return self._N * self._N

    @property
    def _T(self) -> int:
        return len(self.codec.timer_values)

    @property
    def _dup(self) -> bool:
        return self._init_network.kind == UNORDERED_DUPLICATING

    @property
    def _ordered(self) -> bool:
        return self._init_network.kind == ORDERED

    def _timer_bit(self, timer) -> int:
        return self.codec.timer_values.index(timer)

    # -- BatchableModel: shape info ---------------------------------------

    def packed_action_count(self) -> int:
        self._packed_check()
        slots = self._P if self._ordered else self._E
        deliver_drop = slots * (2 if self._lossy_network else 1)
        crash = self._N if self._max_crashes else 0
        return deliver_drop + self._N * self._T + crash

    # -- host <-> packed state conversion ----------------------------------

    def pack_state(self, sys_state: ActorModelState):
        self._packed_check()
        codec = self.codec
        N, E, W, R = self._N, self._E, codec.msg_width, codec.state_width
        rows = np.zeros((N, R), np.uint32)
        for i, actor_state in enumerate(sys_state.actor_states):
            rows[i] = codec.pack_actor_state(i, actor_state)
        timers = np.zeros((N,), np.uint32)
        for i, tset in enumerate(sys_state.timers_set):
            for t in tset:
                timers[i] |= np.uint32(1) << np.uint32(self._timer_bit(t))

        if self._ordered:
            Q, P = self._Q, self._P
            lookup, _, _ = self._pair_tables()
            flow_msg = np.zeros((P, Q, W), np.uint32)
            flow_len = np.zeros((P,), np.uint32)
            for (src, dst), msgs in sys_state.network.data.items():
                if not msgs:
                    continue
                if len(msgs) > Q:
                    raise ValueError(
                        f"flow {src!r}->{dst!r} holds {len(msgs)} messages; "
                        f"flow_capacity={Q} is too small"
                    )
                p = int(lookup[int(src) * N + int(dst)])
                if p < 0:
                    raise ValueError(
                        f"flow {src!r}->{dst!r} holds messages but is not "
                        "in flow_pairs"
                    )
                flow_len[p] = len(msgs)
                for i, m in enumerate(msgs):
                    flow_msg[p, i] = codec.pack_msg(m)
            out = {
                "rows": rows,
                "timers": timers,
                "flow_msg": flow_msg,
                "flow_len": flow_len,
            }
        else:
            envs = []
            if self._init_network.kind == UNORDERED_NONDUPLICATING:
                items = list(sys_state.network.data.items())
            else:
                items = [(env, 1) for env in sys_state.network.data]
            if len(items) > E:
                raise ValueError(
                    f"state has {len(items)} distinct envelopes; "
                    f"envelope_capacity={E} is too small"
                )
            for env, count in items:
                envs.append(
                    (
                        int(env.src),
                        int(env.dst),
                        tuple(int(x) for x in codec.pack_msg(env.msg)),
                        int(count),
                    )
                )
            envs.sort()
            net_src = np.zeros((E,), np.uint32)
            net_dst = np.zeros((E,), np.uint32)
            net_msg = np.zeros((E, W), np.uint32)
            net_cnt = np.zeros((E,), np.uint32)
            for slot, (src, dst, msg, count) in enumerate(envs):
                net_src[slot] = src
                net_dst[slot] = dst
                net_msg[slot] = msg
                net_cnt[slot] = count
            out = {
                "rows": rows,
                "timers": timers,
                "net_src": net_src,
                "net_dst": net_dst,
                "net_msg": net_msg,
                "net_cnt": net_cnt,
            }
        if self._max_crashes:
            out["crashed"] = np.array(
                [1 if c else 0 for c in sys_state.crashed], np.uint32
            )
        if codec.history_width:
            hist = np.asarray(
                codec.pack_history(sys_state.history), np.uint32
            )
            if hist.shape != (codec.history_width,):
                raise ValueError(
                    f"pack_history returned shape {hist.shape}; expected "
                    f"({codec.history_width},)"
                )
            out["hist"] = hist
        return out

    def unpack_state(self, packed) -> ActorModelState:
        codec = self.codec
        rows = np.asarray(packed["rows"])
        timers = np.asarray(packed["timers"])
        actor_states = [
            codec.unpack_actor_state(i, rows[i]) for i in range(self._N)
        ]
        timers_set = []
        for i in range(self._N):
            tset = Timers()
            for b, timer in enumerate(codec.timer_values):
                if int(timers[i]) & (1 << b):
                    tset.set(timer)
            timers_set.append(tset)
        network = self._init_network.copy()
        if self._ordered:
            flow_msg = np.asarray(packed["flow_msg"])
            flow_len = np.asarray(packed["flow_len"])
            _, psrc, pdst = self._pair_tables()
            for p in range(self._P):
                src, dst = Id(int(psrc[p])), Id(int(pdst[p]))
                for i in range(int(flow_len[p])):
                    network.send(
                        Envelope(src=src, dst=dst, msg=codec.unpack_msg(flow_msg[p, i]))
                    )
        else:
            cnt = np.asarray(packed["net_cnt"])
            src = np.asarray(packed["net_src"])
            dst = np.asarray(packed["net_dst"])
            msg = np.asarray(packed["net_msg"])
            for slot in range(self._E):
                if int(cnt[slot]):
                    env = Envelope(
                        src=Id(int(src[slot])),
                        dst=Id(int(dst[slot])),
                        msg=codec.unpack_msg(msg[slot]),
                    )
                    for _ in range(int(cnt[slot])):
                        network.send(env)
        history = None
        if codec.history_width:
            history = codec.unpack_history(np.asarray(packed["hist"]))
        crashed = [False] * self._N
        if self._max_crashes:
            crashed = [bool(c) for c in np.asarray(packed["crashed"])]
        return ActorModelState(
            actor_states=actor_states,
            network=network,
            timers_set=timers_set,
            crashed=crashed,
            history=history,
        )

    def packed_init_states(self):
        import jax.numpy as jnp

        self._packed_check()
        packed = [self.pack_state(s) for s in self.init_states()]
        return {
            k: jnp.stack([np.asarray(p[k]) for p in packed])
            for k in packed[0]
        }

    # -- traceable transition ----------------------------------------------

    def packed_fingerprint_view(self, state):
        """The fingerprintable view of a packed system state:

        - crash flags are excluded, mirroring the host state hash
          (reference ``src/actor/model_state.rs:86-97``);
        - the unordered envelope table is reduced to an order-insensitive
          multiset digest (``ops.fingerprint.multiset_digest``), so equal
          envelope multisets fingerprint identically WITHOUT the table
          being kept sorted — transitions and symmetry permutations never
          pay a per-candidate sort. Ordered flows are positionally
          canonical (head at slot 0) and hash as-is.
        """
        import jax.numpy as jnp

        from ..ops.fingerprint import multiset_digest

        out = {k: v for k, v in state.items() if k != "crashed"}
        if not self._ordered:
            src = out.pop("net_src")
            dst = out.pop("net_dst")
            msg = out.pop("net_msg")
            cnt = out.pop("net_cnt")
            rows = jnp.concatenate(
                [src[:, None], dst[:, None], msg, cnt[:, None]], axis=1
            ).astype(jnp.uint32)
            out["net_digest"] = multiset_digest(rows, cnt > 0)
        return out

    def packed_component_pairs(self, state):
        """Component-hash pairs of one packed state: ``(his, los)``, each
        ``(C,)`` uint32, one pair per component in a fixed order —

        - actor components ``0..N-1``: actor row ‖ timer word (crash flags
          excluded, like the view);
        - ordered nets: flow components ``N..N+P-1``: FIFO queue ‖ length;
          unordered nets: one component ``N``: the order-insensitive
          multiset digest of the envelope table;
        - history component last, when the codec carries one.

        Tag-seeded (``ops.fingerprint.component_seeds``) so the scheme is
        positional across components. ``packed_fingerprint`` chains these
        pairs; ``packed_expand_fps`` rehashes only the components a
        transition touches and reuses the parent's pairs for the rest —
        the algebraic identity that makes candidate fingerprints a
        delta-cost operation."""
        import jax.numpy as jnp

        from ..ops.fingerprint import hash_rows, multiset_digest

        N, P = self._N, self._P
        rows_t = jnp.concatenate(
            [state["rows"], state["timers"][:, None]], axis=1
        )
        his = [None]
        los = [None]
        his[0], los[0] = hash_rows(rows_t, jnp.arange(N, dtype=jnp.uint32))
        if self._ordered:
            Q, W = self._Q, self.codec.msg_width
            flow_t = jnp.concatenate(
                [
                    state["flow_msg"].reshape(P, Q * W),
                    state["flow_len"][:, None],
                ],
                axis=1,
            )
            fh, fl = hash_rows(
                flow_t, jnp.uint32(N) + jnp.arange(P, dtype=jnp.uint32)
            )
            net_comps = P
        else:
            rows = jnp.concatenate(
                [
                    state["net_src"][:, None],
                    state["net_dst"][:, None],
                    state["net_msg"],
                    state["net_cnt"][:, None],
                ],
                axis=1,
            ).astype(jnp.uint32)
            digest = multiset_digest(rows, state["net_cnt"] > 0)
            fh, fl = hash_rows(digest[None, :], jnp.asarray([N], jnp.uint32))
            net_comps = 1
        his.append(fh)
        los.append(fl)
        if self.codec.history_width:
            tag = jnp.asarray([N + net_comps], jnp.uint32)
            hh, hl = hash_rows(state["hist"][None, :], tag)
            his.append(hh)
            los.append(hl)
        return jnp.concatenate(his), jnp.concatenate(los)

    def packed_fingerprint(self, state):
        """Component-hash fingerprint (see ``packed_component_pairs``).
        Replaces the word-serial murmur over the fingerprint view: same
        view semantics (crash-excluded, net-order-insensitive), but
        per-candidate recomputation touches only changed components."""
        from ..ops.fingerprint import combine_pairs

        self._packed_check()
        return combine_pairs(*self.packed_component_pairs(state))

    # -- symmetry (orbit-proper; see core/batch.py) ------------------------

    def packed_symmetry(self):
        from ..core.batch import permutation_tables

        if self.codec.history_width:
            raise NotImplementedError(
                "symmetry with packed auxiliary history is unsupported "
                "(histories carry client identities that are not "
                "interchangeable)"
            )
        if self.flow_pairs is not None:
            raise NotImplementedError(
                "full-group symmetry with restricted flow_pairs is "
                "unsupported (the pair set is generally not closed under "
                "actor permutations)"
            )
        return permutation_tables(self._N)

    def packed_apply_permutation(self, state, new_to_old, old_to_new):
        """The symmetry group action on a packed system state: gather
        actor-indexed arrays by ``new_to_old`` and rewrite embedded ids via
        the codec hooks (device analog of the host
        ``ActorModelState._permuted``). The envelope table needs no re-sort:
        the fingerprint view digests it order-insensitively."""
        import jax
        import jax.numpy as jnp

        codec = self.codec
        n = self._N
        rows = state["rows"][new_to_old]
        rows = jax.vmap(
            lambda r: codec.rewrite_actor_row(self, r, old_to_new)
        )(rows)
        out = dict(state, rows=rows, timers=state["timers"][new_to_old])
        if "crashed" in state:
            out["crashed"] = state["crashed"][new_to_old]
        if self._ordered:
            if self.flow_pairs is not None:
                # Unreachable through the checkers (packed_symmetry
                # raises first); direct callers get the same message.
                raise NotImplementedError(
                    "permutation action with restricted flow_pairs is "
                    "unsupported"
                )
            # Flow (a, b) of the permuted state held flow
            # (new_to_old[a], new_to_old[b]) originally; queue order is
            # preserved, so the gathered table stays positionally canonical.
            flow = (new_to_old[:, None] * n + new_to_old[None, :]).reshape(-1)
            fmsg = state["flow_msg"][flow]
            fmsg = jax.vmap(
                jax.vmap(lambda v: codec.rewrite_msg_ids(self, v, old_to_new))
            )(fmsg)
            flen = state["flow_len"][flow]
            # Re-zero queue padding so id rewrites of dead slots cannot
            # perturb the canonical array.
            slot = jnp.arange(fmsg.shape[1])
            fmsg = jnp.where(
                slot[None, :, None] < flen[:, None, None], fmsg, jnp.uint32(0)
            )
            out.update(flow_msg=fmsg, flow_len=flen)
        else:
            cnt = state["net_cnt"]
            occ = cnt > 0
            o2n = old_to_new.astype(jnp.uint32)
            src = jnp.where(occ, o2n[state["net_src"]], jnp.uint32(0))
            dst = jnp.where(occ, o2n[state["net_dst"]], jnp.uint32(0))
            msg = jax.vmap(
                lambda v: codec.rewrite_msg_ids(self, v, old_to_new)
            )(state["net_msg"])
            msg = jnp.where(occ[:, None], msg, jnp.uint32(0))
            out.update(net_src=src, net_dst=dst, net_msg=msg)
            # No re-sort needed: the fingerprint view digests the envelope
            # table order-insensitively.
        return out

    def packed_refine_colors(self, state, colors):
        """Generic equivariant WL round for packed actor systems (see
        ``core/batch.py``): each actor's new color hashes its own row (with
        embedded ids replaced by their colors, reusing the codec's
        ``rewrite_actor_row``/``rewrite_msg_ids`` relabeling hooks — which
        must therefore be value-wise and shift-safe for arbitrary uint32
        "names", not just true permutations), its timer bits, and
        commutative digests of its incoming/outgoing envelopes tagged with
        the peer's color. ``crashed`` is EXCLUDED, matching
        ``packed_fingerprint_view`` — the dedup key the colors steer hashes
        the view, so including crash flags could split view-equal states
        into different canonical permutations."""
        import jax
        import jax.numpy as jnp

        from ..ops.fingerprint import avalanche32

        codec = self.codec
        n = self._N
        u = jnp.uint32

        def rows_under(c):
            return jax.vmap(
                lambda r: codec.rewrite_actor_row(self, r, c)
            )(state["rows"])

        rows_c = rows_under(colors)
        acc = colors * u(0x9E3779B1) + u(0x7F4A7C15)
        for j in range(rows_c.shape[1]):
            acc = acc * u(0x01000193) ^ rows_c[:, j]
        acc = avalanche32(acc * u(0x01000193) ^ state["timers"].astype(u))

        # Reverse row-references: envelopes flow colors both ways below,
        # but a row embedding actor j's id (votedFor, vote bitmaps, ...)
        # informs only the REFERRER's color — actor j must also learn who
        # references it or WL leaves non-automorphic actors tied (and
        # every such tie pays the n! fallback). References are detected
        # generically and exactly: rewrites gather by INDEX, so perturbing
        # slot j's name changes exactly the rows that reference j.
        hcol = avalanche32(colors * u(0x27D4EB2F) + u(0x165667B1))

        def rev_body(j, rev):
            cj = colors.at[j].set(colors[j] ^ u(0x80000001))
            refs = (rows_under(cj) != rows_c).any(axis=1)
            return rev.at[j].set(jnp.where(refs, hcol, u(0)).sum(dtype=u))

        rev = jax.lax.fori_loop(0, n, rev_body, jnp.zeros((n,), u))
        acc = avalanche32(acc ^ rev * u(0x9E3779B7))

        if self._ordered:
            P, Q = self._P, self._Q
            fmsg_c = jax.vmap(
                jax.vmap(lambda v: codec.rewrite_msg_ids(self, v, colors))
            )(state["flow_msg"])
            flen = state["flow_len"].astype(u)
            live = jnp.arange(Q, dtype=u)[None, :] < flen[:, None]
            h = jnp.full((P,), 0x811C9DC5, u)
            for q in range(Q):
                hq = h
                for w in range(fmsg_c.shape[2]):
                    hq = hq * u(0x01000193) ^ fmsg_c[:, q, w]
                h = jnp.where(live[:, q], hq, h)
            h = avalanche32(h ^ flen * u(0x9E3779B9))
            _, psrc, pdst = self._pair_tables()
            a = jnp.asarray(psrc)
            b = jnp.asarray(pdst)
            out_c = avalanche32(h ^ colors[b] * u(0xCC9E2D51) + u(0x52DCE729))
            in_c = avalanche32(h ^ colors[a] * u(0x1B873593) + u(0x38495AB5))
            out_sum = jax.ops.segment_sum(out_c, a, num_segments=n)
            in_sum = jax.ops.segment_sum(in_c, b, num_segments=n)
        else:
            msg_c = jax.vmap(
                lambda v: codec.rewrite_msg_ids(self, v, colors)
            )(state["net_msg"])
            cnt = state["net_cnt"].astype(u)
            occ = cnt > 0
            h = jnp.full((cnt.shape[0],), 0x811C9DC5, u)
            for w in range(msg_c.shape[1]):
                h = h * u(0x01000193) ^ msg_c[:, w]
            h = avalanche32(h ^ cnt * u(0x9E3779B9))
            src = state["net_src"].astype(jnp.int32)
            dst = state["net_dst"].astype(jnp.int32)
            out_c = jnp.where(
                occ,
                avalanche32(h ^ colors[dst] * u(0xCC9E2D51) + u(0x52DCE729)),
                u(0),
            )
            in_c = jnp.where(
                occ,
                avalanche32(h ^ colors[src] * u(0x1B873593) + u(0x38495AB5)),
                u(0),
            )
            out_sum = jax.ops.segment_sum(out_c, src, num_segments=n)
            in_sum = jax.ops.segment_sum(in_c, dst, num_segments=n)
        return avalanche32(
            acc ^ out_sum * u(0x85EBCA6B) ^ in_sum * u(0xC2B2AE35)
        )

    def _net_send(self, state, src, dst, msg, active):
        """One network send (host ``Network.send``): duplicating nets dedup,
        non-duplicating nets count, ordered nets append to the (src, dst)
        FIFO. Returns (state, overflow)."""
        import jax.numpy as jnp

        if self._ordered:
            Q = self._Q
            lookup, _, _ = self._pair_tables()
            full = src.astype(jnp.int32) * self._N + dst.astype(jnp.int32)
            p = jnp.asarray(lookup)[
                jnp.clip(full, 0, self._N * self._N - 1)
            ]
            # Excluded pairs behave as zero-capacity flows: the send
            # overflows and the transition is pruned (boundary semantics).
            allowed = p >= 0
            p = jnp.clip(p, 0, self._P - 1)
            length = state["flow_len"][p]
            ok = active & allowed & (length < Q)
            at = jnp.clip(length, 0, Q - 1).astype(jnp.int32)
            state = dict(state)
            state["flow_msg"] = state["flow_msg"].at[p, at].set(
                jnp.where(ok, msg, state["flow_msg"][p, at])
            )
            state["flow_len"] = state["flow_len"].at[p].add(
                jnp.where(ok, jnp.uint32(1), jnp.uint32(0))
            )
            return state, active & (~allowed | (length >= Q))

        src = src.astype(jnp.uint32)
        dst = dst.astype(jnp.uint32)
        cnt = state["net_cnt"]
        match = (
            (state["net_src"] == src)
            & (state["net_dst"] == dst)
            & (state["net_msg"] == msg[None, :]).all(axis=1)
            & (cnt > 0)
        )
        exists = match.any()
        first_match = jnp.argmax(match)
        empty = cnt == 0
        has_empty = empty.any()
        claim = jnp.argmax(empty)

        slot = jnp.where(exists, first_match, claim)
        ok = active & (exists | has_empty)
        if self._dup:
            add = jnp.where(exists, jnp.uint32(0), jnp.uint32(1))
        else:
            add = jnp.uint32(1)
        new_cnt = cnt.at[slot].add(jnp.where(ok, add, jnp.uint32(0)))
        write = ok & ~exists
        state = dict(state)
        state["net_src"] = state["net_src"].at[slot].set(
            jnp.where(write, src, state["net_src"][slot])
        )
        state["net_dst"] = state["net_dst"].at[slot].set(
            jnp.where(write, dst, state["net_dst"][slot])
        )
        state["net_msg"] = state["net_msg"].at[slot].set(
            jnp.where(write, msg, state["net_msg"][slot])
        )
        state["net_cnt"] = new_cnt
        overflow = active & ~exists & ~has_empty
        return state, overflow

    def _apply_callback(self, state, actor, row_new, sends, set_bits, cancel_bits, fired_bit=None):
        """Applies a callback's effects: row write, timer bookkeeping
        (fired timer cleared first, then sets, then cancels — matching the
        host's sequential command processing for set-then-cancel), sends.
        Returns (state, overflow)."""
        import jax.numpy as jnp

        state = dict(state)
        state["rows"] = state["rows"].at[actor].set(row_new)
        t = state["timers"][actor]
        if fired_bit is not None:
            t = t & ~(jnp.uint32(1) << fired_bit.astype(jnp.uint32))
        t = (t | set_bits) & ~cancel_bits
        state["timers"] = state["timers"].at[actor].set(t)
        overflow = jnp.bool_(False)
        for s in range(self.codec.send_capacity):
            dst = sends[s, 0]
            msg = sends[s, 1:]
            active = dst != jnp.uint32(self.codec.SEND_NONE)
            state, ov = self._net_send(
                state, state_src(actor), dst, msg, active
            )
            if self.codec.history_width:
                # Host: each Send runs record_msg_out on the running history
                # (sequential command processing, ``model.py:163-172``).
                hist_new = self.codec.history_on_send(
                    self, state["hist"], state_src(actor), dst, msg
                )
                state["hist"] = jnp.where(active, hist_new, state["hist"])
            overflow = overflow | ov
        return state, overflow

    def packed_step(self, state, action_id):
        import jax
        import jax.numpy as jnp

        self._packed_check()
        codec = self.codec
        N, E, T, W = self._N, self._E, self._T, codec.msg_width
        lossy = self._lossy_network
        aid = action_id.astype(jnp.int32)
        msg_branches = codec.on_msg_branches(self)
        timeout_branches = codec.on_timeout_branches(self)
        if not timeout_branches:
            # Timer-free codec: lax.cond still traces the timeout arm, so
            # substitute an inert branch (never selected — T == 0 means no
            # timeout action ids exist).
            def _inert(actor, row, bit):
                z = jnp.uint32(0)
                return (
                    row,
                    jnp.full(
                        (codec.send_capacity, 1 + codec.msg_width),
                        codec.SEND_NONE,
                    ),
                    z,
                    z,
                    jnp.bool_(False),
                )

            timeout_branches = [_inert] * max(1, len(msg_branches))
        type_ids = [
            codec.actor_type_id(i, a) for i, a in enumerate(self.actors_list)
        ]
        type_arr = jnp.asarray(type_ids, jnp.int32)

        ordered = self._ordered
        crashes = bool(self._max_crashes)
        deliver_ids = self._P if ordered else E
        drop_ids = deliver_ids if lossy else 0
        timeout_ids = N * T
        is_deliver = aid < deliver_ids
        is_drop = lossy & (aid >= deliver_ids) & (aid < deliver_ids + drop_ids)
        is_timeout = (aid >= deliver_ids + drop_ids) & (
            aid < deliver_ids + drop_ids + timeout_ids
        )
        is_crash = crashes & (aid >= deliver_ids + drop_ids + timeout_ids)

        slot = jnp.clip(
            jnp.where(is_drop, aid - deliver_ids, aid), 0, deliver_ids - 1
        )
        # T == 0 (timer-free systems): no timeout action ids exist, so
        # is_timeout is always false; T1 only keeps the index math traceable.
        T1 = max(T, 1)
        tk = jnp.clip(aid - deliver_ids - drop_ids, 0, N * T1 - 1)
        t_actor = tk // T1
        t_bit = (tk % T1).astype(jnp.uint32)
        crash_actor = jnp.clip(
            aid - deliver_ids - drop_ids - timeout_ids, 0, N - 1
        )

        if ordered:
            flow_len = state["flow_len"]
            present = flow_len[slot] > 0
            _, psrc, pdst = self._pair_tables()
            env_src = jnp.asarray(psrc)[slot]
            env_dst = jnp.asarray(pdst)[slot]
            env_msg = state["flow_msg"][slot, 0]
            cnt = None
        else:
            cnt = state["net_cnt"]
            present = cnt[slot] > 0
            env_src = state["net_src"][slot].astype(jnp.int32)
            env_dst = state["net_dst"][slot].astype(jnp.int32)
            env_msg = state["net_msg"][slot]
        dst_ok = env_dst < N
        if crashes:
            dst_crashed = state["crashed"][jnp.clip(env_dst, 0, N - 1)] == 1
        else:
            dst_crashed = jnp.bool_(False)

        # Which actor's callback runs (clamped for safety; masked by valid).
        actor = jnp.clip(jnp.where(is_timeout, t_actor, env_dst), 0, N - 1)
        row = state["rows"][actor]

        def run_msg(args):
            row, actor, src, msg, bit = args
            return jax.lax.switch(
                type_arr[actor],
                [
                    (lambda r, a, s, m, fn=fn: fn(a, r, s, m))
                    for fn in msg_branches
                ],
                row,
                actor,
                src,
                msg,
            )

        def run_timeout(args):
            row, actor, src, msg, bit = args
            return jax.lax.switch(
                type_arr[actor],
                [
                    (lambda r, a, b, fn=fn: fn(a, r, b))
                    for fn in timeout_branches
                ],
                row,
                actor,
                bit,
            )

        row_new, sends, set_bits, cancel_bits, changed = jax.lax.cond(
            is_timeout,
            run_timeout,
            run_msg,
            (row, actor, env_src, env_msg, t_bit),
        )

        no_sends = (sends[:, 0] == codec.SEND_NONE).all()
        no_bits_cmds = (set_bits == 0) & (cancel_bits == 0)
        is_no_op = ~changed & no_sends & no_bits_cmds
        # Host is_no_op_with_timer: unchanged + exactly a renewal of the
        # fired timer.
        renews_only = (
            ~changed
            & no_sends
            & (cancel_bits == 0)
            & (set_bits == (jnp.uint32(1) << t_bit))
        )

        timer_set = (
            (state["timers"][t_actor] >> t_bit) & jnp.uint32(1)
        ) == 1
        # Ordered networks must consume no-op deliveries to preserve FIFO
        # state (host ``model.py:246-249``); unordered prunes them.
        deliver_effective = (
            jnp.bool_(True) if ordered else ~is_no_op
        )
        valid_deliver = (
            is_deliver & present & dst_ok & ~dst_crashed & deliver_effective
        )
        valid_drop = is_drop & present
        valid_timeout = is_timeout & timer_set & ~renews_only
        if crashes:
            crash_count = state["crashed"].sum()
            valid_crash = (
                is_crash
                & (crash_count < jnp.uint32(self._max_crashes))
                & (state["crashed"][crash_actor] == 0)
            )
        else:
            valid_crash = jnp.bool_(False)

        # -- build each outcome and select ----------------------------------

        def consume_head(st):
            """Removes the head of ordered flow ``slot`` (shift keeps the
            queue canonical: head always at index 0)."""
            st = dict(st)
            q = st["flow_msg"][slot]
            shifted = jnp.concatenate(
                [q[1:], jnp.zeros((1, W), jnp.uint32)], axis=0
            )
            st["flow_msg"] = st["flow_msg"].at[slot].set(shifted)
            st["flow_len"] = st["flow_len"].at[slot].add(
                jnp.uint32(0) - 1
            )
            return st

        # Drop: duplicating removes the envelope entirely; counting nets
        # decrement; ordered removes the flow head (host Network.on_drop).
        if ordered:
            drop_state = consume_head(state)
        else:
            drop_state = dict(state)
            if self._dup:
                drop_state["net_cnt"] = cnt.at[slot].set(jnp.uint32(0))
            else:
                drop_state["net_cnt"] = cnt.at[slot].add(jnp.uint32(0) - 1)

        # Deliver: network effect first (host: on_deliver before
        # process_commands), then callback effects. The record_msg_in analog
        # applies to the PRE-send history (host: ``model.py:250-262``).
        deliver_state = dict(state)
        if codec.history_width:
            deliver_state["hist"] = codec.history_on_deliver(
                self, state["hist"], env_src, env_dst, env_msg
            )
        if ordered:
            deliver_state = consume_head(deliver_state)
        elif not self._dup:
            deliver_state["net_cnt"] = cnt.at[slot].add(jnp.uint32(0) - 1)
        # Ordered no-op deliveries consume the message but apply no other
        # effect (the host skips the callback result entirely).
        row_eff = jnp.where(is_no_op, state["rows"][actor], row_new)
        no_send_buf = jnp.full_like(sends, codec.SEND_NONE)
        sends_eff = jnp.where(is_no_op, no_send_buf, sends)
        set_eff = jnp.where(is_no_op, jnp.uint32(0), set_bits)
        cancel_eff = jnp.where(is_no_op, jnp.uint32(0), cancel_bits)
        deliver_state, ov_d = self._apply_callback(
            deliver_state, actor, row_eff, sends_eff, set_eff, cancel_eff
        )

        timeout_state, ov_t = self._apply_callback(
            dict(state), actor, row_new, sends, set_bits, cancel_bits,
            fired_bit=t_bit,
        )

        if crashes:
            crash_state = dict(state)
            crash_state["crashed"] = state["crashed"].at[crash_actor].set(
                jnp.uint32(1)
            )
            crash_state["timers"] = state["timers"].at[crash_actor].set(
                jnp.uint32(0)
            )

        overflow = (valid_deliver & ov_d) | (valid_timeout & ov_t)

        def pick(a, b, cond):
            return jax.tree_util.tree_map(
                lambda x, y: jnp.where(cond, x, y), a, b
            )

        out = pick(drop_state, deliver_state, is_drop)
        out = pick(timeout_state, out, is_timeout)
        if crashes:
            out = pick(crash_state, out, is_crash)
        valid = (
            valid_deliver | valid_drop | valid_timeout | valid_crash
        ) & ~overflow
        return out, valid

    def packed_expand(self, state):
        """Per-class expansion fast path (see ``BatchableModel``): builds
        the deliver / drop / timeout / crash candidate blocks separately,
        in ``packed_step``'s action-id order, so each class pays only its
        own work. ``packed_step`` (kept as the single-action path for the
        TPU simulation checker, and as the oracle for
        ``tests/test_packed_expand.py``) materializes all four outcome
        variants and runs BOTH callback switches per candidate — under
        vmap every lane executes every branch — which dominated wave time
        on action-heavy models (raft-5: expand was 92% of the wave; drop
        candidates here cost one FIFO/count update instead of two full
        callback traces + four state builds)."""
        import jax

        s = self._class_steps(state)
        return self._expand_from_steps(s)

    def _expand_from_steps(self, s):
        import jax
        import jax.numpy as jnp

        slots = jnp.arange(s["D"], dtype=jnp.int32)
        parts = [jax.vmap(s["deliver"])(slots)]
        if self._lossy_network:
            parts.append(jax.vmap(s["drop"])(slots))
        if s["T"]:
            parts.append(
                jax.vmap(s["timeout"])(
                    jnp.arange(self._N * s["T"], dtype=jnp.int32)
                )
            )
        if s["crashes"]:
            parts.append(
                jax.vmap(s["crash"])(jnp.arange(self._N, dtype=jnp.int32))
            )
        cand = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *[p[0] for p in parts]
        )
        valid = jnp.concatenate([p[1] for p in parts])
        return cand, valid

    def _class_steps(self, state):
        """The four per-class child builders (deliver/drop/timeout/crash)
        as closures over ``state``, in ``packed_step``'s action-id order.
        Shared by ``packed_expand`` (vmapped per class) and
        ``packed_take`` (lax.switch for one action id)."""
        import jax
        import jax.numpy as jnp

        self._packed_check()
        codec = self.codec
        N, E, T, W = self._N, self._E, self._T, codec.msg_width
        ordered = self._ordered
        crashes = bool(self._max_crashes)
        msg_branches = codec.on_msg_branches(self)
        timeout_branches = codec.on_timeout_branches(self)
        type_arr = jnp.asarray(
            [codec.actor_type_id(i, a) for i, a in enumerate(self.actors_list)],
            jnp.int32,
        )
        D = self._P if ordered else E

        def env_at(slot):
            """(present, src, dst, msg) of deliver/drop slot ``slot``."""
            if ordered:
                _, psrc, pdst = self._pair_tables()
                return (
                    state["flow_len"][slot] > 0,
                    jnp.asarray(psrc)[slot],
                    jnp.asarray(pdst)[slot],
                    state["flow_msg"][slot, 0],
                )
            return (
                state["net_cnt"][slot] > 0,
                state["net_src"][slot].astype(jnp.int32),
                state["net_dst"][slot].astype(jnp.int32),
                state["net_msg"][slot],
            )

        def consume(st, slot):
            """Removes slot's message: FIFO head shift / count decrement
            (identical to packed_step's consume_head / decrement)."""
            st = dict(st)
            if ordered:
                q = st["flow_msg"][slot]
                shifted = jnp.concatenate(
                    [q[1:], jnp.zeros((1, W), jnp.uint32)], axis=0
                )
                st["flow_msg"] = st["flow_msg"].at[slot].set(shifted)
                st["flow_len"] = st["flow_len"].at[slot].add(jnp.uint32(0) - 1)
            else:
                st["net_cnt"] = st["net_cnt"].at[slot].add(jnp.uint32(0) - 1)
            return st

        def crashed_at(dst):
            if crashes:
                return state["crashed"][jnp.clip(dst, 0, N - 1)] == 1
            return jnp.bool_(False)

        def no_op_of(changed, sends, set_bits, cancel_bits):
            no_sends = (sends[:, 0] == codec.SEND_NONE).all()
            return ~changed & no_sends & (set_bits == 0) & (cancel_bits == 0)

        def step_deliver(slot):
            present, env_src, env_dst, env_msg = env_at(slot)
            actor = jnp.clip(env_dst, 0, N - 1)
            row = state["rows"][actor]
            row_new, sends, set_bits, cancel_bits, changed = jax.lax.switch(
                type_arr[actor],
                [
                    (lambda r, a, s, m, fn=fn: fn(a, r, s, m))
                    for fn in msg_branches
                ],
                row,
                actor,
                env_src,
                env_msg,
            )
            is_no_op = no_op_of(changed, sends, set_bits, cancel_bits)
            out = dict(state)
            if codec.history_width:
                out["hist"] = codec.history_on_deliver(
                    self, state["hist"], env_src, env_dst, env_msg
                )
            if ordered or not self._dup:
                out = consume(out, slot)
            # Ordered no-op deliveries consume the message but apply no
            # other effect (host skips the callback result entirely).
            row_eff = jnp.where(is_no_op, row, row_new)
            sends_eff = jnp.where(
                is_no_op, jnp.full_like(sends, codec.SEND_NONE), sends
            )
            set_eff = jnp.where(is_no_op, jnp.uint32(0), set_bits)
            cancel_eff = jnp.where(is_no_op, jnp.uint32(0), cancel_bits)
            out, ov = self._apply_callback(
                out, actor, row_eff, sends_eff, set_eff, cancel_eff
            )
            valid = (
                present
                & (env_dst < N)
                & ~crashed_at(env_dst)
                & (jnp.bool_(True) if ordered else ~is_no_op)
                & ~ov
            )
            return out, valid

        def step_drop(slot):
            present, _, _, _ = env_at(slot)
            out = dict(state)
            if ordered:
                out = consume(out, slot)
            elif self._dup:
                out["net_cnt"] = state["net_cnt"].at[slot].set(jnp.uint32(0))
            else:
                out = consume(out, slot)
            return out, present

        def step_timeout(k):
            t_actor = k // T
            t_bit = (k % T).astype(jnp.uint32)
            row = state["rows"][t_actor]
            row_new, sends, set_bits, cancel_bits, changed = jax.lax.switch(
                type_arr[t_actor],
                [
                    (lambda r, a, b, fn=fn: fn(a, r, b))
                    for fn in timeout_branches
                ],
                row,
                t_actor,
                t_bit,
            )
            renews_only = (
                ~changed
                & (sends[:, 0] == codec.SEND_NONE).all()
                & (cancel_bits == 0)
                & (set_bits == (jnp.uint32(1) << t_bit))
            )
            timer_set = (
                (state["timers"][t_actor] >> t_bit) & jnp.uint32(1)
            ) == 1
            out, ov = self._apply_callback(
                dict(state), t_actor, row_new, sends, set_bits, cancel_bits,
                fired_bit=t_bit,
            )
            return out, timer_set & ~renews_only & ~ov

        def step_crash(i):
            out = dict(state)
            out["crashed"] = state["crashed"].at[i].set(jnp.uint32(1))
            out["timers"] = state["timers"].at[i].set(jnp.uint32(0))
            valid = (state["crashed"].sum() < jnp.uint32(self._max_crashes)) & (
                state["crashed"][i] == 0
            )
            return out, valid

        return {
            "deliver": step_deliver,
            "drop": step_drop,
            "timeout": step_timeout,
            "crash": step_crash,
            "env_at": env_at,
            "consume": consume,
            "crashed_at": crashed_at,
            "no_op_of": no_op_of,
            "D": D,
            "T": T,
            "crashes": crashes,
            "type_arr": type_arr,
            "msg_branches": msg_branches,
            "timeout_branches": timeout_branches,
        }

    def packed_expand_fps_supported(self):
        """The fps wave checks boundaries per changed row; a codec that
        customizes ``packed_within_boundary`` must supply the per-row
        decomposition (``packed_row_within_boundary``) or the fps path
        would silently admit out-of-boundary children. Mismatched codecs
        fall back to the materializing wave."""
        codec_cls = type(self.codec)
        wb_custom = (
            codec_cls.packed_within_boundary
            is not ActorPackedCodec.packed_within_boundary
        )
        row_custom = (
            codec_cls.packed_row_within_boundary
            is not ActorPackedCodec.packed_row_within_boundary
        )
        return (not wb_custom) or row_custom

    def packed_expand_fps(self, state):
        """Fingerprints + validity of every child WITHOUT materializing
        them (``core/batch.py`` contract): candidate fingerprints are
        computed from the parent's component-hash pairs
        (``packed_component_pairs``) by rehashing only the components a
        transition touches — the changed actor row, the consumed/appended
        flow rows (ordered) or the algebraically-updated multiset digest
        (unordered), and the history vector. Per-candidate cost is the
        delta size plus the 2C-round combine chain; the F × A candidate
        grid never exists as state arrays, which is the byte diet VERDICT
        r04 #2 demanded (abd3o measured 29.5KB of HBM traffic per
        candidate on the materializing path). Replaces the reference's
        per-state hashing in its BFS hot loop
        (``/root/reference/src/checker/bfs.rs:275-315``)."""
        import jax
        import jax.numpy as jnp

        from ..ops.fingerprint import (
            acc_finalize,
            hash_rows,
            multiset_row_pairs,
            pairs_acc,
        )

        self._packed_check()
        codec = self.codec
        N, E, T, W = self._N, self._E, self._T, codec.msg_width
        ordered, dup = self._ordered, self._dup
        crashes = bool(self._max_crashes)
        S = codec.send_capacity
        P = self._P
        Q = self._Q if ordered else 0
        hist_w = codec.history_width
        net_comps = P if ordered else 1
        hist_tag = N + net_comps
        C = N + net_comps + (1 if hist_w else 0)
        K = 1 + S  # working-set slots: consumed row + one per send
        SN = jnp.uint32(codec.SEND_NONE)

        s = self._class_steps(state)
        env_at, no_op_of = s["env_at"], s["no_op_of"]
        crashed_at = s["crashed_at"]
        type_arr = s["type_arr"]
        msg_branches, timeout_branches = s["msg_branches"], s["timeout_branches"]
        D = s["D"]
        phis, plos = self.packed_component_pairs(state)
        parent_acc = pairs_acc(phis, plos)

        def actor_pair(actor, row, tmr):
            words = jnp.concatenate([row, tmr[None]])
            h, l = hash_rows(words[None, :], actor[None].astype(jnp.uint32))
            return h[0], l[0]

        def flow_pair(pid, q, ln):
            words = jnp.concatenate([q.reshape(Q * W), ln[None]])
            tag = (jnp.uint32(N) + pid.astype(jnp.uint32))[None]
            h, l = hash_rows(words[None, :], tag)
            return h[0], l[0]

        def net_pair(digest):
            h, l = hash_rows(digest[None, :], jnp.asarray([N], jnp.uint32))
            return h[0], l[0]

        def hist_pair(hist):
            tag = jnp.asarray([hist_tag], jnp.uint32)
            h, l = hash_rows(hist[None, :], tag)
            return h[0], l[0]

        def final_fp(subs):
            """Parent accumulator with per-candidate component
            substitutions (O(1) arithmetic each — the commutative combine
            is what makes candidate fps delta-cost). ``subs``:
            (comp_idx, hi, lo, enabled); the targeted components must be
            DISTINCT within one candidate (the working sets guarantee it),
            or a later delta would be computed against the parent's value
            instead of the earlier substitution's."""
            sum_hi, xor_hi = parent_acc[0], parent_acc[1]
            sum_lo, xor_lo = parent_acc[2], parent_acc[3]
            for ci, nh, nl, en in subs:
                oh = phis[ci]
                ol = plos[ci]
                dh = jnp.where(en, nh, oh)
                dl = jnp.where(en, nl, ol)
                sum_hi = sum_hi + dh - oh
                xor_hi = xor_hi ^ dh ^ oh
                sum_lo = sum_lo + dl - ol
                xor_lo = xor_lo ^ dl ^ ol
            return acc_finalize(
                jnp.stack([sum_hi, xor_hi, sum_lo, xor_lo]), C
            )

        def row_pair_of(row_words):
            h, l = multiset_row_pairs(row_words[None, :])
            return h[0], l[0]

        if not ordered:
            parent_digest = self.packed_fingerprint_view(state)["net_digest"]

            def env_row(src, dst, msg, cnt):
                return jnp.concatenate(
                    [
                        src.astype(jnp.uint32)[None],
                        dst.astype(jnp.uint32)[None],
                        msg.astype(jnp.uint32),
                        cnt[None],
                    ]
                )

            def digest_adjust(digest, src, dst, msg, old_cnt, new_cnt, en):
                """- old row contribution + new row contribution, exactly
                as ``multiset_digest`` folds active rows."""
                oh, ol = row_pair_of(env_row(src, dst, msg, old_cnt))
                nh, nl = row_pair_of(env_row(src, dst, msg, new_cnt))
                rm = en & (old_cnt > 0)
                ad = en & (new_cnt > 0)
                sum_hi, xor_hi, sum_lo, xor_lo = digest
                sum_hi = sum_hi - jnp.where(rm, oh, 0) + jnp.where(ad, nh, 0)
                xor_hi = xor_hi ^ jnp.where(rm, oh, 0) ^ jnp.where(ad, nh, 0)
                sum_lo = sum_lo - jnp.where(rm, ol, 0) + jnp.where(ad, nl, 0)
                xor_lo = xor_lo ^ jnp.where(rm, ol, 0) ^ jnp.where(ad, nl, 0)
                return jnp.stack([sum_hi, xor_hi, sum_lo, xor_lo])

        if ordered:
            lookup, _, _ = self._pair_tables()
            lookup_a = jnp.asarray(lookup)

            def flows_apply(init, sends, src):
                """Sequential send application on a K-entry working set of
                touched flow rows — mirrors ``_net_send``'s ordered branch
                (including overflow/excluded-pair pruning) without copying
                the flow table."""
                ids = jnp.full((K,), -1, jnp.int32)
                qs = jnp.zeros((K, Q, W), jnp.uint32)
                lns = jnp.zeros((K,), jnp.uint32)
                if init is not None:
                    slot, q0, ln0 = init
                    ids = ids.at[0].set(slot)
                    qs = qs.at[0].set(q0)
                    lns = lns.at[0].set(ln0)
                ov = jnp.bool_(False)
                for si in range(S):
                    dst = sends[si, 0]
                    msg = sends[si, 1:]
                    active = dst != SN
                    # Index expression kept IDENTICAL to _net_send's so the
                    # fingerprinted append and the materialized append can
                    # never diverge on out-of-range dst values.
                    full = src.astype(jnp.int32) * N + dst.astype(jnp.int32)
                    p = lookup_a[jnp.clip(full, 0, N * N - 1)]
                    allowed = p >= 0
                    p = jnp.clip(p, 0, P - 1)
                    match = ids == p
                    found = match.any()
                    j = jnp.where(
                        found, jnp.argmax(match), jnp.argmax(ids < 0)
                    )
                    base_q = jnp.where(found, qs[j], state["flow_msg"][p])
                    base_ln = jnp.where(found, lns[j], state["flow_len"][p])
                    ok = active & allowed & (base_ln < Q)
                    at = jnp.clip(base_ln, 0, Q - 1).astype(jnp.int32)
                    nq = base_q.at[at].set(jnp.where(ok, msg, base_q[at]))
                    nln = base_ln + jnp.where(ok, jnp.uint32(1), jnp.uint32(0))
                    touch = active & allowed
                    ids = ids.at[j].set(jnp.where(touch, p, ids[j]))
                    qs = qs.at[j].set(jnp.where(touch, nq, qs[j]))
                    lns = lns.at[j].set(jnp.where(touch, nln, lns[j]))
                    ov = ov | (active & (~allowed | (base_ln >= Q)))
                return ids, qs, lns, ov

            def flow_subs(ids, qs, lns):
                subs = []
                for j in range(K):
                    h, l = flow_pair(ids[j], qs[j], lns[j])
                    subs.append((N + ids[j], h, l, ids[j] >= 0))
                return subs

        else:

            def net_apply(digest, cons_slot, do_consume, sends, src):
                """Sequential send application on the multiset digest with
                a K-entry working set of touched (src, dst, msg) rows —
                mirrors ``_net_send``'s unordered branch: duplicating nets
                dedup, non-duplicating count, empty-slot availability
                tracked as a running count (the digest is slot-agnostic,
                so only the COUNT of empties matters for overflow)."""
                esrc = jnp.zeros((K,), jnp.uint32)
                edst = jnp.zeros((K,), jnp.uint32)
                emsg = jnp.zeros((K, W), jnp.uint32)
                eold = jnp.zeros((K,), jnp.uint32)
                enew = jnp.zeros((K,), jnp.uint32)
                # 0/1 in uint32, not bool: with a bool flag the sends
                # vanished from the digest on the TPU (13,634 of 32,970
                # paxos 2c/3s candidate fingerprints wrong; none with
                # uint32). A minimal vmapped bool scatter does not show
                # it, so the cause is open (ROADMAP A5).
                eused = jnp.zeros((K,), jnp.uint32)
                empties = (state["net_cnt"] == 0).sum(dtype=jnp.int32)
                if do_consume:
                    c0 = state["net_cnt"][cons_slot]
                    esrc = esrc.at[0].set(state["net_src"][cons_slot])
                    edst = edst.at[0].set(state["net_dst"][cons_slot])
                    emsg = emsg.at[0].set(state["net_msg"][cons_slot])
                    eold = eold.at[0].set(c0)
                    enew = enew.at[0].set(c0 - 1)
                    eused = eused.at[0].set(jnp.uint32(1))
                    empties = empties + (c0 == 1)
                ov = jnp.bool_(False)
                for si in range(S):
                    dst = sends[si, 0]
                    msg = sends[si, 1:]
                    active = dst != SN
                    src_u = src.astype(jnp.uint32)
                    wmatch = (
                        (eused != 0)
                        & (esrc == src_u)
                        & (edst == dst)
                        & (emsg == msg[None, :]).all(axis=1)
                    )
                    wfound = wmatch.any()
                    wj = jnp.argmax(wmatch)
                    pmatch = (
                        (state["net_src"] == src_u)
                        & (state["net_dst"] == dst)
                        & (state["net_msg"] == msg[None, :]).all(axis=1)
                        & (state["net_cnt"] > 0)
                    )
                    pfound = pmatch.any()
                    pcnt = state["net_cnt"][jnp.argmax(pmatch)]
                    cur = jnp.where(
                        wfound, enew[wj], jnp.where(pfound, pcnt, 0)
                    )
                    old0 = jnp.where(pfound, pcnt, 0)  # first-touch old cnt
                    exists = cur > 0
                    has_empty = empties > 0
                    if dup:
                        add = jnp.where(exists, jnp.uint32(0), jnp.uint32(1))
                    else:
                        add = jnp.uint32(1)
                    ok = active & (exists | has_empty)
                    ncnt = cur + jnp.where(ok, add, jnp.uint32(0))
                    claim = ok & ~exists
                    j = jnp.where(wfound, wj, jnp.argmax(eused == 0))
                    touch = ok
                    esrc = esrc.at[j].set(jnp.where(touch, src_u, esrc[j]))
                    edst = edst.at[j].set(jnp.where(touch, dst, edst[j]))
                    emsg = emsg.at[j].set(jnp.where(touch, msg, emsg[j]))
                    eold = eold.at[j].set(
                        jnp.where(touch & ~wfound, old0, eold[j])
                    )
                    enew = enew.at[j].set(jnp.where(touch, ncnt, enew[j]))
                    eused = eused.at[j].set(eused[j] | touch.astype(jnp.uint32))
                    empties = empties - claim
                    ov = ov | (active & ~exists & ~has_empty)
                for j in range(K):
                    digest = digest_adjust(
                        digest,
                        esrc[j],
                        edst[j],
                        emsg[j],
                        eold[j],
                        enew[j],
                        eused[j] != 0,
                    )
                return digest, ov

        def hist_after(hist, sends, src):
            if not hist_w:
                return hist
            for si in range(S):
                dst = sends[si, 0]
                msg = sends[si, 1:]
                active = dst != SN
                hn = codec.history_on_send(self, hist, src, dst, msg)
                hist = jnp.where(active, hn, hist)
            return hist

        def callback_effects(actor, branches, *args):
            row_new, sends, set_bits, cancel_bits, changed = jax.lax.switch(
                type_arr[actor], branches, *args
            )
            return row_new, sends, set_bits, cancel_bits, changed

        def fps_deliver(slot):
            present, env_src, env_dst, env_msg = env_at(slot)
            actor = jnp.clip(env_dst, 0, N - 1)
            row = state["rows"][actor]
            row_new, sends, set_bits, cancel_bits, changed = callback_effects(
                actor,
                [
                    (lambda r, a, sr, m, fn=fn: fn(a, r, sr, m))
                    for fn in msg_branches
                ],
                row,
                actor,
                env_src,
                env_msg,
            )
            is_no_op = no_op_of(changed, sends, set_bits, cancel_bits)
            row_eff = jnp.where(is_no_op, row, row_new)
            sends_eff = jnp.where(
                is_no_op, jnp.full_like(sends, codec.SEND_NONE), sends
            )
            set_eff = jnp.where(is_no_op, jnp.uint32(0), set_bits)
            cancel_eff = jnp.where(is_no_op, jnp.uint32(0), cancel_bits)
            t_new = (state["timers"][actor] | set_eff) & ~cancel_eff
            src = state_src(actor)
            ah, al = actor_pair(actor, row_eff, t_new)
            subs = [(actor.astype(jnp.int32), ah, al, jnp.bool_(True))]
            if ordered:
                q = state["flow_msg"][slot]
                shifted = jnp.concatenate(
                    [q[1:], jnp.zeros((1, W), jnp.uint32)], axis=0
                )
                ids, qs, lns, ov = flows_apply(
                    (slot, shifted, state["flow_len"][slot] - 1),
                    sends_eff,
                    src,
                )
                subs += flow_subs(ids, qs, lns)
            else:
                digest, ov = net_apply(
                    parent_digest, slot, not dup, sends_eff, src
                )
                dh, dl = net_pair(digest)
                subs.append((jnp.int32(N), dh, dl, jnp.bool_(True)))
            if hist_w:
                hist = codec.history_on_deliver(
                    self, state["hist"], env_src, env_dst, env_msg
                )
                hist = hist_after(hist, sends_eff, src)
                hh, hl = hist_pair(hist)
                subs.append((jnp.int32(hist_tag), hh, hl, jnp.bool_(True)))
            hi, lo = final_fp(subs)
            valid = (
                present
                & (env_dst < N)
                & ~crashed_at(env_dst)
                & (jnp.bool_(True) if ordered else ~is_no_op)
                & ~ov
                & codec.packed_row_within_boundary(self, row_eff)
            )
            return hi, lo, valid

        def fps_drop(slot):
            present, env_src, env_dst, env_msg = env_at(slot)
            if ordered:
                q = state["flow_msg"][slot]
                shifted = jnp.concatenate(
                    [q[1:], jnp.zeros((1, W), jnp.uint32)], axis=0
                )
                h, l = flow_pair(slot, shifted, state["flow_len"][slot] - 1)
                subs = [(N + slot, h, l, jnp.bool_(True))]
            else:
                c0 = state["net_cnt"][slot]
                new_cnt = jnp.uint32(0) if dup else c0 - 1
                digest = digest_adjust(
                    parent_digest,
                    state["net_src"][slot],
                    state["net_dst"][slot],
                    state["net_msg"][slot],
                    c0,
                    new_cnt,
                    jnp.bool_(True),
                )
                dh, dl = net_pair(digest)
                subs = [(jnp.int32(N), dh, dl, jnp.bool_(True))]
            return (*final_fp(subs), present)

        def fps_timeout(k):
            t_actor = k // T
            t_bit = (k % T).astype(jnp.uint32)
            row = state["rows"][t_actor]
            row_new, sends, set_bits, cancel_bits, changed = callback_effects(
                t_actor,
                [
                    (lambda r, a, b, fn=fn: fn(a, r, b))
                    for fn in timeout_branches
                ],
                row,
                t_actor,
                t_bit,
            )
            renews_only = (
                ~changed
                & (sends[:, 0] == codec.SEND_NONE).all()
                & (cancel_bits == 0)
                & (set_bits == (jnp.uint32(1) << t_bit))
            )
            timer_set = (
                (state["timers"][t_actor] >> t_bit) & jnp.uint32(1)
            ) == 1
            t = state["timers"][t_actor] & ~(
                jnp.uint32(1) << t_bit
            )
            t_new = (t | set_bits) & ~cancel_bits
            src = state_src(t_actor)
            ah, al = actor_pair(t_actor, row_new, t_new)
            subs = [(t_actor.astype(jnp.int32), ah, al, jnp.bool_(True))]
            if ordered:
                ids, qs, lns, ov = flows_apply(None, sends, src)
                subs += flow_subs(ids, qs, lns)
            else:
                digest, ov = net_apply(
                    parent_digest, jnp.int32(0), False, sends, src
                )
                dh, dl = net_pair(digest)
                subs.append((jnp.int32(N), dh, dl, jnp.bool_(True)))
            if hist_w:
                hist = hist_after(state["hist"], sends, src)
                hh, hl = hist_pair(hist)
                subs.append((jnp.int32(hist_tag), hh, hl, jnp.bool_(True)))
            hi, lo = final_fp(subs)
            valid = (
                timer_set
                & ~renews_only
                & ~ov
                & codec.packed_row_within_boundary(self, row_new)
            )
            return hi, lo, valid

        def fps_crash(i):
            ah, al = actor_pair(i, state["rows"][i], jnp.uint32(0))
            hi, lo = final_fp([(i.astype(jnp.int32), ah, al, jnp.bool_(True))])
            valid = (
                state["crashed"].sum() < jnp.uint32(self._max_crashes)
            ) & (state["crashed"][i] == 0)
            return hi, lo, valid

        slots = jnp.arange(D, dtype=jnp.int32)
        parts = [jax.vmap(fps_deliver)(slots)]
        if self._lossy_network:
            parts.append(jax.vmap(fps_drop)(slots))
        if T:
            parts.append(
                jax.vmap(fps_timeout)(jnp.arange(N * T, dtype=jnp.int32))
            )
        if crashes:
            parts.append(
                jax.vmap(fps_crash)(jnp.arange(N, dtype=jnp.int32))
            )
        hi = jnp.concatenate([p[0] for p in parts])
        lo = jnp.concatenate([p[1] for p in parts])
        valid = jnp.concatenate([p[2] for p in parts])
        return hi, lo, valid

    def packed_take(self, state, action_id):
        """Single-child materializer for the fps wave (``core/batch.py``):
        builds exactly ``packed_step``'s outcome for one action id using
        the per-class builders — no four-variant materialization. Under
        vmap a ``lax.switch`` runs every branch per lane, but this is
        called on the post-dedup *fresh* lanes only (a fraction of the
        F × A grid), so the all-branches cost is paid n_new times, not
        B times. Equivalence with ``packed_step`` is pinned by
        ``tests/test_expand_fps.py``."""
        import jax
        import jax.numpy as jnp

        s = self._class_steps(state)
        D, T = s["D"], s["T"]
        aid = jnp.asarray(action_id, jnp.int32)
        branches = [lambda o: s["deliver"](o)[0]]
        bounds = [D]
        if self._lossy_network:
            branches.append(lambda o: s["drop"](o - D)[0])
            bounds.append(2 * D)
        if T:
            off = bounds[-1]
            branches.append(lambda o, off=off: s["timeout"](o - off)[0])
            bounds.append(off + self._N * T)
        if s["crashes"]:
            off = bounds[-1]
            branches.append(lambda o, off=off: s["crash"](o - off)[0])
            bounds.append(off + self._N)
        cls = jnp.int32(0)
        for k in range(1, len(bounds)):
            cls = jnp.where(aid >= bounds[k - 1], jnp.int32(k), cls)
        out = jax.lax.switch(cls, branches, aid)
        # Normalize leaf dtypes/structure to the input's (builders always
        # return full dicts, so structure already matches).
        return {k: out[k] for k in state}

    def packed_conditions(self):
        self._packed_check()
        conds = self.codec.packed_conditions(self)
        # Codecs emit one condition per property *as originally added*;
        # ``retain_properties`` may have since narrowed the model, so select
        # by the recorded append positions.
        if len(conds) != self._properties_added:
            raise ValueError(
                "codec.packed_conditions must align with the model's "
                f"properties as added: {len(conds)} != {self._properties_added}"
            )
        return [conds[i] for i in self._property_codec_pos]

    def packed_within_boundary(self, state):
        return self.codec.packed_within_boundary(self, state)


def state_src(actor):
    """The sender id for commands emitted by ``actor`` (host: commands are
    processed with ``src = the acting actor``)."""
    import jax.numpy as jnp

    return actor.astype(jnp.int32)
