"""Device-resident fingerprint set: open addressing with scatter-claim insert.

This replaces the reference's concurrent visited set (``DashMap`` keyed by
fingerprint, ``/root/reference/src/checker/bfs.rs:28-29``) with an XLA-native
structure: a ``(capacity + MAX_PROBES, 2)`` uint32 table of (hi, lo)
fingerprint pairs, linear probing, and batched insert where competing lanes
claim empty slots via a row-window scatter (duplicate scatter indices
resolve to exactly one winning row — XLA applies each update as an atomic
window) and re-read to learn who won. Lanes that lose a claim race keep
probing, exactly like a CAS-loop insert on CPU.

The home slot is *monotone in the key*: ``home = top log2(capacity) bits of
hi``, a multiply-shift hash by a power of two. The checkers always insert
keys in sorted order (the wave dedup sorts them), so consecutive lanes probe
consecutive table regions — turning the per-probe gather/scatter into a
nearly-sequential HBM access pattern instead of random walks over the whole
table. Probes run ``home, home+1, ...`` without wraparound into a
``MAX_PROBES``-row apron past the end (no modulo in the hot loop, and a
future tiled/Pallas kernel never needs a circular window).

Keys must be wave-unique before insertion (dedup by sort upstream) so a
"slot holds my key" observation implies *this lane* inserted or the key was
already present from an earlier wave — the two outcomes the checker needs to
distinguish are disambiguated by ``fresh`` (claim won) vs ``found``.

A probe round's gathers and claim scatter cost per lane issued, masked or
not, so the sorted insert probes at a width that follows its pending lanes:
a static ladder of widths from the batch width down by eighths (rounded up
to 128 lanes; a batch of 1,024 lanes or fewer keeps one width). Before a round,
once every pending lane fits the next width, the pending lanes move there in
their order (an order-preserving sort on "not pending"), keep their key and
home, and go on probing at ``home + r``. A lane is never left pending by the
width, so the fresh set, the found set, the resident key set and the overflow
lanes are those of a single-width loop; only the slot a tied claim lands in
may differ, as it may between any two runs of the claim scatter.

The all-zero pair is the empty sentinel (fingerprints are never (0, 0) —
see ``ops.fingerprint``).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "hashset_new",
    "hashset_insert",
    "hashset_insert_unsorted",
    "hashset_insert_salted",
    "insert_counted",
    "probe_widths",
    "INSERT_COUNTERS",
    "hashset_contains",
    "hashset_probe_length_counts",
    "MAX_PROBES",
]

# Probe cap per insert; lanes still unplaced after this report overflow and
# the host grows the table. With load factor kept under ~0.6 by the checker,
# linear-probe clusters practically never approach this.
MAX_PROBES = 128


def hashset_new(capacity: int) -> jax.Array:
    """An empty table. ``capacity`` must be a power of two; the allocation
    carries a ``MAX_PROBES``-row apron so probes never wrap."""
    assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
    return jnp.zeros((capacity + MAX_PROBES, 2), dtype=jnp.uint32)


def _home(key_hi: jax.Array, capacity: int) -> jax.Array:
    """Monotone home slot: the top ``log2(capacity)`` bits of ``hi``."""
    k = capacity.bit_length() - 1
    if k == 0:
        return jnp.zeros_like(key_hi, dtype=jnp.int32)
    return (key_hi >> jnp.uint32(32 - k)).astype(jnp.int32)


def hashset_insert(
    table: jax.Array,
    key_hi: jax.Array,
    key_lo: jax.Array,
    active: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Inserts a batch of (wave-unique) keys.

    Returns ``(table, fresh, found, overflow)`` where per lane:
    - ``fresh``: this lane claimed an empty slot (key was NOT in the set);
    - ``found``: key was already present;
    - ``overflow``: probe cap exhausted (host must grow and retry).
    Inactive lanes report none of the three.
    """
    table, fresh, found, pending, _counts = insert_counted(
        table, key_hi, key_lo, active
    )
    return table, fresh, found, pending


# Fields of ``insert_counted``'s counter vector: float32, so that a sum over
# a long drain cannot wrap as int32 would.
INSERT_COUNTERS = ("rounds", "lanes_issued", "lanes_live", "narrowings")


def probe_widths(n: int) -> Tuple[int, ...]:
    """The static ladder of probe widths for a batch of ``n`` lanes: ``n``,
    then an eighth of the previous width rounded up to a multiple of 128,
    while the previous width exceeds 1,024 lanes. Each width is a probe
    loop and a sort in every program that inserts, megabytes of device
    code at the widest, so the ladder steps by eighths, not quarters."""
    widths = [n]
    while widths[-1] > 1024:
        widths.append(-(-widths[-1] // 1024) * 128)
    return tuple(widths)


def insert_counted(
    table: jax.Array,
    key_hi: jax.Array,
    key_lo: jax.Array,
    active: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """``hashset_insert`` plus its counters: returns ``(table, fresh, found,
    overflow, counts)`` with ``counts`` a float32 vector of
    ``INSERT_COUNTERS``: probe rounds, lanes issued (each round's width),
    live lanes (pending at each round's start) and narrowings.

    Each width of ``probe_widths`` is one ``while_loop`` over the same round
    body, run until the pending lanes fit the next width. The table is
    carried by those loops alone (a ``lax.cond`` only skips a narrowing's
    sort when nothing is left to probe), so it stays in place. Outcomes come
    back to the original lanes by one gather per narrowing, through each
    pending lane's position among the pending."""
    capacity = table.shape[0] - MAX_PROBES
    oob = capacity + MAX_PROBES
    widths = probe_widths(key_hi.shape[0])

    def probe_round(table, r, khi, klo, base, pending):
        idx = base + r
        row = table[idx]
        empty = (row[:, 0] == 0) & (row[:, 1] == 0)
        match = (row[:, 0] == khi) & (row[:, 1] == klo)
        attempt = pending & empty & ~match
        # Claim: one full-row update wins per index; losers observe the
        # winner's key on re-read and continue probing. (OOB sentinel must
        # lie past the apron — ``capacity`` itself is a valid apron slot.)
        scatter_idx = jnp.where(attempt, idx, oob)
        update = jnp.stack([khi, klo], axis=-1)
        table = table.at[scatter_idx].set(update, mode="drop")
        row2 = table[idx]
        won = attempt & (row2[:, 0] == khi) & (row2[:, 1] == klo)
        return table, pending & ~match & ~won, won, pending & match

    def probe_stage(table, r, khi, klo, pending, npend, counts, limit):
        w = khi.shape[0]
        base = _home(khi, capacity)

        def cond(c):
            return (c[1] < MAX_PROBES) & (c[6] > limit)

        def body(c):
            table, r, pending, fresh, found, counts, npend = c
            counts = counts + jnp.stack([
                jnp.float32(1), jnp.float32(w), npend.astype(jnp.float32),
                jnp.float32(0),
            ])
            table, pending, won, match = probe_round(
                table, r, khi, klo, base, pending
            )
            return (table, r + 1, pending, fresh | won, found | match,
                    counts, pending.sum(dtype=jnp.int32))

        falses = jnp.zeros((w,), bool)
        return jax.lax.while_loop(
            cond, body, (table, r, pending, falses, falses, counts, npend)
        )

    def pending_first(pending, w2):
        """The first ``w2`` lanes of an order-preserving sort on "not
        pending": every pending lane, in lane order, then filler."""
        lane = jnp.arange(pending.shape[0], dtype=jnp.uint32)
        order = jax.lax.sort(
            jnp.where(pending, lane, lane | jnp.uint32(1 << 31))
        )[:w2]
        return (
            (order & jnp.uint32((1 << 31) - 1)).astype(jnp.int32),
            order < jnp.uint32(1 << 31),
        )

    def nothing(_pending, w2):
        return jnp.zeros((w2,), jnp.int32), jnp.zeros((w2,), bool)

    r = jnp.int32(0)
    khi, klo, pending = key_hi, key_lo, active
    npend = active.sum(dtype=jnp.int32)
    counts = jnp.zeros((4,), jnp.float32)
    narrowed = []
    for w2 in widths[1:] + (0,):
        table, r, pending, fresh, found, counts, npend = probe_stage(
            table, r, khi, klo, pending, npend, counts, w2
        )
        if not w2:
            break
        go = (npend > 0) & (r < MAX_PROBES)
        j, keep = jax.lax.cond(
            go, partial(pending_first, w2=w2), partial(nothing, w2=w2),
            pending,
        )
        narrowed.append((fresh, found, pending, pending & go, w2))
        counts = counts + jnp.where(
            go, jnp.array([0.0, 0.0, 0.0, 1.0], jnp.float32), 0.0
        )
        khi, klo, pending = khi[j], klo[j], keep
    # A lane still pending when the probes ran out was not moved on.
    for fresh0, found0, pending0, moved, w2 in reversed(narrowed):
        code = (fresh.astype(jnp.int32) | (found.astype(jnp.int32) << 1)
                | (pending.astype(jnp.int32) << 2))
        slot = jnp.where(moved, jnp.cumsum(moved.astype(jnp.int32)) - 1, w2)
        back = jnp.take(code, slot, mode="fill", fill_value=0)
        fresh = fresh0 | ((back & 1) != 0)
        found = found0 | ((back & 2) != 0)
        pending = jnp.where(moved, (back & 4) != 0, pending0)
    return table, fresh, found, pending, counts


def hashset_insert_unsorted(
    table: jax.Array,
    key_hi: jax.Array,
    key_lo: jax.Array,
    active: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``hashset_insert`` without the wave-unique precondition: the batch
    may contain DUPLICATE active keys in any order, and exactly one lane
    per distinct key reports ``fresh``.

    Two consumers ride this variant: the scatter wave-dedup policy
    below, and the swarm engine's visited-sample table
    (``checker/swarm.py`` — walk fingerprints arrive unsorted and
    duplicated by construction, and the exactly-one-fresh guarantee is
    what makes ``unique_sample`` an honest distinct count).

    Same-key lanes attempt the same slot; the row-window claim alone
    cannot tell them apart (each re-reads its own key either way), so a
    table-sized *owner ticket* scratch — scatter-min of lane ids per slot
    — breaks the tie: a lane's claim is fresh only if its ticket
    survived. Duplicate losers observe a key match on the next round and
    resolve as ``found``. The scratch costs one table-shaped memset +
    one extra scatter/gather per probe round; what it buys is dropping
    the wave's ``lax.sort`` over the full F x A candidate grid, which
    dominates wide waves (66% of the 2pc-7 wave at F=8192 on CPU —
    ``checker/tpu.py`` exposes the trade as ``wave_dedup``). The sorted
    variant keeps its nearly-sequential probe pattern and stays the
    default until the scatter pattern is measured on TPU HBM.

    ``found`` counts duplicate losers as found-in-set (indistinguishable
    from an earlier-wave hit by design; the checkers only consume
    ``fresh``).

    Two phases: 2 bulk probe rounds at full batch width resolve the vast
    majority of lanes (measured on a realistic 2pc-7 wave: 303K lanes
    drop to ~5% pending after 2 rounds, yet the probe tail runs ~27
    rounds), then the stragglers are COMPACTED to a quarter-width batch
    for the remaining rounds — XLA cannot skip masked lanes, so without
    the compaction every straggler round pays full-width table traffic
    (2pc-7 leg: 61.7K -> 72.9K states/s). Straggler lanes beyond the
    compact width (pathological loads only — the n/4 width is ~5x the
    measured straggler count) report as pending, which the checker
    already answers with grow-and-retry; growth shortens probe chains,
    so the loop terminates. Fewer bulk rounds measured faster still but
    only by overflowing real lanes into spurious table growth — not
    worth the fragility. Phase boundaries cannot split same-key twins
    (same key => same home => lockstep rounds). Both phases run inside
    while_loops (NOT unrolled) so XLA aliases the table/owner carries;
    an unrolled bulk phase measured slower than no split at all (each
    round copied the multi-MB table).
    """
    capacity = table.shape[0] - MAX_PROBES
    n = key_hi.shape[0]
    owner0 = jnp.full((table.shape[0],), jnp.uint32(0xFFFFFFFF))
    oob = capacity + MAX_PROBES
    bulk_rounds = 2
    m = max(128, n // 4)  # straggler width

    def round_step(carry_table, owner, r, khi, klo, kbase, pending, lanes):
        idx = kbase + r
        row = carry_table[idx]
        empty = (row[:, 0] == 0) & (row[:, 1] == 0)
        match = (row[:, 0] == khi) & (row[:, 1] == klo)
        found_now = pending & match
        attempt = pending & empty & ~match
        scatter_idx = jnp.where(attempt, idx, oob)
        update = jnp.stack([khi, klo], axis=-1)
        carry_table = carry_table.at[scatter_idx].set(update, mode="drop")
        row2 = carry_table[idx]
        key_won = attempt & (row2[:, 0] == khi) & (row2[:, 1] == klo)
        # Ticket tie-break ONLY among lanes whose key actually landed
        # (same-key twins): a different-key contender must not write a
        # ticket, or the table-write winner and ticket winner could
        # disagree and a landed key would end up with no fresh lane (a
        # silently lost state).
        owner = owner.at[jnp.where(key_won, idx, oob)].min(
            lanes, mode="drop"
        )
        won = key_won & (owner[idx] == lanes)
        # Duplicate losers whose key DID land resolve as found and stop;
        # different-key losers keep probing.
        pending = pending & ~match & ~key_won
        return carry_table, owner, pending, won, found_now | (key_won & ~won)

    # Phase 1: bulk rounds at full width. A while_loop (not an unrolled
    # python loop) so XLA aliases the table/owner carries in place —
    # unrolled rounds were measured SLOWER than the single-phase loop
    # (each round copied the multi-MB table).
    base = _home(key_hi, capacity)
    lane = jnp.arange(n, dtype=jnp.uint32)
    falses = jnp.zeros((n,), dtype=bool)

    def probe_loop(khi, klo, kbase, lanes, carry, stop):
        """Probe rounds at the carry's width until ``stop`` (or resolved).
        One definition serves the bulk phase, the small-batch finish, and
        the straggler phase."""

        def cond(c):
            _t, _o, r, pending, _f, _fo = c
            return (r < stop) & pending.any()

        def body(c):
            t, o, r, pending, f, fo = c
            t, o, pending, won, fnow = round_step(
                t, o, r, khi, klo, kbase, pending, lanes
            )
            return t, o, r + 1, pending, f | won, fo | fnow

        return jax.lax.while_loop(cond, body, carry)

    two_phase = m < n and bulk_rounds < MAX_PROBES
    stop1 = min(bulk_rounds, MAX_PROBES) if two_phase else MAX_PROBES
    table, owner, _r, pending, fresh, found = probe_loop(
        key_hi, key_lo, base, lane,
        (table, owner0, jnp.int32(0), active, falses, falses),
        stop1,
    )
    if not two_phase:
        return table, fresh, found, pending

    # Phase 2: compact stragglers to width m and finish there.
    pos = jnp.cumsum(pending.astype(jnp.int32)) - 1
    kept = pending & (pos < m)
    over = pending & (pos >= m)
    slot = jnp.where(kept, pos, m)
    khi2 = jnp.zeros((m,), jnp.uint32).at[slot].set(key_hi, mode="drop")
    klo2 = jnp.zeros((m,), jnp.uint32).at[slot].set(key_lo, mode="drop")
    base2 = jnp.zeros((m,), jnp.int32).at[slot].set(base, mode="drop")
    # Padding slots MUST hold an out-of-bounds lane id (n), not 0: the
    # scatter-back below indexes original lanes by lane2, and a padding
    # slot aliasing real lane 0 would clobber its outcome with False
    # (duplicate-index .set is last-write-wins, NOT an OR — a straggler
    # lane 0 would silently lose its fresh/pending bit and the state
    # would never be expanded or retried).
    lane2 = jnp.full((m,), n, jnp.uint32).at[slot].set(lane, mode="drop")
    act2 = jnp.zeros((m,), bool).at[slot].set(kept, mode="drop")

    mfalses = jnp.zeros((m,), dtype=bool)
    table, _o, _r, pending2, fresh2, found2 = probe_loop(
        khi2, klo2, base2, lane2,
        (table, owner, jnp.int32(bulk_rounds), act2, mfalses, mfalses),
        MAX_PROBES,
    )
    # Scatter straggler outcomes back to their original lanes (each kept
    # lane is a distinct original and padding indexes drop, so .set under
    # the OR is exact).
    li = lane2.astype(jnp.int32)
    fresh = fresh | falses.at[li].set(fresh2 & act2, mode="drop")
    found = found | falses.at[li].set(found2 & act2, mode="drop")
    pending_out = over | falses.at[li].set(pending2 & act2, mode="drop")
    return table, fresh, found, pending_out


def hashset_insert_salted(
    table: jax.Array,
    key_hi: jax.Array,
    key_lo: jax.Array,
    salt_hi: jax.Array,
    salt_lo: jax.Array,
    active: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Tenant-salted visited-set insert for a packed multi-tenant wave
    (``checker/packed_tenancy.py``): each lane's (hi, lo) fingerprint is
    XOR-salted by its tenant's per-lane salt before claiming, so many
    tenants share ONE table without cross-tenant dedup (see
    ``ops.fingerprint.salt_keys`` for why XOR keeps every tenant's dedup
    bit-identical to its solo run).

    Built on the duplicate-tolerant UNSORTED insert on purpose: sorting
    by salted key would interleave tenants' lanes in salt order, but the
    owner-ticket scatter insert keeps natural lane order — so each
    tenant's fresh lanes come out in its own FIFO frontier order, the
    exact claim order its solo run (``wave_dedup="scatter"``, the CPU
    backend default) produces. That order-preservation is what makes the
    packed run's parent pointers, discovery fingerprints, and golden
    reports per-tenant bit-identical, not just count-identical.
    """
    from .fingerprint import salt_keys

    shi, slo = salt_keys(key_hi, key_lo, salt_hi, salt_lo)
    return hashset_insert_unsorted(table, shi, slo, active)


def hashset_probe_length_counts(table):
    """Probe-chain length distribution of the RESIDENT keys: for each
    occupied slot, the displacement from its key's home slot (linear
    probing never wraps, so ``slot - home`` IS the probe count that
    insert paid and every future lookup repays). Returns an int64 array
    of length ``MAX_PROBES + 1`` where index ``d`` counts keys resting
    ``d`` slots past home.

    Audit path, not hot: pure numpy over a host copy of the table (the
    attribution engine pulls it once at run end). The distribution is
    the observed cost of the probabilistic machinery — a heavy tail here
    means key clustering is eroding the nearly-sequential probe pattern
    the sorted insert is built around."""
    import numpy as np

    tab = np.asarray(table)
    capacity = tab.shape[0] - MAX_PROBES
    live = (tab[:, 0] != 0) | (tab[:, 1] != 0)
    idx = np.flatnonzero(live)
    counts = np.zeros(MAX_PROBES + 1, np.int64)
    if len(idx) == 0:
        return counts
    k = capacity.bit_length() - 1
    if k == 0:
        home = np.zeros(len(idx), np.int64)
    else:
        home = (
            tab[idx, 0].astype(np.uint32) >> np.uint32(32 - k)
        ).astype(np.int64)
    disp = np.clip(idx - home, 0, MAX_PROBES)
    return np.bincount(disp, minlength=MAX_PROBES + 1)


def hashset_contains(
    table: jax.Array, key_hi: jax.Array, key_lo: jax.Array
) -> jax.Array:
    """Batched membership probe (no mutation)."""
    capacity = table.shape[0] - MAX_PROBES
    base = _home(key_hi, capacity)
    n = key_hi.shape[0]

    def cond(carry):
        r, pending, _found = carry
        return (r < MAX_PROBES) & pending.any()

    def body(carry):
        r, pending, found = carry
        idx = base + r
        row = table[idx]
        empty = (row[:, 0] == 0) & (row[:, 1] == 0)
        match = (row[:, 0] == key_hi) & (row[:, 1] == key_lo)
        found = found | (pending & match)
        pending = pending & ~match & ~empty
        return r + 1, pending, found

    _r, _pending, found = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.ones((n,), bool), jnp.zeros((n,), bool))
    )
    return found
