"""The visited-set insert at the width of its pending lanes
(``ops.hashset.insert_counted``) against a plain NumPy model of the insert:
linear probing from the monotone home, one claim per slot per round. Fresh
lanes are the active keys absent before, found lanes those present, the
resident set grows by the fresh keys, and a lane is pending only when its
probes ran out; the counters follow the round schedule the model derives
from its pending counts and ``probe_widths``."""

import jax
import numpy as np
import pytest

from stateright_tpu.ops.hashset import (
    INSERT_COUNTERS,
    MAX_PROBES,
    hashset_new,
    insert_counted,
    probe_widths,
)

_U32_MAX = np.uint32(0xFFFFFFFF)
_insert = jax.jit(insert_counted)


def _homes(hi, capacity):
    k = capacity.bit_length() - 1
    return (hi.astype(np.uint64) >> np.uint64(32 - k)).astype(np.int64)


def model_insert(table, hi, lo, active):
    """One lane at a time per slot and round (the lowest lane wins a tie),
    over a copy of ``table``. Returns (table, fresh, found, pending,
    counters)."""
    table = table.copy()
    capacity = table.shape[0] - MAX_PROBES
    widths = probe_widths(len(hi))
    home = _homes(hi, capacity)
    pending = active.copy()
    fresh = np.zeros_like(active)
    found = np.zeros_like(active)
    rounds = issued = live = narrowings = 0
    s = r = 0
    while True:
        p = int(pending.sum())
        while s + 1 < len(widths) and 0 < p <= widths[s + 1] and r < MAX_PROBES:
            s += 1
            narrowings += 1
        if p == 0 or r >= MAX_PROBES:
            break
        rounds += 1
        issued += widths[s]
        live += p
        lanes = np.flatnonzero(pending)
        idx = home[lanes] + r
        row = table[idx]
        match = (row[:, 0] == hi[lanes]) & (row[:, 1] == lo[lanes])
        empty = (row[:, 0] == 0) & (row[:, 1] == 0)
        found[lanes[match]] = True
        attempt = lanes[empty & ~match]
        slots, first = np.unique(home[attempt] + r, return_index=True)
        winners = attempt[first]
        table[slots, 0] = hi[winners]
        table[slots, 1] = lo[winners]
        fresh[winners] = True
        pending[lanes[match]] = False
        pending[winners] = False
        r += 1
    return table, fresh, found, pending, (rounds, issued, live, narrowings)


def _keys(rng, n, homes=None, capacity=None):
    """Random keys; with ``homes``, key i on home slot ``homes[i]`` (the top
    bits of hi fixed, the rest random)."""
    hi = rng.integers(1, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    if homes is not None:
        shift = 32 - (capacity.bit_length() - 1)
        low = (1 << shift) - 1
        hi = ((homes.astype(np.uint64) << np.uint64(shift))
              | (hi & np.uint32(low))).astype(np.uint32)
    lo = rng.integers(1, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    return hi, lo


def _batch(hi, lo, active):
    """Keys in the wave's order: sorted, inactive lanes as U32_MAX."""
    shi = np.where(active, hi, _U32_MAX)
    slo = np.where(active, lo, _U32_MAX)
    order = np.lexsort((slo, shi))
    return shi[order], slo[order], active[order]


def _resident(table):
    live = (table[:, 0] != 0) | (table[:, 1] != 0)
    return set(zip(table[live, 0].tolist(), table[live, 1].tolist()))


def _case(width, share=0.0, clusters=(0, 0), present=0.0, seed=0):
    """(table, hi, lo, active): ``share`` of ``width`` lanes active on
    random keys, plus ``clusters`` = (homes, keys per home) active keys on
    homes spread over the table; ``present`` of the active keys inserted
    beforehand."""
    rng = np.random.default_rng(seed)
    k, m = clusters
    n_rand = int(round(share * width))
    n_act = n_rand + k * m
    capacity = max(1024, 1 << int(np.ceil(np.log2(max(n_act, 1) / 0.5))))
    hi, lo = _keys(rng, width)
    if k:
        homes = np.repeat(np.arange(k) * (capacity // k) + 7, m)
        hi[:k * m], lo[:k * m] = _keys(rng, k * m, homes, capacity)
    active = np.zeros(width, bool)
    active[:k * m] = True
    active[k * m + rng.permutation(width - k * m)[:n_rand]] = True
    table = np.asarray(hashset_new(capacity))
    if present:
        pre = active & (rng.random(width) < present)
        table, *_ = model_insert(table, hi, lo, pre)
    shi, slo, act = _batch(hi, lo, active)
    return table, shi, slo, act


CASES = {
    **{
        f"w{w}-{int(100 * a)}pct": dict(width=w, share=a)
        for w in (100, 2048, 303_104)
        for a in (0.0, 0.01, 0.07, 1.0)
    },
    # Homes that each hold 100 keys: a stage runs tens of rounds (one key
    # per home per round) before the pending lanes fit the next width.
    "w2048-cluster": dict(width=2048, clusters=(8, 100), share=0.05),
    "w303104-cluster": dict(width=303_104, clusters=(50, 60), share=0.02),
    "w2048-present": dict(width=2048, share=0.5, present=0.6),
    "w303104-present": dict(width=303_104, share=0.07, present=0.5),
    # More keys on one home than MAX_PROBES slots past it: the rest stay
    # pending, at the widest stage (700 > 256) and at a narrow one.
    "w2048-exhaust": dict(width=2048, clusters=(1, 700)),
    "w303104-exhaust": dict(width=303_104, clusters=(1, 300), share=0.01),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_insert_matches_model(case):
    table, hi, lo, active = _case(**CASES[case])
    want_table, want_fresh, want_found, want_pending, want_counts = (
        model_insert(table, hi, lo, active)
    )
    out = _insert(table, hi, lo, active)
    got_table, fresh, found, pending, counts = map(np.asarray, out)

    before = _resident(table)
    keys = list(zip(hi.tolist(), lo.tolist()))
    present = np.array([k in before for k in keys])
    np.testing.assert_array_equal(found, active & present)
    np.testing.assert_array_equal(found, want_found)
    assert not (fresh & pending).any() and not (found & (fresh | pending)).any()
    np.testing.assert_array_equal(fresh | pending, active & ~present)
    assert fresh.sum() == want_fresh.sum()
    assert pending.sum() == want_pending.sum()
    if "exhaust" in case:
        assert pending.sum() > 0
    else:
        assert pending.sum() == 0
    after = _resident(got_table)
    assert after == before | {k for k, f in zip(keys, fresh) if f}
    # Which of a home's tied keys land when its probes run out follows
    # the claim's tie order, so with pending lanes only the size agrees.
    want_after = _resident(want_table)
    assert after == want_after if not pending.any() else (
        len(after) == len(want_after)
    )
    # A pending lane's key did not land anywhere.
    assert not {k for k, p in zip(keys, pending) if p} & after

    got = dict(zip(INSERT_COUNTERS, counts.tolist()))
    rounds, issued, live, narrowings = want_counts
    assert got == dict(rounds=rounds, lanes_issued=issued, lanes_live=live,
                       narrowings=narrowings)
    assert got["lanes_live"] <= got["lanes_issued"]
    if active.any():
        assert got["rounds"] >= 1
    assert got["narrowings"] < len(probe_widths(len(hi)))


def test_probe_widths():
    assert probe_widths(100) == (100,)
    assert probe_widths(1024) == (1024,)
    assert probe_widths(2048) == (2048, 256)
    assert probe_widths(303_104) == (303_104, 37_888, 4_736, 640)
