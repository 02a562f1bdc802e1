"""TPU breadth-first checker: frontier waves expanded as fused device kernels.

This is the TPU-native re-architecture of the reference's ``BfsChecker``
(``/root/reference/src/checker/bfs.rs``). Where the reference runs N worker
threads popping 1500-state blocks from a ``JobBroker`` and deduplicating
through a concurrent ``DashMap``, this checker runs the whole search
inside one compiled device loop (the *deep drain*): a device-resident
FIFO ring holds the pending frontier, and each iteration runs one wave

    frontier batch ──vmap(packed_step over F×A grid)──▶ candidates
      ──fingerprint (u32-pair murmur fold)──▶ keys
      ──sort-dedup within wave──▶ wave-unique keys
      ──scatter-claim insert into device hash set──▶ fresh mask
      ──masked-cumsum compaction──▶ ring push + next frontier dequeue

exiting to the host only when the parent-fp log fills, the visited table
or ring needs growing, or an undiscovered property hit. At each exit the
host receives: scalar counters, per-property discovery fingerprints, and
the (child fp, parent fp) pairs needed for TLC-style path reconstruction
(Yu/Manolios/Lamport), which replays the *host* model along the
fingerprint trail exactly like the reference
(``/root/reference/src/checker/path.rs:20-97``). Wave-at-a-time mode
(``max_drain_waves=1``, or any visitor/target-count run) keeps the old
per-wave host loop for callback and overshoot granularity.

Semantics parity notes (all mirrored from the reference):
- ``eventually`` bits propagate along paths and are NOT part of the
  fingerprint, reproducing the documented false-negative on DAG joins and
  cycles (``/root/reference/src/checker/bfs.rs:285-305``).
- ``target_state_count``/``target_max_depth`` may overshoot by up to a wave
  (the reference overshoots by up to a block, ``src/checker.rs:234-236``).
- Symmetry reduction (``.symmetry()``) EXCEEDS the reference's BFS (which
  ignores it): visited keys become orbit-minimum fingerprints, re-avalanched
  for home-slot uniformity (see ``_make_key_fn``).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batch import BatchableModel
from ..core.model import Expectation
from ..core.path import Path
from ..native import make_fingerprint_store
from ..ops.fingerprint import (
    FP_SCHEME,
    avalanche32,
    fingerprint_state,
    fp64_pairs,
    fp_to_int,
)
from ..ops.hashset import (
    INSERT_COUNTERS,
    hashset_insert,
    hashset_insert_unsorted,
    insert_counted,
    hashset_new,
)
from ..ops.ring import ring_export, ring_push, ring_rows, ring_take
from ..telemetry import CheckTracer, WaveInstruments, metrics_registry
from ..utils.faults import fault_point
from .base import _NULL_CTX, Checker  # noqa: F401 - _NULL_CTX re-exported
from .pipeline import HostPipeline
from .programs import CUSTOM_REP_SCHEME, STEPS, Programs

_DEPTH_INF = (1 << 31) - 1
_U32_MAX = np.uint32(0xFFFFFFFF)  # numpy: keeps module import backend-free
# Grow the device hash set before load factor can exceed this.
_MAX_LOAD = 0.55
# Smallest bucket in the occupancy-adaptive wave ladder: one packed tile
# (8 sublanes) — narrower dispatches are dominated by fixed launch cost.
_MIN_BUCKET = 8
# Default ladder depth: F_max/16 … F_max (4 power-of-two halvings).
_DEFAULT_BUCKET_STEPS = 4
# The ladder auto-engages (bucket_ladder=None) only at this frontier
# capacity or above: below it a full wave is already microseconds of
# masked waste, so the rung compiles could never pay for themselves.
# Pass bucket_ladder explicitly to force either way.
_AUTO_BUCKET_MIN_F = 512


def bucket_ladder_widths(f_max: int, steps: int) -> list:
    """The descending power-of-two wave-width ladder for a checker with
    frontier capacity ``f_max``: ``[F_max, F_max/2, …]`` down to
    ``max(F_max >> steps, _MIN_BUCKET)``. ``steps=0`` disables bucketing
    (a single fixed-width rung). Shared by the checkers and the
    breakdown mirror so the measured ladder is the dispatched ladder."""
    floor = max(min(f_max, _MIN_BUCKET), f_max >> max(0, steps))
    return [f_max >> i for i in range(steps + 1) if (f_max >> i) >= floor]


def bucket_for(widths, live: int) -> int:
    """The smallest ladder width that holds ``live`` lanes (``widths``
    descending; the widest rung is returned when nothing smaller fits)."""
    chosen = widths[0]
    for w in widths[1:]:
        if live <= w:
            chosen = w
    return chosen


def packed_model_digest(model, action_count: int) -> str:
    """Digest of a model's packed configuration, guarding checkpoint resume:
    the class-name check alone would let e.g. a 3-RM checkpoint resume a
    4-RM model."""
    from hashlib import blake2b

    h = blake2b(digest_size=16)
    h.update(type(model).__name__.encode())
    h.update(str(action_count).encode())
    for leaf in jax.tree_util.tree_leaves(model.packed_init_states()):
        arr = np.asarray(leaf)
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def checkpoint_header(
    kind: str, model, action_count: int, symmetry: bool, sym_scheme=None
) -> dict:
    """Common checkpoint header shared by every device checker.
    ``sym_scheme`` is the visited-key scheme tag (``sym_key_scheme``);
    legacy callers passing only the bool get the group scheme."""
    if symmetry and sym_scheme is None:
        sym_scheme = SYM_KEY_SCHEME
    return {
        # v2 (out-of-core tiering): adds the optional "storage" payload
        # (L1/L2 fingerprint runs + Bloom filters). v3 (device
        # liveness): adds the optional "liveness" payload (the
        # condition-false edge store + roots/terminals) — writers stamp
        # 3 only when that payload is present, so v2 readers keep
        # restoring every checkpoint written without liveness="device".
        # v1/v2 checkpoints still restore; see MIGRATING.md.
        "version": 2,
        "kind": kind,
        "model": type(model).__name__,
        "model_digest": packed_model_digest(model, action_count),
        "symmetry": symmetry,
        "sym_scheme": sym_scheme if symmetry else None,
        "fp_scheme": FP_SCHEME,
    }


def validate_checkpoint_header(
    payload: dict,
    kind: str,
    wrong_kind_hint: str,
    model,
    action_count: int,
    symmetry: bool,
    sym_scheme=None,
) -> None:
    """Rejects checkpoints another checker kind, model, model configuration,
    or symmetry setting wrote. Checkpoints predating the ``kind`` field were
    written by the single-device checker (the only kind that existed)."""
    if payload.get("version") not in (1, 2, 3):
        raise ValueError(f"unsupported checkpoint version: {payload!r}")
    found_kind = payload.get("kind", "tpu_bfs")
    if found_kind != kind:
        raise ValueError(
            f"checkpoint kind {found_kind!r} does not match this checker "
            f"({kind!r}): {wrong_kind_hint}"
        )
    if payload["model"] != type(model).__name__:
        raise ValueError(
            f"checkpoint was written by model {payload['model']!r}, "
            f"resuming with {type(model).__name__!r}"
        )
    if payload.get("model_digest") != packed_model_digest(model, action_count):
        raise ValueError(
            "checkpoint was written by a differently-configured model "
            "(packed init states / action count do not match); resuming "
            "would mix two state spaces"
        )
    if payload.get("symmetry", False) != symmetry:
        raise ValueError(
            "checkpoint symmetry setting does not match this checker "
            "(visited keys are canonical-form fingerprints under symmetry, "
            "plain fingerprints otherwise; the two key spaces cannot mix)"
        )
    if symmetry:
        want = sym_scheme if sym_scheme is not None else SYM_KEY_SCHEME
        if payload.get("sym_scheme") != want:
            raise ValueError(
                f"checkpoint symmetry-key scheme "
                f"{payload.get('sym_scheme')!r} does not match this "
                f"checker ({want!r}); its visited keys cannot be mixed "
                "into a resumed run"
            )
    if payload.get("fp_scheme") != FP_SCHEME:
        raise ValueError(
            f"checkpoint fingerprint scheme {payload.get('fp_scheme')!r} "
            f"does not match this build ({FP_SCHEME!r}); its visited keys "
            "and parent fps cannot be mixed into a resumed run"
        )


def atomic_pickle(path, payload) -> None:
    """Writes the pickle to ``path`` atomically (tmp file + rename), so a
    kill mid-checkpoint never corrupts the previous checkpoint."""
    import os
    import pickle

    # Injection seam: a real checkpoint write fails on ENOSPC, a torn
    # NFS rename, or fs remount — always BEFORE the rename, so the
    # previous checkpoint survives the fault (the atomicity guarantee
    # this function exists for).
    fault_point("checkpoint.write")
    tmp = f"{path}.tmp"
    with open(tmp, "wb") as f:
        pickle.dump(payload, f)
    os.replace(tmp, path)


# Symmetry visited-key scheme. r2 keyed on the n!-loop orbit-minimum
# fingerprint; r3 keys verified lanes on the canonical-permutation
# fingerprint (WL refinement) with per-lane orbit-minimum fallback — a
# different (still orbit-proper) key space, so symmetry checkpoints
# record and validate this tag.
SYM_KEY_SCHEME = "wl-canon+orbitmin-v2"


def sym_key_scheme(symmetry) -> "Optional[str]":
    """The visited-key scheme tag a symmetry setting implies (None when
    symmetry is off) — recorded in checkpoints so runs never resume across
    incompatible key spaces."""
    if symmetry is None:
        return None
    from .builder import default_representative

    return (
        SYM_KEY_SCHEME
        if symmetry is default_representative
        else CUSTOM_REP_SCHEME
    )


def _make_key_fn(model, fp_fn, symmetry):
    """Batched dedup-key function for the device checkers, or ``None`` when
    symmetry is off (callers then use the plain fingerprints they already
    computed).

    Under symmetry the key is a canonical-form fingerprint — a true orbit
    invariant, so dedup merges states iff they share an orbit. Two routes
    compute it:

    - **Refined (fast)**: when the model implements
      ``packed_refine_colors`` (see ``core/batch.py``), iterate the
      WL-style equivariant color refinement, sort actors by final color
      (candidate canonical permutation), and VERIFY remaining color ties
      are automorphisms by checking each adjacent tied transposition
      leaves the fingerprint unchanged (adjacent transpositions generate
      each tie class's full symmetric group). Verified lanes key on the
      canonical-permutation fingerprint: ~``n`` fingerprint passes per
      state.
    - **Orbit-minimum (exact fallback)**: a sequential ``fori_loop`` over
      all ``n!`` permutations taking the minimum fingerprint — vmapping
      the group axis instead would materialize ``B x n!`` permuted states
      at once. Used for the whole batch when the model has no refine
      hook, and selected per-lane (via ``lax.cond``, so the loop only
      executes on waves that need it) for lanes whose verification
      failed.

    The mix is consistent across waves: verification outcomes are orbit
    invariants (computed on the canonical state), so every member of an
    orbit takes the same route and thus the same key.
    """
    if symmetry is None:
        return None
    from .builder import default_representative

    if symmetry is not default_representative:
        from ..core.batch import BatchableModel

        has_rep = (
            type(model).packed_representative
            is not BatchableModel.packed_representative
        )
        if not has_rep:
            raise ValueError(
                "device checkers cannot honor a custom symmetry_fn unless "
                "the model implements packed_representative(): the built-in "
                "keys reduce by the FULL actor-permutation group, which "
                "would over-merge states under a partial symmetry. "
                "Implement packed_representative (core/batch.py), use "
                ".symmetry(), or a host checker."
            )

        def rep_keys(states_batch):
            # Plain fingerprints of the user's canonical form — they
            # inherit fingerprint_words' sentinel nudges, so no finalize.
            return jax.vmap(
                lambda s: fp_fn(model.packed_representative(s))
            )(states_batch)

        return rep_keys
    try:
        n2o, o2n = model.packed_symmetry()
    except (AttributeError, NotImplementedError) as e:
        raise TypeError(
            "symmetry on the device path requires the model to implement "
            "packed_symmetry()/packed_apply_permutation() (see "
            "stateright_tpu.core.batch)"
        ) from e
    n2o = jnp.asarray(n2o)
    o2n = jnp.asarray(o2n)
    n_perms, n = n2o.shape

    def full_min(states_batch):
        leaves = jax.tree_util.tree_leaves(states_batch)
        b = leaves[0].shape[0]

        def body(k, acc):
            mhi, mlo = acc
            his, los = jax.vmap(
                lambda s: fp_fn(
                    model.packed_apply_permutation(s, n2o[k], o2n[k])
                )
            )(states_batch)
            better = (his < mhi) | ((his == mhi) & (los < mlo))
            return jnp.where(better, his, mhi), jnp.where(better, los, mlo)

        full = jnp.full((b,), _U32_MAX)
        return jax.lax.fori_loop(0, n_perms, body, (full, full))

    def finalize(khi, klo):
        # Re-avalanche the keys: an orbit minimum over |G| uniform draws
        # concentrates in the low 1/|G| of the key space, which would pile
        # every home slot (top bits of hi — ops/hashset._home) into the
        # first capacity/|G| rows. The murmur finalizer is a bijection on
        # u32, so scrambling each word introduces no new collisions;
        # sentinels are nudged exactly like ops/fingerprint
        # .fingerprint_words. Canonical-permutation keys share the
        # finalizer so both routes draw from one key space.
        khi = avalanche32(khi ^ jnp.uint32(0x51A7CC9E))
        klo = avalanche32(klo ^ jnp.uint32(0xE3779B97))
        zero = (khi == 0) & (klo == 0)
        klo = jnp.where(zero, jnp.uint32(1), klo)
        maxed = (khi == _U32_MAX) & (klo == _U32_MAX)
        klo = jnp.where(maxed, jnp.uint32(_U32_MAX - 1), klo)
        return khi, klo

    from ..core.batch import BatchableModel

    has_refine = (
        type(model).packed_refine_colors
        is not BatchableModel.packed_refine_colors
    )
    if not has_refine:
        def orbit_keys(states_batch):
            return finalize(*full_min(states_batch))

        return orbit_keys

    # WL color partitions on n actors stabilize within n-1 rounds; extra
    # rounds only re-hash a stable partition.
    rounds = max(1, min(n - 1, 6))
    iota = jnp.arange(n, dtype=jnp.int32)
    # Adjacent-transposition index table, row i = identity with (i, i+1)
    # swapped. Both this loop and the refine loop run as fori_loops, not
    # unrolled — the key fn is traced inside every checker's wave/drain,
    # and an n-times-smaller HLO is real compile-warmup savings
    # (semantics are iteration-identical).
    swap_rows = np.tile(np.arange(n, dtype=np.int32), (max(n - 1, 1), 1))
    for i in range(n - 1):
        swap_rows[i, i], swap_rows[i, i + 1] = i + 1, i
    swap_tab = jnp.asarray(swap_rows)

    def refined_keys(states_batch):
        def one(s):
            colors = jax.lax.fori_loop(
                0,
                rounds,
                lambda _i, c: model.packed_refine_colors(s, c),
                jnp.zeros((n,), jnp.uint32),
            )
            sorted_colors, cand = jax.lax.sort(
                (colors, iota), num_keys=1
            )
            inv = jnp.zeros((n,), jnp.int32).at[cand].set(iota)
            hi0, lo0 = fp_fn(model.packed_apply_permutation(s, cand, inv))

            def check(i, ok):
                tie = sorted_colors[i] == sorted_colors[i + 1]
                cand_i = cand[swap_tab[i]]
                inv_i = jnp.zeros((n,), jnp.int32).at[cand_i].set(iota)
                hi_i, lo_i = fp_fn(
                    model.packed_apply_permutation(s, cand_i, inv_i)
                )
                return ok & (~tie | ((hi_i == hi0) & (lo_i == lo0)))

            ok = jax.lax.fori_loop(0, n - 1, check, jnp.bool_(True))
            return hi0, lo0, ok

        khi, klo, ok = jax.vmap(one)(states_batch)
        fhi, flo = jax.lax.cond(
            ok.all(), lambda: (khi, klo), lambda: full_min(states_batch)
        )
        return finalize(
            jnp.where(ok, khi, fhi), jnp.where(ok, klo, flo)
        )

    return refined_keys


def supports_expand_fps(model) -> bool:
    """Whether the model provides the fingerprint-only expansion hooks
    (``packed_expand_fps`` + ``packed_take``) AND allows them — THE
    definition shared by the checker's auto policy and bench.py's
    measured-policy calibration, so they cannot disagree about which
    pipelines exist for a model."""
    return (
        type(model).packed_expand_fps is not BatchableModel.packed_expand_fps
        and type(model).packed_take is not BatchableModel.packed_take
        and model.packed_expand_fps_supported()
    )


def default_wave_dedup(platform: str, hashset_impl: str = "xla") -> str:
    """THE definition of the backend wave-dedup default, shared by
    ``TpuBfsChecker``, ``measure_wave_breakdown``, and ``bench.py``:
    "scatter" on the CPU backend (the duplicate-tolerant unsorted insert
    measured 2.3x on 2pc-7 — XLA's single-threaded sort dominates wide
    waves there), "sort" elsewhere (sequential probe pattern, pending
    the on-chip A/B) and always under the Pallas insert kernel (it
    requires sorted batches)."""
    if hashset_impl == "pallas" or platform != "cpu":
        return "sort"
    return "scatter"


def _insert_args(counts):
    """Span args of the visited-set insert's counters (a float32 vector of
    ``INSERT_COUNTERS``), as ``insert_<counter>``: whole numbers."""
    return {f"insert_{k}": int(v) for k, v in zip(INSERT_COUNTERS, counts)}


def _pow2ceil(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def min_admissible_hbm_budget_mib(model, frontier_capacity: int) -> float:
    """The smallest ``hbm_budget_mib`` a checker with this model and
    frontier width accepts — i.e. MAXIMUM eviction pressure. THE shared
    definition (the inverse of ``storage.max_table_rows_for_budget``,
    priced with the same 8-byte row + probe apron): one worst-case wave
    (frontier × action_count candidates) must fit a freshly-evicted
    table under ``_MAX_LOAD``. bench.py's --async-ab leg and the
    equivalence tests both use it, so a load-factor or layout change
    cannot silently stop their budgets from binding."""
    from ..ops.hashset import MAX_PROBES

    rows = _pow2ceil(
        int(
            _pow2ceil(frontier_capacity)
            * model.packed_action_count()
            / _MAX_LOAD
        )
        + 1
    )
    return ((rows + MAX_PROBES) * 8) / (1 << 20)


def compact(chunk, width):
    """Gathers a chunk's live lanes into a dense prefix and narrows it to
    ``width`` (the chosen bucket), so masked padding lanes never reach the
    expand grid. The cumsum scatter is stable — live lanes keep their
    relative order, so in-wave dedup tie-breaks (first claim wins by lane
    order) pick the same winner as the fixed-width dispatch and the
    bucketed path stays bit-identical."""
    mask = chunk["mask"]
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    # Scatter straight into the bucket-wide buffer (the chosen bucket
    # holds every live lane by construction; the < width guard only drops
    # lanes a torn mask could produce) — a full-width scatter sliced
    # afterwards would still write O(F_max) bytes per leaf.
    dest = jnp.where(mask & (pos < width), pos, width)

    def scat(x):
        z = jnp.zeros((width,) + x.shape[1:], x.dtype)
        return z.at[dest].set(x, mode="drop")

    out = {
        k: jax.tree_util.tree_map(scat, v) if k == "states" else scat(v)
        for k, v in chunk.items()
        if k != "mask"
    }
    out["mask"] = jnp.arange(width, dtype=jnp.int32) < mask.sum(
        dtype=jnp.int32
    )
    return out


class TpuBfsChecker(Checker):
    """Requires the model to implement ``BatchableModel``.

    ``frontier_capacity`` caps lanes per wave (larger frontiers split into
    chunks); ``table_capacity`` is the initial device hash-set size (grows
    by doubling + rehash). ``bucket_ladder`` is the occupancy-adaptive
    dispatch depth: the number of power-of-two bucket widths below
    ``F_max`` a wave may dispatch at (None auto-selects 4 →
    ``F_max/16 … F_max`` when ``F_max >= 512`` and fixed width below
    that, where rung compiles cannot pay for themselves; 0 forces fixed
    width); see README "Performance tuning".

    ``hbm_budget_mib`` enables out-of-core mode: the device table is
    hard-capped at the budget, growth past it evicts the full table to
    host-resident delta-compressed runs (L1), and ``host_budget_mib`` /
    ``spill_dir`` spill merged runs to disk (L2). Results are
    bit-identical to the unbounded run; see README "Memory hierarchy".

    ``async_pipeline=True`` turns the wave loop into a two-deep
    pipeline: wave N+1's expand/fingerprint/insert runs on device while
    a host worker thread applies wave N's tiered-store probe, eviction
    absorbs, and checkpoint serialization; survivors of the deferred
    probe re-enter the frontier one wave late at the queue tail —
    exactly where the synchronous path would have appended them — so
    results stay bit-identical (README "Async pipeline"). Requires no
    visitor (per-chunk callbacks need each wave's verdict before the
    next dispatch).
    """

    def __init__(
        self,
        options,
        frontier_capacity=1 << 13,
        table_capacity=1 << 16,
        checkpoint_path=None,
        checkpoint_every_chunks=32,
        checkpoint_min_interval_s=0.0,
        resume_from=None,
        profile_dir=None,
        max_drain_waves=100_000,
        drain_log_factor=8,
        pool_factor=16,
        hashset_impl="xla",
        wave_dedup=None,
        expand_fps=None,
        bucket_ladder=None,
        hbm_budget_mib=None,
        host_budget_mib=None,
        spill_dir=None,
        attribution=False,
        coverage=False,
        run_id=None,
        aot_cache=None,
        aot_store=None,
        async_pipeline=False,
        liveness=None,
        edge_log_capacity=None,
        wave_kernel="staged",
        config_notes=None,
    ):
        model = options.model
        if not isinstance(model, BatchableModel):
            raise TypeError(
                f"spawn_tpu_bfs requires a BatchableModel; {type(model).__name__} "
                "does not implement the packed protocol (see stateright_tpu.core.batch)"
            )
        self._model = model
        self._properties = model.properties()
        # Run identity (checking-as-a-service): ``run_id=`` gives this
        # checker its own metrics registry (no instrument collisions
        # between concurrent runs in one process). Every span of the check
        # carries its ``check`` id (the run_id when given, so monitors can
        # select this run's stream) and a parent in the check's tree.
        self.run_id = run_id
        self._registry = metrics_registry(run_id) if run_id else None
        self._tracer = CheckTracer(run_id)
        self._drains = 0
        self._fp_calls = 0
        self._conditions = model.packed_conditions()
        if len(self._conditions) != len(self._properties):
            raise ValueError(
                "packed_conditions() must align 1:1 with properties(): "
                f"{len(self._conditions)} != {len(self._properties)}"
            )
        eventually = [
            i
            for i, p in enumerate(self._properties)
            if p.expectation == Expectation.EVENTUALLY
        ]
        if len(eventually) > 32:
            raise ValueError("at most 32 eventually properties supported")
        self._ebit: Dict[int, int] = {pi: b for b, pi in enumerate(eventually)}
        self._ebits0 = sum(1 << b for b in self._ebit.values())
        self._A = model.packed_action_count()
        # Waves dispatch on a power-of-two bucket ladder (F_max down to
        # F_max >> bucket_ladder): each chunk runs at the smallest bucket
        # holding its live lanes, so wave cost scales with occupancy
        # instead of capacity. Every rung compiles once per table shape
        # (the AOT cache below is keyed on (bucket, table_capacity)), so
        # steady state never recompiles — a recompile costs seconds to
        # tens of seconds per shape.
        # ``bucket_ladder``: number of halvings below F_max (None = the
        # default ladder, 0 = fixed-width dispatch).
        self._F_max = _pow2ceil(frontier_capacity)
        if bucket_ladder is None:
            bucket_ladder = (
                _DEFAULT_BUCKET_STEPS
                if self._F_max >= _AUTO_BUCKET_MIN_F
                else 0
            )
        if bucket_ladder < 0:
            raise ValueError(
                f"bucket_ladder must be >= 0, got {bucket_ladder}"
            )
        self._buckets = bucket_ladder_widths(self._F_max, bucket_ladder)
        self._capacity = table_capacity
        # Out-of-core tiering (stateright_tpu.storage): ``hbm_budget_mib``
        # hard-caps the device hash table. Growth past the cap drains the
        # full table to host L1 runs instead of doubling (``_evict_l0``),
        # and every later wave's L0-fresh lanes batch-probe L1/L2 at the
        # wave's host exit — membership is the union of the tiers, so
        # results stay bit-identical to the unbounded single-tier path
        # (tests/test_storage_equivalence.py). See README "Memory
        # hierarchy".
        from ..storage import (
            TieredVisitedStore,
            max_table_rows_for_budget,
            validate_budget_knobs,
        )

        validate_budget_knobs(hbm_budget_mib, host_budget_mib, spill_dir)
        self._tier = None
        self._max_capacity = None
        if hbm_budget_mib is not None:
            max_cap = max_table_rows_for_budget(hbm_budget_mib)
            # A freshly-evicted (empty) table must absorb one worst-case
            # wave (F_max × A candidates) under the load cap, or the
            # grow-and-retry loop could never terminate.
            min_cap = _pow2ceil(
                int(self._F_max * self._A / _MAX_LOAD) + 1
            )
            if max_cap < min_cap:
                raise ValueError(
                    f"hbm_budget_mib={hbm_budget_mib} allows a device "
                    f"table of {max_cap} rows, but one worst-case wave "
                    f"(frontier_capacity × action_count = "
                    f"{self._F_max * self._A} candidates) needs at least "
                    f"{min_cap}; raise the budget or shrink "
                    "frontier_capacity"
                )
            self._max_capacity = max_cap
            self._capacity = min(self._capacity, max_cap)
            from ..storage import StorageInstruments

            self._tier = TieredVisitedStore(
                host_budget_mib=host_budget_mib,
                spill_dir=spill_dir,
                instruments=StorageInstruments(
                    "tpu_bfs", registry=self._registry
                ),
                tracer=self._tracer,
            )
        # Keys currently RESIDENT in the device table (== unique_count
        # until the first eviction; afterwards the table holds only the
        # working set plus re-claimed hot keys).
        self._l0_count = 0
        self._drain_exit = None
        # Visited-set insert kernel for the sorted wave batches: "xla"
        # (gather/scatter probing, ops/hashset.py) or "pallas" (tile-sweep
        # DMA kernel, ops/pallas_hashset.py — measure both with
        # ``python -m stateright_tpu.ops.bench_hashset`` and pick the
        # winner per backend). The unsorted sites (_rehash, checkpoint
        # restore) always use the XLA path.
        if hashset_impl not in ("xla", "pallas"):
            raise ValueError(
                f"hashset_impl must be 'xla' or 'pallas', got {hashset_impl!r}"
            )
        self._hashset_impl = hashset_impl
        if wave_kernel not in ("staged", "fused"):
            raise ValueError(
                f"wave_kernel must be 'staged' or 'fused', got "
                f"{wave_kernel!r}"
            )
        self._wave_kernel = wave_kernel
        # Run-configuration notes, surfaced once at run end through
        # ``Reporter.report_config_notes`` — a silently adjusted knob is
        # a dishonest one. Callers (the service's warm-start plane) may
        # pre-seed notes of their own.
        self.config_notes: List[str] = list(config_notes or ())
        if wave_kernel == "fused":
            # The fused wave grids over TILE_ROWS-row table tiles; round
            # the capacity up to the next admissible size (and say so)
            # instead of refusing admission. The staged pallas insert
            # below keeps its hard refusal: rounding there would change
            # the documented contract of an existing knob.
            from ..ops.pallas_hashset import TILE_ROWS, round_table_capacity

            rounded = round_table_capacity(self._capacity)
            if rounded != self._capacity:
                if (
                    self._max_capacity is not None
                    and rounded > self._max_capacity
                ):
                    raise ValueError(
                        f"table_capacity={self._capacity} rounds up to "
                        f"{rounded} rows for the tile-sweep kernels "
                        f"({TILE_ROWS}-row tiles), which exceeds the "
                        f"hbm_budget_mib cap of {self._max_capacity} rows; "
                        "raise the budget or shrink table_capacity"
                    )
                self.config_notes.append(
                    f"table_capacity rounded {self._capacity} -> {rounded} "
                    f"(tile-sweep kernels grid over {TILE_ROWS}-row table "
                    "tiles)"
                )
                self._capacity = rounded
        elif hashset_impl == "pallas":
            from ..ops.pallas_hashset import TILE_ROWS

            if self._capacity % TILE_ROWS:
                raise ValueError(
                    "hashset_impl='pallas' needs table_capacity to be a "
                    f"multiple of {TILE_ROWS} (got {self._capacity})"
                )
        # In-wave dedup strategy; None = the shared backend default
        # (``default_wave_dedup``). The fused wave embeds the sort-dedup
        # in its prologue, so its default is always "sort".
        if wave_dedup is None:
            wave_dedup = (
                "sort"
                if wave_kernel == "fused"
                else default_wave_dedup(jax.default_backend(), hashset_impl)
            )
        if wave_dedup not in ("sort", "scatter"):
            raise ValueError(
                f"wave_dedup must be 'sort' or 'scatter', got {wave_dedup!r}"
            )
        if wave_dedup == "scatter" and (
            hashset_impl == "pallas" or wave_kernel == "fused"
        ):
            raise ValueError(
                "wave_dedup='scatter' is incompatible with the tile-sweep "
                "Pallas kernels (hashset_impl='pallas' and "
                "wave_kernel='fused' both require sorted batches); drop "
                "the scatter override or select wave_kernel='staged' with "
                "hashset_impl='xla'"
            )
        self._wave_dedup = wave_dedup
        self._visitor = options._visitor
        self._target_state_count: Optional[int] = options._target_state_count
        self._depth_cap = options._target_max_depth or _DEPTH_INF
        self._setup_lasso(options)

        self._checkpoint_path = checkpoint_path
        # Counts dequeued frontier chunks (a wide BFS level splits into many
        # F_max-sized chunks); the time floor keeps wide frontiers from
        # checkpointing (full parent-map export + pickle) back to back.
        self._checkpoint_every = max(1, checkpoint_every_chunks)
        self._checkpoint_min_interval = checkpoint_min_interval_s
        self._resume_from = resume_from
        # SURVEY §5: per-frontier-wave profiler hooks. When set, the run is
        # wrapped in a JAX profiler trace (viewable in TensorBoard /
        # Perfetto) and every wave gets a StepTraceAnnotation.
        self._profile_dir = profile_dir
        # Deep device drain: the BFS runs inside one lax.while_loop with a
        # device-resident FIFO ring of pending states (the "pool"), exiting
        # to the host only to drain the parent-fp log, grow the table,
        # record a property discovery, or spill a pool overflow. Each host
        # round trip costs a dispatch and a sync; amortizing it over
        # thousands of waves is what makes the device path win
        # (SURVEY §7-5c's host-loop concern). 1 disables (wave-at-a-time);
        # also disabled when a visitor needs per-chunk callbacks or a
        # target count caps the run (overshoot would span whole drains).
        self._max_drain_waves = max(1, max_drain_waves)
        if checkpoint_path is not None:
            # A deep drain can span the whole run, which would starve the
            # periodic checkpointer; durability caps waves-per-drain so a
            # checkpoint opportunity arises at least every N waves. The
            # floor of 2 keeps the deep path selected (1 means "disabled").
            self._max_drain_waves = min(
                self._max_drain_waves, max(2, checkpoint_every_chunks)
            )
        # Log must hold at least one worst-case wave (F·A fresh states) or
        # such a wave could never be consumed device-side.
        self._drain_log_capacity = max(
            max(1, drain_log_factor) * self._F_max, self._F_max * self._A
        )
        # Pool ring capacity (power of two, ≥ one worst-case wave output).
        self._pool_capacity = _pow2ceil(
            max(max(1, pool_factor) * self._F_max, self._F_max * self._A)
        )

        self._state_count = 0
        self._unique_count = 0
        self._max_depth = 0
        self._discoveries_fp: Dict[str, int] = {}
        # (child fps u64, parent fps u64 — 0 encodes "init state") per wave,
        # ingested into the native parent-pointer store (C++ open-addressing
        # map; see stateright_tpu.native) for path reconstruction.
        self._wave_log: List = []
        # Under symmetry: the u64 visited-set keys claimed so far (the
        # checkpoint needs them — the table cannot be rebuilt from the
        # original fps in the parent store).
        self._key_log: List = []
        self._store = make_fingerprint_store()
        # Telemetry: instruments resolved once; the wave/drain loops emit
        # one span per wave (frontier width, new-unique, dedup hit-rate,
        # hash-set occupancy, max depth) through them — the live
        # observability the offline breakdown.py stage mirror cannot give.
        # (Tracer/registry already bound above — run_id-scoped when set.)
        self._wi = WaveInstruments("tpu_bfs", registry=self._registry)
        # Wave-timeline attribution (opt-in, telemetry/attribution.py):
        # fences each wave at phase boundaries and classifies its wall
        # into device/host_probe/evict/table_grow/checkpoint/compile/gap.
        # Results stay bit-identical — the fences change pacing only.
        self._init_attribution("tpu_bfs", attribution)
        self._ingested = 0
        self._ingest_lock = threading.Lock()
        self._done_event = threading.Event()
        self._error: Optional[BaseException] = None
        # Preemption (checking-as-a-service): request_preempt() asks the
        # worker to suspend at the next wave/drain boundary; the run's
        # state drains into an in-memory checkpoint payload instead of a
        # file and the worker exits (see request_preempt).
        self._preempt_event = threading.Event()
        self._preempt_payload: Optional[dict] = None
        # Async pipelined wave engine (README "Async pipeline"): one FIFO
        # host worker (checker/pipeline.py) applies each wave's host-tier
        # verdict — two-phase probe, parent-fp log, survivor re-entry —
        # plus eviction absorbs and checkpoint pickles, while the device
        # runs the next wave. FIFO submission order reproduces the
        # synchronous path's exact tier-operation sequence, and epoch
        # barriers (drain) at checkpoint/preempt/queue-empty boundaries
        # make every observable snapshot identical.
        self._async = bool(async_pipeline)
        if self._async and self._visitor is not None:
            raise ValueError(
                "async_pipeline is incompatible with a visitor: per-chunk "
                "callbacks reconstruct paths through verdicts the "
                "pipeline defers; drop the visitor or run synchronously"
            )
        self._pipe = (
            HostPipeline(name="tpu-bfs-host") if self._async else None
        )
        if self._attr is not None and self._async:
            self._attr.set_overlap_mode(True)

        # Fingerprints go through the model's view hook (e.g. actor systems
        # exclude crash flags, mirroring the host state hash).
        self._fp_fn = model.packed_fingerprint
        # Dedup keys: plain fingerprints, or — under symmetry reduction —
        # the minimum fingerprint over every actor permutation (an
        # orbit-proper canonical key; see core/batch.py for why the
        # reference's sort heuristic cannot be used on a wave BFS).
        self._symmetry_enabled = options._symmetry is not None
        if self._wave_kernel == "fused" and self._symmetry_enabled:
            raise ValueError(
                "wave_kernel='fused' does not support symmetry reduction "
                "yet (orbit-minimum keys need an in-kernel permutation "
                "sweep); use wave_kernel='staged'"
            )
        self._sym_scheme = sym_key_scheme(options._symmetry)
        self._key_fn = _make_key_fn(model, self._fp_fn, options._symmetry)
        # Fingerprint-only expansion (the byte diet, VERDICT r04 #2): when
        # the model provides ``packed_expand_fps`` + ``packed_take``, the
        # wave dedups on candidate fingerprints computed from deltas and
        # materializes ONLY the fresh lanes — candidate states never
        # round-trip through HBM. ``expand_fps``: None = auto (on when
        # supported), True = require, False = force the materializing wave.
        has_fps = supports_expand_fps(model)
        if expand_fps is None:
            # Symmetry needs candidate states for orbit keys; fps path
            # yields to the materializing wave there. The fused wave
            # stages the candidate grid in VMEM scratch, so it too runs
            # the materializing wave.
            self._use_fps = (
                has_fps
                and not self._symmetry_enabled
                and self._wave_kernel != "fused"
            )
        elif expand_fps:
            if self._wave_kernel == "fused":
                raise ValueError(
                    "expand_fps=True is incompatible with "
                    "wave_kernel='fused' (the fused wave materializes the "
                    "candidate grid in VMEM scratch); use "
                    "wave_kernel='staged'"
                )
            if not has_fps:
                raise ValueError(
                    "expand_fps=True requires the model to implement "
                    "packed_expand_fps and packed_take (and "
                    "packed_expand_fps_supported() to allow them — e.g. a "
                    "codec boundary without a per-row decomposition "
                    "vetoes the fps wave)"
                )
            if self._symmetry_enabled:
                raise ValueError(
                    "expand_fps is incompatible with symmetry reduction "
                    "(orbit keys need candidate states)"
                )
            self._use_fps = True
        else:
            self._use_fps = False
        # Device-native liveness (``liveness="device"``, README
        # "Trustworthy liveness"): the wave jits log the condition-false
        # edge relation per ``eventually`` property into a
        # capacity-budgeted device store (ops/edge_store.py; evicted to
        # storage/edge_log.py when over budget), and a run-end
        # trim+reach pass decides lasso/masked-terminal existence with a
        # concrete certificate — closing the reference's documented
        # false negative without the O(region) host post-pass. Forces
        # the materializing wave (child conditions need candidate
        # states), which the expand_fps resolution above already
        # honored via validate_liveness_mode's raise on the explicit
        # conflict.
        from .device_liveness import validate_liveness_mode

        self._live = validate_liveness_mode(
            liveness,
            symmetry=self._symmetry_enabled,
            expand_fps=(expand_fps is True),
            options=options,
        )
        if self._wave_kernel == "fused" and self._live == "device":
            raise ValueError(
                "liveness='device' is incompatible with "
                "wave_kernel='fused' (the edge-log append is not fused "
                "yet); use wave_kernel='staged' or the host liveness "
                "post-pass"
            )
        if self._live is not None:
            self._use_fps = False
        self._live_enabled = self._live == "device" and bool(self._ebit)
        self._live_paths: Dict[str, Path] = {}
        self._live_outcomes: Dict[str, dict] = {}
        self._live_store = None
        self._elog = None
        self._elog_count = 0
        self._live_ins = None
        if self._live_enabled:
            from ..storage import LivenessEdgeStore, LivenessInstruments

            # One worst-case wave appends F·A edge rows + F terminal
            # rows; the default store holds four of them so drains
            # amortize the eviction pull.
            self._elog_capacity = _pow2ceil(
                edge_log_capacity
                or 4 * (self._F_max * self._A + self._F_max)
            )
            if self._elog_capacity < self._F_max * (self._A + 1):
                raise ValueError(
                    f"edge_log_capacity={edge_log_capacity} cannot hold "
                    f"one worst-case wave "
                    f"({self._F_max * (self._A + 1)} rows)"
                )
            self._live_ins = LivenessInstruments(
                "tpu_bfs", registry=self._registry
            )
            self._live_store = LivenessEdgeStore(
                instruments=self._live_ins, spill_dir=spill_dir,
                host_budget_mib=host_budget_mib,
            )
        # State-space cartography (opt-in, telemetry/coverage.py): the
        # per-action/per-property/shape reductions ride INSIDE the wave
        # jit (one extra int32 vector per existing host exit; the deep
        # drain accumulates it in its carry), so coverage=True runs stay
        # bit-identical and coverage=False traces no extra ops at all.
        # Must precede the jit construction below — _wave reads _cov at
        # trace time.
        self._init_coverage(
            "tpu_bfs", coverage, self._A, symmetry=self._symmetry_enabled
        )
        # Fused wave megakernel (README "Fused wave megakernel"): the
        # whole wave body — expand, fingerprint, sort-dedup, VMEM
        # tile-sweep insert, compaction, properties, coverage — in ONE
        # Pallas dispatch (ops/pallas_wave.py). Off-TPU the kernel runs
        # in interpret mode: exact semantics, so tier-1/CI exercise the
        # real kernel logic on CPU. Attribution bins its dispatches under
        # the dedicated "wave_kernel" phase so the ledger shows the
        # dispatch-overhead collapse instead of mis-binning it under
        # "device".
        self._fused_spec = None
        self._device_phase = "device"
        if self._wave_kernel == "fused":
            from ..ops.pallas_wave import FusedWaveSpec

            self._fused_spec = FusedWaveSpec(
                expand=model.packed_expand,
                within_boundary=model.packed_within_boundary,
                fp_fn=self._fp_fn,
                conditions=tuple(self._conditions),
                expectations=tuple(
                    p.expectation.value for p in self._properties
                ),
                ebit=tuple(sorted(self._ebit.items())),
                action_count=self._A,
                cov_layout=self._cov_layout,
                cov_antecedents=(
                    tuple(self._cov_antecedents)
                    if self._cov_antecedents is not None
                    else ()
                ),
                interpret=jax.default_backend() != "tpu",
            )
            self._device_phase = "wave_kernel"
            # Honest packability: the packed-tenancy engine has no fused
            # wave yet, so a fused-configured job never packs.
            self.packing_reason = (
                "wave_kernel='fused' runs solo: the tenant-packed engine "
                "dispatches the staged wave only"
            )
        # Buffer donation kills the per-call copy of the big operands
        # (hash table, pool ring): every donated argnum below is audited —
        # the caller never touches the donated buffer after the call
        # (it rebinds to the returned one). The checkpoint/export reads
        # (_jit_pool_export, _jit_take) are deliberately NOT donated: the
        # exported pool / padded arrays must survive the call (checkpoints
        # happen mid-run; _jit_take slices the same padded array
        # repeatedly).
        if self._live_enabled:
            # The edge log rides the wave as a second donated operand
            # (it is rebound to the returned one every dispatch, like
            # the table).
            def _wave_live(table, elog, *rest):
                return self._wave(*((table,) + rest), elog=elog)

            self._jit_wave = jax.jit(_wave_live, donate_argnums=(0, 1))
        else:
            self._jit_wave = jax.jit(self._wave, donate_argnums=(0,))
        # Every program runs as an AOT executable from ``_programs``: the
        # wave keyed (table rows, bucket width), the deep drain (width,
        # table rows, pool capacity) — one per ladder rung visited, so a
        # run that never leaves F_max compiles one drain — and the smaller
        # programs (kind, static args, argument avals). ``_drain_jits``
        # holds the width-closed drain jit objects.
        self._drain_jits = {}
        self._programs = Programs(
            "tpu_bfs", self._tracer, signature=self._aot_signature(),
            aot_cache=aot_cache, aot_store=aot_store,
            registry=self._registry,
            compile_phase=lambda: self._phase("compile"),
            on_compile=self._charge_compile,
        )
        aot = self._programs.jit
        self._jit_pool_zero = aot(
            "pool_zero", self._pool_zero, static_argnums=(0,)
        )
        # The ring is rebound to the returned one; the pushed chunk's
        # buffers cannot alias the ring (scatter), so donating them would
        # only trade a copy for an unusable-donation warning.
        self._jit_pool_push = aot(
            "pool_push", self._pool_push, donate_argnums=(0,)
        )
        self._jit_pool_export = aot("pool_export", self._pool_export)
        self._jit_init = aot("init", self._init_wave, donate_argnums=(0,))
        self._jit_take = aot("take", self._take, static_argnums=(2,))
        self._jit_finish = aot("finish", self._finish, static_argnums=(2,))
        self._jit_materialize = aot("materialize", self._materialize)
        # Only the destination table (arg 1) can alias the output; the old
        # table has a different shape and is freed by the caller's rebind.
        self._jit_rehash = aot("rehash", self._rehash, donate_argnums=(1,))
        self._jit_fp_single = aot("fp_single", self._fp_fn)
        # Live-lane compaction to a bucket width (see _compact_chunk).
        self._jit_compact = aot("compact", compact, static_argnums=(1,))
        self.donation_enabled = True
        self._last_dispatch = None  # (bucket, live) of the last chunk wave

        self._handles = [
            threading.Thread(target=self._run, name="tpu-bfs", daemon=True)
        ]
        self._handles[0].start()

    # -- device functions (jitted) ----------------------------------------

    def _insert_sorted(self, table, shi, slo, active):
        """Visited-set insert for a wave batch (keys sorted ascending —
        both impls rely on it: XLA for first-claim-wins tie order, Pallas
        for its single left-to-right table sweep). Off-TPU the Pallas
        kernel runs in interpret mode: exact semantics, testing speed only.
        Returns ``hashset_insert``'s four outputs and the XLA insert's
        counter vector (``INSERT_COUNTERS``; None for Pallas)."""
        if self._hashset_impl == "pallas":
            from ..ops.pallas_hashset import pallas_hashset_insert

            return (*pallas_hashset_insert(
                table, shi, slo, active,
                interpret=jax.default_backend() != "tpu",
            ), None)
        return insert_counted(table, shi, slo, active)

    def _init_wave(self, table):
        states = self._model.packed_init_states()
        valid = jax.vmap(self._model.packed_within_boundary)(states)
        hi, lo = jax.vmap(self._fp_fn)(states)
        if self._symmetry_enabled:
            khi, klo = self._key_fn(states)
        else:
            khi, klo = hi, lo
        n0 = hi.shape[0]
        shi = jnp.where(valid, khi, _U32_MAX)
        slo = jnp.where(valid, klo, _U32_MAX)
        shi, slo, sidx = jax.lax.sort(
            (shi, slo, jnp.arange(n0, dtype=jnp.int32)), num_keys=2
        )
        uniq = jnp.concatenate(
            [jnp.ones((1,), bool), (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1])]
        )
        wave_unique = valid[sidx] & uniq
        table, fresh, _found, pending, _counts = self._insert_sorted(
            table, shi, slo, wave_unique
        )
        out = {
            "table": table,
            "states": states,
            "valid": valid,
            "hi": hi,
            "lo": lo,
            "khi": khi,
            "klo": klo,
            "n_unique": fresh.sum(),
            "n_valid": valid.sum(),
            "overflow": pending.sum(),
        }
        if self._live_enabled:
            # Analysis roots: condition-false init states, per
            # eventually property (device_liveness.py).
            from .device_liveness import seed_root_mask

            out["root_mask"] = seed_root_mask(
                self._conditions, self._ebit, states, valid
            )
        return out

    def _wave(self, table, states, hi, lo, ebits, depth, mask, depth_cap,
              elog=None):
        if self._fused_spec is not None:
            # Fused megakernel: the entire wave body in one Pallas
            # dispatch, bit-identical out-dict (elog is refused at
            # construction, so it is always None here).
            from ..ops.pallas_wave import fused_wave

            return fused_wave(
                self._fused_spec, table, states, hi, lo, ebits, depth,
                mask, depth_cap,
            )
        model = self._model
        A = self._A
        F = hi.shape[0]
        B = F * A
        eval_mask = mask & (depth < depth_cap)

        # Stage scopes (``jax.named_scope``) name each stage's operations
        # in the device trace's op metadata; they change no computation.
        # Property conditions on the frontier (the states being "popped").
        with jax.named_scope("tpu_bfs.cond"):
            cond_vals = [jax.vmap(c)(states) for c in self._conditions]
            ebits_after = ebits
            for pi, b in self._ebit.items():
                ebits_after = jnp.where(
                    cond_vals[pi], ebits_after & ~jnp.uint32(1 << b),
                    ebits_after,
                )

        if self._use_fps:
            # Fingerprint-only expansion: candidate fps computed from the
            # parent's component hashes + per-transition deltas; no
            # candidate state arrays exist. Validity (including
            # within-boundary) is the model's contract (core/batch.py).
            with jax.named_scope("tpu_bfs.expand"):
                chi_g, clo_g, cvalid = jax.vmap(model.packed_expand_fps)(
                    states
                )
                cvalid = cvalid & eval_mask[:, None]
                generated = cvalid.sum(dtype=jnp.int32)
                terminal = eval_mask & ~cvalid.any(axis=1)
                cvalid_flat = cvalid.reshape(B)
                chi, clo = chi_g.reshape(B), clo_g.reshape(B)
            khi, klo = chi, clo
        else:
            # Expand the F × A action grid (packed_expand: per-class fast
            # path where the model provides one, else vmap of packed_step).
            with jax.named_scope("tpu_bfs.expand"):
                cand, cvalid = jax.vmap(model.packed_expand)(states)
                cvalid = cvalid & eval_mask[:, None]
                cvalid = cvalid & jax.vmap(
                    jax.vmap(model.packed_within_boundary)
                )(cand)
                generated = cvalid.sum(dtype=jnp.int32)
                terminal = eval_mask & ~cvalid.any(axis=1)

            # Fingerprint all candidates, dedup within the wave by sorting.
            with jax.named_scope("tpu_bfs.fingerprint"):
                cand_flat = jax.tree_util.tree_map(
                    lambda x: x.reshape((B,) + x.shape[2:]), cand
                )
                cvalid_flat = cvalid.reshape(B)
                chi, clo = jax.vmap(self._fp_fn)(cand_flat)
                # Dedup/visited-set keys (== the fingerprints unless
                # symmetry is on, when they are orbit-minimum
                # fingerprints). Frontier rows, parent pointers, and
                # discoveries always carry the ORIGINAL fingerprints so
                # paths replay through concrete states (the reference
                # keeps original fps under symmetry too,
                # src/checker/dfs.rs:300-309).
                if self._symmetry_enabled:
                    khi, klo = self._key_fn(cand_flat)
                else:
                    khi, klo = chi, clo
        if elog is not None:
            # Device-native liveness: this wave's condition-false edge
            # and terminal rows, appended to the device store in-jit
            # (one scatter; natural lane order — chi/clo are the
            # pre-sort candidate fps). None of the wave's own outputs
            # depend on the log, so results are bit-identical with
            # liveness off.
            from .device_liveness import wave_edge_rows

            live_rows, live_n = wave_edge_rows(
                self._conditions, self._ebit, cond_vals, cand_flat,
                cvalid_flat, terminal, hi, lo, chi, clo, A,
            )
            from ..ops.edge_store import edge_log_append

            elog = edge_log_append(
                elog, live_rows, live_n, self._elog_capacity
            )
        insert_counts = None
        if self._wave_dedup == "scatter":
            # Sort-free dedup: the duplicate-tolerant insert resolves
            # in-wave twins itself (owner-ticket tie-break), so the
            # lax.sort over the full F x A grid — 66% of the 2pc-7 wave
            # at F=8192 on CPU — disappears. Lanes keep natural order:
            # lane // A is the parent row directly.
            with jax.named_scope("tpu_bfs.insert"):
                table, fresh, _found, pending = hashset_insert_unsorted(
                    table, khi, klo, cvalid_flat
                )
            sidx = jnp.arange(B, dtype=jnp.int32)
            shi, slo = khi, klo
        else:
            with jax.named_scope("tpu_bfs.dedup"):
                shi = jnp.where(cvalid_flat, khi, _U32_MAX)
                slo = jnp.where(cvalid_flat, klo, _U32_MAX)
                shi, slo, sidx = jax.lax.sort(
                    (shi, slo, jnp.arange(B, dtype=jnp.int32)), num_keys=2
                )
                uniq = jnp.concatenate(
                    [
                        jnp.ones((1,), bool),
                        (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1]),
                    ]
                )
                wave_unique = cvalid_flat[sidx] & uniq

            # Claim slots in the visited set; fresh lanes form the next
            # frontier.
            with jax.named_scope("tpu_bfs.insert"):
                table, fresh, _found, pending, insert_counts = (
                    self._insert_sorted(table, shi, slo, wave_unique)
                )
        with jax.named_scope("tpu_bfs.compact"):
            overflow = pending.sum()
            n_new = fresh.sum()

            # Compact fresh lanes (sorted or natural order) into prefix
            # slots.
            pos = jnp.cumsum(fresh.astype(jnp.int32)) - 1
            out_slot = jnp.where(fresh, pos, B)
            zi = jnp.zeros((B,), jnp.int32)
            zu = jnp.zeros((B,), jnp.uint32)
            src_idx = zi.at[out_slot].set(sidx, mode="drop")
            parent_row = sidx // A
            new = {
                "hi": zu.at[out_slot].set(chi[sidx], mode="drop"),
                "lo": zu.at[out_slot].set(clo[sidx], mode="drop"),
                "ebits": zu.at[out_slot].set(
                    ebits_after[parent_row], mode="drop"
                ),
                "depth": zi.at[out_slot].set(
                    depth[parent_row] + 1, mode="drop"
                ),
            }
            if self._use_fps:
                # Fresh lanes as (parent, action) references; the
                # consumer materializes them F_max at a time (enqueue
                # segments / the drain's segment loop) so only winners
                # are ever built.
                new["src_idx"] = src_idx
            else:
                new["states"] = jax.tree_util.tree_map(
                    lambda x: x[src_idx], cand_flat
                )
            out = {
                "table": table,
                "generated": generated,
                "n_new": n_new,
                "overflow": overflow,
                "max_depth": jnp.max(jnp.where(mask, depth, 0)),
                "new": new,
                "parent_hi": zu.at[out_slot].set(
                    hi[parent_row], mode="drop"
                ),
                "parent_lo": zu.at[out_slot].set(
                    lo[parent_row], mode="drop"
                ),
            }
            if self._symmetry_enabled:
                # The visited-set keys the fresh lanes claimed
                # (orbit-minimum fps) — checkpointing needs them to
                # rebuild the table, since original fps cannot be
                # re-keyed without states.
                out["key_hi"] = zu.at[out_slot].set(shi, mode="drop")
                out["key_lo"] = zu.at[out_slot].set(slo, mode="drop")

        with jax.named_scope("tpu_bfs.props"):
            # Per-property discovery scan over the evaluated frontier.
            hits, fhis, flos = [], [], []
            for i, p in enumerate(self._properties):
                if p.expectation == Expectation.ALWAYS:
                    h = eval_mask & ~cond_vals[i]
                elif p.expectation == Expectation.SOMETIMES:
                    h = eval_mask & cond_vals[i]
                else:  # EVENTUALLY: unmet bit at a terminal state
                    b = self._ebit[i]
                    h = terminal & (
                        ((ebits_after >> jnp.uint32(b)) & 1) == 1
                    )
                idx = jnp.argmax(h)
                hits.append(h.any())
                fhis.append(hi[idx])
                flos.append(lo[idx])
            if self._properties:
                out["prop_hit"] = jnp.stack(hits)
                out["prop_hi"] = jnp.stack(fhis)
                out["prop_lo"] = jnp.stack(flos)
        if self._cov is not None:
            # Coverage reductions (telemetry/coverage.py) fused into the
            # wave: per-action fired/fresh, per-property exercise,
            # terminal/successor/depth shape stats — one extra int32
            # vector per wave, drained at the existing host exits. None
            # of the wave's own outputs depend on these, so results are
            # bit-identical with coverage off.
            exercised = []
            for pi, p in enumerate(self._properties):
                if p.expectation == Expectation.ALWAYS:
                    ant = self._cov_antecedents[pi]
                    exercised.append(
                        eval_mask & jax.vmap(ant)(states)
                        if ant is not None
                        else eval_mask
                    )
                elif p.expectation == Expectation.SOMETIMES:
                    exercised.append(eval_mask & cond_vals[pi])
                else:  # EVENTUALLY: met == the unmet bit already cleared
                    eb = self._ebit[pi]
                    exercised.append(
                        eval_mask
                        & (((ebits_after >> jnp.uint32(eb)) & 1) == 0)
                    )
            uniq_fp = uniq_key = None
            if self._symmetry_enabled:
                # Orbit compression: in-wave distinct plain fps over
                # distinct orbit keys (two extra sorts, coverage mode
                # only).
                uniq_fp = self._cov_layout.count_distinct(
                    chi, clo, cvalid_flat
                )
                uniq_key = self._cov_layout.count_distinct(
                    khi, klo, cvalid_flat
                )
            out["cov"] = self._cov_layout.wave_reduce(
                eval_mask=eval_mask,
                cvalid=cvalid,
                fresh=fresh,
                lane_action=sidx % A,
                new_depth=depth[sidx // A] + 1,
                exercised=exercised,
                uniq_fp=uniq_fp,
                uniq_key=uniq_key,
            )
        # One consolidated scalar vector: each np.asarray() pull costs a
        # device-to-host round trip, so the host loop reads counters
        # (and property-hit flags) in a single transfer per wave.
        stats = [
            generated,
            n_new,
            pending.sum(dtype=jnp.int32),
            jnp.max(jnp.where(mask, depth, 0)),
        ]
        if self._properties:
            stats.append(out["prop_hit"].any().astype(jnp.int32))
        if insert_counts is not None:
            out["insert"] = insert_counts
        if elog is not None:
            out["elog"] = elog
            # Absolute fill count — the host's pre-dispatch eviction
            # decision reads it from the stats pull it already pays.
            stats.append(elog["count"])
        out["stats"] = jnp.stack(
            [s.astype(jnp.int32) for s in stats]
        )
        return out

    def _pool_zero(self, capacity):
        """An empty device frontier pool (FIFO ring of pending states)."""
        return ring_rows(self._model, capacity)

    def _pool_push(self, pool, head, count, chunk):
        """Appends a host chunk's masked lanes at the ring tail."""
        return ring_push(
            pool, head, count, chunk, chunk["mask"], self._pool_capacity
        )

    def _pool_push_fps(self, pool, head, count, new, parent_states, n_new, width):
        """Ring push for the fps wave: fresh lanes arrive as (parent,
        action) references (``new["src_idx"]``, prefix-compacted), and
        their states are materialized straight into the ring in
        ``width``-wide segments inside a dynamic-trip-count loop — real
        traffic is ``n_new`` children, never the F × A candidate grid,
        and no B-wide state buffer exists between the wave and the ring.
        ``width`` is the producing wave's lane width (the drain's bucket)."""
        A, F = self._A, width
        B = F * A
        PC = self._pool_capacity
        lanes = jnp.arange(B, dtype=jnp.int32)
        valid = lanes < n_new
        dest = jnp.where(valid, (head + count + lanes) & (PC - 1), PC)
        meta = {
            k: pool[k].at[dest].set(new[k], mode="drop")
            for k in ("hi", "lo", "ebits", "depth")
        }
        take = jax.vmap(self._model.packed_take)

        def cond(sc):
            return sc[0] * F < n_new

        def body(sc):
            seg, pstates = sc
            base = seg * F
            idxs = jax.lax.dynamic_slice_in_dim(new["src_idx"], base, F)
            parents = jax.tree_util.tree_map(
                lambda x: x[idxs // A], parent_states
            )
            childs = take(parents, idxs % A)
            seg_lanes = base + jnp.arange(F, dtype=jnp.int32)
            m = seg_lanes < n_new
            d = jnp.where(m, (head + count + seg_lanes) & (PC - 1), PC)
            pstates = jax.tree_util.tree_map(
                lambda dst, src: dst.at[d].set(src, mode="drop"),
                pstates,
                childs,
            )
            return seg + 1, pstates

        _, pstates = jax.lax.while_loop(
            cond, body, (jnp.int32(0), pool["states"])
        )
        return {"states": pstates, **meta}, count + n_new

    def _pool_take(self, pool, head, count, width=None):
        """Dequeues up to ``width`` (default ``F_max``) lanes from the
        ring head as a frontier."""
        return ring_take(
            pool, head, count, self._pool_capacity,
            self._F_max if width is None else width,
        )

    def _pool_export(self, pool, head, count):
        """The ring contents in FIFO order (for checkpointing), padded to
        the full pool width with the valid-lane mask attached."""
        return ring_export(pool, head, count, self._pool_capacity)

    def _grow_pool(self, pool, head, count):
        """Doubles the ring, preserving FIFO order (export + re-push). The
        dependent jits retrace automatically on the new shapes."""
        exported = self._jit_pool_export(pool, head, count)
        self._pool_capacity *= 2
        pool = self._jit_pool_zero(self._pool_capacity)
        pool, count = self._jit_pool_push(
            pool, jnp.int32(0), jnp.int32(0), exported
        )
        return pool, jnp.int32(0), count

    def _deep_drain(self, width, table, pool, head, count, undiscovered,
                    budget, depth_cap, elog=None):
        """Runs the BFS inside one device ``while_loop``: each iteration
        pushes the previous wave's fresh states into the FIFO ring, dequeues
        the next ``width`` lanes, and expands them. The loop exits to the
        host only when a wave is *unconsumable* device-side: the parent-fp
        log is full, the visited set needs growing, an undiscovered property
        hit, the ring would overflow, or a hash probe overflowed. Host round
        trips (the per-wave floor) are thus amortized over
        entire BFS phases instead of paid per wave (SURVEY §7-5c).

        ``width`` (static) is the drain's wave width — a rung of the
        occupancy-adaptive bucket ladder, so a sparse pending frontier
        drains at e.g. ``F_max/16`` lanes per wave instead of burning
        ``F_max``-wide expand grids on masked padding. The host picks the
        rung from the exact ring count at each drain entry (lazily
        AOT-compiling new rungs), and the loop additionally exits when the
        ring backlog outgrows the rung (``count > width`` with a wider
        rung available) so a growing frontier promotes itself back up the
        ladder. The popped lane sequence is width-independent (strict
        FIFO), so results are bit-identical across rungs.

        Returns the final (unconsumed) wave output, the frontier that
        produced it (for overflow retry), the ring, accumulated totals for
        the consumed waves, and their (child, parent[, key]) log entries.
        """
        F, A = width, self._A
        B = F * A
        L = self._drain_log_capacity
        PC = self._pool_capacity
        P = len(self._properties)

        def wave_of(tbl, fr, el=None):
            return self._wave(
                tbl,
                fr["states"],
                fr["hi"],
                fr["lo"],
                fr["ebits"],
                fr["depth"],
                fr["mask"],
                depth_cap,
                elog=el,
            )

        # The loop holds the one copy of the wave in the program: its first
        # iteration consumes an empty wave (nothing to log or push) and
        # runs the drain's first wave, so no wave runs ahead of the loop
        # (each copy is tens of MB of device code, resident while the
        # executable is loaded).
        def zeros(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, x.dtype), tree
            )

        frontier0 = zeros(
            jax.eval_shape(
                lambda *a: self._pool_take(*a, F), pool, head, count
            )[0]
        )
        out0 = zeros(jax.eval_shape(wave_of, table, frontier0, elog))
        out0["table"] = table
        if elog is not None:
            out0["elog"] = elog
        zl = jnp.zeros((L,), jnp.uint32)
        log0 = {
            "child_hi": zl,
            "child_lo": zl,
            "parent_hi": zl,
            "parent_lo": zl,
        }
        if self._symmetry_enabled:
            log0.update(key_hi=zl, key_lo=zl)
        carry = {
            "pool": pool,
            "head": head,
            "count": count,
            "frontier": frontier0,
            "out": out0,
            "log": log0,
            "log_n": jnp.int32(0),
            "generated": jnp.int32(0),
            "consumed_unique": jnp.int32(0),
            "max_depth": jnp.int32(0),
            "budget": budget,
            # Waves run (the cap backs the checkpoint-durability
            # guarantee: a drain runs at most max_drain_waves waves).
            "waves": jnp.int32(0),
            # Live lanes dispatched (the drain's compaction-ratio
            # numerator; the denominator is waves × width, host-side).
            "live_sum": jnp.int32(0),
        }
        if self._cov is not None:
            # Consumed waves' coverage vectors accumulate in the carry
            # (all slices are additive counts); the final unconsumed
            # wave's vector rides out["cov"] and is consumed host-side.
            carry["cov_acc"] = jnp.zeros(
                (self._cov_layout.size,), jnp.int32
            )
        if "insert" in out0:
            # The consumed waves' insert counters, likewise.
            carry["insert_acc"] = jnp.zeros_like(out0["insert"])

        def cond(c):
            o = c["out"]
            n_new = o["n_new"]
            ok = (n_new > 0) | (c["count"] > 0)
            # The first iteration always runs: it is the drain's first wave.
            first = c["waves"] == 0
            ok &= o["overflow"] == 0
            if P:
                ok &= ~(o["prop_hit"] & undiscovered).any()
            ok &= c["log_n"] + n_new <= L
            ok &= c["count"] + n_new <= PC
            if elog is not None:
                # The edge store must absorb another worst-case wave
                # (B edge rows + F terminal rows) or the host must
                # evict first.
                ok &= o["elog"]["count"] + (B + F) <= self._elog_capacity
            if F < self._F_max:
                # Promote-exit: a backlog beyond one more wave means the
                # frontier outgrew this rung — hand back to the host,
                # which re-enters at the bucket the exact count selects.
                ok &= c["count"] <= F
            # Insert budget must survive consuming this wave plus another
            # full worst-case wave (B candidates).
            ok &= c["budget"] - n_new >= B
            ok &= c["waves"] < self._max_drain_waves
            # The generated counter is device int32 (no x64); exit to the
            # host (which accumulates in a Python int) long before a
            # billion-generated drain could wrap it.
            ok &= c["generated"] < jnp.int32(1 << 30)
            return ok | first

        def body(c):
            o = c["out"]
            n_new = o["n_new"]
            new = o["new"]
            lanes = jnp.arange(B, dtype=jnp.int32)
            valid = lanes < n_new
            with jax.named_scope("tpu_bfs.log"):
                slot = jnp.where(valid, c["log_n"] + lanes, L)
                log = dict(c["log"])
                log["child_hi"] = log["child_hi"].at[slot].set(
                    new["hi"], mode="drop"
                )
                log["child_lo"] = log["child_lo"].at[slot].set(
                    new["lo"], mode="drop"
                )
                log["parent_hi"] = log["parent_hi"].at[slot].set(
                    o["parent_hi"], mode="drop"
                )
                log["parent_lo"] = log["parent_lo"].at[slot].set(
                    o["parent_lo"], mode="drop"
                )
                if self._symmetry_enabled:
                    log["key_hi"] = log["key_hi"].at[slot].set(
                        o["key_hi"], mode="drop"
                    )
                    log["key_lo"] = log["key_lo"].at[slot].set(
                        o["key_lo"], mode="drop"
                    )
            # Push the fresh (compacted-prefix) lanes at the ring tail, then
            # dequeue the next frontier from the head — strict FIFO keeps
            # exact BFS order, so parent pointers stay shortest-path.
            with jax.named_scope("tpu_bfs.ring"):
                if self._use_fps:
                    pool, count = self._pool_push_fps(
                        c["pool"],
                        c["head"],
                        c["count"],
                        new,
                        c["frontier"]["states"],
                        n_new,
                        F,
                    )
                else:
                    pool, count = self._pool_push(
                        c["pool"],
                        c["head"],
                        c["count"],
                        {
                            "states": new["states"],
                            "hi": new["hi"],
                            "lo": new["lo"],
                            "ebits": new["ebits"],
                            "depth": new["depth"],
                            "mask": valid,
                        },
                    )
                frontier, head, count = self._pool_take(
                    pool, c["head"], count, F
                )
            nxt = {
                "pool": pool,
                "head": head,
                "count": count,
                "frontier": frontier,
                "out": wave_of(
                    o["table"], frontier,
                    o["elog"] if elog is not None else None,
                ),
                "log": log,
                "log_n": c["log_n"] + n_new,
                "generated": c["generated"] + o["generated"],
                "consumed_unique": c["consumed_unique"] + n_new,
                "max_depth": jnp.maximum(c["max_depth"], o["max_depth"]),
                "budget": c["budget"] - n_new,
                "waves": c["waves"] + 1,
                "live_sum": c["live_sum"]
                + frontier["mask"].sum(dtype=jnp.int32),
            }
            if self._cov is not None:
                nxt["cov_acc"] = c["cov_acc"] + o["cov"]
            if "insert_acc" in c:
                nxt["insert_acc"] = c["insert_acc"] + o["insert"]
            return nxt

        res = jax.lax.while_loop(cond, body, carry)
        # One consolidated transfer for the consumed-wave bookkeeping, and
        # the log columns stacked into a single array so the host pulls the
        # whole drain's parent-fp stream in one more transfer.
        res["drain_stats"] = jnp.stack(
            [
                res["log_n"],
                res["generated"],
                res["consumed_unique"],
                res["max_depth"],
                res["waves"],
                res["count"],
                res["live_sum"],
            ]
        )
        if "insert_acc" in res:
            # The float32 insert counters ride the same transfer, bit for
            # bit (the host views entries 7.. as float32).
            res["drain_stats"] = jnp.concatenate([
                res["drain_stats"],
                jax.lax.bitcast_convert_type(res["insert_acc"], jnp.int32),
            ])
        cols = ["child_hi", "child_lo", "parent_hi", "parent_lo"]
        if self._symmetry_enabled:
            cols += ["key_hi", "key_lo"]
        res["log_pack"] = jnp.stack([res["log"][c] for c in cols])
        return res

    def _take(self, arrs, start, size):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, start, size, axis=0), arrs
        )

    def _finish(self, arrs, n_new, target):
        """Pads chunk arrays to ``target`` rows and attaches the lane mask.

        Wave outputs are compacted (valid rows form a prefix), so the mask
        derives from ``n_new``; the init frontier arrives uncompacted with
        an explicit ``mask`` that is padded through instead.
        """
        has_mask = "mask" in arrs

        def pad(x):
            n = x.shape[0]
            if n == target:
                return x
            widths = [(0, target - n)] + [(0, 0)] * (x.ndim - 1)
            return jnp.pad(x, widths)

        out = jax.tree_util.tree_map(pad, arrs)
        if not has_mask:
            out["mask"] = jnp.arange(target, dtype=jnp.int32) < n_new
        return out

    def _rehash(self, old_table, new_table):
        active = (old_table[:, 0] != 0) | (old_table[:, 1] != 0)
        new_table, _fresh, _found, pending = hashset_insert(
            new_table, old_table[:, 0], old_table[:, 1], active
        )
        return new_table, pending.sum()

    def _aot_signature(self) -> tuple:
        """Everything baked into the wave/drain traces that the shared
        AOT cache must key on (runtime args — depth cap, budget,
        undiscovered mask — excluded; runtime SHAPES — table rows, bucket
        width, pool capacity — ride the per-entry keys)."""
        return (
            jax.default_backend(),
            packed_model_digest(self._model, self._A),
            tuple(
                (p.name, str(p.expectation)) for p in self._properties
            ),
            self._sym_scheme,
            self._use_fps,
            self._wave_dedup,
            self._hashset_impl,
            self._wave_kernel,
            self._cov is not None,
            self._F_max,
            tuple(self._buckets),
            self._drain_log_capacity,
            self._max_drain_waves,
            self._max_capacity,
            self._live_enabled,
            self._elog_capacity if self._live_enabled else None,
        )

    # -- host exploration loop ---------------------------------------------

    def _run(self):
        try:
            # The check's root span: every span of this check descends
            # from it, on this thread and on its caller's (discoveries()).
            with self._tracer.span("tpu_bfs.check") as check:
                self._tracer.root = check.id
                try:
                    if self._profile_dir:
                        jax.profiler.start_trace(self._profile_dir)
                        try:
                            self._explore()
                        finally:
                            jax.profiler.stop_trace()
                    else:
                        self._explore()
                except BaseException as e:  # noqa: BLE001 - worker_error
                    self._error = e
                    self._abort_attribution()
                finally:
                    # The pipeline must be quiescent before done is
                    # observable: counters/logs a late verdict would
                    # mutate are read the moment join() returns.
                    self._shutdown_pipeline()
                    self._finalize_coverage(set(self._discoveries_fp))
                    check.set(drains=self._drains, unique=self._unique_count)
        finally:
            self._done_event.set()

    def _grow_table(self, table, min_capacity, defer_evict=False):
        """Grows (or, under an HBM budget, evicts) the device table.
        ``defer_evict=True`` — async wave loop only — hands the tier
        absorb to the pipeline worker; deep-drain and restore callers
        keep it synchronous because they branch on ``tier.is_empty()``
        immediately afterwards (the out-of-core handoff)."""
        if (
            self._max_capacity is not None
            and min_capacity > self._max_capacity
        ):
            return self._evict_l0(table, defer=defer_evict)
        capacity = self._capacity
        while capacity < min_capacity:
            capacity *= 2
        while True:
            with self._tracer.span(
                "tpu_bfs.table_grow", from_capacity=self._capacity,
                to_capacity=capacity,
            ), self._phase("table_grow"):
                new_table, leftover = self._jit_rehash(
                    table, hashset_new(capacity)
                )
                if self._attr is not None:
                    self._attr.fence(new_table)
                # Inside the span: reading the count waits for the rehash.
                leftover = int(leftover)
            if not leftover:
                break
            # A pathological key cluster can exhaust the probe cap during
            # rehash; that costs capacity (the next doubling shortens
            # probe chains), never the run. Under an HBM budget the next
            # doubling may not exist — evict instead.
            capacity *= 2
            if (
                self._max_capacity is not None
                and capacity > self._max_capacity
            ):
                return self._evict_l0(table, defer=defer_evict)
        self._capacity = capacity
        self._wi.table_grows.inc()
        self._wi.capacity.set(capacity)
        return new_table

    def _evict_l0(self, table, defer=False):
        """Budget-capped growth: drains the FULL device table to a host
        L1 run (delta-compressed, Bloom-fronted) and resets it — the
        out-of-core alternative to doubling. Capacity settles at the
        budget cap; the emptied table carries the hot working set from
        here on while older fingerprints answer through the host probe.

        ``defer=True`` (async wave loop): the device-serial half — table
        pull + reset — stays here, but the host absorb (run build, LSM
        merges, spills) rides the pipeline worker. FIFO keeps it ordered
        exactly as the synchronous path would: after every
        already-submitted wave verdict (whose fresh keys this eviction
        now holds) and before every later one (whose probes must see
        these keys)."""
        with self._phase("evict"):
            tab = np.asarray(table)
            live = (tab[:, 0] != 0) | (tab[:, 1] != 0)
            keys = (
                tab[live, 0].astype(np.uint64) << np.uint64(32)
            ) | tab[live, 1].astype(np.uint64)
            if defer and self._pipe is not None:
                self._pipe.submit(lambda: self._evict_absorb(keys))
            else:
                self._tier.evict(keys)
            self._capacity = self._max_capacity
            self._l0_count = 0
            self._wi.capacity.set(self._capacity)
            self._tier.instruments.set_l0(0)
            return hashset_new(self._capacity)

    def _evict_absorb(self, keys):
        """Pipeline-worker half of a deferred eviction."""
        with self._phase_overlapped("evict"):
            self._tier.evict(keys)

    # -- device-native liveness (liveness="device") -------------------------

    def _maybe_evict_elog(self, defer=False) -> None:
        """Evicts the device edge store to the host tier when one more
        worst-case wave (F·A edge rows + F terminal rows) could
        overflow it."""
        self._live_ins.occupancy.set(
            self._elog_count / self._elog_capacity
        )
        if (
            self._elog_count + self._F_max * (self._A + 1)
            > self._elog_capacity
        ):
            self._evict_elog(defer=defer)

    def _evict_elog(self, defer=False) -> None:
        """Drains the filled prefix of the device edge store into the
        host :class:`~..storage.LivenessEdgeStore` and resets the fill
        count. The device pull stays on the checker thread; with
        ``defer=True`` (async mode) the host absorb — dedup, budget
        spill — rides the FIFO pipeline worker, shadowed under the next
        dispatch."""
        n = self._elog_count
        if self._elog is None or n == 0:
            return
        if n > self._elog_capacity:
            raise RuntimeError(
                "liveness edge store overflowed despite headroom checks "
                f"({n} > {self._elog_capacity}); this is a bug"
            )
        from ..ops.edge_store import EDGE_COLS

        with self._tracer.span("tpu_bfs.liveness.evict", rows=n):
            cols = {c: np.asarray(self._elog[c])[:n] for c in EDGE_COLS}
            if defer and self._pipe is not None:
                self._pipe.submit(
                    lambda: self._live_store.absorb(**cols)
                )
            else:
                self._live_store.absorb(**cols)
            self._elog = dict(self._elog, count=jnp.int32(0))
            self._elog_count = 0
        self._live_ins.occupancy.set(0.0)

    def _flush_live_edges(self) -> None:
        """Analysis/checkpoint pre-hook (base's liveness runner): the
        single-device checker keeps the edge store device-resident, so
        it must drain before any host read."""
        self._evict_elog()

    def _set_warmup(self, seconds: float) -> None:
        """First-result warmup stamp, mirrored into telemetry so traces
        carry the warmup/steady split the benches subtract."""
        self.warmup_seconds = seconds
        self._wi.warmup.set(seconds)
        self._tracer.instant("tpu_bfs.warmup_complete", warmup_s=seconds)

    def _explore(self):
        t_start = time.perf_counter()
        # Wall-clock burned before the first wave returns — dominated by XLA
        # compilation; benchmarks subtract it to report steady-state rate.
        self.warmup_seconds: Optional[float] = None
        if self._live_enabled:
            from ..ops.edge_store import edge_log_new

            self._elog = edge_log_new(self._elog_capacity)
        if self._resume_from is not None:
            table, queue = self._restore(self._resume_from)
        else:
            with self._tracer.span("tpu_bfs.seed"):
                table, queue = self._seed()
        depth_cap = jnp.int32(self._depth_cap)
        # Deep drain is off when a visitor needs per-chunk callbacks or a
        # target caps the run (overshoot would span whole drains instead of
        # single waves).
        # A resumed out-of-core run (non-empty L1/L2) needs the per-wave
        # host probe immediately, which only the wave path performs.
        if (
            self._max_drain_waves > 1
            and self._visitor is None
            and self._target_state_count is None
            and (self._tier is None or self._tier.is_empty())
        ):
            # A non-None return is the out-of-core handoff: the first L0
            # eviction ended deep-drain mode and the remaining frontier
            # continues on the wave path. Unwinding _explore_deep's frame
            # first releases the abandoned device ring (its locals pin
            # pool-capacity lanes of packed state in HBM otherwise).
            handoff = self._explore_deep(table, queue, depth_cap, t_start)
            if handoff is not None:
                table, queue = handoff
                self._explore_waves(table, queue, depth_cap, t_start)
        else:
            self._explore_waves(table, queue, depth_cap, t_start)
        # Sound `eventually` verdicts (liveness="device"): decide
        # cycle/masked-terminal existence over the logged
        # condition-false edge relation, with a concrete certificate.
        self._run_liveness_analysis("tpu_bfs")

    def _compact_chunk(self, chunk, width):
        """Gathers a chunk's live lanes into a dense prefix and narrows it
        to ``width`` (the chosen bucket); see ``compact``."""
        return self._jit_compact(chunk, width)

    def _call_wave(self, table, chunk, depth_cap):
        """Runs one wave through an AOT-compiled executable keyed on
        (bucket width, table capacity) — the only shapes that vary at
        runtime. The chunk is dispatched at the smallest ladder bucket
        holding its live lanes (compacted to a dense prefix first), so
        wave cost scales with occupancy instead of F_max. Returns
        ``(wave_out, dispatched_chunk)`` — the caller must enqueue /
        materialize against the *dispatched* chunk, whose lane indices the
        wave's parent references point into.

        Explicit AOT keeps warmup accounting exact: a compile triggered
        mid-run (table growth or a new ladder rung) is measured and moved
        into ``warmup_seconds`` instead of polluting the steady-state
        window. During the initial pre-first-result window
        ``warmup_seconds`` is still None and the caller's own stamp covers
        the compile."""
        # Injection seam, PRE-dispatch: a device wave raise (XLA error,
        # HBM OOM, lost device) fires before any counter for this wave
        # mutates — the retry-from-checkpoint path never sees a
        # half-applied wave.
        fault_point("device.wave")
        if self._live_enabled:
            # Edge-store headroom for this wave's worst case (B edge
            # rows + F terminal rows) — evict to the host tier first
            # when the device store could overflow.
            self._maybe_evict_elog(defer=self._pipe is not None)
        f_in = chunk["hi"].shape[0]
        if (
            len(self._buckets) > 1
            and f_in == self._F_max
            # A table-growth retry re-dispatches the SAME logical wave
            # (same chunk, _last_dispatch already recorded): selecting and
            # counting again would double the bucket_dispatch histogram
            # and re-pay the blocking live-count pull.
            and self._last_dispatch is None
        ):
            # One tiny transfer to learn the live count; the wave-at-a-time
            # path already syncs per wave (np.asarray of the stats vector),
            # so this adds a second scalar-sized pull, not a new regime.
            live = int(np.asarray(chunk["mask"].sum()))
            width = bucket_for(self._buckets, live)
            if width < f_in:
                chunk = self._compact_chunk(chunk, width)
            self._record_dispatch(width, live)
        args = (
            table,
            chunk["states"],
            chunk["hi"],
            chunk["lo"],
            chunk["ebits"],
            chunk["depth"],
            chunk["mask"],
            jnp.asarray(depth_cap, jnp.int32),
        )
        if self._live_enabled:
            args = (table, self._elog) + args[1:]
        key = (table.shape[0], chunk["hi"].shape[0])
        exe = self._programs.obtain(
            "wave", key, self._jit_wave, args,
            table_capacity=key[0], frontier=key[1],
        )
        if self._attr is None:
            out = exe(*args)
        else:
            # Attribution mode: fence the wave output so the device-class
            # phase ("device", or "wave_kernel" under the fused
            # megakernel) measures dispatch + device compute, not async
            # launch latency.
            with self._attr.phase(self._device_phase):
                out = exe(*args)
                self._attr.fence(out)
        if self._live_enabled:
            # Rebind the donated edge log to the wave's output.
            self._elog = out["elog"]
        return out, chunk

    def _audit_table(self, table):
        """Run-end audit of the probabilistic machinery: the device hash
        set's probe-length distribution, observed into the
        ``tpu_bfs.hashset.probe_length`` histogram (attribution mode
        only — the table pull is a full HBM read)."""
        if self._attr is None:
            return
        from ..ops.hashset import hashset_probe_length_counts

        self._attr.observe_probe_lengths(
            hashset_probe_length_counts(np.asarray(table))
        )

    def _record_dispatch(self, width, live):
        """One bucketed dispatch's telemetry (gauges + per-rung counter);
        the live/width pair is kept for the wave span's args."""
        self._last_dispatch = (width, live)
        self._wi.bucket.set(width)
        self._wi.bucket_dispatch(width)
        self._wi.compaction.set(live / width if width else 0.0)
        self._wi.frontier_fill.set(live / self._F_max)

    def _consume_wave(self, table, wave, chunk, queue, depth_cap, span=None,
                      pending=None):
        """Applies one wave output host-side (counters, discoveries, log,
        requeue), retrying the producing frontier after table growth until
        no probe overflows. Returns ``(table, wave_new)`` — the updated
        table and the wave's fresh-unique count (the deep loop uses it as
        the exact live size of the chunks spilled into the host queue).
        ``span`` (optional, a telemetry span covering this wave) is filled
        with the per-wave quantities the acceptance trace carries;
        ``pending`` (deep-drain path) is the ring's residual count, so the
        span's ``live_lanes`` = pending + this wave's spill — the exact
        live frontier at the drain boundary."""
        attempt = 0
        generated = 0
        wave_new = 0
        stale_total = 0
        insert_counts = 0
        self._last_dispatch = None
        while True:
            if wave is None:
                # Rebind to the dispatched (bucketed/compacted) chunk: the
                # wave's parent references index into ITS lanes.
                wave, chunk = self._call_wave(table, chunk, depth_cap)
            table = wave["table"]
            if "insert" in wave:
                # Every attempt's probe rounds were issued: they all count.
                insert_counts = insert_counts + np.asarray(wave["insert"])
            # Single host transfer per wave: [generated, n_new, overflow,
            # max_depth, any_prop_hit?, edge_count?]; per-property
            # fingerprints are pulled only on a hit.
            stats = np.asarray(wave["stats"])
            if self._live_enabled:
                self._elog_count = int(stats[-1])
            if self._cov is not None:
                # One extra (small) pull per wave in coverage mode; a
                # table-growth retry re-expands the same frontier, so
                # only the fresh-based slices accumulate then.
                self._cov.consume_device(
                    np.asarray(wave["cov"]),
                    self._cov_layout,
                    first_attempt=(attempt == 0),
                    max_depth=int(stats[3]),
                )
            if attempt == 0:
                generated = self._apply_wave_stats(wave, stats, chunk)
            n_new = int(stats[1])
            keep, k64, survivors, n_stale = self._probe_fresh(wave, n_new)
            stale_total += n_stale
            self._l0_count += n_new
            wave_new += survivors
            self._unique_count += survivors
            if survivors:
                self._log_wave(wave, n_new, keep, k64)
                # Lane width of the DISPATCHED chunk (the bucket), so the
                # enqueue padding target scales with the bucket instead of
                # re-inflating every sparse wave's output to F_max × A.
                self._enqueue(
                    queue, wave, n_new, chunk["hi"].shape[0] * self._A,
                    chunk, keep,
                )
            if not int(stats[2]):
                self._record_wave_metrics(
                    span, chunk["hi"].shape[0], generated, wave_new,
                    stale=stale_total, pending=pending,
                )
                if span is not None and "insert" in wave:
                    span.set(**_insert_args(insert_counts))
                if self._cov is not None:
                    self._cov.emit_wave_span()
                return table, wave_new
            if self._max_capacity is not None and attempt >= 8:
                # Pathological probe-window cluster: the wave overflows
                # even a freshly-evicted budget-capped table — a
                # configuration error, not a loop to spin in (mirrors
                # the sharded checker's guard).
                raise RuntimeError(
                    "a wave's candidates overflow the budget-capped "
                    "device table after repeated evictions; raise "
                    "hbm_budget_mib or shrink frontier_capacity"
                )
            table = self._grow_table(table, self._capacity * 2)
            attempt += 1
            wave = None

    def _probe_fresh(self, wave, n_new, overlapped=False):
        """The two-phase probe for one wave attempt's fresh prefix
        (out-of-core mode): the device table only vouches for the keys
        it currently holds — L0-fresh lanes whose key lives in an
        evicted L1/L2 run are STALE and must not be re-counted,
        re-logged, or re-expanded. One batched host probe per wave
        (Bloom prefilter + block binary search). ONE site for the sync
        path and the async verdict job — the key selection and stale
        gather must never diverge between them. ``overlapped`` picks
        the attribution ledger (worker-thread time is shadowed, not
        serial wall). Returns ``(keep, k64, survivors, n_stale)``."""
        keep = None
        k64 = None
        survivors = n_new
        n_stale = 0
        if (
            n_new
            and self._tier is not None
            and not self._tier.is_empty()
        ):
            phase = self._phase_overlapped if overlapped else self._phase
            with phase("host_probe"):
                if self._symmetry_enabled:
                    k64 = fp64_pairs(
                        wave["key_hi"][:n_new], wave["key_lo"][:n_new]
                    )
                else:
                    k64 = fp64_pairs(
                        wave["new"]["hi"][:n_new],
                        wave["new"]["lo"][:n_new],
                    )
                stale = self._tier.probe(k64)
            n_stale = int(stale.sum())
            if n_stale:
                keep = np.flatnonzero(~stale).astype(np.int32)
                survivors = n_new - n_stale
        return keep, k64, survivors, n_stale

    def _apply_wave_stats(self, wave, stats, chunk=None):
        """First-attempt device bookkeeping shared by the sync and async
        consume paths (a growth retry re-expands the same frontier, so
        this runs once per wave): generated/depth counters, discovery
        fingerprints, and the visitor callback. ONE site on purpose —
        the bit-identical guarantee depends on both paths applying the
        same stats the same way. Returns the wave's generated count."""
        generated = int(stats[0])
        self._state_count += generated
        self._max_depth = max(self._max_depth, int(stats[3]))
        props = self._properties
        if props and stats[4]:
            hit = np.asarray(wave["prop_hit"])
            phi = np.asarray(wave["prop_hi"])
            plo = np.asarray(wave["prop_lo"])
            for i, p in enumerate(props):
                if hit[i] and p.name not in self._discoveries_fp:
                    self._discoveries_fp[p.name] = fp_to_int(phi[i], plo[i])
        if chunk is not None and self._visitor is not None:
            self._visit_chunk(chunk)
        return generated

    def _consume_wave_async(self, table, chunk, queue, depth_cap, wave_no):
        """Device half of one wave (async pipeline mode), on the checker
        thread: dispatch, stats pull, counters/discoveries, and the
        growth/eviction retry loop — everything the NEXT dispatch
        decision depends on. The host-tier verdict of each attempt is
        submitted to the pipeline worker *before* any growth/eviction
        that follows it, so the tier sees probes and evictions in the
        synchronous order (an eviction holds the attempt's fresh keys —
        probing after absorbing them would mark the whole wave stale).
        Returns the updated table; survivors re-enter via the worker."""
        attempt = 0
        self._last_dispatch = None
        # Shared across this wave's attempt verdicts (worker-thread
        # mutation only; FIFO serializes the attempts).
        ctx = {"wave_new": 0, "stale": 0, "generated": 0}
        while True:
            wave, chunk = self._call_wave(table, chunk, depth_cap)
            table = wave["table"]
            stats = np.asarray(wave["stats"])
            if self._live_enabled:
                self._elog_count = int(stats[-1])
            if self._cov is not None:
                self._cov.consume_device(
                    np.asarray(wave["cov"]),
                    self._cov_layout,
                    first_attempt=(attempt == 0),
                    max_depth=int(stats[3]),
                )
            if attempt == 0:
                ctx["generated"] = self._apply_wave_stats(wave, stats, chunk)
            n_new = int(stats[1])
            self._l0_count += n_new
            final = not int(stats[2])
            # Point-in-time captures: by the time the verdict job runs,
            # the checker thread's live fields (dispatch, warmup,
            # l0/capacity/depth) describe a LATER wave — a deferred
            # eviction even resets l0 to 0 — so the span must carry
            # this wave's values, not a future's.
            self._pipe.submit(
                lambda w=wave, c=chunk, n=n_new, f=final,
                d=self._last_dispatch, warm=self.warmup_seconds is not None,
                st=(self._l0_count, self._capacity, self._max_depth):
                    self._wave_verdict(
                        ctx, w, c, queue, n, f, wave_no, d, warm, st
                    )
            )
            if final:
                if self._cov is not None:
                    self._cov.emit_wave_span()
                return table
            if self._max_capacity is not None and attempt >= 8:
                raise RuntimeError(
                    "a wave's candidates overflow the budget-capped "
                    "device table after repeated evictions; raise "
                    "hbm_budget_mib or shrink frontier_capacity"
                )
            table = self._grow_table(
                table, self._capacity * 2, defer_evict=True
            )
            attempt += 1

    def _wave_verdict(self, ctx, wave, chunk, queue, n_new, final, wave_no,
                      dispatch, warm, state):
        """One wave attempt's host-tier verdict, on the pipeline worker:
        the two-phase probe against the evicted runs, the parent-fp log,
        and survivor re-entry at the queue tail. Reads the wave's
        (non-donated) output buffers while the device runs the next
        wave. The final attempt emits the ``tpu_bfs.wave`` span the
        monitor's estimator and SSE stream consume — it is the first
        moment the wave's true survivor count exists."""
        def verdict():
            # tier.is_empty() inside _probe_fresh is exact HERE: every
            # eviction is applied on this same thread, in submission
            # order (the merge fence).
            keep, k64, survivors, n_stale = self._probe_fresh(
                wave, n_new, overlapped=True
            )
            ctx["stale"] += n_stale
            self._unique_count += survivors
            ctx["wave_new"] += survivors
            if survivors:
                self._log_wave(wave, n_new, keep, k64)
                self._enqueue(
                    queue, wave, n_new,
                    chunk["hi"].shape[0] * self._A, chunk, keep,
                )

        if not final:
            verdict()
            return
        # The async wave span covers the HOST VERDICT only (the device
        # half overlaps later waves) — flagged so trace readers don't
        # compare its dur against sync wave walls; wave wall in async
        # mode is the .pipeline span's wall_ms.
        with self._tracer.span(
            "tpu_bfs.wave", wave=wave_no, async_verdict=True
        ) as sp:
            verdict()
            self._record_wave_metrics(
                sp, chunk["hi"].shape[0], ctx["generated"],
                ctx["wave_new"], stale=ctx["stale"], dispatch=dispatch
                or (None, None), warm=warm, state=state,
            )

    def _save_checkpoint_maybe_async(self, queue_chunks):
        """Checkpoint at an epoch boundary. The payload snapshot is
        always built synchronously (it must capture exactly this
        boundary), but in async mode the pickle + atomic rename ride the
        pipeline worker, off the critical path. Safe because the payload
        is immutable once built (numpy copies of the chunks, exported
        parent arrays, immutable run-state snapshots) and FIFO runs the
        write before any later-submitted eviction.

        ``queue_chunks`` is the LIVE pending-frontier container: it is
        snapshotted only after the epoch barrier, because in-flight
        verdicts append survivor chunks during the drain — a pre-barrier
        snapshot would checkpoint their keys (counters, parent log)
        without their frontier chunks, and the resumed run would never
        expand them."""
        if self._pipe is None:
            self.save_checkpoint(self._checkpoint_path, list(queue_chunks))
            return
        self._pipe.drain()
        payload = self.checkpoint_payload(list(queue_chunks))
        path = self._checkpoint_path
        self._pipe.submit(lambda: self._checkpoint_write(path, payload))

    def _record_wave_metrics(self, span, frontier, generated, n_new,
                             stale=None, pending=None, dispatch=None,
                             warm=None, state=None):
        """One wave's telemetry (the shared bundle does the recording).
        Occupancy is the TABLE's (L0-resident keys over capacity) — under
        tiering the global unique count keeps growing past what the
        device holds. ``dispatch``/``warm``/``state`` (= (l0, capacity,
        max_depth)) are point-in-time captures the async verdict job
        passes in — by the time it runs, the checker thread's live
        fields describe a LATER wave (a deferred eviction even resets
        l0 to 0 mid-flight)."""
        if dispatch is not None:
            bucket, live = dispatch
        else:
            bucket, live = self._last_dispatch or (None, None)
        steady = (
            warm if warm is not None else self.warmup_seconds is not None
        )
        if state is not None:
            l0, capacity, depth = state
        else:
            l0, capacity, depth = (
                self._l0_count, self._capacity, self._max_depth
            )
        # `live` stays the last DISPATCH's live lanes (the compaction
        # denominator pairs with it); the monitor-facing live frontier is
        # separate — at a deep-drain boundary it is the ring residue plus
        # this wave's spill (the next drain's bucket selector input).
        live_lanes = pending + n_new if pending is not None else live
        extra = {}
        if live_lanes is not None:
            # Live (pre-padding) lanes: the monitor's frontier fit reads
            # this over the dispatch-width `frontier` when present.
            extra["live_lanes"] = live_lanes
        if self._tier is not None:
            self._tier.instruments.set_l0(l0)
            extra["storage_stale"] = stale or 0
            # total_fps is exact on the verdict worker too: tier
            # mutations are FIFO-ordered, so at this job's position the
            # tier state matches the synchronous path's.
            extra["storage_fps"] = self._tier.total_fps
        self._wi.record(
            span,
            frontier=frontier,
            generated=generated,
            n_new=n_new,
            occupancy=l0 / capacity,
            capacity=capacity,
            max_depth=depth,
            phase="steady" if steady else "warmup",
            bucket=bucket,
            compaction_ratio=(live / bucket if bucket else None),
            **extra,
        )

    def _explore_waves(self, table, queue, depth_cap, t_start):
        """Wave-at-a-time host loop (visitor callbacks / target counts /
        out-of-core probes).

        With ``async_pipeline=True`` this loop becomes the two-deep
        pipeline: each iteration dispatches the NEXT chunk as soon as
        the previous wave's device stats are in, while the pipeline
        worker applies the previous wave's host-tier verdict. The
        dispatched wave sequence is identical to the synchronous path's
        because (a) survivors only ever re-enter at the queue TAIL —
        exactly where the synchronous path appends them — so popping the
        head early pops the same chunk, and (b) every dispatch-affecting
        decision (growth/eviction from ``_l0_count``, target caps,
        discovery exits) is made from the stats the checker thread
        already pulled, in the same order. When the queue runs dry with
        verdicts still in flight, the epoch barrier waits for their
        survivors before concluding the space is exhausted."""
        props = self._properties
        pipe = self._pipe
        chunks = 0
        last_checkpoint = time.perf_counter()
        while True:
            # Injection seam: a wedged wave (hung device call, stuck
            # host probe) simulated as a sleep — what the service's
            # stall watchdog must detect and auto-preempt through.
            fault_point("wave.stall")
            if pipe is not None and not queue and pipe.pending():
                # In-flight verdicts may refill the queue (survivors
                # land one wave late); only an empty queue AFTER the
                # barrier means the space is exhausted.
                pipe.drain()
            if not queue:
                break
            if not props:
                break
            if len(self._discoveries_fp) == len(props):
                break
            if (
                self._target_state_count is not None
                and self._target_state_count <= self._state_count
            ):
                break
            if self._preempt_event.is_set():
                # Wave-granular yield point: the pending chunk queue IS
                # the whole remaining frontier here, so the checkpoint
                # payload machinery captures the run exactly (resume is
                # bit-identical — same argument as checkpoint/restore).
                # Epoch barrier first: in-flight verdicts still own part
                # of that frontier.
                if pipe is not None:
                    pipe.drain()
                self._preempt_payload = self.checkpoint_payload(list(queue))
                self._tracer.instant(
                    "tpu_bfs.preempted", chunks=len(queue), mode="wave"
                )
                return
            # The attribution window covers the whole iteration (the
            # inter-wave checkpoint and pre-grow included): its phases
            # plus the residual gap sum to this wall by construction.
            with self._wave_window():
                if (
                    self._checkpoint_path is not None
                    and chunks
                    and chunks % self._checkpoint_every == 0
                    and (time.perf_counter() - last_checkpoint)
                    >= self._checkpoint_min_interval
                ):
                    with self._phase("checkpoint"):
                        self._save_checkpoint_maybe_async(queue)
                    last_checkpoint = time.perf_counter()
                chunks += 1
                chunk = queue.popleft()
                B = chunk["hi"].shape[0] * self._A
                if (self._l0_count + B) > _MAX_LOAD * self._capacity:
                    table = self._grow_table(
                        table,
                        _pow2ceil(int((self._l0_count + B) / _MAX_LOAD)),
                        defer_evict=pipe is not None,
                    )
                if pipe is None:
                    with self._tracer.span("tpu_bfs.wave", wave=chunks) as sp:
                        table, _ = self._consume_wave(
                            table, None, chunk, queue, depth_cap, span=sp
                        )
                else:
                    # Bounded pending-verdict lane set: at most
                    # max_pending waves of device output pinned at once.
                    pipe.throttle()
                    table = self._consume_wave_async(
                        table, chunk, queue, depth_cap, chunks
                    )
            if self.warmup_seconds is None:
                self._set_warmup(time.perf_counter() - t_start)
        if pipe is not None:
            # Run-end epoch barrier: counters and the parent-fp log must
            # be settled before the audit and the done flag.
            pipe.drain()
        self._audit_table(table)

    def _explore_deep(self, table, queue, depth_cap, t_start):
        """Deep-drain host loop: keeps the pending frontier in the device
        ring and re-enters ``_deep_drain`` until the space is exhausted,
        paying host round trips only at drain exits. Each exit, from a
        drain's result to the next drain's dispatch (or the loop's end),
        is one ``tpu_bfs.drain_exit`` span."""
        try:
            return self._drain_loop(table, queue, depth_cap, t_start)
        finally:
            self._close_drain_exit()

    def _close_drain_exit(self, **args):
        span, self._drain_exit = self._drain_exit, None
        if span is not None:
            span.set(**args)
            span.__exit__(None, None, None)

    def _drain_loop(self, table, queue, depth_cap, t_start):
        props = self._properties
        if not props:
            return
        B = self._F_max * self._A
        pool = self._jit_pool_zero(self._pool_capacity)
        head = jnp.int32(0)
        count = jnp.int32(0)
        pool_count = 0  # host view; exact after each drain, bounded after pushes
        # Exact pending live lanes (ring + spilled queue) — the bucket
        # selector's input. None until the first drain exit: the first
        # drain always runs at F_max, so a run that finishes in one drain
        # (every small space) compiles exactly one rung, and the ramp-up
        # phase never ladder-climbs through the narrow rungs' compiles.
        live_est = None
        # Consecutive-entry votes per rung: a NEW rung's drain compile is
        # only paid once the same rung is selected on two consecutive
        # entries. A ramp-up phase sweeps through each narrow live count
        # once (votes never accumulate → it stays on already-compiled
        # rungs), while a persistent sparse regime selects the same rung
        # every entry and adapts on its second drain.
        rung_votes = {}
        drains = 0
        last_checkpoint = time.perf_counter()
        while True:
            # Injection seam: a wedged drain loop (see _explore_waves).
            fault_point("wave.stall")
            if len(self._discoveries_fp) == len(props):
                break
            if self._preempt_event.is_set():
                # Drain-granular yield point. Ring contents are OLDER
                # than any host-queue spill (same ordering argument as
                # _handoff_queue), so ring-then-queue preserves exact
                # FIFO and the resumed run stays bit-identical. A drain
                # yields only between drains; bound preemption latency
                # with max_drain_waves (the service spawns jobs with a
                # small cap, like the checkpoint-durability clamp).
                if self._pipe is not None:
                    # In-flight checkpoint writes must land before the
                    # worker dies with the run.
                    self._pipe.drain()
                chunks = self._export_pool_chunks(pool, head, count)
                chunks.extend(queue)
                self._preempt_payload = self.checkpoint_payload(chunks)
                self._tracer.instant(
                    "tpu_bfs.preempted", chunks=len(chunks), mode="drain"
                )
                return None
            # First L0 eviction ends deep-drain mode: from here every
            # wave's fresh set needs the host-side L1/L2 probe, which a
            # device-resident drain cannot perform mid-loop.
            if self._tier is not None and not self._tier.is_empty():
                return table, self._handoff_queue(pool, head, count, queue)
            # The host queue must FULLY drain into the ring before the next
            # drain: leftover spilled chunks are older than anything the
            # drain will push, so leaving them queued would let newer states
            # jump ahead and break exact BFS order (depth labels and
            # shortest-path parents). Grow the ring when they don't fit —
            # exact BFS inherently holds the whole pending frontier, just
            # like the reference's host queue. Push dispatches stay
            # device-side (no blocking transfer).
            refill_chunks = len(queue)
            while queue:
                if pool_count + self._F_max > self._pool_capacity:
                    # The host bound overcounts (F_max per push); refresh it
                    # from the device before paying for a ring doubling.
                    pool_count = int(np.asarray(count))
                    if pool_count + self._F_max > self._pool_capacity:
                        pool, head, count = self._grow_pool(pool, head, count)
                chunk = queue.popleft()
                pool, count = self._jit_pool_push(pool, head, count, chunk)
                pool_count += self._F_max
            if pool_count == 0:
                break
            # Every drain exit is a checkpoint opportunity (waves-per-drain
            # is capped when a checkpoint path is set); the time floor
            # throttles the full parent-map export + pickle.
            # Attribution window for the whole drain iteration (the
            # checkpoint, pre-grow, drain execution, and the final
            # host-consumed wave). The out-of-core handoff return closes
            # it explicitly first so the handoff's queue rebuild is not
            # misattributed to the drain; exit is idempotent, so the
            # with-block's unwind (normal, return, or exception) is safe
            # either way.
            drain_window = self._wave_window("drain")
            with drain_window:
                if (
                    self._checkpoint_path is not None
                    and drains
                    and (time.perf_counter() - last_checkpoint)
                    >= self._checkpoint_min_interval
                ):
                    # The ring is the sole pending-frontier store here: the
                    # push loop above always fully drains the host queue.
                    assert not queue
                    with self._phase("checkpoint"):
                        # Async mode: only the pickle+rename is deferred
                        # (deep drains run tier-empty, so the pipeline
                        # carries nothing else here).
                        self._save_checkpoint_maybe_async(
                            self._export_pool_chunks(pool, head, count)
                        )
                    last_checkpoint = time.perf_counter()
                drains += 1
                self._drains = drains
                if (self._l0_count + B) > _MAX_LOAD * self._capacity:
                    table = self._grow_table(
                        table, _pow2ceil(int((self._l0_count + B) / _MAX_LOAD))
                    )
                    if self._tier is not None and not self._tier.is_empty():
                        # The pregrow evicted (budget hit): the queue is
                        # empty (flushed above), the ring holds the whole
                        # frontier. Close the window first so the
                        # handoff's queue rebuild is not attributed to
                        # this drain (exit is idempotent — the with's
                        # unwind after the return is a no-op).
                        drain_window.__exit__(None, None, None)
                        return table, self._handoff_queue(
                            pool, head, count, queue
                        )
                undiscovered = np.array(
                    [p.name not in self._discoveries_fp for p in props]
                )
                # Clamp: the budget rides device int32; a > 2^31-slot table
                # must saturate, not overflow.
                budget = jnp.int32(
                    min(
                        int(_MAX_LOAD * self._capacity) - self._l0_count,
                        (1 << 31) - 1 - B,
                    )
                )
                # Ladder rung for this drain: the smallest bucket holding the
                # exact pending-live count (F_max for the first drain — see
                # live_est above). A sparse steady state drains at e.g.
                # F_max/16 lanes per wave; the promote-exit inside the drain
                # hands back control if the frontier outgrows the rung.
                width = self._F_max
                if live_est is not None and len(self._buckets) > 1:
                    want = bucket_for(
                        self._buckets, max(1, min(live_est, self._F_max))
                    )
                    if want in self._drain_jits or want == self._F_max:
                        width = want
                        rung_votes = {}
                    else:
                        votes = rung_votes.get(want, 0) + 1
                        rung_votes = {want: votes}
                        if votes >= 2:
                            width = want
                        else:
                            # Not yet worth a compile: the narrowest rung
                            # already compiled that still holds the load
                            # (F_max as the floor fallback).
                            width = min(
                                (
                                    w
                                    for w in self._drain_jits
                                    if w >= want
                                ),
                                default=self._F_max,
                            )
                args = (
                    table,
                    pool,
                    head,
                    count,
                    jnp.asarray(undiscovered),
                    budget,
                    depth_cap,
                )
                if self._live_enabled:
                    # Edge-store headroom for at least one wave; the
                    # drain self-exits when the log fills mid-drain.
                    self._maybe_evict_elog()
                    args += (self._elog,)
                # Compile ahead of the real call so warmup measures pure
                # compilation: a single deep drain can run the whole
                # exploration, so "time until the first result returned"
                # (the wave path's proxy) would fold exploration into
                # warmup and corrupt steady-state rates. Mid-run compiles
                # (new rung, grown table/ring) are measured into warmup too.
                exe = self._drain_exe(width, args, t_start)
                # Injection seam, pre-dispatch (the deep-drain twin of
                # _call_wave's site): the ring still holds the frontier,
                # so nothing of this drain is half-applied on a raise.
                fault_point("device.wave")
                self._close_drain_exit(refill_chunks=refill_chunks)
                drain_span = self._tracer.span(
                    "tpu_bfs.drain", drain=drains, bucket=width
                )
                with drain_span:
                    with self._phase(self._device_phase):
                        res = exe(*args)
                        if self._attr is not None:
                            self._attr.fence(res)
                    dstats = np.asarray(res["drain_stats"])
                    log_n = int(dstats[0])
                    self._state_count += int(dstats[1])
                    self._unique_count += int(dstats[2])
                    # Drains only run while the tier is empty, so every drain
                    # fresh is also an L0 resident.
                    self._l0_count += int(dstats[2])
                    self._max_depth = max(self._max_depth, int(dstats[3]))
                    # A drain consumes many waves device-side; its span carries
                    # the aggregate (per-wave granularity would need per-wave
                    # host exits — the cost the drain exists to amortize). The
                    # drain's final, unconsumed wave is accounted by the
                    # _consume_wave call below, hence waves - 1 here.
                    self._wi.drains.inc()
                    self._wi.waves.inc(max(int(dstats[4]) - 1, 0))
                    # Bucket accounting for the drain's waves: every wave in
                    # this drain ran at ``width`` lanes; the compaction ratio
                    # is live lanes over dispatched lanes, the frontier fill
                    # live lanes over F_max capacity.
                    waves_n = int(dstats[4])
                    live_sum = int(dstats[6])
                    self._wi.bucket.set(width)
                    self._wi.bucket_dispatch(width, waves_n)
                    compaction = (
                        live_sum / (waves_n * width) if waves_n else None
                    )
                    if compaction is not None:
                        self._wi.compaction.set(compaction)
                        self._wi.frontier_fill.set(
                            live_sum / (waves_n * self._F_max)
                        )
                    self._wi.record(
                        drain_span,
                        frontier=self._F_max,
                        generated=int(dstats[1]),
                        n_new=int(dstats[2]),
                        occupancy=self._l0_count / self._capacity,
                        capacity=self._capacity,
                        max_depth=self._max_depth,
                        count_wave=False,
                        observe=False,
                        # Final unconsumed wave rides the _consume_wave span
                        # below — same minus-one as the waves counter above,
                        # so monitor /status waves match the registry.
                        waves=max(waves_n - 1, 0),
                        log_n=log_n,
                        ring_count=int(dstats[5]),
                        bucket=width,
                        compaction_ratio=compaction,
                    )
                    if len(dstats) > 7:
                        drain_span.set(**_insert_args(dstats[7:11].view(
                            np.float32
                        )))
                # The drain's result is in (its stats pull above waited
                # for the device): host time until the next dispatch.
                self._drain_exit = self._tracer.span(
                    "tpu_bfs.drain_exit", drain=drains, log_bytes=0,
                    refill_chunks=0,
                )
                self._drain_exit.__enter__()
                pool, head, count = res["pool"], res["head"], res["count"]
                if self._live_enabled:
                    # Rebind the donated edge log to the drain's output
                    # (the final unconsumed wave's appends included).
                    self._elog = res["out"]["elog"]
                pool_count = int(dstats[5])
                if self._cov is not None:
                    # The drain's consumed-wave coverage aggregate (the
                    # final unconsumed wave rides _consume_wave below).
                    self._cov.consume_device(
                        np.asarray(res["cov_acc"]),
                        self._cov_layout,
                        max_depth=int(dstats[3]),
                    )
                if log_n:
                    # The whole drain's parent-fp stream in one transfer.
                    pack = np.asarray(res["log_pack"][:, :log_n])
                    self._drain_exit.set(log_bytes=pack.nbytes)
                    self._wave_log.append(
                        (fp64_pairs(pack[0], pack[1]), fp64_pairs(pack[2], pack[3]))
                    )
                    if self._symmetry_enabled:
                        self._key_log.append(fp64_pairs(pack[4], pack[5]))
                # Consume the final (unconsumable device-side) wave the slow
                # way; its fresh chunks spill into the host queue and are fed
                # back into the ring on the next loop pass.
                with self._tracer.span("tpu_bfs.wave", drain=drains) as sp:
                    table, spilled = self._consume_wave(
                        table, res["out"], res["frontier"], queue, depth_cap,
                        span=sp, pending=pool_count,
                    )
            # Exact pending live lanes: the ring's count plus the final
            # wave's fresh spill — the next drain's bucket selector input.
            live_est = pool_count + spilled
        self._audit_table(table)

    def _handoff_queue(self, pool, head, count, queue):
        """Builds the wave-mode chunk queue for the permanent switch out
        of deep-drain mode (first L0 eviction). The device ring's
        contents are OLDER than any host-queue spill (the drain's final
        wave spilled after everything it had consumed), so the ring
        exports ahead of the queue — exact FIFO, hence exact BFS order,
        is preserved and the run stays bit-identical. The caller
        (_explore) resumes on the wave path only after _explore_deep's
        frame unwinds, releasing the ring's device buffers."""
        chunks = self._export_pool_chunks(pool, head, count)
        newq = deque(chunks)
        newq.extend(queue)
        self._tracer.instant(
            "tpu_bfs.storage.wave_mode", ring_chunks=len(chunks),
            spilled_chunks=len(queue),
        )
        return newq

    def _charge_compile(self, kind, seconds):
        """A wave or drain compile after the warm-up stamp is added to
        ``warmup_seconds``, keeping the steady-state window honest; the
        smaller programs' compiles are not."""
        if kind in STEPS and self.warmup_seconds is not None:
            self.warmup_seconds += seconds
            self._wi.warmup.set(self.warmup_seconds)

    def _drain_exe(self, width, args, t_start):
        """The AOT-compiled deep drain for one ladder rung. The first drain
        stamps the warm-up (compiled or not: leaving it None would make
        the service's stall watchdog treat the whole run as warm-up);
        later compiles (new rung or grown shapes) are added to it."""
        jit_fn = self._drain_jits.get(width)
        if jit_fn is None:

            def fn(*a, _w=width):
                return self._deep_drain(_w, *a)

            # The executable's name in device traces, one per ladder rung
            # (``jit(fn)`` otherwise).
            fn.__name__ = fn.__qualname__ = f"tpu_bfs_drain_w{width}"
            donate = (0, 1, 7) if self._live_enabled else (0, 1)
            jit_fn = self._drain_jits[width] = jax.jit(
                fn, donate_argnums=donate
            )
        table_rows = args[0].shape[0]
        exe = self._programs.obtain(
            "drain", (width, table_rows, self._pool_capacity), jit_fn, args,
            bucket=width, table_capacity=table_rows,
        )
        if self.warmup_seconds is None:
            self._set_warmup(time.perf_counter() - t_start)
        return exe

    def _export_pool_chunks(self, pool, head, count):
        """The ring contents as F_max-wide host chunks (for checkpoints)."""
        exported = self._jit_pool_export(pool, head, count)
        n = int(np.asarray(count))
        chunks = []
        for start in range(0, n, self._F_max):
            chunks.append(
                self._jit_take(exported, jnp.int32(start), self._F_max)
            )
        return chunks

    def _seed(self):
        """Inserts + enqueues the initial states; returns (table, queue)."""
        table = hashset_new(self._capacity)
        while True:
            out = self._jit_init(table)
            if not int(out["overflow"]):
                break
            table = hashset_new(self._capacity * 2)
            self._capacity *= 2
        table = out["table"]
        self._state_count = int(out["n_valid"])
        self._unique_count = int(out["n_unique"])
        self._l0_count = self._unique_count
        # Seed the cumulative counters too, so the registry's totals match
        # the checker's (init states never flow through a wave).
        self._wi.generated.inc(self._state_count)
        self._wi.unique.inc(self._unique_count)
        if self._cov is not None:
            self._cov.record_seed(self._unique_count)
        hi = np.asarray(out["hi"])
        lo = np.asarray(out["lo"])
        valid = np.asarray(out["valid"])
        child64 = fp64_pairs(hi, lo)[valid]
        self._wave_log.append((child64, np.zeros_like(child64)))
        if self._symmetry_enabled:
            self._key_log.append(fp64_pairs(out["khi"], out["klo"])[valid])
        if self._live_enabled:
            self._live_store.add_roots(
                child64, np.asarray(out["root_mask"])[valid]
            )

        F0 = hi.shape[0]
        init_arrs = {
            "states": out["states"],
            "hi": out["hi"],
            "lo": out["lo"],
            "ebits": jnp.full((F0,), self._ebits0, jnp.uint32),
            "depth": jnp.ones((F0,), jnp.int32),
            "mask": out["valid"],
        }
        target0 = -(-F0 // self._F_max) * self._F_max
        padded0 = self._jit_finish(init_arrs, jnp.int32(0), target0)
        queue = deque()
        for start in range(0, F0, self._F_max):
            queue.append(self._jit_take(padded0, jnp.int32(start), self._F_max))
        return table, queue

    # -- checkpoint/resume (new capability: the reference loses all progress
    # on a kill, SURVEY §5) ------------------------------------------------

    def _model_digest(self) -> str:
        return packed_model_digest(self._model, self._A)

    def save_checkpoint(self, path, queue) -> None:
        """Atomically serializes counters, discoveries, the parent-pointer
        map, and the pending frontier chunks. The visited set is not stored
        separately — it is exactly the parent map's keys, and the device
        table is rebuilt from them on resume."""
        atomic_pickle(path, self.checkpoint_payload(queue))

    def checkpoint_payload(self, queue) -> dict:
        """The checkpoint as an in-memory payload dict (format v2, the
        exact object ``save_checkpoint`` pickles). The preempt/resume
        path round-trips this without touching disk: pass it straight to
        a new checker's ``resume_from=``."""
        self._ingest_wave_log()
        children, parents = self._store.export()
        payload = {
            **checkpoint_header(
                "tpu_bfs",
                self._model,
                self._A,
                self._symmetry_enabled,
                self._sym_scheme,
            ),
            "state_count": self._state_count,
            "unique_count": self._unique_count,
            "max_depth": self._max_depth,
            "discoveries": dict(self._discoveries_fp),
            "children": children,
            "parents": parents,
            "capacity": self._capacity,
            "chunks": [
                jax.tree_util.tree_map(np.asarray, chunk) for chunk in queue
            ],
        }
        if self._symmetry_enabled:
            payload["keys"] = (
                np.concatenate(self._key_log)
                if self._key_log
                else np.zeros((0,), np.uint64)
            )
        if self._tier is not None and not self._tier.is_empty():
            # Out-of-core: the evicted runs + Bloom filters ride the
            # checkpoint (CRC-validated on restore); the L0 set is
            # rebuilt on restore as "known keys not in any run", which
            # always fits the budget.
            payload["storage"] = self._tier.export_state()
        if self._live_enabled:
            # v3 payload extension: the condition-false edge relation
            # (device store flushed first) + roots/terminals, so a
            # resumed run's final liveness verdict never depends on
            # where the run was cut.
            self._evict_elog()
            payload["liveness"] = self._live_store.export_state()
            payload["version"] = 3
        return payload

    def _restore(self, path):
        if isinstance(path, dict):
            # In-memory resume (preempt/resume): the payload dict itself,
            # no pickle round trip.
            payload = path
        else:
            import pickle

            with open(path, "rb") as f:
                payload = pickle.load(f)
        validate_checkpoint_header(
            payload,
            "tpu_bfs",
            "sharded checkpoints carry a frontier pool, not the chunk "
            "queue this restore needs",
            self._model,
            self._A,
            self._symmetry_enabled,
            self._sym_scheme,
        )
        self._state_count = payload["state_count"]
        self._unique_count = payload["unique_count"]
        self._max_depth = payload["max_depth"]
        self._discoveries_fp = dict(payload["discoveries"])
        children = payload["children"]
        parents = payload["parents"]
        self._wave_log.append((children, parents))
        # Visited-set keys == the original fps unless symmetry was on (then
        # the checkpoint carries the orbit-key stream separately).
        keys = children
        if self._symmetry_enabled:
            keys = payload["keys"]
            self._key_log.append(keys)

        # Out-of-core checkpoints carry the evicted runs; load them first
        # (CRC-validated per run) so the L0 rebuild below inserts only
        # the keys no run holds — that set always fits the HBM budget.
        storage_state = payload.get("storage")
        if storage_state:
            if self._tier is None:
                # Restored without budget knobs: hold the runs anyway
                # (unbounded L0 from here on, probes stay correct).
                from ..storage import TieredVisitedStore

                from ..storage import StorageInstruments

                self._tier = TieredVisitedStore(
                    instruments=StorageInstruments(
                        "tpu_bfs", registry=self._registry
                    ),
                    tracer=self._tracer,
                )
            self._tier.load_state(storage_state)
        # Device-liveness state must round-trip with the run: resuming a
        # liveness="device" run without the knob (or vice versa) would
        # finish with a silently truncated edge relation — an unsound
        # verdict — so mode mismatches are refused, not papered over.
        live_state = payload.get("liveness")
        if self._live_enabled and live_state is None:
            raise ValueError(
                "liveness='device' cannot resume a checkpoint written "
                "without it: the edges explored before the checkpoint "
                "were never logged, so the final verdict would be "
                "unsound"
            )
        if live_state is not None:
            if not self._live_enabled:
                raise ValueError(
                    "checkpoint carries a liveness edge store; resume "
                    "with liveness='device' (dropping it would discard "
                    "the soundness the original run paid for)"
                )
            self._live_store.load_state(live_state)
        insert_keys = keys
        if self._tier is not None and not self._tier.is_empty():
            insert_keys = keys[~self._tier.probe(keys)]

        # Rebuild the device visited set by claim-inserting the L0 keys.
        self._capacity = max(self._capacity, payload["capacity"])
        if self._max_capacity is not None:
            self._capacity = min(self._capacity, self._max_capacity)
        table = hashset_new(self._capacity)
        hi = (insert_keys >> np.uint64(32)).astype(np.uint32)
        lo = (insert_keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        batch = 1 << 16
        if self._max_capacity is not None:
            # A batch must fit a freshly-evicted table under the load cap
            # or the grow-and-retry below could loop.
            batch = min(batch, int(self._max_capacity * _MAX_LOAD))
        for start in range(0, len(insert_keys), batch):
            bh = jnp.asarray(hi[start : start + batch])
            bl = jnp.asarray(lo[start : start + batch])
            active = jnp.ones((bh.shape[0],), bool)
            table, fresh, _found, pending = hashset_insert(
                table, bh, bl, active
            )
            self._l0_count += int(fresh.sum())
            if int(pending.sum()):
                table = self._grow_table(table, self._capacity * 2)
                table, f2, _fo, pend2 = hashset_insert(table, bh, bl, active)
                self._l0_count += int(f2.sum())
                if int(pend2.sum()):
                    raise RuntimeError("checkpoint restore overflowed table")
        queue = deque(
            jax.tree_util.tree_map(jnp.asarray, chunk)
            for chunk in payload["chunks"]
        )
        return table, queue

    def _log_wave(self, wave, n_new, keep=None, probe_keys=None):
        """Logs the wave's fresh (child, parent[, key]) fps; ``keep``
        (optional int32 positions into the fresh prefix) restricts to the
        lanes that survived the L1/L2 host probe. ``probe_keys`` is the
        u64 key array that probe already pulled for the same prefix
        (== the child fps, or the orbit keys under symmetry) — reused so
        the hot out-of-core path pays one device pull, not two."""
        if probe_keys is not None and not self._symmetry_enabled:
            child = probe_keys
        else:
            child = fp64_pairs(
                wave["new"]["hi"][:n_new], wave["new"]["lo"][:n_new]
            )
        parent = fp64_pairs(
            wave["parent_hi"][:n_new], wave["parent_lo"][:n_new]
        )
        if keep is not None:
            child, parent = child[keep], parent[keep]
        self._wave_log.append((child, parent))
        if self._symmetry_enabled:
            keys = (
                probe_keys
                if probe_keys is not None
                else fp64_pairs(
                    wave["key_hi"][:n_new], wave["key_lo"][:n_new]
                )
            )
            if keep is not None:
                keys = keys[keep]
            self._key_log.append(keys)

    def _enqueue(self, queue, wave, n_new, B, chunk, keep=None):
        if keep is not None:
            return self._enqueue_survivors(queue, wave, chunk, keep)
        target = -(-B // self._F_max) * self._F_max
        padded = self._jit_finish(dict(wave["new"]), jnp.int32(n_new), target)
        for start in range(0, n_new, self._F_max):
            piece = self._jit_take(padded, jnp.int32(start), self._F_max)
            if self._use_fps:
                # Materialize this chunk's fresh children from (parent,
                # action) references against the producing frontier —
                # ceil(n_new / F_max) materializations per wave, never the
                # full F × A grid.
                piece = self._jit_materialize(chunk["states"], piece)
            queue.append(piece)

    def _enqueue_survivors(self, queue, wave, chunk, keep):
        """Enqueue path for a host-probe-filtered wave: gathers the
        surviving lanes (host index list into the fresh prefix) into
        F_max-wide chunks. Relative lane order is preserved, so the
        frontier sequence matches the unbounded run's exactly — the keys
        dropped here are precisely the ones that run never saw fresh."""
        new = wave["new"]
        F = self._F_max
        for start in range(0, len(keep), F):
            sel = keep[start : start + F]
            idx = np.zeros((F,), np.int32)
            idx[: len(sel)] = sel
            idx_j = jnp.asarray(idx)
            piece = {
                k: (
                    jax.tree_util.tree_map(lambda x: x[idx_j], v)
                    if k == "states"
                    else v[idx_j]
                )
                for k, v in new.items()
            }
            piece["mask"] = jnp.arange(F, dtype=jnp.int32) < len(sel)
            if self._use_fps:
                piece = self._jit_materialize(chunk["states"], piece)
            queue.append(piece)

    def _materialize(self, parent_states, piece):
        """Builds one queue chunk's states via ``packed_take`` from its
        fresh-lane (parent, action) references (fps wave path). Padding
        lanes reference parent 0 / action 0 and are masked."""
        idxs = piece.pop("src_idx")
        parents = jax.tree_util.tree_map(
            lambda x: x[idxs // self._A], parent_states
        )
        piece["states"] = jax.vmap(self._model.packed_take)(
            parents, idxs % self._A
        )
        return piece

    def _visit_chunk(self, chunk):
        mask = np.asarray(chunk["mask"])
        depth = np.asarray(chunk["depth"])
        hi = np.asarray(chunk["hi"])
        lo = np.asarray(chunk["lo"])
        for i in range(len(mask)):
            if mask[i] and depth[i] < self._depth_cap:
                self._visitor.visit(
                    self._model, self._reconstruct(fp_to_int(hi[i], lo[i]))
                )

    # -- path reconstruction ----------------------------------------------

    def _host_fp(self, host_state) -> int:
        self._fp_calls += 1
        hi, lo = self._jit_fp_single(self._model.pack_state(host_state))
        return fp_to_int(hi, lo)

    def _ingest_wave_log(self) -> int:
        """Moves the logged (child, parent) fps into the parent store;
        returns how many entries this call ingested."""
        # Raced by the worker (visitor reconstruction) and the user thread
        # (mid-run discoveries()); must not skip a wave. First-writer-wins
        # inserts keep the shortest-path parent.
        entries = 0
        with self._ingest_lock:
            while self._ingested < len(self._wave_log):
                children, parents = self._wave_log[self._ingested]
                self._store.insert_batch(children, parents)
                self._ingested += 1
                entries += len(children)
        return entries

    def _reconstruct(self, fp: int) -> Path:
        self._ingest_wave_log()
        chain = self._store.chain(fp)
        return Path.from_fingerprints(self._model, chain, fp_of=self._host_fp)

    # -- preemption (checking-as-a-service) --------------------------------

    supports_preempt = True

    def request_preempt(self) -> None:
        """Asks the worker to suspend at the next wave/drain boundary:
        the run's full state (counters, parent map, pending frontier,
        storage tiers) drains into an in-memory checkpoint payload
        (``preempt_payload()``) and the worker thread exits. Resume by
        spawning a new checker with ``resume_from=<payload>`` and the
        same configuration — the resumed run is bit-identical to an
        uninterrupted one (counts, depths, discoveries, golden reporter;
        same machinery as checkpoint/restore, minus the pickle). A run
        that finishes before reaching a yield point completes normally
        and ``preempt_payload()`` stays None."""
        self._preempt_event.set()

    # -- Checker surface ---------------------------------------------------

    @property
    def pipeline(self) -> str:
        """The expansion pipeline this run dispatches: ``"fps"``
        (fingerprint-only expansion, candidates never materialized) or
        ``"materialize"`` (the full F × A state grid). bench.py's
        measured-policy calibration compares this against the timed
        winner."""
        return "fps" if self._use_fps else "materialize"

    def model(self):
        return self._model

    def state_count(self) -> int:
        return max(self._state_count, self._unique_count)

    def unique_state_count(self) -> int:
        return self._unique_count

    def max_depth(self) -> int:
        return self._max_depth

    supports_device_liveness = True

    def discoveries(self) -> Dict[str, Path]:
        with self._tracer.span("tpu_bfs.paths") as sp:
            fp_calls = self._fp_calls
            entries = self._ingest_wave_log()
            out = {
                name: self._reconstruct(fp)
                for name, fp in list(self._discoveries_fp.items())
            }
            out = self._with_device_liveness(out)
            out = self._with_lassos(
                out,
                self._done_event.is_set(),
                set(self._discoveries_fp) | set(self._live_paths),
            )
            sp.set(log_entries=entries, fp_calls=self._fp_calls - fp_calls)
        return out

    def handles(self) -> List[threading.Thread]:
        handles, self._handles = self._handles, []
        return handles

    def is_done(self) -> bool:
        return self._done_event.is_set()

    def worker_error(self) -> Optional[BaseException]:
        return self._error

    def _discovery_names(self) -> List[str]:
        # Names only — the flight recorder's digest must not trigger the
        # full path reconstruction discoveries() performs.
        return list(set(self._discoveries_fp) | set(self._live_paths))

    def state_digest(self) -> dict:
        digest = super().state_digest()
        digest.update(
            table_capacity=self._capacity,
            frontier_capacity=self._F_max,
            wave_kernel=self._wave_kernel,
            warmup_seconds=getattr(self, "warmup_seconds", None),
            checkpoint_path=self._checkpoint_path,
            last_dispatch=self._last_dispatch,
            preempted=self.preempted,
            liveness_mode=self.liveness_mode,
        )
        if self._live_store is not None:
            try:
                digest["liveness_edge_store"] = self._live_store.stats()
            except Exception:  # noqa: BLE001 - mid-crash best effort
                digest["liveness_edge_store"] = None
        if self._tier is not None:
            try:
                digest["storage"] = self._tier.instruments.bench_stats()
            except Exception:  # noqa: BLE001 - mid-crash best effort
                digest["storage"] = None
        return digest
