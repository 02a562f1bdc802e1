"""Tenant-packed waves: many jobs advanced by ONE device dispatch.

``CheckService`` time-slicing (PR 10) pays a full checkpoint-v2 drain +
restore per slice — BENCH_r10 shows four concurrent 2pc-5 jobs burning
~2/3 of the device on that churn. This engine makes concurrency ~free
instead: up to ``max_tenants`` same-shape jobs share one physical wave.
Each dispatch compacts the live lanes of every resident tenant into one
dense frontier (a per-lane ``tid`` tenant-slot vector rides through
expand/fingerprint/property eval), dedups against ONE shared visited
table under **tenant-salted fingerprints**, and reduces results
(generated/fresh/depth/discoveries) per tenant by segmenting on the
lane's tenant id. Preempting a tenant is "drop its lanes" — its pending
frontier, counters, parent log, and storage partition hand back as a
standard checkpoint-v2 payload, with no device drain — and admission is
"claim a free lane slot" (optionally restoring such a payload, so a
dropped tenant resumes into a LATER pack or into a solo checker
unchanged).

Why each tenant's results are bit-identical to its solo run
-----------------------------------------------------------

Two properties carry the whole argument:

1. **XOR salting preserves within-tenant dedup exactly.** A tenant's
   table key is ``fp ^ salt`` — a bijection — so two of its states
   collide salted iff they collide raw; cross-tenant keys differ by an
   avalanche-mixed 64-bit constant (``ops/fingerprint.tenant_salt_pair``).
   Frontier rows, parent logs, discoveries, payloads, and the host-tier
   partitions always carry the ORIGINAL fingerprints.
2. **The owner-ticket scatter insert preserves lane order.** Packing
   uses ``hashset_insert_salted`` (the duplicate-tolerant unsorted
   insert): fresh lanes compact in natural lane order, and each tenant's
   lanes are assembled in its own FIFO frontier order — so a tenant's
   claim sequence is candidate-order-equivalent to its solo run under
   ``wave_dedup="scatter"`` (the CPU backend default). Re-chunking a
   FIFO frontier across different wave widths never changes claims:
   the first claimant of a key in per-tenant candidate order wins in
   every grouping (the same argument the bucket ladder's
   width-independence rests on). Hence counts, depths, parent pointers,
   ebit propagation, discovery fingerprints, and golden reports all
   match the solo run. (Early-exit runs — every property discovered —
   may overshoot by a different amount, exactly as the reference
   overshoots by up to a block.)

Out-of-core packing partitions the host tiers per tenant
(``storage.TenantPartitions``): the shared table's salted keys cannot be
attributed after the fact, but the engine knows each tenant's L0 claims
exactly (they are its parent-log stream), so an eviction drains each
tenant's since-last-eviction claims into its own run set and the wave's
two-phase probe runs per tenant partition. With ``async_pipeline=True``
those probes, the parent-log appends, and survivor re-entry ride one
FIFO ``HostPipeline`` worker behind the same merge fence the solo async
engine uses, overlapping with the next packed dispatch.

Device-transfer note: lane blocks live host-side (numpy) between waves,
so each wave pays one host->device frontier upload and one fresh-lane
download. On the CPU backend these are memcpys; a device-resident
per-tenant ring is the follow-up once this architecture lands on real
HBM.
"""

from __future__ import annotations

import itertools
import threading
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batch import BatchableModel
from ..core.model import Expectation
from ..core.path import Path
from ..native import make_fingerprint_store
from ..ops.fingerprint import fp64_pairs, fp_to_int, tenant_salt_pair
from ..ops.hashset import hashset_insert_salted, hashset_new
from ..telemetry import (
    TenantInstruments,
    WaveInstruments,
    get_tracer,
    metrics_registry,
)
from ..utils.faults import TenantFaultError, fault_point
from .base import Checker
from .pipeline import HostPipeline
from .programs import Programs
from .tpu import (
    _DEPTH_INF,
    _MAX_LOAD,
    _pow2ceil,
    bucket_for,
    bucket_ladder_widths,
    checkpoint_header,
    packed_model_digest,
    validate_checkpoint_header,
)

__all__ = ["TenantPackedEngine", "TenantRun"]

# Fixed batch width for bulk (resume-admission) inserts: one compile
# serves every restored payload regardless of its key count.
_BULK_INSERT_WIDTH = 1 << 13


class _LaneStore:
    """One tenant's pending frontier: a FIFO of dense host-side lane
    blocks (numpy struct-of-arrays: states pytree + hi/lo/ebits/depth).
    Push (async verdict worker) and take (engine thread) are guarded by
    a lock; blocks are immutable once pushed."""

    def __init__(self):
        self._blocks = deque()
        self._lock = threading.Lock()
        self.pending = 0

    def push(self, block: dict, n: int) -> None:
        if n == 0:
            return
        with self._lock:
            self._blocks.append((block, n))
            self.pending += n

    def take(self, k: int) -> List[dict]:
        """Up to ``k`` lanes off the head, as dense blocks (a partially
        consumed block is split; FIFO lane order is preserved)."""
        out = []
        with self._lock:
            while k > 0 and self._blocks:
                block, n = self._blocks.popleft()
                if n <= k:
                    out.append(block)
                    self.pending -= n
                    k -= n
                else:
                    head = _slice_block(block, 0, k)
                    tail = _slice_block(block, k, n)
                    out.append(head)
                    self._blocks.appendleft((tail, n - k))
                    self.pending -= k
                    k = 0
        return out

    def clear(self) -> None:
        with self._lock:
            self._blocks.clear()
            self.pending = 0


def _slice_block(block: dict, start: int, stop: int) -> dict:
    return {
        k: (
            jax.tree_util.tree_map(lambda x: x[start:stop], v)
            if k == "states"
            else v[start:stop]
        )
        for k, v in block.items()
    }


class _Tenant:
    """One resident tenant's host state (slot, salt, frontier, ledgers)."""

    def __init__(self, key, run_id, slot, epoch, depth_cap, registry):
        self.key = key
        self.run_id = run_id
        self.slot = slot
        self.salt_hi, self.salt_lo = tenant_salt_pair(epoch)
        self.depth_cap = depth_cap if depth_cap is not None else _DEPTH_INF
        self.registry = registry
        self.instruments = TenantInstruments("pack", registry=registry)
        self.lanes = _LaneStore()
        self.state_count = 0
        self.unique_count = 0
        self.max_depth = 0
        self.discoveries_fp: Dict[str, int] = {}
        # (child u64, parent u64) arrays per wave — the parent-pointer
        # stream (path reconstruction + the preempt payload + the
        # eviction attribution source).
        self.wave_log: List = []
        self._ingested = 0
        self._ingest_lock = threading.Lock()
        self.store = make_fingerprint_store()
        # Unsalted fps claimed fresh in L0 since the last eviction —
        # exactly what an eviction must drain into this tenant's
        # partition.
        self.resident: List[np.ndarray] = []
        self.done = False      # no further lanes scheduled
        self.finished = False  # reported complete (view.is_done)
        # A fault was attributed to this tenant: it is rolled back to
        # its pre-wave boundary, excluded from further scheduling, and
        # waits for the service to drop() it (its payload slice is
        # exact — see TenantPackedEngine._tenant_rollback).
        # ``fault_error`` keeps THIS tenant's own exception — several
        # tenants can fault in one wave, and each one's retry filter
        # and flight dump must see its own error, not the first's.
        self.faulted = False
        self.fault_error: Optional[BaseException] = None
        self.compile_offset = 0.0
        self.view: Optional["TenantRun"] = None
        # Device liveness (engine liveness="device"): this tenant's own
        # condition-false edge partition + the finish-time verdicts.
        # Absorbs are idempotent facts about the state graph (the store
        # dedups), so fault rollback never needs to undo them.
        self.live_store = None
        self.live_paths: Dict[str, "Path"] = {}
        self.live_outcomes: Dict[str, dict] = {}

    def ingest(self) -> None:
        with self._ingest_lock:
            while self._ingested < len(self.wave_log):
                children, parents = self.wave_log[self._ingested]
                self.store.insert_batch(children, parents)
                self._ingested += 1


class TenantRun(Checker):
    """The caller-facing handle for one packed tenant — the standard
    ``Checker`` surface (counts, discoveries with reconstructed paths,
    golden reporter, assertions) over the engine's per-tenant state, so
    the service finalizes a packed job exactly like a solo one."""

    supports_preempt = True  # preemption == lane drop, engine-mediated

    def __init__(self, engine: "TenantPackedEngine", tenant: _Tenant):
        self._engine = engine
        self._t = tenant
        self.run_id = tenant.run_id
        self._registry = tenant.registry
        self.warmup_seconds = 0.0
        # Liveness surfaces (checker/base.py) read these per tenant.
        self._live = engine._live
        self._live_enabled = engine._live_enabled

    supports_device_liveness = True

    @property
    def _live_store(self):
        return self._t.live_store

    @property
    def _live_paths(self):
        return self._t.live_paths

    @property
    def _live_outcomes(self):
        return self._t.live_outcomes

    def model(self):
        return self._engine._model

    def state_count(self) -> int:
        return max(self._t.state_count, self._t.unique_count)

    def unique_state_count(self) -> int:
        return self._t.unique_count

    def max_depth(self) -> int:
        return self._t.max_depth

    def discoveries(self) -> Dict[str, Path]:
        out = {
            name: self._reconstruct(fp)
            for name, fp in list(self._t.discoveries_fp.items())
        }
        return self._with_device_liveness(out)

    def _discovery_names(self) -> List[str]:
        return list(set(self._t.discoveries_fp) | set(self._t.live_paths))

    def _reconstruct(self, fp: int) -> Path:
        self._t.ingest()
        chain = self._t.store.chain(fp)
        return Path.from_fingerprints(
            self.model(), chain, fp_of=self._engine._host_fp
        )

    def handles(self) -> List[threading.Thread]:
        return []

    def is_done(self) -> bool:
        return self._t.finished

    def worker_error(self) -> Optional[BaseException]:
        return None

    def state_digest(self) -> dict:
        digest = super().state_digest()
        digest.update(
            packed=True,
            tenant_slot=self._t.slot,
            pending_lanes=self._t.lanes.pending,
            preempted=self.preempted,
        )
        return digest


class TenantPackedEngine:
    """The packer: shared table + shared wave executables, per-tenant
    lane accounting. Driven wave-at-a-time by one caller thread (the
    service scheduler): ``admit()`` claims a lane slot (optionally
    restoring a checkpoint-v2 payload), ``step()`` advances every
    resident tenant by one packed wave and returns the tenants that
    completed, ``drop()`` preempts one tenant into a payload slice.

    ``aot_cache`` (a namespace string) shares the wave/seed/rehash
    executables process-globally, so a later engine instance for the
    same pack configuration compiles nothing (same discipline as
    ``TpuBfsChecker``'s shared AOT cache).
    """

    def __init__(
        self,
        model,
        *,
        frontier_capacity: int = 1 << 10,
        table_capacity: int = 1 << 16,
        max_tenants: int = 8,
        bucket_ladder: Optional[int] = None,
        hbm_budget_mib: Optional[float] = None,
        host_budget_mib: Optional[float] = None,
        spill_dir: Optional[str] = None,
        async_pipeline: bool = False,
        aot_cache: Optional[str] = None,
        resume_capacity: Optional[int] = None,
        run_id: Optional[str] = None,
        liveness=None,
    ):
        if not isinstance(model, BatchableModel):
            raise TypeError(
                "TenantPackedEngine requires a BatchableModel; "
                f"{type(model).__name__} does not implement the packed "
                "protocol"
            )
        self._model = model
        self._properties = model.properties()
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
        self._ebit = {pi: b for b, pi in enumerate(eventually)}
        self._ebits0 = sum(1 << b for b in self._ebit.values())
        # Device-native liveness, packed: the wave logs each lane's
        # condition-false edges with its tenant id, and the verdict
        # splits them into PER-TENANT host edge partitions — fps are
        # the ORIGINAL (pre-salt) ones (chi/clo are computed before
        # ``hashset_insert_salted`` applies the XOR), so each tenant's
        # relation is bit-identical to its solo run's and the per-tenant
        # trim/reach verdict at finish time matches the solo verdict
        # exactly (tests/test_device_liveness.py).
        from .device_liveness import LIVENESS_MODES

        if liveness not in LIVENESS_MODES:
            raise ValueError(
                f"liveness must be one of {LIVENESS_MODES}, "
                f"got {liveness!r}"
            )
        self._live = "device" if liveness == "device" else None
        self._live_enabled = self._live == "device" and bool(self._ebit)
        self._A = model.packed_action_count()
        self._fp_fn = model.packed_fingerprint
        self._K = max(1, int(max_tenants))
        self._F_max = _pow2ceil(frontier_capacity)
        from .tpu import _AUTO_BUCKET_MIN_F, _DEFAULT_BUCKET_STEPS

        if bucket_ladder is None:
            bucket_ladder = (
                _DEFAULT_BUCKET_STEPS
                if self._F_max >= _AUTO_BUCKET_MIN_F
                else 0
            )
        self._buckets = bucket_ladder_widths(self._F_max, bucket_ladder)
        self._capacity = _pow2ceil(table_capacity)
        self._resume_capacity = resume_capacity or table_capacity

        from ..storage import (
            TenantPartitions,
            max_table_rows_for_budget,
            validate_budget_knobs,
        )

        validate_budget_knobs(hbm_budget_mib, host_budget_mib, spill_dir)
        self._max_capacity = None
        if hbm_budget_mib is not None:
            max_cap = max_table_rows_for_budget(hbm_budget_mib)
            min_cap = _pow2ceil(int(self._F_max * self._A / _MAX_LOAD) + 1)
            if max_cap < min_cap:
                raise ValueError(
                    f"hbm_budget_mib={hbm_budget_mib} allows a device "
                    f"table of {max_cap} rows, but one worst-case packed "
                    f"wave needs at least {min_cap}; raise the budget or "
                    "shrink frontier_capacity"
                )
            self._max_capacity = max_cap
            self._capacity = min(self._capacity, max_cap)
        self.run_id = run_id
        self._registry = metrics_registry(run_id) if run_id else None
        self._tracer = get_tracer(run_id)
        self._partitions = TenantPartitions(
            host_budget_mib=host_budget_mib,
            spill_dir=spill_dir,
            tracer=self._tracer,
        )
        self._wi = WaveInstruments("pack", registry=self._registry)
        reg = (
            self._registry
            if self._registry is not None
            else metrics_registry()
        )
        # Lane accounting: dispatched = width x waves (what the device
        # executed), live = real tenant lanes in them. live/dispatched
        # is the pack's occupancy — the whole point of packing.
        self._c_lanes_dispatched = reg.counter("pack.lanes_dispatched")
        self._c_lanes_live = reg.counter("pack.lanes_live")

        self._table = hashset_new(self._capacity)
        self._l0 = 0
        self._slots: List[Optional[_Tenant]] = [None] * self._K
        self._by_key: Dict[object, _Tenant] = {}
        self._salt_epochs = itertools.count(1)
        self._rr = 0  # rotating lane-allocation offset (fairness)
        self.waves = 0
        self.compile_seconds = 0.0
        self.lanes_dispatched = 0
        self.lanes_live = 0

        self._pipe = (
            HostPipeline(name="pack-host") if async_pipeline else None
        )

        # Host-side state template (per-lane leaf shapes/dtypes) for
        # frontier assembly; the treedef is the packed pytree structure.
        init_np = jax.tree_util.tree_map(
            np.asarray, model.packed_init_states()
        )
        leaves, treedef = jax.tree_util.tree_flatten(init_np)
        self._state_treedef = treedef
        self._leaf_specs = [(x.shape[1:], x.dtype) for x in leaves]

        # Executables by (kind, *shape); shared process-wide under a
        # namespace, so engines for one pack config never recompile.
        self._programs = Programs(
            "pack", self._tracer, signature=self._aot_signature(),
            aot_cache=aot_cache, registry=self._registry,
            on_compile=self._charge_compile,
        )
        self._jit_wave = jax.jit(self._wave, donate_argnums=(0,))
        self._jit_seed = jax.jit(self._seed_wave, donate_argnums=(0,))
        self._jit_bulk = jax.jit(self._bulk_insert, donate_argnums=(0,))
        self._jit_rehash = jax.jit(self._rehash, donate_argnums=(1,))
        self._jit_fp_single = jax.jit(self._fp_fn)

    # -- identity ----------------------------------------------------------

    def _aot_signature(self) -> tuple:
        return (
            jax.default_backend(),
            packed_model_digest(self._model, self._A),
            tuple((p.name, str(p.expectation)) for p in self._properties),
            self._K,
            self._F_max,
            tuple(self._buckets),
            self._max_capacity,
            self._live_enabled,
        )

    def _host_fp(self, host_state) -> int:
        hi, lo = self._jit_fp_single(self._model.pack_state(host_state))
        return fp_to_int(hi, lo)

    # -- device functions (jitted) -----------------------------------------

    def _wave(self, table, states, hi, lo, ebits, depth, mask, tid,
              salt_hi, salt_lo, depth_caps):
        """One packed wave over ``F`` mixed-tenant lanes: the solo
        materializing wave body (checker/tpu.py ``_wave``) with a
        tenant-lane dimension — per-lane depth caps, salted claims, and
        per-tenant (one-hot segmented) reductions."""
        model = self._model
        A, K = self._A, self._K
        F = hi.shape[0]
        B = F * A
        eval_mask = mask & (depth < depth_caps[tid])

        cond_vals = [jax.vmap(c)(states) for c in self._conditions]
        ebits_after = ebits
        for pi, b in self._ebit.items():
            ebits_after = jnp.where(
                cond_vals[pi], ebits_after & ~jnp.uint32(1 << b), ebits_after
            )

        cand, cvalid = jax.vmap(model.packed_expand)(states)
        cvalid = cvalid & eval_mask[:, None]
        cvalid = cvalid & jax.vmap(
            jax.vmap(model.packed_within_boundary)
        )(cand)
        terminal = eval_mask & ~cvalid.any(axis=1)

        cand_flat = jax.tree_util.tree_map(
            lambda x: x.reshape((B,) + x.shape[2:]), cand
        )
        cvalid_flat = cvalid.reshape(B)
        chi, clo = jax.vmap(self._fp_fn)(cand_flat)
        lanes = jnp.arange(B, dtype=jnp.int32)
        parent_row = lanes // A
        ctid = tid[parent_row]
        # Salted claim in the one shared table; natural lane order is
        # preserved (see module docstring for why that is the whole
        # bit-identity story).
        table, fresh, _found, pending = hashset_insert_salted(
            table, chi, clo, salt_hi[ctid], salt_lo[ctid], cvalid_flat
        )
        overflow = pending.sum()

        # Per-tenant segmented reductions (K is small and static: the
        # one-hot forms fuse into a handful of masked sums).
        slot_ids = jnp.arange(K, dtype=jnp.int32)
        onehot_f = (tid[:, None] == slot_ids[None, :]) & mask[:, None]
        gen_lane = cvalid.sum(axis=1, dtype=jnp.int32)
        gen_t = jnp.sum(
            jnp.where(onehot_f, gen_lane[:, None], 0), axis=0,
            dtype=jnp.int32,
        )
        maxd_t = jnp.max(
            jnp.where(onehot_f, depth[:, None], 0), axis=0
        ).astype(jnp.int32)
        onehot_b = (ctid[:, None] == slot_ids[None, :]) & fresh[:, None]
        new_t = jnp.sum(onehot_b, axis=0, dtype=jnp.int32)

        # Fresh lanes compact to a prefix in natural lane order.
        pos = jnp.cumsum(fresh.astype(jnp.int32)) - 1
        out_slot = jnp.where(fresh, pos, B)
        zi = jnp.zeros((B,), jnp.int32)
        zu = jnp.zeros((B,), jnp.uint32)
        src_idx = zi.at[out_slot].set(lanes, mode="drop")
        new = {
            "hi": zu.at[out_slot].set(chi, mode="drop"),
            "lo": zu.at[out_slot].set(clo, mode="drop"),
            "ebits": zu.at[out_slot].set(
                ebits_after[parent_row], mode="drop"
            ),
            "depth": zi.at[out_slot].set(
                depth[parent_row] + 1, mode="drop"
            ),
            "tid": zi.at[out_slot].set(ctid, mode="drop"),
            "parent_hi": zu.at[out_slot].set(hi[parent_row], mode="drop"),
            "parent_lo": zu.at[out_slot].set(lo[parent_row], mode="drop"),
            "states": jax.tree_util.tree_map(
                lambda x: x[src_idx], cand_flat
            ),
        }

        out = {"table": table, "new": new}
        if self._live_enabled:
            # Per-tenant condition-false edge rows (ORIGINAL fps — the
            # salt never touches chi/clo), tenant id riding each row so
            # the verdict can split them into per-tenant partitions.
            from .device_liveness import wave_edge_rows

            live_rows, live_n = wave_edge_rows(
                self._conditions, self._ebit, cond_vals, cand_flat,
                cvalid_flat, terminal, hi, lo, chi, clo, A,
                extra_lane={"tid": ctid}, extra_row={"tid": tid},
            )
            out["live"] = live_rows
            out["live_n"] = live_n
        # Per-(tenant, property) discovery scan over the evaluated
        # frontier — argmax picks the tenant's FIRST hit in lane order,
        # which is its first hit in its own FIFO order.
        P = len(self._properties)
        if P:
            hits, fhis, flos = [], [], []
            for i, p in enumerate(self._properties):
                if p.expectation == Expectation.ALWAYS:
                    h = eval_mask & ~cond_vals[i]
                elif p.expectation == Expectation.SOMETIMES:
                    h = eval_mask & cond_vals[i]
                else:
                    b = self._ebit[i]
                    h = terminal & (
                        ((ebits_after >> jnp.uint32(b)) & 1) == 1
                    )
                for k in range(K):
                    hk = h & (tid == k)
                    idx = jnp.argmax(hk)
                    hits.append(hk.any())
                    fhis.append(hi[idx])
                    flos.append(lo[idx])
            out["prop_hit"] = jnp.stack(hits).reshape(P, K)
            out["prop_hi"] = jnp.stack(fhis).reshape(P, K)
            out["prop_lo"] = jnp.stack(flos).reshape(P, K)

        stats = [overflow.astype(jnp.int32)]
        if P:
            stats.append(out["prop_hit"].any().astype(jnp.int32))
        else:
            stats.append(jnp.int32(0))
        cols = [jnp.stack(stats), gen_t, new_t, maxd_t]
        if self._live_enabled:
            cols.append(out["live_n"][None].astype(jnp.int32))
        out["stats"] = jnp.concatenate(cols)
        return out

    def _seed_wave(self, table, salt_hi, salt_lo):
        """Claims one tenant's init states in the shared table (salted);
        mirrors the solo ``_init_wave``'s counting exactly (duplicate
        valid inits resolve to one fresh claim)."""
        model = self._model
        states = model.packed_init_states()
        valid = jax.vmap(model.packed_within_boundary)(states)
        hi, lo = jax.vmap(self._fp_fn)(states)
        n0 = hi.shape[0]
        table, fresh, _found, pending = hashset_insert_salted(
            table,
            hi,
            lo,
            jnp.full((n0,), salt_hi, jnp.uint32),
            jnp.full((n0,), salt_lo, jnp.uint32),
            valid,
        )
        out = {
            "table": table,
            "states": states,
            "valid": valid,
            "hi": hi,
            "lo": lo,
            "n_unique": fresh.sum(dtype=jnp.int32),
            "n_valid": valid.sum(dtype=jnp.int32),
            "overflow": pending.sum(dtype=jnp.int32),
        }
        if self._live_enabled:
            from .device_liveness import seed_root_mask

            out["root_mask"] = seed_root_mask(
                self._conditions, self._ebit, states, valid
            )
        return out

    def _bulk_insert(self, table, hi, lo, salt_hi, salt_lo, active):
        """Fixed-width salted claim batch (resume admission)."""
        n = hi.shape[0]
        table, fresh, _found, pending = hashset_insert_salted(
            table,
            hi,
            lo,
            jnp.full((n,), salt_hi, jnp.uint32),
            jnp.full((n,), salt_lo, jnp.uint32),
            active,
        )
        return table, fresh.sum(dtype=jnp.int32), pending.sum(
            dtype=jnp.int32
        )

    def _rehash(self, old_table, new_table):
        from ..ops.hashset import hashset_insert

        active = (old_table[:, 0] != 0) | (old_table[:, 1] != 0)
        new_table, _fresh, _found, pending = hashset_insert(
            new_table, old_table[:, 0], old_table[:, 1], active
        )
        return new_table, pending.sum()

    # -- AOT dispatch ------------------------------------------------------

    def _charge_compile(self, kind, seconds):
        self.compile_seconds += seconds
        self._wi.warmup.set(self.compile_seconds)

    # -- membership --------------------------------------------------------

    def free_slots(self) -> int:
        return sum(1 for s in self._slots if s is None)

    def live_count(self) -> int:
        return sum(
            1
            for s in self._slots
            if s is not None and not s.finished
        )

    def tenants(self) -> List[_Tenant]:
        return [s for s in self._slots if s is not None]

    def view(self, key) -> Optional[TenantRun]:
        t = self._by_key.get(key)
        return t.view if t is not None else None

    def admit(self, key, run_id=None, *, depth_cap=None,
              resume_from=None) -> TenantRun:
        """Claims a free lane slot for one tenant. ``resume_from`` is a
        checkpoint-v2 payload (a prior ``drop()``'s slice, or a solo
        ``TpuBfsChecker`` preempt payload of the same model config):
        counters, discoveries, the parent log, the pending frontier, and
        any storage partition restore; the tenant's known keys bulk-claim
        salted slots under a FRESH salt epoch, so leftovers of departed
        tenants can never alias it."""
        if key in self._by_key:
            raise ValueError(f"tenant {key!r} is already packed")
        if depth_cap is not None and self._live_enabled:
            raise ValueError(
                "liveness='device' packs cannot admit depth-capped "
                "tenants: a capped exploration logs a truncated edge "
                "relation, so the finish-time verdict would be unsound"
            )
        slot = next(
            (i for i, s in enumerate(self._slots) if s is None), None
        )
        if slot is None:
            raise RuntimeError(
                f"no free lanes (max_tenants={self._K})"
            )
        registry = metrics_registry(run_id) if run_id else (
            self._registry or metrics_registry()
        )
        t = _Tenant(
            key, run_id, slot, next(self._salt_epochs), depth_cap, registry
        )
        t.compile_offset = self.compile_seconds
        # Register BEFORE seeding/restoring: a budget-capped eviction
        # fired by the admission's own table claims must flush THIS
        # tenant's resident keys into its partition too — an
        # unregistered tenant's earlier-batch claims would vanish from
        # the reset table and be silently re-counted as fresh later.
        self._slots[slot] = t
        self._by_key[key] = t
        try:
            if resume_from is not None:
                self._restore_tenant(t, resume_from)
            else:
                self._seed_tenant(t)
        except BaseException:
            self._slots[slot] = None
            del self._by_key[key]
            self._partitions.drop(key)
            raise
        if not self._properties:
            # Nothing to discover: mirror the solo wave loop's immediate
            # exit after seeding.
            t.done = True
        t.instruments.joins.inc()
        t.view = TenantRun(self, t)
        self._tracer.instant(
            "pack.tenant_join", tenant=str(key), slot=slot,
            resumed=resume_from is not None,
        )
        return t.view

    def _seed_tenant(self, t: _Tenant) -> None:
        # Fresh claims accumulate across growth retries: the shared
        # table cannot be reset between attempts (other tenants live in
        # it), so a retry's already-claimed inits report found, not
        # fresh, and the attempts' fresh counts sum to the solo seed's.
        n_unique = 0
        attempt = 0
        while True:
            exe = self._programs.obtain(
                "seed", (self._table.shape[0],), self._jit_seed,
                (self._table, jnp.uint32(t.salt_hi), jnp.uint32(t.salt_lo)),
            )
            out = exe(
                self._table, jnp.uint32(t.salt_hi), jnp.uint32(t.salt_lo)
            )
            self._table = out["table"]
            n_unique += int(out["n_unique"])
            if not int(out["overflow"]):
                break
            attempt += 1
            if attempt > 8:
                raise RuntimeError(
                    "packed seeding overflowed the shared table"
                )
            self._grow(self._capacity * 2)
        t.state_count = int(out["n_valid"])
        self._l0 += n_unique
        hi = np.asarray(out["hi"])
        lo = np.asarray(out["lo"])
        valid = np.asarray(out["valid"])
        child64 = fp64_pairs(hi, lo)[valid]
        # Count distinct inits host-side: exact even if a mid-seed
        # eviction (budget mode) forced claims to repeat.
        t.unique_count = int(len(np.unique(child64)))
        t.wave_log.append((child64, np.zeros_like(child64)))
        t.resident.append(np.unique(child64))
        if self._live_enabled:
            self._live_tenant_store(t).add_roots(
                child64, np.asarray(out["root_mask"])[valid]
            )
        states_np = jax.tree_util.tree_map(np.asarray, out["states"])
        n_live = int(valid.sum())
        block = {
            "states": jax.tree_util.tree_map(
                lambda x: x[valid], states_np
            ),
            "hi": hi[valid],
            "lo": lo[valid],
            "ebits": np.full((n_live,), self._ebits0, np.uint32),
            "depth": np.ones((n_live,), np.int32),
        }
        t.lanes.push(block, n_live)

    def _restore_tenant(self, t: _Tenant, payload: dict) -> None:
        validate_checkpoint_header(
            payload,
            "tpu_bfs",
            "packed admission restores single-device payloads only",
            self._model,
            self._A,
            False,
            None,
        )
        t.state_count = payload["state_count"]
        t.unique_count = payload["unique_count"]
        t.max_depth = payload["max_depth"]
        t.discoveries_fp = dict(payload["discoveries"])
        children = payload["children"]
        parents = payload["parents"]
        t.wave_log.append((children, parents))
        keys = np.unique(np.asarray(children, np.uint64))
        storage_state = payload.get("storage")
        if storage_state:
            store = self._partitions.store(t.key, registry=t.registry)
            store.load_state(storage_state)
            keys = keys[~store.probe(keys)]
        t.resident.append(keys)
        # Liveness edge partition must round-trip with the tenant (see
        # checker/tpu.py for why mode mismatches are refused).
        live_state = payload.get("liveness")
        if self._live_enabled and live_state is None:
            raise ValueError(
                "liveness='device' packs cannot admit a payload written "
                "without it: pre-checkpoint edges were never logged, so "
                "the finish-time verdict would be unsound"
            )
        if live_state is not None:
            if not self._live_enabled:
                raise ValueError(
                    "payload carries a liveness edge store; admit into "
                    "a liveness='device' pack (or resume solo with "
                    "liveness='device')"
                )
            self._live_tenant_store(t).load_state(live_state)
        # Bulk-claim the tenant's known keys under its fresh salt.
        hi = (keys >> np.uint64(32)).astype(np.uint32)
        lo = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        W = _BULK_INSERT_WIDTH
        for start in range(0, len(keys), W):
            bh = np.zeros((W,), np.uint32)
            bl = np.zeros((W,), np.uint32)
            act = np.zeros((W,), bool)
            n = min(W, len(keys) - start)
            bh[:n] = hi[start : start + n]
            bl[:n] = lo[start : start + n]
            act[:n] = True
            attempt = 0
            while True:
                args = (
                    self._table,
                    jnp.asarray(bh),
                    jnp.asarray(bl),
                    jnp.uint32(t.salt_hi),
                    jnp.uint32(t.salt_lo),
                    jnp.asarray(act),
                )
                exe = self._programs.obtain(
                    "bulk", (self._table.shape[0],), self._jit_bulk, args
                )
                self._table, fresh_n, pend = exe(*args)
                self._l0 += int(fresh_n)
                if not int(pend):
                    break
                attempt += 1
                if attempt > 8:
                    raise RuntimeError(
                        "packed admission overflowed the shared table"
                    )
                self._grow(self._capacity * 2)
        for chunk in payload["chunks"]:
            mask = np.asarray(chunk["mask"])
            n = int(mask.sum())
            if n == 0:
                continue
            block = {
                "states": jax.tree_util.tree_map(
                    lambda x: np.asarray(x)[mask], chunk["states"]
                ),
                "hi": np.asarray(chunk["hi"])[mask],
                "lo": np.asarray(chunk["lo"])[mask],
                "ebits": np.asarray(chunk["ebits"])[mask],
                "depth": np.asarray(chunk["depth"])[mask],
            }
            t.lanes.push(block, n)
        if self._properties and len(t.discoveries_fp) == len(
            self._properties
        ):
            t.done = True

    def drop(self, key, *, discard: bool = False) -> Optional[dict]:
        """Preempts one tenant by dropping its lanes: no device drain —
        its pending frontier, counters, parent log, and storage
        partition leave as a checkpoint-v2 payload slice (``None`` with
        ``discard=True``, the cancel path). The slot and its lane share
        free immediately; the departed tenant's salted table keys are
        garbage that dies at the next growth rehash or eviction."""
        t = self._by_key.get(key)
        if t is None:
            raise KeyError(f"no packed tenant {key!r}")
        if self._pipe is not None:
            self._pipe.drain()
        t.instruments.lane_drops.inc(t.lanes.pending)
        payload = None
        if not discard and not t.finished:
            payload = self._payload(t)  # consumes the lane store
            if t.view is not None:
                t.view._preempt_payload = payload
        t.lanes.clear()
        self._slots[t.slot] = None
        del self._by_key[t.key]
        self._partitions.drop(t.key)
        self._finish_view(t)
        self._tracer.instant(
            "pack.tenant_drop", tenant=str(t.key), slot=t.slot,
            discarded=discard,
        )
        return payload

    def _payload(self, t: _Tenant) -> dict:
        """The tenant's state as a standard checkpoint-v2 payload —
        loadable by ``TpuBfsChecker(resume_from=...)`` or a later
        ``admit(resume_from=...)``, bit-identically either way."""
        t.ingest()
        children, parents = t.store.export()
        chunks = []
        F = self._F_max
        blocks = t.lanes.take(t.lanes.pending)
        lanes_np = _concat_blocks(blocks, self._leaf_specs,
                                  self._state_treedef)
        if lanes_np is not None:
            total = len(lanes_np["hi"])
            for start in range(0, total, F):
                n = min(F, total - start)
                piece = _slice_block(lanes_np, start, start + n)
                chunk = {
                    "states": jax.tree_util.tree_map(
                        lambda x: _pad_rows(x, F), piece["states"]
                    ),
                    "hi": _pad_rows(piece["hi"], F),
                    "lo": _pad_rows(piece["lo"], F),
                    "ebits": _pad_rows(piece["ebits"], F),
                    "depth": _pad_rows(piece["depth"], F),
                    "mask": np.arange(F, dtype=np.int32) < n,
                }
                chunks.append(chunk)
        payload = {
            **checkpoint_header(
                "tpu_bfs", self._model, self._A, False, None
            ),
            "state_count": t.state_count,
            "unique_count": t.unique_count,
            "max_depth": t.max_depth,
            "discoveries": dict(t.discoveries_fp),
            "children": children,
            "parents": parents,
            "capacity": self._resume_capacity,
            "chunks": chunks,
        }
        store = self._partitions.get(t.key)
        if store is not None and not store.is_empty():
            payload["storage"] = store.export_state()
        if self._live_enabled:
            payload["liveness"] = self._live_tenant_store(
                t
            ).export_state()
            payload["version"] = 3
        return payload

    def _finish_view(self, t: _Tenant) -> None:
        if t.view is not None:
            t.view.warmup_seconds = max(
                0.0, self.compile_seconds - t.compile_offset
            )

    # -- table management --------------------------------------------------

    def _grow(self, min_capacity: int) -> None:
        if (
            self._max_capacity is not None
            and min_capacity > self._max_capacity
        ):
            self._evict()
            return
        capacity = self._capacity
        while capacity < min_capacity:
            capacity *= 2
        while True:
            args = (self._table, hashset_new(capacity))
            exe = self._programs.obtain(
                "rehash", (self._table.shape[0], capacity), self._jit_rehash,
                args,
            )
            with self._tracer.span(
                "pack.table_grow", from_capacity=self._capacity,
                to_capacity=capacity,
            ):
                new_table, leftover = exe(*args)
            if not int(leftover):
                break
            capacity *= 2
            if (
                self._max_capacity is not None
                and capacity > self._max_capacity
            ):
                self._evict()
                return
        self._table = new_table
        self._capacity = capacity
        self._wi.table_grows.inc()
        self._wi.capacity.set(capacity)

    def _evict(self) -> None:
        """Budget-capped growth: drains every tenant's since-eviction L0
        claims into its own partition and resets the shared table. The
        pipeline drains first so in-flight verdicts land their keys
        before the flush (the FIFO merge fence, engine-side)."""
        if self._pipe is not None:
            self._pipe.drain()
        deferred: Optional[TenantFaultError] = None
        for t in self.tenants():
            if t.resident and not t.faulted:
                try:
                    # Injection seam: one tenant's partition eviction
                    # dies (spill ENOSPC included — the partition store
                    # carries the same owner tag). Contained: the other
                    # tenants' claims still absorb, and the faulted
                    # tenant's payload rebuilds its visited set from
                    # the parent log, not from `resident`.
                    fault_point("pack.tenant.evict", tenant=t.key)
                    fps = np.unique(np.concatenate(t.resident))
                    if len(fps):
                        self._partitions.store(
                            t.key, registry=t.registry
                        ).evict(fps)
                    t.resident = []
                except BaseException as e:  # noqa: BLE001 - per-tenant
                    t.faulted = True
                    t.fault_error = e
                    if deferred is None:
                        deferred = TenantFaultError(t.key, e)
                        deferred.__cause__ = e
        self._capacity = self._max_capacity
        self._table = hashset_new(self._capacity)
        self._l0 = 0
        self._wi.capacity.set(self._capacity)
        self._tracer.instant("pack.evict", capacity=self._capacity)
        if deferred is not None:
            raise deferred

    # -- the packed wave loop ----------------------------------------------

    def _quotas(self, ready: List[_Tenant], width: int) -> Dict[int, int]:
        """Deterministic fair lane split: equal base share in rotating
        slot order, leftovers greedily to tenants with deeper backlogs."""
        order = sorted(
            ready, key=lambda t: (t.slot - self._rr) % self._K
        )
        self._rr = (self._rr + 1) % self._K
        base = max(1, width // len(order))
        q: Dict[int, int] = {}
        rem = width
        for t in order:
            share = min(t.lanes.pending, base, rem)
            q[t.slot] = share
            rem -= share
        for t in order:
            if rem <= 0:
                break
            extra = min(t.lanes.pending - q[t.slot], rem)
            q[t.slot] += extra
            rem -= extra
        return q

    def _assemble(self, ready: List[_Tenant]):
        total = sum(t.lanes.pending for t in ready)
        width = bucket_for(self._buckets, max(1, min(total, self._F_max)))
        quotas = self._quotas(ready, width)
        tid = np.zeros((width,), np.int32)
        mask = np.zeros((width,), bool)
        hi = np.zeros((width,), np.uint32)
        lo = np.zeros((width,), np.uint32)
        ebits = np.zeros((width,), np.uint32)
        depth = np.zeros((width,), np.int32)
        leaves = [
            np.zeros((width,) + shape, dtype)
            for shape, dtype in self._leaf_specs
        ]
        cursor = 0
        lanes_by_slot: Dict[int, int] = {}
        for t in sorted(ready, key=lambda t: t.slot):
            take = quotas.get(t.slot, 0)
            if take <= 0:
                continue
            got = 0
            for block in t.lanes.take(take):
                n = len(block["hi"])
                sl = slice(cursor, cursor + n)
                hi[sl] = block["hi"]
                lo[sl] = block["lo"]
                ebits[sl] = block["ebits"]
                depth[sl] = block["depth"]
                tid[sl] = t.slot
                mask[sl] = True
                for dst, src in zip(
                    leaves, jax.tree_util.tree_leaves(block["states"])
                ):
                    dst[sl] = src
                cursor += n
                got += n
            lanes_by_slot[t.slot] = got
        states = jax.tree_util.tree_unflatten(self._state_treedef, leaves)
        return (
            width,
            lanes_by_slot,
            dict(
                states=states, hi=hi, lo=lo, ebits=ebits, depth=depth,
                mask=mask, tid=tid,
            ),
        )

    def _salt_arrays(self):
        sh = np.zeros((self._K,), np.uint32)
        sl = np.zeros((self._K,), np.uint32)
        dc = np.full((self._K,), _DEPTH_INF, np.int32)
        for t in self.tenants():
            sh[t.slot] = t.salt_hi
            sl[t.slot] = t.salt_lo
            dc[t.slot] = min(t.depth_cap, _DEPTH_INF)
        return sh, sl, dc

    def _schedulable(self) -> List[_Tenant]:
        return [
            t
            for t in self.tenants()
            if not t.done and not t.finished and not t.faulted
            and t.lanes.pending > 0
        ]

    def _tenant_snapshot(self, t: _Tenant) -> dict:
        """Everything one wave can mutate for a tenant, captured BEFORE
        ``_assemble`` consumes its lanes. Blocks are immutable once
        pushed, so snapshotting the deque as a list of references is
        exact and cheap — a fault rolls the tenant back to this
        boundary bit-identically (the fault-containment contract)."""
        with t.lanes._lock:
            blocks = list(t.lanes._blocks)
        return dict(
            state_count=t.state_count,
            unique_count=t.unique_count,
            max_depth=t.max_depth,
            discoveries=dict(t.discoveries_fp),
            wave_log_len=len(t.wave_log),
            resident_len=len(t.resident),
            lane_blocks=blocks,
            done=t.done,
        )

    def _tenant_rollback(self, t: _Tenant, snap: dict) -> None:
        """Restores a tenant to its pre-wave snapshot after a fault:
        scalars and discoveries rewind, append-only logs truncate, and
        the lane deque is restored wholesale (consumed inputs included,
        survivor pushes dropped), so ``drop()`` hands back the exact
        last-good-wave-boundary payload. ``resident`` only truncates —
        an eviction that replaced it with [] absorbed those keys into
        the partition, which must not be undone."""
        t.state_count = snap["state_count"]
        t.unique_count = snap["unique_count"]
        t.max_depth = snap["max_depth"]
        t.discoveries_fp = dict(snap["discoveries"])
        del t.wave_log[snap["wave_log_len"]:]
        del t.resident[snap["resident_len"]:]
        with t.lanes._lock:
            t.lanes._blocks = deque(snap["lane_blocks"])
            t.lanes.pending = sum(n for _b, n in snap["lane_blocks"])
        t.done = snap["done"]

    def step(self) -> List[object]:
        """One packed wave (or a finish pass when no lanes are pending).
        Returns the tenant keys that COMPLETED during this step; fetch
        their ``view()`` for verdicts. Raises on engine errors — the
        caller owns failure routing. A :class:`TenantFaultError`
        (synchronous mode) is the blast-radius contract: the named
        tenant is rolled back to its pre-wave boundary and excluded
        from scheduling (drop it for its exact payload slice) while
        every other tenant's state is already consistent — the caller
        keeps stepping the survivors. In async-pipeline mode faults
        surface as pipeline poisoning and are never attributable (the
        poisoned worker skips later tenants' verdicts), so callers must
        treat them engine-wide."""
        ready = self._schedulable()
        if not ready:
            if self._pipe is not None and self._pipe.pending():
                # Survivors may still be in flight; only an empty queue
                # AFTER the barrier means a tenant is exhausted.
                self._pipe.drain()
                ready = self._schedulable()
            if not ready:
                return self._finish_idle()
        if self._pipe is not None:
            self._pipe.throttle()
        snaps = {t.slot: (t, self._tenant_snapshot(t)) for t in ready}
        width, lanes_by_slot, frontier = self._assemble(ready)
        sh, sl, dc = self._salt_arrays()
        self.waves += 1
        self.lanes_live += sum(lanes_by_slot.values())
        self.lanes_dispatched += width
        self._c_lanes_live.inc(sum(lanes_by_slot.values()))
        self._c_lanes_dispatched.inc(width)
        try:
            with self._tracer.span(
                "pack.wave", wave=self.waves, bucket=width,
                tenants=len(lanes_by_slot),
            ) as span:
                gens, news = self._run_attempts(
                    frontier, width, lanes_by_slot, sh, sl, dc
                )
                self._wi.record(
                    span,
                    frontier=width,
                    generated=int(gens.sum()),
                    n_new=int(news.sum()),
                    occupancy=self._l0 / self._capacity,
                    capacity=self._capacity,
                    max_depth=max(
                        (t.max_depth for t in self.tenants()), default=0
                    ),
                    bucket=width,
                    compaction_ratio=sum(lanes_by_slot.values()) / width,
                    tenants=len(lanes_by_slot),
                )
        except TenantFaultError as e:
            if self._pipe is None:
                if e.pre_dispatch:
                    # The wave never executed: every participant's
                    # consumed inputs go back where they came from.
                    for t, snap in snaps.values():
                        self._tenant_rollback(t, snap)
                else:
                    # Roll back EVERY tenant flagged during this wave
                    # (an eviction can fault several at once), not just
                    # the one the raised error names — each must leave
                    # with an exact pre-wave payload.
                    for t, snap in snaps.values():
                        if t.faulted or t.key == e.tenant_key:
                            self._tenant_rollback(t, snap)
                ft = self._by_key.get(e.tenant_key)
                if ft is not None:
                    ft.faulted = True
                self._tracer.instant(
                    "pack.tenant_fault", tenant=str(e.tenant_key),
                    pre_dispatch=e.pre_dispatch,
                )
            raise
        return self._finish_idle()

    def faulted_keys(self) -> List[object]:
        """Every resident tenant currently flagged faulted — the caller
        must drop each one (a single wave can fault several tenants,
        e.g. one eviction pass over every partition); leaving a flagged
        tenant resident would exclude it from scheduling while still
        counting it live."""
        return [t.key for t in self.tenants() if t.faulted]

    def fault_error(self, key) -> Optional[BaseException]:
        """The flagged tenant's OWN exception (each co-faulted tenant
        keeps its own — retry filtering and forensics must not read
        another tenant's error)."""
        t = self._by_key.get(key)
        return t.fault_error if t is not None else None

    def _run_attempts(self, frontier, width, lanes_by_slot, sh, sl, dc):
        """Dispatch + growth-retry loop for one packed wave; returns the
        per-slot (generated, fresh) vectors of the first attempt /
        accumulated fresh."""
        K = self._K
        try:
            self._ensure_capacity(width * self._A)
        except TenantFaultError as e:
            # Pre-dispatch eviction fault: nothing executed yet, so the
            # caller can restore EVERY participant's inputs exactly.
            e.pre_dispatch = True
            raise
        gens = np.zeros((K,), np.int64)
        news = np.zeros((K,), np.int64)
        attempt = 0
        deferred: Optional[TenantFaultError] = None
        while True:
            args = (
                self._table,
                frontier["states"],
                frontier["hi"],
                frontier["lo"],
                frontier["ebits"],
                frontier["depth"],
                frontier["mask"],
                frontier["tid"],
                jnp.asarray(sh),
                jnp.asarray(sl),
                jnp.asarray(dc),
            )
            exe = self._programs.obtain(
                "wave", (self._table.shape[0], width), self._jit_wave, args
            )
            # Injection seam: a packed device-wave raise is inherently
            # engine-wide (every tenant's lanes ride the one dispatch)
            # — the service retries all members solo from their last
            # checkpointed boundaries.
            fault_point("device.wave")
            out = exe(*args)
            self._table = out["table"]
            stats = np.asarray(out["stats"])
            overflow = int(stats[0])
            any_hit = int(stats[1])
            gen_t = stats[2 : 2 + K]
            new_t = stats[2 + K : 2 + 2 * K]
            maxd_t = stats[2 + 2 * K : 2 + 3 * K]
            if attempt == 0:
                gens += gen_t
                self._apply_stats(gen_t, maxd_t, any_hit, out)
            news += new_t
            n_total = int(new_t.sum())
            self._l0 += n_total
            ticket = dict(
                out=out,
                n_total=n_total,
                new_t=new_t,
                gen_t=gen_t if attempt == 0 else np.zeros((K,), np.int64),
                width=width,
                lanes_by_slot=lanes_by_slot if attempt == 0 else {},
                live_n=(
                    int(stats[2 + 3 * K]) if self._live_enabled else 0
                ),
            )
            if self._pipe is None:
                try:
                    self._verdict(ticket)
                except TenantFaultError as e:
                    # Defer: the remaining growth attempts must still
                    # run so every OTHER tenant's wave completes in
                    # full — the faulted tenant (already flagged) is
                    # skipped by later verdicts and rolled back by the
                    # caller.
                    if deferred is None:
                        deferred = e
            else:
                self._pipe.submit(lambda tk=ticket: self._verdict(tk))
            if not overflow:
                if deferred is not None:
                    raise deferred
                return gens, news
            if self._max_capacity is not None and attempt >= 8:
                raise RuntimeError(
                    "a packed wave's candidates overflow the "
                    "budget-capped shared table after repeated "
                    "evictions; raise the budget or shrink "
                    "frontier_capacity"
                )
            try:
                self._grow(self._capacity * 2)
            except TenantFaultError as e:
                # Mid-wave eviction fault: the overflow retry this grow
                # was serving never runs, so EVERY tenant's wave is
                # incomplete — per-tenant attribution would be a lie.
                raise RuntimeError(
                    "packed eviction failed mid-wave (overflow retry "
                    "pending); engine-wide fault"
                ) from e
            attempt += 1

    def _apply_stats(self, gen_t, maxd_t, any_hit, out) -> None:
        """First-attempt caller-side bookkeeping: generated/depth
        counters and per-tenant discovery fingerprints (a tenant whose
        every property is discovered stops scheduling, mirroring the
        solo loop's early exit)."""
        props = self._properties
        hit = phi = plo = None
        if props and any_hit:
            hit = np.asarray(out["prop_hit"])
            phi = np.asarray(out["prop_hi"])
            plo = np.asarray(out["prop_lo"])
        for t in self.tenants():
            k = t.slot
            t.state_count += int(gen_t[k])
            t.max_depth = max(t.max_depth, int(maxd_t[k]))
            if hit is not None:
                for i, p in enumerate(props):
                    if hit[i, k] and p.name not in t.discoveries_fp:
                        t.discoveries_fp[p.name] = fp_to_int(
                            phi[i, k], plo[i, k]
                        )
                if len(t.discoveries_fp) == len(props) and not t.done:
                    t.done = True
                    t.lanes.clear()

    def _verdict(self, ticket: dict) -> None:
        """One wave attempt's host half (pipeline worker in async mode):
        per-tenant partition probe, parent-log append, survivor
        re-entry at each tenant's queue tail, lane-accounting metrics."""
        n_total = ticket["n_total"]
        out = ticket["out"]
        width = ticket["width"]
        live_cols = live_tid = None
        if self._live_enabled and ticket.get("live_n"):
            from ..ops.edge_store import EDGE_COLS

            nlive = ticket["live_n"]
            live_cols = {
                c: np.asarray(out["live"][c])[:nlive] for c in EDGE_COLS
            }
            live_tid = np.asarray(out["live"]["tid"])[:nlive]
        if n_total:
            new = out["new"]
            hi = np.asarray(new["hi"])[:n_total]
            lo = np.asarray(new["lo"])[:n_total]
            ebits = np.asarray(new["ebits"])[:n_total]
            depth = np.asarray(new["depth"])[:n_total]
            tid = np.asarray(new["tid"])[:n_total]
            parent_hi = np.asarray(new["parent_hi"])[:n_total]
            parent_lo = np.asarray(new["parent_lo"])[:n_total]
            states = jax.tree_util.tree_map(
                lambda x: np.asarray(x)[:n_total], new["states"]
            )
        deferred: Optional[TenantFaultError] = None
        for t in self.tenants():
            if t.faulted:
                # A flagged tenant's verdict slice is skipped: it is
                # rolled back to its pre-wave boundary either way, so
                # applying (or half-applying) this wave would only
                # corrupt the payload it leaves with.
                continue
            k = t.slot
            n_k = int(ticket["new_t"][k])
            survivors = 0
            stale = 0
            try:
                if live_cols is not None and not t.done:
                    # This tenant's slice of the wave's edge rows into
                    # its own partition — inside the per-tenant try, so
                    # an absorb fault (the liveness.edge_evict seam)
                    # lane-drops only this tenant (pack-local blast
                    # radius; absorbs are idempotent, so the rolled-back
                    # tenant's retry re-absorbing them is harmless).
                    lsel = np.flatnonzero(live_tid == k)
                    if len(lsel):
                        self._live_tenant_store(t).absorb(
                            **{
                                c: live_cols[c][lsel]
                                for c in live_cols
                            }
                        )
                if n_k and not t.done:
                    # Injection seam: one tenant's host-tier verdict
                    # slice dies (its probe, its numpy, its partition)
                    # — the pack-local blast-radius case. Fires before
                    # any of this tenant's state mutates, and the
                    # partition probe below carries the same per-tenant
                    # owner tag.
                    fault_point("pack.tenant.verdict", tenant=t.key)
                    sel = np.flatnonzero(tid == k)
                    child = fp64_pairs(hi[sel], lo[sel])
                    keep = np.arange(len(sel))
                    store = self._partitions.get(t.key)
                    if store is not None and not store.is_empty():
                        stale_mask = store.probe(child)
                        stale = int(stale_mask.sum())
                        keep = np.flatnonzero(~stale_mask)
                    survivors = len(keep)
                    if survivors:
                        kept = sel[keep]
                        child = child[keep]
                        parent = fp64_pairs(
                            parent_hi[kept], parent_lo[kept]
                        )
                        t.wave_log.append((child, parent))
                        t.resident.append(child)
                        t.unique_count += survivors
                        block = {
                            "states": jax.tree_util.tree_map(
                                lambda x: x[kept], states
                            ),
                            "hi": hi[kept],
                            "lo": lo[kept],
                            "ebits": ebits[kept],
                            "depth": depth[kept],
                        }
                        t.lanes.push(block, survivors)
                elif n_k and t.done:
                    # Discovery-complete tenants discard late fresh
                    # lanes (the solo loop would never have expanded
                    # them either way; their claims are table garbage
                    # like a dropped tenant's).
                    pass
            except BaseException as e:  # noqa: BLE001 - contained per tenant
                # Flag now (later attempts of this wave skip the
                # tenant) and defer the raise so every OTHER tenant's
                # slice of this verdict still applies — the whole point
                # of a pack-local blast radius.
                t.faulted = True
                t.fault_error = e
                if deferred is None:
                    deferred = TenantFaultError(t.key, e)
                    deferred.__cause__ = e
                continue
            lanes_k = ticket["lanes_by_slot"].get(k, 0)
            if lanes_k or n_k:
                if stale:
                    t.instruments.stale.inc(stale)
                t.instruments.record_wave(
                    lanes=lanes_k,
                    width=width,
                    generated=int(ticket["gen_t"][k]),
                    n_new=survivors,
                    pending=t.lanes.pending,
                    max_depth=t.max_depth,
                )
        if deferred is not None:
            raise deferred

    def _live_tenant_store(self, t: _Tenant):
        """The tenant's lazily-created liveness edge partition (its own
        store — per-tenant partitions mirror storage.TenantPartitions,
        and the owner tag routes the fault seam's tenant filter)."""
        if t.live_store is None:
            from ..storage import LivenessEdgeStore, LivenessInstruments

            t.live_store = LivenessEdgeStore(
                instruments=LivenessInstruments(
                    "pack", registry=t.registry
                ),
                owner=t.key,
            )
        return t.live_store

    def _tenant_liveness(self, t: _Tenant) -> None:
        """Finish-time per-tenant device-liveness verdict: the shared
        trim/reach pass over THIS tenant's edge partition (unsalted
        fps), so the packed verdict is exactly the solo run's."""
        from .device_liveness import analyze_liveness

        t.live_paths, t.live_outcomes = analyze_liveness(
            self._model,
            self._properties,
            self._ebit,
            self._live_tenant_store(t),
            self._host_fp,
            set(t.discoveries_fp),
            tracer=self._tracer,
        )
        self._tracer.instant(
            "pack.tenant_liveness", tenant=str(t.key),
            verdicts={
                k: v.get("verdict") for k, v in t.live_outcomes.items()
            },
        )

    def _ensure_capacity(self, incoming: int) -> None:
        need = self._l0 + incoming
        if need <= _MAX_LOAD * self._capacity:
            return
        self._grow(_pow2ceil(int(need / _MAX_LOAD)))

    def _finish_idle(self) -> List[object]:
        """Completes tenants with no pending lanes. The pre-scan below
        is only an optimization (skip the pipeline barrier while every
        tenant clearly has work); the DECIDING scan runs strictly AFTER
        the barrier. Checking ``pending()`` after snapshotting the
        candidates is the one intermittent bug this engine has shipped:
        a verdict completing in between pushes a tenant's survivors yet
        leaves a stale pending==0 snapshot, and with the pipe now idle
        the recheck never ran — the tenant finished with work still
        queued. After a barrier (or an observed-idle pipe), every push
        is visible, so the deciding scan is exact."""
        def scan():
            return [
                t
                for t in self.tenants()
                if not t.finished and not t.faulted
                and (t.done or t.lanes.pending == 0)
            ]

        if not scan():
            return []
        if self._pipe is not None and self._pipe.pending():
            self._pipe.drain()
        candidates = scan()
        if not candidates:
            return []
        finished = []
        for t in candidates:
            t.done = True
            if self._live_enabled and not t.faulted:
                # The tenant's exploration is complete: decide its
                # `eventually` verdicts before is_done() can observe
                # the finish (the service finalizes right after).
                self._tenant_liveness(t)
            t.finished = True
            t.lanes.clear()
            self._finish_view(t)
            finished.append(t.key)
            self._tracer.instant(
                "pack.tenant_done", tenant=str(t.key),
                unique=t.unique_count,
            )
        return finished

    def release(self, key) -> None:
        """Frees a COMPLETED tenant's slot (keep the view; its counters
        and parent store live on the view, not the slot)."""
        t = self._by_key.get(key)
        if t is None:
            return
        if not t.finished:
            raise RuntimeError(
                "release() is for completed tenants; use drop() to "
                "preempt a live one"
            )
        self._slots[t.slot] = None
        del self._by_key[t.key]
        self._partitions.drop(t.key)

    def close(self) -> None:
        if self._pipe is not None:
            try:
                self._pipe.drain()
            except Exception:  # noqa: BLE001 - poisoned: already surfaced
                pass
            finally:
                self._pipe.close()


def _pad_rows(x: np.ndarray, target: int) -> np.ndarray:
    n = len(x)
    if n == target:
        return x
    widths = [(0, target - n)] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, widths)


def _concat_blocks(blocks, leaf_specs, treedef):
    """Dense concatenation of lane blocks (None when empty)."""
    if not blocks:
        return None
    out = {
        k: np.concatenate([b[k] for b in blocks])
        for k in ("hi", "lo", "ebits", "depth")
    }
    leaves = [
        np.concatenate(
            [jax.tree_util.tree_leaves(b["states"])[i] for b in blocks]
        )
        for i in range(len(leaf_specs))
    ]
    out["states"] = jax.tree_util.tree_unflatten(treedef, leaves)
    return out
