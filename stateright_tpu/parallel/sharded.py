"""Multi-device BFS: fingerprint-sharded visited set + all-to-all key routing.

This is the scale-out design SURVEY §2.8 calls for: where the reference
shares one concurrent ``DashMap`` between N worker threads
(``/root/reference/src/checker/bfs.rs:28-29``, ``src/job_market.rs``), here
every device in a ``jax.sharding.Mesh`` owns

- a *shard of the visited hash set*, keyed by fingerprint range
  (``owner = hi mod n_shards``), and
- a *slice of the frontier*, which is purely data-parallel (any state may
  live on any device — only the visited set is fingerprint-addressed).

One wave, inside ``shard_map`` over mesh axis ``"fp"``:

1. each device expands its local frontier slice (F_loc × A grid) and
   fingerprints the candidates — pure local compute, MXU/VPU friendly;
2. candidate *keys* (8 bytes each — never the packed states) are bucketed
   by owner shard and exchanged with ``lax.all_to_all`` over ICI;
3. each owner sort-dedups the keys it received, claim-inserts them into its
   hash-set shard, and returns per-key fresh flags by the reverse
   ``all_to_all``;
4. senders compact their fresh candidates into the next local frontier
   slice — new states never move off the device that generated them.

The host loop only moves compacted *new-state* batches through a queue
(the host↔device frontier scheduler replacing the reference's
``JobBroker``) and ingests (child fp, parent fp) pairs for TLC-style path
reconstruction, identical to the single-device ``TpuBfsChecker``.

Multi-host: the same program runs under ``jax.distributed`` — the mesh
spans hosts, all-to-all rides ICI within a slice and DCN across slices,
every controller executes this same host loop in lockstep (host pulls
allgather; checkpoints written by process 0). Exercised end-to-end on a
2-process mesh in ``tests/test_multihost.py``.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.batch import BatchableModel
from ..core.model import Expectation
from ..core.path import Path
from ..native import make_fingerprint_store
from ..ops import comm_sieve
from ..ops.fingerprint import fingerprint_state, fp64_pairs, fp_to_int
from ..ops.hashset import MAX_PROBES, hashset_insert
from ..ops.ring import ring_export, ring_push, ring_rows, ring_take
from ..telemetry import (
    CheckTracer,
    CommsInstruments,
    WaveInstruments,
    metrics_registry,
)
from .base_mesh import default_mesh
from ..checker.base import Checker
from ..checker.pipeline import HostPipeline
from ..checker.programs import Programs
from ..utils.faults import fault_point
from ..checker.tpu import (
    _AUTO_BUCKET_MIN_F,
    _DEFAULT_BUCKET_STEPS,
    _make_key_fn,
    atomic_pickle,
    bucket_for,
    bucket_ladder_widths,
    checkpoint_header,
    packed_model_digest,
    sym_key_scheme,
    validate_checkpoint_header,
)

_DEPTH_INF = (1 << 31) - 1
_U32_MAX = np.uint32(0xFFFFFFFF)
_MAX_LOAD = 0.5


def _pow2ceil(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _sort_dedup(hi, lo, active):
    """Sorts (hi, lo) keys, returns (shi, slo, sidx, unique_mask).

    Inactive lanes sort to the end (key = U32_MAX pair) and are excluded
    from ``unique_mask``.
    """
    m = hi.shape[0]
    shi = jnp.where(active, hi, _U32_MAX)
    slo = jnp.where(active, lo, _U32_MAX)
    shi, slo, sidx = jax.lax.sort(
        (shi, slo, jnp.arange(m, dtype=jnp.int32)), num_keys=2
    )
    uniq = jnp.concatenate(
        [jnp.ones((1,), bool), (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1])]
    )
    return shi, slo, sidx, active[sidx] & uniq


class ShardedTpuBfsChecker(Checker):
    """BFS over a device mesh; requires a ``BatchableModel``.

    ``frontier_per_device`` is the per-device frontier slice width (the
    global chunk is ``n_devices ×`` that); ``table_capacity_per_device``
    is each shard's initial hash-set size (grows by doubling + local
    rehash — keys never change owner, so rehash needs no communication).
    ``bucket_ladder`` is the occupancy-adaptive chunk-dispatch depth
    (power-of-two rungs below ``F_loc``; None auto-selects 4 when
    ``F_loc >= 512``, else fixed width; 0 forces fixed width); the
    wave-at-a-time path shrinks global chunks to ``n × bucket``.
    """

    def __init__(
        self,
        options,
        mesh: Optional[Mesh] = None,
        frontier_per_device: int = 1 << 10,
        table_capacity_per_device: int = 1 << 15,
        checkpoint_path=None,
        checkpoint_every_chunks=32,
        checkpoint_min_interval_s=0.0,
        resume_from=None,
        max_drain_waves=100_000,
        drain_log_factor=8,
        pool_factor=16,
        bucket_ladder=None,
        hbm_budget_mib=None,
        host_budget_mib=None,
        spill_dir=None,
        attribution=False,
        coverage=False,
        run_id=None,
        async_pipeline=False,
        liveness=None,
        wave_kernel="staged",
        aot_cache=None,
        aot_store=None,
        sieve=None,
        sieve_slots_per_device=None,
        sieve_bloom_bits=None,
        fleet=True,
    ):
        model = options.model
        if not isinstance(model, BatchableModel):
            raise TypeError(
                f"spawn_sharded_tpu_bfs requires a BatchableModel; "
                f"{type(model).__name__} does not implement the packed protocol"
            )
        # Honest capability surfacing (the single-device checker's
        # packing_reason pattern): there is no sharded fused path — the
        # Pallas megakernel fuses ONE device's wave into a single kernel
        # and cannot express the cross-shard all_to_all key exchange —
        # so asking for it refuses with the reason instead of silently
        # dispatching the staged wave under a label that lies.
        if wave_kernel not in ("staged", "fused"):
            raise ValueError(
                f"wave_kernel must be 'staged' or 'fused', got {wave_kernel!r}"
            )
        self._wave_kernel = "staged"
        self.wave_kernel_reason = (
            "wave_kernel='fused' has no sharded path: the fused Pallas "
            "megakernel runs one device's wave as a single kernel and "
            "cannot express the cross-shard all_to_all key exchange; "
            "use the single-device checker for the fused engine, or "
            "wave_kernel='staged' here"
            if wave_kernel == "fused"
            else None
        )
        if wave_kernel == "fused":
            raise ValueError(self.wave_kernel_reason)
        # Run identity (checking-as-a-service): own metrics registry +
        # run-stamped trace spans, mirroring TpuBfsChecker. Every span
        # carries this check's id and descends from its
        # ``sharded_bfs.check`` span.
        self.run_id = run_id
        self._registry = metrics_registry(run_id) if run_id else None
        self._tracer = CheckTracer(run_id)
        self._mesh = mesh if mesh is not None else default_mesh()
        n = self._mesh.devices.size
        self._n = n
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
        self._ebit: Dict[int, int] = {pi: b for b, pi in enumerate(eventually)}
        self._ebits0 = sum(1 << b for b in self._ebit.values())
        self._A = model.packed_action_count()
        self._F_loc = _pow2ceil(frontier_per_device)
        self._G = n * self._F_loc  # global frontier chunk width
        # Occupancy-adaptive chunk dispatch (wave-at-a-time path): global
        # chunks shrink to ``n × bucket`` where bucket is the smallest
        # per-device ladder rung holding the pending rows — the host pool
        # count is exact, so no transfer is needed to pick it. The deep
        # drain keeps fixed F_loc waves (its ring pops already compact
        # live lanes to a per-device dense prefix).
        if bucket_ladder is None:
            bucket_ladder = (
                _DEFAULT_BUCKET_STEPS
                if self._F_loc >= _AUTO_BUCKET_MIN_F
                else 0
            )
        if bucket_ladder < 0:
            raise ValueError(
                f"bucket_ladder must be >= 0, got {bucket_ladder}"
            )
        self._buckets = bucket_ladder_widths(self._F_loc, bucket_ladder)
        # Probing masks with (capacity - 1): non-pow2 would address only a
        # subset of rows.
        self._cap_loc = _pow2ceil(table_capacity_per_device)
        # Compression-and-sieve routing (README "Pod-scale sharding"):
        # sieve=None resolves OFF — the rung-ladder exchange traces one
        # branch per rung, a compile cost the many tiny sharded runs in
        # the test tier cannot afford; dedicated tests and the multichip
        # bench opt in explicitly. Results are bit-identical either way:
        # the receipt cache only drops lanes whose key it re-checked in
        # full, i.e. lanes the owner would have answered fresh=False.
        self._sieve = bool(sieve) if sieve is not None else False
        if sieve_slots_per_device is None:
            sieve_slots_per_device = min(1 << 16, self._cap_loc)
        self._sieve_slots = _pow2ceil(max(8, sieve_slots_per_device))
        if sieve_bloom_bits is None:
            # Sized for the resident population one shard can hold under
            # the load cap (the filter is flushed whenever that
            # population evicts), clamped to 1 MiB of bit-bytes.
            sieve_bloom_bits = comm_sieve.bloom_bits_for(
                min(int(_MAX_LOAD * self._cap_loc), 1 << 20)
            )
        if sieve_bloom_bits & (sieve_bloom_bits - 1):
            raise ValueError(
                f"sieve_bloom_bits must be a power of two, got "
                f"{sieve_bloom_bits}"
            )
        self._sieve_bits = sieve_bloom_bits
        self._sieve_dev = None
        self._last_comms = None
        # Key lanes every all-to-all of this run carried, summed over
        # devices: each drain and wave span records what it added.
        self._exchange_lanes = 0
        self._last_comms_per = None
        # Fleet skew forensics (telemetry/fleet.py): per-shard per-wave
        # rows ride the existing out_specs=P("fp") pulls (five extra
        # int32 scalars per shard per wave) and host tier walls are
        # attributed per shard. Opt-out (`fleet=False`) — the fold is
        # host-side numpy over n-length vectors and never feeds back
        # into the search, so bit-identity holds either way.
        self._fleet_on = bool(fleet)
        self._fi = None
        self._fleet_lock = threading.Lock()
        self._fleet_probe_s = [0.0] * n
        self._fleet_evict_s = [0.0] * n
        self._fleet_evict_bytes = [0] * n
        self._visitor = options._visitor
        self._target_state_count: Optional[int] = options._target_state_count
        self._depth_cap = options._target_max_depth or _DEPTH_INF
        self._setup_lasso(options)

        self._checkpoint_path = checkpoint_path
        # Counts dequeued global chunks; the time floor keeps wide frontiers
        # from checkpointing (full parent-map export + pickle) back to back.
        self._checkpoint_every = max(1, checkpoint_every_chunks)
        self._checkpoint_min_interval = checkpoint_min_interval_s
        self._resume_from = resume_from
        # Deep drain (device frontier rings; see _deep_drain_local). As in
        # TpuBfsChecker: 1 disables, and durability caps waves-per-drain.
        self._max_drain_waves = max(1, max_drain_waves)
        if checkpoint_path is not None:
            self._max_drain_waves = min(
                self._max_drain_waves, max(2, checkpoint_every_chunks)
            )
        self._Ll = max(
            max(1, drain_log_factor) * self._F_loc, self._F_loc * self._A
        )
        self._PCl = _pow2ceil(
            max(max(1, pool_factor) * self._F_loc, self._F_loc * self._A)
        )

        # Out-of-core tiering (stateright_tpu.storage): ``hbm_budget_mib``
        # hard-caps each shard's table; growth past the cap drains every
        # shard to its own host tier (fps are mesh-partitioned by
        # ``hi % n``, so runs stay shard-local), and harvested fresh rows
        # batch-probe the tiers at the wave's host exit. Probes take the
        # union over all stores (Bloom filters make non-owner probes O(1)
        # rejects), which keeps elastic restores — where ownership
        # re-routes — correct for free. ``host_budget_mib`` divides
        # evenly across the shards' stores.
        from ..storage import (
            StorageInstruments,
            TieredVisitedStore,
            max_table_rows_for_budget,
            validate_budget_knobs,
        )

        validate_budget_knobs(hbm_budget_mib, host_budget_mib, spill_dir)
        self._tiers = []
        self._si = None
        self._max_cap_loc = None
        if hbm_budget_mib is not None:
            max_cap = max_table_rows_for_budget(hbm_budget_mib)
            # A freshly-evicted shard must absorb one wave of received
            # keys under the load cap. Keys are uniform over shards
            # (fingerprints), so the floor is the balanced share
            # (F_loc×A) with 4x skew slack — the true worst case (every
            # key routing to one shard) is astronomically unlikely and
            # is caught by the eviction-retry guard in the wave loop
            # instead of pricing every budget for it.
            worst = 4 * self._F_loc * self._A
            min_cap = _pow2ceil(int(worst / _MAX_LOAD) + 1)
            if max_cap < min_cap:
                raise ValueError(
                    f"hbm_budget_mib={hbm_budget_mib} allows a per-shard "
                    f"table of {max_cap} rows, but one wave "
                    f"({worst} routed keys at 4x skew) needs at least "
                    f"{min_cap}; raise the budget or shrink "
                    "frontier_per_device"
                )
            self._max_cap_loc = max_cap
            self._cap_loc = min(self._cap_loc, max_cap)
            self._si = StorageInstruments(
                "sharded_bfs", registry=self._registry
            )
            self._tiers = [
                TieredVisitedStore(
                    host_budget_mib=(
                        host_budget_mib / n
                        if host_budget_mib is not None
                        else None
                    ),
                    spill_dir=spill_dir,
                    instruments=self._si,
                    shard=d,
                    tracer=self._tracer,
                    # Fault-attribution tag (utils/faults.py): lets a
                    # chaos spec stall/kill exactly one shard's host
                    # tier — the injected-straggler seam the fleet skew
                    # forensics are tested against (tests/test_fleet.py).
                    owner=f"shard-{d}",
                )
                for d in range(n)
            ]
            # Out-of-core needs the per-wave host probe, which only the
            # wave-at-a-time path performs.
            self._max_drain_waves = 1
        # Keys currently resident across the shard tables (== unique_count
        # until the first eviction).
        self._l0_count = 0
        self._wave_stale = 0

        self._state_count = 0
        self._unique_count = 0
        self._max_depth = 0
        self._discoveries_fp: Dict[str, int] = {}
        self._wave_log: List = []
        # Under symmetry: the u64 visited-set keys claimed so far (the
        # checkpoint rebuild needs them; original fps cannot be re-keyed).
        self._key_log: List = []
        self._store = make_fingerprint_store()
        self._ingested = 0
        self._ingest_lock = threading.Lock()
        self._done_event = threading.Event()
        self._error: Optional[BaseException] = None
        # Preemption (checking-as-a-service): wave/drain-boundary yield
        # points drain the run into an in-memory checkpoint payload —
        # same API as TpuBfsChecker (see checker/base.py).
        self._preempt_event = threading.Event()
        self._preempt_payload: Optional[dict] = None
        # Async pipelined wave engine (README "Async pipeline"; see
        # TpuBfsChecker for the full design note). Sharded twist: the
        # host pool COALESCES rows into chunks, so the loop may only
        # dispatch ahead of in-flight verdicts while the pool already
        # holds a full chunk without them — below that, the epoch
        # barrier restores the synchronous composition (the partial
        # overlap is exactly the wide-frontier regime where the probe
        # is expensive). The pool therefore gains a lock: the worker
        # appends survivors while the checker thread slices chunks.
        self._async = bool(async_pipeline)
        if self._async and self._visitor is not None:
            raise ValueError(
                "async_pipeline is incompatible with a visitor: per-chunk "
                "callbacks reconstruct paths through verdicts the "
                "pipeline defers; drop the visitor or run synchronously"
            )
        if self._async and jax.process_count() > 1:
            raise ValueError(
                "async_pipeline is single-controller only: deferred "
                "verdicts issue process_allgather collectives from the "
                "worker thread, which cannot be ordered against the "
                "checker thread's across processes"
            )
        self._pipe = (
            HostPipeline(name="sharded-bfs-host") if self._async else None
        )
        self._pool_lock = threading.Lock()
        # In-flight harvest verdicts (jobs that may still append pool
        # rows) — the coalescing barrier's predicate. Deferred
        # checkpoint pickles and evict absorbs never grow the pool, so
        # they must not re-serialize the loop (pool_lock-guarded).
        self._inflight_verdicts = 0

        self._shard = NamedSharding(self._mesh, P("fp"))
        self._replicated = NamedSharding(self._mesh, P())
        # Multi-controller (multi-host) mode: under ``jax.distributed`` the
        # mesh spans processes and device arrays are only partially
        # addressable from each host — host pulls must allgather.
        self._mp = jax.process_count() > 1
        # Buffer donation on the jitted collectives mirrors TpuBfsChecker:
        # the hash-table shards and pool rings are rebound to the returned
        # arrays by every caller, so the per-call copy of the largest
        # operands disappears. The export path (_jit_ring_export) is
        # deliberately NOT donated — checkpoints read the rings mid-run
        # and the pool must survive the call.
        # With the sieve on, the wave and deep drain carry two extra
        # sharded operands (receipt cache + Bloom filter) that are
        # donated and rebound every call, like the table.
        wave_in = (P("fp"),) * 7 + (P(),)
        wave_donate = (0,)
        deep_in = (P("fp"),) * 4 + (P(), P(), P())
        deep_donate = (0, 1)
        if self._sieve:
            wave_in = wave_in + (P("fp"), P("fp"))
            wave_donate = (0, 8, 9)
            deep_in = deep_in + (P("fp"), P("fp"))
            deep_donate = (0, 1, 7, 8)

        def local(fn, in_specs):
            return jax.shard_map(
                fn,
                mesh=self._mesh,
                in_specs=in_specs,
                out_specs=P("fp"),
                check_vma=False,
            )

        self._jit_wave = jax.jit(
            local(self._wave_local, wave_in), donate_argnums=wave_donate
        )
        self._jit_deep_drain = jax.jit(
            local(self._deep_drain_local, deep_in), donate_argnums=deep_donate
        )
        # Fingerprints go through the model's view hook (e.g. actor systems
        # exclude crash flags, mirroring the host state hash).
        self._fp_fn = model.packed_fingerprint
        # Visited/routing keys: orbit-minimum fingerprints under symmetry
        # reduction (see checker/tpu.py and core/batch.py).
        self._symmetry_enabled = options._symmetry is not None
        self._sym_scheme = sym_key_scheme(options._symmetry)
        self._key_fn = _make_key_fn(model, self._fp_fn, options._symmetry)
        # Device-native liveness (liveness="device"; see checker/tpu.py
        # and README "Trustworthy liveness"). Sharded twist: the edge
        # rows ride each wave's sharded output and are absorbed at the
        # harvest exit the wave already pays (the sharded drain has no
        # per-wave host exit to evict through, so liveness forces the
        # wave-at-a-time path — same clamp as out-of-core mode).
        from ..checker.device_liveness import validate_liveness_mode

        self._live = validate_liveness_mode(
            liveness,
            symmetry=self._symmetry_enabled,
            expand_fps=False,
            options=options,
        )
        self._live_enabled = self._live == "device" and bool(self._ebit)
        self._live_paths: Dict[str, Path] = {}
        self._live_outcomes: Dict[str, dict] = {}
        self._live_store = None
        if self._live_enabled:
            from ..storage import LivenessEdgeStore, LivenessInstruments

            self._max_drain_waves = 1
            self._live_ins = LivenessInstruments(
                "sharded_bfs", registry=self._registry
            )
            self._live_store = LivenessEdgeStore(
                instruments=self._live_ins, spill_dir=spill_dir,
                host_budget_mib=host_budget_mib,
            )

        # Telemetry: one span per host-visible wave/drain (see
        # stateright_tpu.telemetry); occupancy is global across shards.
        # (Tracer/registry already bound above — run_id-scoped when set.)
        self._wi = WaveInstruments("sharded_bfs", registry=self._registry)
        # Cross-shard exchange ledger — recorded sieve-on AND sieve-off
        # (the unsieved wave ships the full width), so A/B runs compare
        # lanes/bytes like for like.
        self._ci = CommsInstruments("sharded_bfs", registry=self._registry)
        if self._fleet_on:
            from ..telemetry.fleet import FleetInstruments

            self._fi = FleetInstruments(
                "sharded_bfs", n, registry=self._registry,
                hosts=jax.process_count(),
            )
        # Wave-timeline attribution (opt-in, telemetry/attribution.py):
        # same engine and phase names as TpuBfsChecker, prefixed
        # ``sharded_bfs`` — results stay bit-identical (fences change
        # pacing only).
        self._init_attribution("sharded_bfs", attribution)
        if self._attr is not None and self._async:
            self._attr.set_overlap_mode(True)
        # State-space cartography (opt-in, telemetry/coverage.py): the
        # same fused reductions as TpuBfsChecker, computed per shard
        # inside the wave/drain shard_maps and summed across the mesh at
        # the existing host exits. coverage=False traces no extra ops.
        self._init_coverage(
            "sharded_bfs", coverage, self._A,
            symmetry=self._symmetry_enabled,
        )
        self.donation_enabled = True
        # Every program runs as an AOT executable from ``_programs``:
        # waves keyed (table rows, chunk width), deep drains (table rows,
        # ring rows), the rest by their arguments. Checks under one
        # ``aot_cache`` share every program, the drains compiled for grown
        # tables included.
        self._programs = Programs(
            "sharded_bfs", self._tracer, signature=self._aot_signature(),
            aot_cache=aot_cache, aot_store=aot_store,
            registry=self._registry,
            compile_phase=lambda: self._phase("compile"),
            on_compile=self._charge_compile,
        )
        aot = self._programs.jit
        self._jit_insert = aot(
            "insert", local(self._insert_local, (P("fp"),) * 4),
            donate_argnums=(0,),
        )
        # Only the destination table (arg 1) can alias the output; the
        # old, smaller table is freed by the caller's rebind.
        self._jit_rehash = aot(
            "rehash", local(self._rehash_local, (P("fp"), P("fp"))),
            donate_argnums=(1,),
        )
        self._jit_ring_push = aot(
            "ring_push", local(self._push_local, (P("fp"),) * 4),
            donate_argnums=(0,),
        )
        self._jit_ring_export = aot(
            "ring_export", local(self._ring_export_local, (P("fp"),) * 3)
        )
        # Pre-sharded allocations (shape and dtype static).
        self._jit_zeros = aot(
            "zeros", jnp.zeros, static_argnums=(0, 1),
            out_shardings=self._shard,
        )
        self._jit_pool = aot(
            "pool", lambda rows: ring_rows(model, rows), static_argnums=(0,),
            out_shardings=self._shard,
        )
        self._jit_fp_batch = aot("fp_batch", jax.vmap(self._fp_fn))
        self._jit_key_batch = (
            aot("key_batch", self._key_fn)
            if self._symmetry_enabled
            else self._jit_fp_batch
        )
        self._jit_fp_single = aot("fp_single", self._fp_fn)
        self._jit_within = aot(
            "within", jax.vmap(model.packed_within_boundary)
        )

        self._handles = [
            threading.Thread(target=self._run, name="sharded-tpu-bfs", daemon=True)
        ]
        self._handles[0].start()

    # -- per-device kernels (inside shard_map) ----------------------------

    def _route_insert(self, table_loc, hi, lo, valid):
        """Key exchange + sharded claim-insert; returns
        (table, fresh, overflow, recv_uniq).

        ``hi/lo/valid`` are this device's local candidate keys (m lanes).
        ``fresh`` marks, per local lane, that *this* lane's key claimed a
        brand-new slot somewhere in the global set. Exactly one lane wins
        per distinct key across the whole mesh.
        """
        n = self._n
        m = hi.shape[0]
        owner = (hi % jnp.uint32(n)).astype(jnp.int32)

        # Vectorized owner bucketing: one stable sort groups lanes by owner
        # (invalid lanes to a sentinel bucket), the within-bucket offset is
        # index-minus-group-start via cummax, and three scatters place the
        # keys — compile cost stays flat as the mesh grows instead of
        # emitting n cumsum+scatter rounds.
        lanes = jnp.arange(m, dtype=jnp.int32)
        okey = jnp.where(valid, owner, n)
        okey_s, lane_s = jax.lax.sort((okey, lanes), num_keys=1)
        is_start = jnp.concatenate(
            [jnp.ones((1,), bool), okey_s[1:] != okey_s[:-1]]
        )
        group_start = jax.lax.cummax(jnp.where(is_start, lanes, 0))
        pos = lanes - group_start
        table_loc, fresh, _ack, overflow, recv_uniq = self._exchange_at(
            table_loc, hi[lane_s], lo[lane_s], lane_s, okey_s, pos, m, m,
            want_ack=False,
        )
        return table_loc, fresh, overflow, recv_uniq

    def _exchange_at(
        self, table_loc, hi_s, lo_s, lane_s, okey_s, pos, R, m,
        want_ack=False,
    ):
        """The owner exchange + claim-insert at per-destination width
        ``R`` (``R == m`` reproduces the historical full-width exchange
        op for op). Inputs are the owner-sorted keys with within-group
        offsets; outputs are per ORIGINAL lane.

        Returns ``(table, fresh, acked, overflow, recv_uniq)``.
        ``recv_uniq`` is the OWNER-side insert load: how many unique keys
        arrived at THIS shard's table in the exchange (the fleet skew
        ledger's hash-partition imbalance column — free, the dedup mask
        already exists). ``acked``
        (``want_ack=True`` — the sieved path) marks lanes whose key is
        provably resident at its owner after this exchange: claimed fresh
        OR already found, but NOT probe-cap overflow. That is exactly the
        receipt-cache admission condition — caching a pending
        (overflowed) lane would kill its retry after the host grows the
        table and lose the state. ``want_ack=False`` returns ``None`` for
        it and keeps the legacy bool flag exchange untouched.
        """
        n = self._n
        dest = jnp.where((okey_s < n) & (pos < R), okey_s * R + pos, n * R)
        send_hi = (
            jnp.zeros((n * R,), jnp.uint32)
            .at[dest]
            .set(hi_s, mode="drop")
            .reshape(n, R)
        )
        send_lo = (
            jnp.zeros((n * R,), jnp.uint32)
            .at[dest]
            .set(lo_s, mode="drop")
            .reshape(n, R)
        )
        src_slot = (
            jnp.full((n * R,), m, jnp.int32)
            .at[dest]
            .set(lane_s, mode="drop")
            .reshape(n, R)
        )

        recv_hi = jax.lax.all_to_all(
            send_hi, "fp", split_axis=0, concat_axis=0, tiled=True
        )
        recv_lo = jax.lax.all_to_all(
            send_lo, "fp", split_axis=0, concat_axis=0, tiled=True
        )

        rhi = recv_hi.reshape(n * R)
        rlo = recv_lo.reshape(n * R)
        # (0, 0) is the bucket padding sentinel; fingerprints are never (0,0).
        ractive = (rhi != 0) | (rlo != 0)
        shi, slo, sidx, uniq = _sort_dedup(rhi, rlo, ractive)
        table_loc, fresh_s, found_s, pending = hashset_insert(
            table_loc, shi, slo, uniq
        )
        overflow = pending.sum()
        recv_uniq = uniq.sum(dtype=jnp.int32)
        if want_ack:
            # Pack (fresh, resident) into one uint8 so the reverse
            # exchange stays a single collective.
            flags_s = fresh_s.astype(jnp.uint8) | (
                (fresh_s | found_s).astype(jnp.uint8) << 1
            )
            flags_r = (
                jnp.zeros((n * R,), jnp.uint8)
                .at[sidx]
                .set(flags_s)
                .reshape(n, R)
            )
            back = jax.lax.all_to_all(
                flags_r, "fp", split_axis=0, concat_axis=0, tiled=True
            ).reshape(-1)
            fl = (
                jnp.zeros((m,), jnp.uint8)
                .at[src_slot.reshape(-1)]
                .set(back, mode="drop")
            )
            return table_loc, (fl & 1) != 0, (fl & 2) != 0, overflow, recv_uniq
        # Un-sort fresh flags back to received order, then reverse-exchange.
        fresh_r = (
            jnp.zeros((n * R,), bool).at[sidx].set(fresh_s).reshape(n, R)
        )
        fresh_back = jax.lax.all_to_all(
            fresh_r, "fp", split_axis=0, concat_axis=0, tiled=True
        )
        fresh = (
            jnp.zeros((m,), bool)
            .at[src_slot.reshape(-1)]
            .set(fresh_back.reshape(-1), mode="drop")
        )
        return table_loc, fresh, None, overflow, recv_uniq

    def _comm_rungs(self, m):
        """Ascending per-destination exchange widths for an ``m``-lane
        wave: a base-4 ladder from 8 lanes up (8, 32, 128, ...) capped by
        the always-sufficient full width ``m`` (the bucket-ladder idiom
        from the chunk dispatcher). Base 4 bounds overshoot at 4x the
        survivor count while keeping the ``lax.switch`` branch count —
        each branch traces its own all_to_all pair — at log4(m). One
        mesh-agreed index (``pmax``) picks the rung, so peers never
        diverge on the collective shape."""
        rungs = []
        r = 8
        while r < m:
            rungs.append(r)
            r <<= 2
        return rungs + [m]

    def _route_insert_sieved(self, table_loc, hi, lo, valid, cache, bloom):
        """``_route_insert`` with the sieve + compact stages in front of
        the collective (ISSUE 17 tentpole; returns the updated sieve
        state and the wave's comms vector alongside).

        **Sieve** — the receipt cache re-checks the FULL key on a hit, so
        a kill is a proof this device already routed the key and its
        owner acked residency: the full-width exchange would answer
        ``fresh=False``, which is precisely what a dropped lane reports.
        No false positive exists to repair, so per-lane results are
        bit-identical by construction. The Bloom filter over the same
        routed keys never drops anything — it is the audited advisory
        layer: for a routed lane the owner's verdict IS an exact
        membership re-check, so ``bloom_hit & fresh`` counts true Bloom
        false positives with zero extra probes.

        **Compact** — survivors pack to a dense per-destination prefix
        and the exchange runs at the smallest ladder rung holding the
        mesh-max survivor count; every device takes the same
        ``lax.switch`` branch (the rung index is a ``pmax``), so the
        collectives inside the branches always match up.
        """
        n = self._n
        m = hi.shape[0]
        kill = comm_sieve.cache_probe(cache, hi, lo, valid)
        bhit = comm_sieve.bloom_probe(bloom, hi, lo)
        send = valid & ~kill

        owner = (hi % jnp.uint32(n)).astype(jnp.int32)
        lanes = jnp.arange(m, dtype=jnp.int32)
        okey = jnp.where(send, owner, n)
        okey_s, lane_s = jax.lax.sort((okey, lanes), num_keys=1)
        is_start = jnp.concatenate(
            [jnp.ones((1,), bool), okey_s[1:] != okey_s[:-1]]
        )
        group_start = jax.lax.cummax(jnp.where(is_start, lanes, 0))
        pos = lanes - group_start
        hi_s = hi[lane_s]
        lo_s = lo[lane_s]
        counts = jnp.zeros((n + 1,), jnp.int32).at[okey].add(1)
        need = jax.lax.pmax(counts[:n].max(), "fp")
        rungs = self._comm_rungs(m)
        if len(rungs) == 1:
            ridx = jnp.int32(0)
            table_loc, fresh, ack, overflow, recv_uniq = self._exchange_at(
                table_loc, hi_s, lo_s, lane_s, okey_s, pos, m, m,
                want_ack=True,
            )
        else:
            # Smallest rung >= need; the top rung is m >= any count.
            ridx = (need > jnp.asarray(rungs, jnp.int32)).sum().astype(
                jnp.int32
            )
            branches = [
                (
                    lambda R: lambda tbl, a, b, c, d, e: self._exchange_at(
                        tbl, a, b, c, d, e, R, m, want_ack=True
                    )
                )(R)
                for R in rungs
            ]
            table_loc, fresh, ack, overflow, recv_uniq = jax.lax.switch(
                ridx, branches, table_loc, hi_s, lo_s, lane_s, okey_s, pos
            )
        # Receipts: only owner-acked lanes (see _exchange_at) enter the
        # cache and filter — after this wave those keys ARE resident.
        acked = send & ack
        cache = comm_sieve.cache_insert(cache, hi, lo, acked)
        bloom = comm_sieve.bloom_insert(bloom, hi, lo, acked)
        shipped = n * jnp.asarray(rungs, jnp.int32)[ridx]
        comms = jnp.concatenate(
            [
                jnp.stack(
                    [
                        valid.sum(dtype=jnp.int32),
                        kill.sum(dtype=jnp.int32),
                        send.sum(dtype=jnp.int32),
                        (bhit & send).sum(dtype=jnp.int32),
                        # Exact Bloom FPs: hit, routed, owner says fresh.
                        (bhit & send & fresh).sum(dtype=jnp.int32),
                        shipped,
                    ]
                ),
                (jnp.arange(len(rungs), dtype=jnp.int32) == ridx).astype(
                    jnp.int32
                ),
            ]
        )
        return table_loc, fresh, overflow, cache, bloom, comms, recv_uniq

    def _insert_local(self, table, hi, lo, valid):
        """Standalone sharded insert (used to seed the initial states)."""
        table_loc, fresh, overflow, _recv = self._route_insert(
            table[0], hi, lo, valid
        )
        return {
            "table": table_loc[None],
            "fresh": fresh,
            "overflow": overflow[None],
        }

    def _wave_local(
        self, table, states, hi, lo, ebits, depth, mask, depth_cap,
        cache=None, bloom=None,
    ):
        """shard_map wrapper: unwraps the leading per-device axis, runs the
        wave core, and re-wraps scalars for ``out_specs=P("fp")``."""
        out = self._wave_core(
            table[0], states, hi, lo, ebits, depth, mask, depth_cap,
            cache=None if cache is None else cache[0],
            bloom=None if bloom is None else bloom[0],
        )
        wrapped = dict(out)
        wrapped["table"] = out["table"][None]
        for k in ("generated", "n_new", "overflow", "max_depth"):
            wrapped[k] = out[k][None]
        wrapped["comms"] = out["comms"][None]
        if self._fleet_on:
            wrapped["fleet"] = out["fleet"][None]
        if self._sieve:
            wrapped["sieve_cache"] = out["sieve_cache"][None]
            wrapped["sieve_bloom"] = out["sieve_bloom"][None]
        if self._properties:
            for k in ("prop_hit", "prop_hi", "prop_lo"):
                wrapped[k] = out[k][None]
        if self._cov is not None:
            wrapped["cov"] = out["cov"][None]
        if self._live_enabled:
            wrapped["live_n"] = out["live_n"][None]
        return wrapped

    def _wave_core(
        self, table_loc, states, hi, lo, ebits, depth, mask, depth_cap,
        cache=None, bloom=None,
    ):
        """One expansion wave on local (per-device) arrays: expand,
        fingerprint, pre-dedup, all-to-all claim-insert, compact. Scalars
        come back unwrapped; the deep drain and the wave-at-a-time wrapper
        share this."""
        model = self._model
        A = self._A
        F = hi.shape[0]  # local slice width
        B = F * A
        eval_mask = mask & (depth < depth_cap)

        cond_vals = [jax.vmap(c)(states) for c in self._conditions]
        ebits_after = ebits
        for pi, b in self._ebit.items():
            ebits_after = jnp.where(
                cond_vals[pi], ebits_after & ~jnp.uint32(1 << b), ebits_after
            )

        # packed_expand: per-class fast path where the model provides one.
        cand, cvalid = jax.vmap(model.packed_expand)(states)
        cvalid = cvalid & eval_mask[:, None]
        cvalid = cvalid & jax.vmap(jax.vmap(model.packed_within_boundary))(cand)
        generated = cvalid.sum(dtype=jnp.int32)
        terminal = eval_mask & ~cvalid.any(axis=1)

        cand_flat = jax.tree_util.tree_map(
            lambda x: x.reshape((B,) + x.shape[2:]), cand
        )
        cvalid_flat = cvalid.reshape(B)
        chi, clo = jax.vmap(self._fp_fn)(cand_flat)
        # Routing/visited keys (orbit-minimum fps under symmetry); frontier
        # rows and parent pointers keep the ORIGINAL fingerprints below.
        if self._symmetry_enabled:
            khi, klo = self._key_fn(cand_flat)
        else:
            khi, klo = chi, clo

        # Local pre-dedup: only one lane per distinct key is routed, so the
        # owner-side exchange carries no intra-device duplicates.
        _shi, _slo, sidx, uniq = _sort_dedup(khi, klo, cvalid_flat)
        route = jnp.zeros((B,), bool).at[sidx].set(uniq)
        if self._sieve:
            table_loc, fresh, overflow, cache, bloom, comms, recv_uniq = (
                self._route_insert_sieved(
                    table_loc, khi, klo, route, cache, bloom
                )
            )
        else:
            table_loc, fresh, overflow, recv_uniq = self._route_insert(
                table_loc, khi, klo, route
            )
            # Uniform comms vector (layout as _route_insert_sieved's):
            # the unsieved exchange ships the full n*B lanes per device
            # at a single full-width "rung" — emitted even sieve-off so
            # A/B runs compare ledgers like for like.
            comms = jnp.concatenate(
                [
                    jnp.zeros((5,), jnp.int32),
                    jnp.full((1,), self._n * B, jnp.int32),
                    jnp.ones((1,), jnp.int32),
                ]
            )

        # Compact fresh candidates into the local next-frontier slots.
        pos = jnp.cumsum(fresh.astype(jnp.int32)) - 1
        out_slot = jnp.where(fresh, pos, B)
        zi = jnp.zeros((B,), jnp.int32)
        zu = jnp.zeros((B,), jnp.uint32)
        src_idx = zi.at[out_slot].set(
            jnp.arange(B, dtype=jnp.int32), mode="drop"
        )
        parent_row = src_idx // A
        new_states = jax.tree_util.tree_map(
            lambda x: x[src_idx], cand_flat
        )
        out = {
            "table": table_loc,
            "generated": generated,
            "n_new": fresh.sum(dtype=jnp.int32),
            "overflow": overflow,
            "max_depth": jnp.max(jnp.where(mask, depth, 0)),
            "new_states": new_states,
            "new_hi": zu.at[out_slot].set(chi, mode="drop"),
            "new_lo": zu.at[out_slot].set(clo, mode="drop"),
            "new_ebits": ebits_after[parent_row]
            * (jnp.arange(B) < fresh.sum()),
            "new_depth": (depth[parent_row] + 1)
            * (jnp.arange(B) < fresh.sum()),
            "parent_hi": hi[parent_row] * (jnp.arange(B) < fresh.sum()),
            "parent_lo": lo[parent_row] * (jnp.arange(B) < fresh.sum()),
            "comms": comms,
        }
        if self._fleet_on:
            # Per-shard skew vector (telemetry/fleet.py FLEET_DEVICE_COLS
            # order); stacked per device by out_specs=P("fp") so the
            # controller sees the (n, 5) mesh view every pull. Write-only
            # telemetry — nothing reads it back into the search.
            out["fleet"] = jnp.stack(
                [
                    eval_mask.sum(dtype=jnp.int32),
                    generated,
                    fresh.sum(dtype=jnp.int32),
                    recv_uniq,
                    overflow.astype(jnp.int32),
                ]
            )
        if self._sieve:
            out["sieve_cache"] = cache
            out["sieve_bloom"] = bloom
        if self._symmetry_enabled:
            # Claimed visited-set keys, for checkpoint table rebuild.
            out["new_khi"] = zu.at[out_slot].set(khi, mode="drop")
            out["new_klo"] = zu.at[out_slot].set(klo, mode="drop")

        if self._live_enabled:
            # Condition-false edge + terminal rows for this shard's
            # lanes (checker/device_liveness.py) — compacted per shard,
            # pulled at the harvest exit the wave already pays. The
            # parent fps are this device's frontier slice; duplicates
            # across shards dedup in the host store.
            from ..checker.device_liveness import wave_edge_rows

            live_rows, live_n = wave_edge_rows(
                self._conditions, self._ebit, cond_vals, cand_flat,
                cvalid_flat, terminal, hi, lo, chi, clo, A,
            )
            for c, col in live_rows.items():
                out[f"live_{c}"] = col
            out["live_n"] = live_n

        hits, fhis, flos = [], [], []
        for i, p in enumerate(self._properties):
            if p.expectation == Expectation.ALWAYS:
                h = eval_mask & ~cond_vals[i]
            elif p.expectation == Expectation.SOMETIMES:
                h = eval_mask & cond_vals[i]
            else:
                b = self._ebit[i]
                h = terminal & (((ebits_after >> jnp.uint32(b)) & 1) == 1)
            idx = jnp.argmax(h)
            hits.append(h.any())
            fhis.append(hi[idx])
            flos.append(lo[idx])
        if self._properties:
            out["prop_hit"] = jnp.stack(hits)
            out["prop_hi"] = jnp.stack(fhis)
            out["prop_lo"] = jnp.stack(flos)
        if self._cov is not None:
            # Per-shard coverage reduction (telemetry/coverage.py): the
            # host sums the per-device vectors at its existing exits.
            # ``fresh`` marks this shard's GENERATOR-side claim winners,
            # so per-action fresh attribution stays exact across the
            # mesh exchange.
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
                else:
                    eb = self._ebit[pi]
                    exercised.append(
                        eval_mask
                        & (((ebits_after >> jnp.uint32(eb)) & 1) == 0)
                    )
            uniq_fp = uniq_key = None
            if self._symmetry_enabled:
                uniq_fp = self._cov_layout.count_distinct(
                    chi, clo, cvalid_flat
                )
                uniq_key = self._cov_layout.count_distinct(
                    khi, klo, cvalid_flat
                )
            lanes_b = jnp.arange(B, dtype=jnp.int32)
            out["cov"] = self._cov_layout.wave_reduce(
                eval_mask=eval_mask,
                cvalid=cvalid,
                fresh=fresh,
                lane_action=lanes_b % A,
                new_depth=depth[lanes_b // A] + 1,
                exercised=exercised,
                uniq_fp=uniq_fp,
                uniq_key=uniq_key,
            )
        return out

    def _rehash_local(self, old_table, new_table):
        old = old_table[0]
        new = new_table[0]
        active = (old[:, 0] != 0) | (old[:, 1] != 0)
        new, _fresh, _found, pending = hashset_insert(
            new, old[:, 0], old[:, 1], active
        )
        return {"table": new[None], "overflow": pending.sum()[None]}

    # -- deep drain: per-device frontier rings + all-to-all row balancing --

    def _ring_export_local(self, pool, head, count):
        """Local ring contents in FIFO order, mask attached (shard_map
        entry)."""
        return ring_export(pool, head[0], count[0], self._PCl)

    def _push_local(self, pool, head, count, rows):
        """shard_map entry: pushes a host chunk slice into the local ring."""
        pool, cnt = ring_push(
            pool, head[0], count[0], rows, rows["mask"], self._PCl
        )
        return {"pool": pool, "count": cnt[None]}

    def _balance_exchange(self, rows, n_new):
        """Round-robin all-to-all of the fresh (compacted-prefix) frontier
        rows: lane ``j`` goes to device ``j % n``. Fresh states are born on
        the device that expanded their parent; without this exchange a
        device that seeds the search keeps every descendant and the rest of
        the mesh idles. Round-robin balances by construction (each device
        receives within ±1 of the mean from every sender) with a fixed
        ``ceil(B/n)`` per-pair quota — no data-dependent shapes."""
        n = self._n
        B = rows["hi"].shape[0]
        q = -(-B // n)
        j = jnp.arange(B, dtype=jnp.int32)
        dest = jnp.where(j < n_new, (j % n) * q + j // n, n * q)

        def scat(x):
            z = jnp.zeros((n * q,) + x.shape[1:], x.dtype)
            return z.at[dest].set(x, mode="drop")

        def xch(x):
            return jax.lax.all_to_all(
                x.reshape((n, q) + x.shape[1:]),
                "fp",
                split_axis=0,
                concat_axis=0,
                tiled=True,
            ).reshape((n * q,) + x.shape[1:])

        send_mask = (
            jnp.zeros((n * q,), jnp.uint32)
            .at[dest]
            .set(jnp.ones((B,), jnp.uint32), mode="drop")
        )
        recv = {
            k: (
                jax.tree_util.tree_map(lambda x: xch(scat(x)), v)
                if k == "states"
                else xch(scat(v))
            )
            for k, v in rows.items()
        }
        recv_mask = xch(send_mask) != 0
        return recv, recv_mask

    def _drain_decide(self, out, count_after, log_n, budget, waves, gen_acc, undiscovered):
        """The globally-agreed continue flag (identical on every device —
        all inputs are psums or replicated)."""
        n_new = out["n_new"]
        g_n_new = jax.lax.psum(n_new, "fp")
        g_count = jax.lax.psum(count_after, "fp")
        g_overflow = jax.lax.psum(out["overflow"], "fp")
        ok = (g_n_new > 0) | (g_count > 0)
        ok &= g_overflow == 0
        if self._properties:
            hit = (out["prop_hit"] & undiscovered).any()
            ok &= jax.lax.psum(hit.astype(jnp.int32), "fp") == 0
        # Generator-side log room for appending this wave's fresh rows.
        no_log_room = (log_n + n_new > self._Ll).astype(jnp.int32)
        ok &= jax.lax.psum(no_log_room, "fp") == 0
        # Ring room for pushing the rows this device just received.
        recv_n = out["recv_mask"].sum(dtype=jnp.int32)
        no_ring_room = (count_after + recv_n > self._PCl).astype(jnp.int32)
        ok &= jax.lax.psum(no_ring_room, "fp") == 0
        ok &= budget - g_n_new >= jnp.int32(self._G * self._A)
        ok &= waves < self._max_drain_waves
        # gen_acc is a per-device local counter; the vote must be identical
        # on every device or one device exits the collective-bearing loop
        # while peers keep calling all_to_all (mesh hang). pmax (not psum:
        # a psum over many devices could itself wrap int32) exits when ANY
        # device's accumulator nears the wrap.
        ok &= jax.lax.pmax(gen_acc, "fp") < jnp.int32(1 << 30)
        return ok

    def _deep_drain_local(
        self, table, pool, head, count, undiscovered, budget, depth_cap,
        cache=None, bloom=None,
    ):
        """The sharded deep drain: consecutive waves inside one device
        ``while_loop``. Each iteration appends the previous wave's fresh
        rows to the parent-fp log (generator side), pushes the rows this
        device *received* in the balance exchange onto its ring, dequeues
        the next local frontier, and expands it. Exit is a global vote
        (psum) — log full, ring full, table budget, hash overflow, or an
        undiscovered property hit — mirroring ``TpuBfsChecker``'s deep
        drain with collectives in place of single-device checks."""
        F, A, n = self._F_loc, self._A, self._n
        B = F * A
        Ll = self._Ll

        table_loc = table[0]
        head0 = head[0]
        count0 = count[0]
        budget0 = budget
        cache0 = None if cache is None else cache[0]
        bloom0 = None if bloom is None else bloom[0]

        def wave_plus(tbl, fr, cache, bloom):
            out = self._wave_core(
                tbl,
                fr["states"],
                fr["hi"],
                fr["lo"],
                fr["ebits"],
                fr["depth"],
                fr["mask"],
                depth_cap,
                cache=cache,
                bloom=bloom,
            )
            rows = {
                "states": out["new_states"],
                "hi": out["new_hi"],
                "lo": out["new_lo"],
                "ebits": out["new_ebits"],
                "depth": out["new_depth"],
            }
            recv, recv_mask = self._balance_exchange(rows, out["n_new"])
            out["recv"] = recv
            out["recv_mask"] = recv_mask
            return out

        fr0, head1, count1 = ring_take(
            {k: pool[k] for k in ("states", "hi", "lo", "ebits", "depth")},
            head0,
            count0,
            self._PCl,
            F,
        )
        out0 = wave_plus(table_loc, fr0, cache0, bloom0)
        zl = jnp.zeros((Ll,), jnp.uint32)
        log0 = {
            "child_hi": zl,
            "child_lo": zl,
            "parent_hi": zl,
            "parent_lo": zl,
        }
        if self._symmetry_enabled:
            log0.update(key_hi=zl, key_lo=zl)
        carry = {
            "pool": {k: pool[k] for k in ("states", "hi", "lo", "ebits", "depth")},
            "head": head1,
            "count": count1,
            "frontier": fr0,
            "out": out0,
            "log": log0,
            "log_n": jnp.int32(0),
            "generated": jnp.int32(0),
            "consumed_unique": jnp.int32(0),
            "max_depth": jnp.int32(0),
            # int32 is fine here: lanes/wave × waves/drain stays well
            # under 2^31 for any budget-bounded drain, and the vector is
            # telemetry only — never feeds back into results.
            "comms_acc": jnp.zeros_like(out0["comms"]),
            "budget": budget0,
            **(
                {"fleet_acc": jnp.zeros_like(out0["fleet"])}
                if self._fleet_on
                else {}
            ),
            # The pre-loop wave (out0) counts against the cap too, so a
            # drain runs at most max_drain_waves waves total (the cap backs
            # the checkpoint-durability guarantee).
            "waves": jnp.int32(1),
            "go": self._drain_decide(
                out0, count1, jnp.int32(0), budget0, jnp.int32(1),
                jnp.int32(0), undiscovered,
            ),
        }
        if self._cov is not None:
            carry["cov_acc"] = jnp.zeros(
                (self._cov_layout.size,), jnp.int32
            )

        def cond(c):
            return c["go"]

        def body(c):
            o = c["out"]
            n_new = o["n_new"]
            lanes = jnp.arange(B, dtype=jnp.int32)
            valid = lanes < n_new
            slot = jnp.where(valid, c["log_n"] + lanes, Ll)
            log = dict(c["log"])
            log["child_hi"] = log["child_hi"].at[slot].set(
                o["new_hi"], mode="drop"
            )
            log["child_lo"] = log["child_lo"].at[slot].set(
                o["new_lo"], mode="drop"
            )
            log["parent_hi"] = log["parent_hi"].at[slot].set(
                o["parent_hi"], mode="drop"
            )
            log["parent_lo"] = log["parent_lo"].at[slot].set(
                o["parent_lo"], mode="drop"
            )
            if self._symmetry_enabled:
                log["key_hi"] = log["key_hi"].at[slot].set(
                    o["new_khi"], mode="drop"
                )
                log["key_lo"] = log["key_lo"].at[slot].set(
                    o["new_klo"], mode="drop"
                )
            pool, count = ring_push(
                c["pool"], c["head"], c["count"], o["recv"], o["recv_mask"],
                self._PCl,
            )
            frontier, head, count = ring_take(
                pool, c["head"], count, self._PCl, F
            )
            out = wave_plus(
                o["table"],
                frontier,
                o["sieve_cache"] if self._sieve else None,
                o["sieve_bloom"] if self._sieve else None,
            )
            log_n = c["log_n"] + n_new
            budget = c["budget"] - jax.lax.psum(n_new, "fp")
            waves = c["waves"] + 1
            gen_acc = c["generated"] + o["generated"]
            nxt = {
                "pool": pool,
                "head": head,
                "count": count,
                "frontier": frontier,
                "out": out,
                "log": log,
                "log_n": log_n,
                "generated": gen_acc,
                "consumed_unique": c["consumed_unique"] + n_new,
                "max_depth": jnp.maximum(c["max_depth"], o["max_depth"]),
                "comms_acc": c["comms_acc"] + o["comms"],
                "budget": budget,
                **(
                    {"fleet_acc": c["fleet_acc"] + o["fleet"]}
                    if self._fleet_on
                    else {}
                ),
                "waves": waves,
                "go": self._drain_decide(
                    out, count, log_n, budget, waves, gen_acc, undiscovered
                ),
            }
            if self._cov is not None:
                nxt["cov_acc"] = c["cov_acc"] + o["cov"]
            return nxt

        res = jax.lax.while_loop(cond, body, carry)
        o = res["out"]
        out = {
            "pool": res["pool"],
            "head": res["head"][None],
            "count": res["count"][None],
            "frontier": res["frontier"],
            "final": {
                "table": o["table"][None],
                "recv": o["recv"],
                "recv_mask": o["recv_mask"],
                "new_hi": o["new_hi"],
                "new_lo": o["new_lo"],
                "parent_hi": o["parent_hi"],
                "parent_lo": o["parent_lo"],
            },
            "drain_stats": jnp.stack(
                [
                    res["log_n"],
                    res["generated"],
                    res["consumed_unique"],
                    res["max_depth"],
                    res["waves"],
                    res["count"],
                    o["n_new"],
                    o["generated"],
                    o["overflow"],
                    o["max_depth"],
                ]
            )[None],
            # Consumed waves' exchange totals plus the final (unconsumed)
            # wave's — same accounting boundary as cov_acc below.
            "comms_acc": (res["comms_acc"] + o["comms"])[None],
        }
        if self._fleet_on:
            out["fleet_acc"] = (res["fleet_acc"] + o["fleet"])[None]
        if self._sieve:
            out["final"]["sieve_cache"] = o["sieve_cache"][None]
            out["final"]["sieve_bloom"] = o["sieve_bloom"][None]
        if self._symmetry_enabled:
            out["final"]["new_khi"] = o["new_khi"]
            out["final"]["new_klo"] = o["new_klo"]
        if self._cov is not None:
            # Consumed waves' accumulator plus the final (unconsumed)
            # wave: the final wave's expansion is complete device-side —
            # only its fresh rows' bookkeeping happens in _consume_final,
            # and an overflow retry there records fresh-based slices only.
            out["cov_acc"] = (res["cov_acc"] + o["cov"])[None]
        cols = ["child_hi", "child_lo", "parent_hi", "parent_lo"]
        if self._symmetry_enabled:
            cols += ["key_hi", "key_lo"]
        out["log_pack"] = jnp.stack([res["log"][c] for c in cols], axis=0)[
            None
        ]
        if self._properties:
            out["prop_hit"] = o["prop_hit"][None]
            out["prop_hi"] = o["prop_hi"][None]
            out["prop_lo"] = o["prop_lo"][None]
        return out

    # -- host side ---------------------------------------------------------

    def _run(self):
        try:
            # The check's root span: every span of this check descends
            # from it, on this thread and on its caller's (discoveries()).
            with self._tracer.span("sharded_bfs.check", devices=self._n) as check:
                self._tracer.root = check.id
                try:
                    self._explore()
                except BaseException as e:  # noqa: BLE001 - worker_error
                    self._error = e
                    self._abort_attribution()
                finally:
                    self._shutdown_pipeline()
                    self._finalize_coverage(set(self._discoveries_fp))
                    check.set(unique=self._unique_count)
        finally:
            self._done_event.set()

    def _new_table(self):
        # Allocate pre-sharded: materializing the global table on one device
        # first would OOM exactly when shards are sized near per-device HBM.
        # Each shard carries the probe apron the hashset ops expect.
        return self._jit_zeros(
            (self._n, self._cap_loc + MAX_PROBES, 2), jnp.uint32
        )

    def _new_sieve(self):
        """Cold (flushed) sieve state, pre-sharded: one receipt cache and
        one Bloom filter per device. Cold is always safe — kills only
        become possible again as keys are re-routed and re-acked."""
        return (
            self._jit_zeros((self._n, self._sieve_slots, 2), jnp.uint32),
            self._jit_zeros((self._n, self._sieve_bits), jnp.uint8),
        )

    def _grow_table(self, table, min_cap_loc, defer_evict=False):
        """Grows (or, under an HBM budget, evicts) every shard's table.
        ``defer_evict=True`` — async wave loop only — hands the tier
        absorbs to the pipeline worker; the restore path keeps them
        synchronous (it probes the tiers from the checker thread)."""
        if (
            self._max_cap_loc is not None
            and min_cap_loc > self._max_cap_loc
        ):
            return self._evict_shards(table, defer=defer_evict)
        while self._cap_loc < min_cap_loc:
            self._cap_loc *= 2
        while True:
            with self._phase("table_grow"):
                out = self._jit_rehash(table, self._new_table())
                overflowed = int(self._pull(out["overflow"]).sum())
            if not overflowed:
                break
            # Probe-cap overflow during rehash costs capacity (retry at
            # the next doubling), never the run; under a budget the next
            # doubling may not exist — evict instead.
            self._cap_loc *= 2
            if (
                self._max_cap_loc is not None
                and self._cap_loc > self._max_cap_loc
            ):
                return self._evict_shards(table, defer=defer_evict)
        return out["table"]

    def _record_placement(self, table):
        """Where the visited set's shards sit at run end, read from
        ``addressable_shards``: one ``(device, shard shape)`` per shard."""
        self.visited_placement = [
            (s.device, s.data.shape) for s in table.addressable_shards
        ]

    def _audit_table(self, table):
        """Run-end probe-length audit over every shard's table (summed —
        the shards share one hash scheme, so one distribution describes
        them all). Attribution mode only: the pull is a full-table read."""
        if self._attr is None:
            return
        from ..ops.hashset import hashset_probe_length_counts

        tab = self._pull(table)  # (n, cap_loc + apron, 2)
        counts = None
        for d in range(self._n):
            c = hashset_probe_length_counts(tab[d])
            counts = c if counts is None else counts + c
        self._attr.observe_probe_lengths(counts)

    def _tier_active(self) -> bool:
        return any(not t.is_empty() for t in self._tiers)

    def _evict_shards(self, table, defer=False):
        """Budget-capped growth: every shard's table drains to its own
        host tier (keys stay mesh-partitioned) and the sharded set
        resets at the budget cap. ``defer=True`` (async wave loop): the
        table pull + reset stay device-serial here; the per-shard
        absorbs ride the pipeline worker in shard order, fenced FIFO
        between the surrounding wave verdicts (see TpuBfsChecker.
        _evict_l0)."""
        with self._phase("evict"):
            if self._mp:
                # Compress stage (ISSUE 17): each process delta-encodes
                # ITS shards' live keys with the storage/runs.py wire
                # codec and the hosts exchange the compressed buffers —
                # a few bytes per key over DCN instead of allgathering
                # 8 B for every table slot, empty or not.
                shard_keys = self._allgather_evicted_keys(table)
            else:
                tab = self._pull(table)  # (n, cap_loc + apron, 2)
                shard_keys = []
                for d in range(self._n):
                    sh = tab[d]
                    live = (sh[:, 0] != 0) | (sh[:, 1] != 0)
                    keys = (
                        sh[live, 0].astype(np.uint64) << np.uint64(32)
                    ) | sh[live, 1].astype(np.uint64)
                    shard_keys.append(keys)
            if defer and self._pipe is not None:
                self._pipe.submit(
                    lambda ks=shard_keys: self._evict_absorb(ks)
                )
            else:
                for d, keys in enumerate(shard_keys):
                    t0 = time.perf_counter()
                    self._tiers[d].evict(keys)
                    self._fleet_note_evict(
                        d, time.perf_counter() - t0, keys.nbytes
                    )
            self._cap_loc = self._max_cap_loc
            self._l0_count = 0
            self._si.set_l0(0)
            if self._sieve and self._sieve_dev is not None:
                # Flush: receipts must only cover keys resident in the
                # DEVICE tables. An evicted key re-routed later gets
                # fresh=True from the unsieved exchange (the host-side
                # tier probe filters it); a stale receipt would kill
                # that lane and diverge from the sieve-off run.
                self._sieve_dev = self._new_sieve()
            return self._new_table()

    def _allgather_evicted_keys(self, table):
        """Multi-controller eviction exchange: local shard-key extraction
        plus a delta-compressed two-step allgather (lengths, then padded
        byte rows). Every process returns the identical per-shard sorted
        key lists, keeping the tier evictions SPMD across hosts."""
        from jax.experimental import multihost_utils

        from ..storage.runs import decode_sorted_fps, encode_sorted_fps

        n = self._n
        bufs = [b""] * n
        for sh in table.addressable_shards:
            d = sh.index[0].start or 0
            data = np.asarray(sh.data)[0]  # (cap_loc + apron, 2)
            live = (data[:, 0] != 0) | (data[:, 1] != 0)
            keys = (
                data[live, 0].astype(np.uint64) << np.uint64(32)
            ) | data[live, 1].astype(np.uint64)
            keys.sort()
            bufs[d] = encode_sorted_fps(keys)
        lens = np.array([len(b) for b in bufs], np.int64)
        all_lens = np.asarray(
            multihost_utils.process_allgather(lens)
        ).reshape(-1, n)
        width = max(1, int(all_lens.max()))
        pad = np.zeros((n, width), np.uint8)
        for d, b in enumerate(bufs):
            pad[d, : len(b)] = np.frombuffer(b, np.uint8)
        all_bufs = np.asarray(
            multihost_utils.process_allgather(pad)
        ).reshape(-1, n, width)
        shard_keys = []
        wire_bytes = 0
        for d in range(n):
            # Exactly one process owns shard d (its row is the only
            # non-empty one; an empty shard still carries the codec
            # header, so ownership is unambiguous).
            p = int(all_lens[:, d].argmax())
            ln = int(all_lens[p, d])
            shard_keys.append(decode_sorted_fps(all_bufs[p, d, :ln].tobytes()))
            wire_bytes += ln
        self._ci.evict_wire_bytes.inc(wire_bytes)
        self._tracer.instant(
            "sharded_bfs.evict_wire",
            bytes=wire_bytes,
            raw_bytes=int(table.shape[0]) * int(table.shape[1]) * 8,
            keys=int(sum(len(k) for k in shard_keys)),
        )
        return shard_keys

    def _evict_absorb(self, shard_keys):
        """Pipeline-worker half of a deferred eviction (all shards)."""
        with self._phase_overlapped("evict"):
            for d, keys in enumerate(shard_keys):
                t0 = time.perf_counter()
                self._tiers[d].evict(keys)
                self._fleet_note_evict(
                    d, time.perf_counter() - t0, keys.nbytes
                )

    def _probe_tiers(self, keys):
        """Union membership over every shard's store (L1 then L2 inside
        each; Bloom filters reject non-owner probes in O(1))."""
        found = np.zeros(len(keys), bool)
        for d, t in enumerate(self._tiers):
            rem = np.flatnonzero(~found)
            if not len(rem):
                break
            t0 = time.perf_counter()
            found[rem] = t.probe(keys[rem])
            if self._fleet_on:
                with self._fleet_lock:
                    self._fleet_probe_s[d] += time.perf_counter() - t0
        return found

    def _fleet_note_evict(self, d, seconds, nbytes):
        """Attributes one shard's tier-evict wall/bytes to the fleet
        ledger (called from both the sync loop and the pipeline worker)."""
        if not self._fleet_on:
            return
        with self._fleet_lock:
            self._fleet_evict_s[d] += seconds
            self._fleet_evict_bytes[d] += int(nbytes)

    def _pull(self, x):
        """A numpy view of a device array. Multi-controller: the array's
        shards live on several hosts, so gather them first (every process
        runs this same host loop in lockstep — SPMD over hosts — and gets
        identical values, keeping all host-side decisions consistent)."""
        if self._mp:
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
        return np.asarray(x)

    def _put_sharded(self, x):
        """One host value onto the ``"fp"``-sharded layout. Every process
        passes the identical value (SPMD over hosts), so each one
        materializes just its addressable shards from it — device_put of
        an uncommitted array onto a non-fully-addressable sharding would
        instead broadcast-and-compare the full value through the
        coordination mesh per leaf per wave (jax's assert_equal guard),
        a collective storm the gloo DCN stand-in cannot keep in lockstep
        with the wave loop's own exchanges."""
        if self._mp:
            arr = np.asarray(x)
            return jax.make_array_from_callback(
                arr.shape, self._shard, lambda idx, a=arr: a[idx]
            )
        return jax.device_put(jnp.asarray(x), self._shard)

    def _put_chunk(self, arrs):
        return jax.tree_util.tree_map(self._put_sharded, arrs)

    # The host pool is a deque of harvested row-batches; only the rows that
    # feed the next chunk are ever copied (a single running array would cost
    # O(frontier²/G) re-concatenation on big frontiers).

    @staticmethod
    def _rows_slice(batch, lo, hi):
        return {
            k: (
                jax.tree_util.tree_map(lambda x: x[lo:hi], v)
                if k == "states"
                else v[lo:hi]
            )
            for k, v in batch.items()
        }

    def _pool_append(self, rows):
        n = rows["hi"].shape[0]
        if n:
            # Locked: in async mode the pipeline worker appends
            # survivors while the checker thread slices chunks.
            with self._pool_lock:
                self._pool.append(rows)
                self._pool_count += n

    def _pool_take(self, width):
        """Pops up to ``width`` rows, padding to exactly ``width``."""
        parts = []
        got = 0
        with self._pool_lock:
            while got < width and self._pool:
                batch = self._pool.popleft()
                n = batch["hi"].shape[0]
                if got + n > width:
                    keep = width - got
                    self._pool.appendleft(self._rows_slice(batch, keep, n))
                    batch = self._rows_slice(batch, 0, keep)
                    n = keep
                parts.append(batch)
                got += n
            self._pool_count -= got

        def cat_pad(*xs):
            out = np.concatenate(xs) if len(xs) > 1 else np.asarray(xs[0])
            if out.shape[0] < width:
                pad = [(0, width - out.shape[0])] + [(0, 0)] * (out.ndim - 1)
                out = np.pad(out, pad)
            return out

        chunk = {
            k: (
                jax.tree_util.tree_map(cat_pad, *(p[k] for p in parts))
                if k == "states"
                else cat_pad(*(p[k] for p in parts))
            )
            for k in parts[0]
        }
        # The chunk splits into n contiguous per-device slices; interleave
        # real rows round-robin so a short chunk (got < width) gives every
        # shard ~got/n active lanes instead of idling the tail devices.
        n = self._n
        per = width // n
        dest = np.arange(width)
        src = (dest % per) * n + dest // per
        chunk = {
            k: (
                jax.tree_util.tree_map(lambda x: x[src], v)
                if k == "states"
                else v[src]
            )
            for k, v in chunk.items()
        }
        chunk["mask"] = src < got
        return chunk

    def _explore(self):
        self._t_start = time.perf_counter()
        # Wall-clock burned before the first drain/wave could run —
        # dominated by XLA compilation; benchmarks subtract it to report
        # steady-state rates (parity with TpuBfsChecker.warmup_seconds).
        self.warmup_seconds: Optional[float] = None
        self.visited_placement = None
        self._pool = deque()
        self._pool_count = 0
        if self._resume_from is not None:
            table = self._restore(self._resume_from)
        else:
            table = self._seed()
        if self._sieve:
            # Cold sieve at run start — seed and resume alike. Receipts
            # only ever accumulate from keys THIS run routed and the
            # owner acked, which is the invariant the bit-identity
            # argument rests on; cold is always safe (no kills).
            self._sieve_dev = self._new_sieve()
        depth_cap = jnp.int32(self._depth_cap)
        # Deep drain is off for visitors, target counts, and depth caps:
        # ring scheduling is only approximately global-FIFO across devices,
        # so a depth-capped run could first reach a state via a longer path
        # and prune expansions a strict BFS would keep. (Without a cap the
        # visited SET is order-independent — counts stay exact.) The same
        # approximation means that even UNCAPPED sharded deep runs report
        # depth labels at first-claim: ``max_depth()`` and discovery-path
        # lengths are upper bounds on the true BFS values (the host,
        # single-device, and reference threaded-BFS checkers are the
        # minimal-depth yardstick); counts and property verdicts are exact
        # either way.
        if (
            self._max_drain_waves > 1
            and self._visitor is None
            and self._target_state_count is None
            and self._depth_cap == _DEPTH_INF
            # A resumed out-of-core run needs the per-wave host probe.
            and not (self._tiers and self._tier_active())
        ):
            self._explore_deep(table, depth_cap)
        else:
            self._explore_waves(table, depth_cap)
        # Sound `eventually` verdicts (liveness="device"): the shared
        # trim/reach pass over the harvested edge relation.
        self._run_liveness_analysis("sharded_bfs")

    def _explore_waves(self, table, depth_cap):
        """Wave-at-a-time host loop. With ``async_pipeline=True`` the
        harvest verdict (row pulls, tier probe, survivor re-pooling)
        rides the pipeline worker while the device runs the next chunk.
        The sharded pool COALESCES rows into chunks, so the loop only
        runs ahead of in-flight verdicts while the pool already holds a
        full chunk without them — the head ``G`` rows and the bucket
        choice are then invariant to tail appends, keeping the
        dispatched sequence bit-identical to the synchronous path's;
        below a full chunk the epoch barrier restores the synchronous
        composition exactly."""
        props = self._properties
        n, G, A = self._n, self._G, self._A
        pipe = self._pipe

        chunks = 0
        last_checkpoint = time.perf_counter()
        while True:
            if (
                pipe is not None
                and self._inflight_verdicts > 0
                and self._pool_count < G
            ):
                # Coalescing barrier (see docstring): in-flight harvest
                # verdicts may shape the next chunk — wait for them.
                # Keyed on verdicts, not pipe.pending(): a deferred
                # checkpoint pickle or evict absorb cannot add pool
                # rows, and draining on those would re-serialize the
                # exact work the deferral hides.
                pipe.drain()
            if not self._pool_count:
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
                # Wave-granular yield: the host pool IS the whole
                # remaining frontier here (no chunk in flight between
                # iterations) once the pending verdicts land, so the
                # checkpoint payload captures the run exactly and the
                # resume is bit-identical.
                if pipe is not None:
                    pipe.drain()
                self._preempt_payload = self.checkpoint_payload(
                    list(self._pool)
                )
                self._tracer.instant(
                    "sharded_bfs.preempted", batches=len(self._pool),
                    mode="wave",
                )
                return
            # Attribution window over the whole iteration (checkpoint +
            # pre-grow + dispatch + harvest). No early exit lives inside
            # it, so a plain with-block is exact; an exception unwinds
            # the window like any context manager.
            with self._wave_window():
                if (
                    self._checkpoint_path is not None
                    and chunks
                    and chunks % self._checkpoint_every == 0
                    and (time.perf_counter() - last_checkpoint)
                    >= self._checkpoint_min_interval
                ):
                    with self._phase("checkpoint"):
                        self._save_checkpoint_maybe_async()
                    last_checkpoint = time.perf_counter()
                chunks += 1
                B_glob = G * A
                if (self._l0_count + B_glob) > _MAX_LOAD * n * self._cap_loc:
                    table = self._grow_table(
                        table,
                        _pow2ceil(
                            int((self._l0_count + B_glob) / (_MAX_LOAD * n))
                        ),
                        defer_evict=pipe is not None,
                    )
                # Occupancy-adaptive dispatch: the host pool count is exact
                # (numpy rows), so the global chunk shrinks to n × the
                # smallest per-device ladder rung holding the pending rows —
                # a sparse frontier expands an n×bucket grid, not n×F_loc.
                # _pool_take's round-robin interleave then gives every shard a
                # dense live-lane prefix at that width. (Async: after the
                # coalescing barrier above, this count either matches the
                # synchronous path's exactly, or is >= G with it — same
                # bucket either way.)
                got = min(self._pool_count, G)
                width = G
                bucket = None
                if len(self._buckets) > 1:
                    bucket = bucket_for(
                        self._buckets, max(1, -(-got // n))
                    )
                    width = n * bucket
                    self._wi.bucket.set(bucket)
                    self._wi.bucket_dispatch(bucket)
                    self._wi.compaction.set(got / width)
                    self._wi.frontier_fill.set(got / G)
                chunk = self._pool_take(width)
                dev = self._put_chunk(chunk)

                if pipe is None:
                    table = self._wave_sync(
                        table, chunk, dev, depth_cap, chunks, width,
                        bucket, got,
                    )
                else:
                    # Bounded pending-verdict lane set.
                    pipe.throttle()
                    table = self._wave_async(
                        table, dev, depth_cap, chunks, width, bucket, got,
                    )
                if self.warmup_seconds is None:
                    self.warmup_seconds = time.perf_counter() - self._t_start
                    self._wi.warmup.set(self.warmup_seconds)
                # Re-ingest fresh rows for the next chunks.
                del dev
        if pipe is not None:
            # Run-end epoch barrier: counters and the parent-fp log must
            # be settled before the audit and the done flag.
            pipe.drain()
        self._record_placement(table)
        self._audit_table(table)

    def _apply_wave_stats(self, wave, chunk=None):
        """First-attempt device bookkeeping shared by the sync and async
        wave paths (a growth retry re-expands the same chunk, so this
        runs once per wave): generated/depth counters, discovery
        fingerprints, and the visitor callback. ONE site on purpose —
        the bit-identical guarantee depends on both paths applying the
        same stats the same way. Returns the wave's generated count."""
        props = self._properties
        n = self._n
        generated = int(self._pull(wave["generated"]).sum())
        self._state_count += generated
        self._max_depth = max(
            self._max_depth, int(self._pull(wave["max_depth"]).max())
        )
        if props:
            hit = self._pull(wave["prop_hit"])
            phi = self._pull(wave["prop_hi"])
            plo = self._pull(wave["prop_lo"])
            for i, p in enumerate(props):
                if p.name in self._discoveries_fp:
                    continue
                for d in range(n):
                    if hit[d, i]:
                        self._discoveries_fp[p.name] = fp_to_int(
                            phi[d, i], plo[d, i]
                        )
                        break
        if chunk is not None and self._visitor is not None:
            self._visit_chunk(chunk)
        return generated

    def _wave_sync(self, table, chunk, dev, depth_cap, chunks, width,
                   bucket, got):
        """One wave's synchronous dispatch + harvest (the pre-async
        body, factored out unchanged)."""
        attempt = 0
        wave_generated = 0
        wave_new = 0
        self._wave_stale = 0
        lanes0 = self._exchange_lanes
        with self._tracer.span("sharded_bfs.wave", wave=chunks) as sp:
            while True:
                wave = self._call_wave(table, dev, depth_cap)
                table = wave["table"]
                if attempt == 0:
                    wave_generated = self._apply_wave_stats(wave, chunk)
                if self._cov is not None:
                    # Mesh-summed coverage vector; a growth retry
                    # re-expands the same chunk, so only the
                    # fresh-based slices accumulate then.
                    self._cov.consume_device(
                        np.asarray(
                            self._pull(wave["cov"])
                        ).sum(axis=0),
                        self._cov_layout,
                        first_attempt=(attempt == 0),
                        max_depth=self._max_depth,
                    )
                wave_new += self._harvest(wave)
                self._harvest_liveness(wave)
                if not int(self._pull(wave["overflow"]).sum()):
                    break
                if self._max_cap_loc is not None and attempt >= 8:
                    # Pathological key skew: one shard overflows even
                    # freshly evicted — a configuration error, not a
                    # loop to spin in.
                    raise RuntimeError(
                        "a single wave's routed keys overflow one "
                        "budget-capped shard after repeated "
                        "evictions; raise hbm_budget_mib or shrink "
                        "frontier_per_device"
                    )
                table = self._grow_table(table, self._cap_loc * 2)
                attempt += 1
            self._record_wave_metrics(
                sp,
                width,
                wave_generated,
                wave_new,
                bucket=bucket,
                compaction_ratio=(got / width if bucket else None),
                live_lanes=got,
                exchange_lanes=self._exchange_lanes - lanes0,
            )
            if self._cov is not None:
                self._cov.emit_wave_span()
        return table

    def _wave_async(self, table, dev, depth_cap, chunks, width, bucket,
                    got):
        """One wave's async dispatch (checker thread): device stats,
        discoveries, growth retries — everything the next dispatch
        decision depends on — while each attempt's harvest verdict is
        submitted to the pipeline worker BEFORE any growth/eviction
        that follows it (the tier must see probes and evictions in the
        synchronous order; see TpuBfsChecker._consume_wave_async)."""
        attempt = 0
        ctx = {"wave_new": 0, "stale": 0, "generated": 0}
        lanes0 = self._exchange_lanes
        while True:
            wave = self._call_wave(table, dev, depth_cap)
            table = wave["table"]
            # Every attempt's exchange, read by the final verdict.
            ctx["exchange_lanes"] = self._exchange_lanes - lanes0
            if attempt == 0:
                ctx["generated"] = self._apply_wave_stats(wave)
            if self._cov is not None:
                self._cov.consume_device(
                    np.asarray(self._pull(wave["cov"])).sum(axis=0),
                    self._cov_layout,
                    first_attempt=(attempt == 0),
                    max_depth=self._max_depth,
                )
            n_new = self._pull(wave["n_new"])
            total = int(n_new.sum())
            self._l0_count += total
            final = not int(self._pull(wave["overflow"]).sum())
            # Only jobs that can grow the pool hold up the
            # coalescing barrier (see _explore_waves); count this
            # one in BEFORE it is queued — incrementing after
            # submit could let the job's decrement land first and
            # the barrier miss a genuinely pending verdict.
            with self._pool_lock:
                self._inflight_verdicts += 1
            try:
                # Point-in-time captures: the live l0/capacity/depth
                # fields may describe a later wave by verdict time.
                self._pipe.submit(
                    lambda w=wave, nn=n_new, t=total, f=final,
                    warm=self.warmup_seconds is not None,
                    st=(
                        self._l0_count,
                        self._n * self._cap_loc,
                        self._max_depth,
                    ),
                    cm=self._last_comms:
                        self._harvest_verdict(
                            ctx, w, nn, t, f, chunks, width, bucket,
                            got, warm, st, cm,
                        )
                )
            except BaseException:
                # A poisoned submit never enqueues the job (whose
                # finally would decrement) — rebalance here.
                with self._pool_lock:
                    self._inflight_verdicts -= 1
                raise
            if final:
                if self._cov is not None:
                    self._cov.emit_wave_span()
                return table
            if self._max_cap_loc is not None and attempt >= 8:
                raise RuntimeError(
                    "a single wave's routed keys overflow one "
                    "budget-capped shard after repeated "
                    "evictions; raise hbm_budget_mib or shrink "
                    "frontier_per_device"
                )
            table = self._grow_table(
                table, self._cap_loc * 2, defer_evict=True
            )
            attempt += 1

    def _harvest_verdict(self, ctx, wave, n_new, total, final, wave_no,
                         width, bucket, got, warm, state, comms=None):
        """Pipeline-worker half of a sharded wave: pulls the compacted
        fresh rows, probes the shard tiers (exact here — every eviction
        is applied on this thread, in submission order), logs the
        survivors, and re-pools them at the tail. The final attempt
        emits the ``sharded_bfs.wave`` span + telemetry the monitor's
        estimator consumes."""
        def verdict():
            # Edge rows absorb even on zero-fresh waves (cycle-closing
            # edges target already-visited states).
            self._harvest_liveness(wave)
            if not total:
                return
            # _tier_active() inside _harvest_rows is exact HERE: every
            # eviction is applied on this same thread, in submission
            # order (the merge fence).
            survivors, n_stale = self._harvest_rows(
                wave, n_new, overlapped=True
            )
            ctx["stale"] += n_stale
            ctx["wave_new"] += survivors

        try:
            if not final:
                verdict()
                return
            # Covers the HOST VERDICT only (the device half overlaps
            # later waves) — flagged so trace readers don't compare its
            # dur against sync wave walls.
            with self._tracer.span(
                "sharded_bfs.wave", wave=wave_no, async_verdict=True
            ) as sp:
                verdict()
                self._record_wave_metrics(
                    sp, width, ctx["generated"], ctx["wave_new"],
                    bucket=bucket,
                    compaction_ratio=(got / width if bucket else None),
                    live_lanes=got, stale=ctx["stale"], warm=warm,
                    state=state, comms=comms,
                    exchange_lanes=ctx["exchange_lanes"],
                )
        finally:
            # Decrement even on a verdict error: the barrier predicate
            # must not wedge the checker on a job that will never
            # append (the error itself surfaces via drain/submit).
            with self._pool_lock:
                self._inflight_verdicts -= 1

    def _save_checkpoint_maybe_async(self, batches=None):
        """Checkpoint at an epoch boundary: payload built synchronously
        after the barrier; in async mode the pickle + rename ride the
        pipeline worker (see TpuBfsChecker._save_checkpoint_maybe_async
        for why that is safe). ``batches`` overrides the wave-mode host
        pool (the deep path passes ring exports); the pool itself is
        snapshotted only AFTER the barrier — in-flight verdicts append
        survivor rows during the drain."""
        if self._pipe is None:
            self.save_checkpoint(
                self._checkpoint_path,
                batches if batches is not None else self._pool,
            )
            return
        self._pipe.drain()
        payload = self.checkpoint_payload(
            list(batches) if batches is not None else list(self._pool)
        )
        path = self._checkpoint_path
        self._pipe.submit(lambda: self._checkpoint_write(path, payload))

    def _aot_signature(self) -> tuple:
        """Everything the wave and drain traces close over, for the shared
        and disk executable caches: backend, the mesh's devices, model
        digest, properties, key scheme, widths, ladder, log and drain caps,
        sieve, liveness, coverage, fleet. Shapes that change within a run
        (table and ring rows, chunk width) ride the per-entry keys."""
        return (
            jax.default_backend(),
            jax.process_count(),
            tuple(int(d.id) for d in self._mesh.devices.flat),
            packed_model_digest(self._model, self._A),
            tuple(
                (p.name, str(p.expectation)) for p in self._properties
            ),
            self._sym_scheme,
            self._F_loc,
            tuple(self._buckets),
            self._Ll,
            self._max_drain_waves,
            bool(self._sieve),
            self._sieve_slots if self._sieve else None,
            self._sieve_bits if self._sieve else None,
            self._live_enabled,
            self._cov is not None,
            self._fleet_on,
        )

    def _charge_compile(self, kind, seconds):
        """A compile while the run explores (after the warm-up stamp) is
        added to ``warmup_seconds``."""
        if (
            getattr(self, "warmup_seconds", None) is not None
            and not self._done_event.is_set()
        ):
            self.warmup_seconds += seconds
            self._wi.warmup.set(self.warmup_seconds)

    def _call_wave(self, table, dev, depth_cap):
        """Wave through an AOT-compiled executable (keyed by local table
        capacity): a mid-run compile (table growth changes the shape) is
        measured into ``warmup_seconds`` instead of the steady-state
        window — mirroring ``TpuBfsChecker._call_wave``. During the
        pre-first-result window ``warmup_seconds`` is None and the
        caller's own stamp covers the compile."""
        # Injection seam (utils/faults.py): pre-dispatch, like the
        # single-device checker's — no per-wave state mutated yet.
        fault_point("device.wave")
        args = (
            table,
            dev["states"],
            dev["hi"],
            dev["lo"],
            dev["ebits"],
            dev["depth"],
            dev["mask"],
            jnp.asarray(depth_cap, jnp.int32),
        )
        if self._sieve:
            args = args + self._sieve_dev
        key = (table.shape[1], dev["hi"].shape[0])
        exe = self._programs.obtain(
            "wave", key, self._jit_wave, args,
            table_capacity=key[0], width=key[1],
        )
        if self._attr is None:
            out = exe(*args)
        else:
            with self._attr.phase("device"):
                out = exe(*args)
                self._attr.fence(out)
        if self._sieve:
            # The sieve operands are donated: rebind before anything can
            # touch the stale references.
            self._sieve_dev = (out["sieve_cache"], out["sieve_bloom"])
        args = self._consume_comms(
            out["comms"], dev["hi"].shape[0] // self._n * self._A
        )
        if self._fleet_on:
            # Mutates the stashed span-args dict in place, so the async
            # path's captured ``self._last_comms`` reference carries the
            # fleet columns with no extra plumbing.
            args.update(self._consume_fleet(out["fleet"]))
        return out

    def _consume_comms(self, comms, m):
        """Host accounting for one dispatch's mesh-summed comms vector
        (layout: ``[sieve_probes, killed, bloom_probes, bloom_hits,
        bloom_fps, lanes_shipped, rung one-hot...]`` per shard; ``m`` is
        the per-device candidate-lane width, which fixes the rung
        ladder). Returns (and stashes) the span-args dict the wave span
        rides."""
        per = np.asarray(self._pull(comms), np.int64)  # (n, vec)
        self._last_comms_per = per
        c = per.sum(axis=0)
        self._exchange_lanes += int(c[5])
        args = self._ci.record(
            probes=int(c[0]),
            killed=int(c[1]),
            bloom_probes=int(c[2]),
            bloom_hits=int(c[3]),
            bloom_fps=int(c[4]),
            lanes=int(c[5]),
        )
        rungs = self._comm_rungs(m) if self._sieve else [m]
        for i, width in enumerate(rungs[: max(0, len(c) - 6)]):
            cnt = int(c[6 + i])
            if cnt:
                self._ci.rung_dispatch(width, cnt)
        self._last_comms = args
        return args

    def _consume_fleet(self, fleet_dev, waves=1):
        """Folds one dispatch's per-shard skew rows — device counters
        (``_wave_core``'s ``fleet`` vector), the per-shard columns of the
        comms exchange already pulled by ``_consume_comms``, and the host
        tier walls accumulated per shard since the last fold — into the
        ``fleet.*`` family. Returns the ``fleet_*`` span args."""
        per = np.asarray(self._pull(fleet_dev), np.float64)
        if per.ndim == 1:
            per = per[None]
        n = self._n
        with self._fleet_lock:
            probe_s = self._fleet_probe_s
            evict_s = self._fleet_evict_s
            evict_b = self._fleet_evict_bytes
            self._fleet_probe_s = [0.0] * n
            self._fleet_evict_s = [0.0] * n
            self._fleet_evict_bytes = [0] * n
        rows = {
            "live_lanes": per[:, 0],
            "generated": per[:, 1],
            "fresh": per[:, 2],
            "insert_load": per[:, 3],
            "overflow": per[:, 4],
            "probe_ms": np.asarray(probe_s) * 1e3,
            "evict_ms": np.asarray(evict_s) * 1e3,
            "evict_bytes": np.asarray(evict_b, np.float64),
        }
        cm = self._last_comms_per
        if cm is not None and cm.shape[0] == n and cm.shape[1] >= 3:
            rows["sieve_hits"] = cm[:, 1].astype(np.float64)
            rows["routed"] = cm[:, 2].astype(np.float64)
        return self._fi.record_wave(rows, waves=waves)

    # -- deep-drain host loop ---------------------------------------------

    def _new_pool(self):
        return self._jit_pool(self._n * self._PCl)

    def _new_heads(self):
        return self._jit_zeros((self._n,), jnp.int32)

    def _feed_rings(self, pool, head, count, ring_est):
        """Moves host-pool rows into the device rings, growing them when
        the next global chunk might not fit. Returns updated state."""
        G = self._G
        while self._pool_count:
            if ring_est + self._F_loc > self._PCl:
                # The host bound overcounts (F_loc per chunk regardless of
                # occupancy); refresh it from the device before paying for
                # a ring doubling and its retrace.
                ring_est = int(self._pull(count).max())
                if ring_est + self._F_loc > self._PCl:
                    pool, head, count = self._grow_rings(pool, head, count)
            chunk = self._pool_take(G)
            dev = self._put_chunk(chunk)
            out = self._jit_ring_push(pool, head, count, dev)
            pool, count = out["pool"], out["count"]
            ring_est += self._F_loc
        return pool, head, count, ring_est

    def _grow_rings(self, pool, head, count):
        """Doubles every device's ring (local export + re-push — rows never
        change device, so growth needs no communication)."""
        exported = self._jit_ring_export(pool, head, count)
        self._PCl *= 2
        pool = self._new_pool()
        head = self._new_heads()
        out = self._jit_ring_push(pool, head, self._new_heads(), exported)
        return out["pool"], head, out["count"]

    def _explore_deep(self, table, depth_cap):
        props = self._properties
        if not props:
            return
        n, G, A = self._n, self._G, self._A
        pool = self._new_pool()
        head = self._new_heads()
        count = self._new_heads()
        ring_est = 0  # conservative host bound on the fullest ring
        drains = 0
        last_checkpoint = time.perf_counter()
        while True:
            if len(self._discoveries_fp) == len(props):
                break
            if self._preempt_event.is_set():
                # Drain-granular yield: rings + host-pool leftovers are
                # the whole pending frontier between drains (same
                # capture as _checkpoint_rings), into an in-memory
                # payload instead of a file.
                self._preempt_payload = self.checkpoint_payload(
                    self._rings_pool_batches(pool, head, count)
                )
                self._tracer.instant(
                    "sharded_bfs.preempted", mode="drain"
                )
                return
            pool, head, count, ring_est = self._feed_rings(
                pool, head, count, ring_est
            )
            if ring_est == 0:
                break
            # Attribution window over the whole drain iteration. No
            # early exit lives inside it (unlike TpuBfsChecker's, which
            # needs the mid-loop handoff return), so a with-block is
            # exact.
            with self._wave_window("drain"):
                if (
                    self._checkpoint_path is not None
                    and drains
                    and (time.perf_counter() - last_checkpoint)
                    >= self._checkpoint_min_interval
                ):
                    with self._phase("checkpoint"):
                        self._checkpoint_rings(pool, head, count)
                    last_checkpoint = time.perf_counter()
                drains += 1
                B_glob = G * A
                if (self._l0_count + B_glob) > _MAX_LOAD * n * self._cap_loc:
                    table = self._grow_table(
                        table,
                        _pow2ceil(
                            int((self._l0_count + B_glob) / (_MAX_LOAD * n))
                        ),
                    )
                undiscovered = np.array(
                    [p.name not in self._discoveries_fp for p in props]
                )
                # Clamp: the budget rides device int32; a huge global table
                # (> 2^31 slots across the mesh) must saturate, not overflow.
                budget = jnp.int32(
                    min(
                        int(_MAX_LOAD * n * self._cap_loc) - self._l0_count,
                        (1 << 31) - 1 - G * A,
                    )
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
                if self._sieve:
                    args = args + self._sieve_dev
                # Obtained before the first drain runs (which may run the
                # whole exploration), so no compile folds into it.
                key = (table.shape[1], self._PCl)
                exe = self._programs.obtain(
                    "drain", key, self._jit_deep_drain, args,
                    table_capacity=key[0], width=key[1],
                )
                if self.warmup_seconds is None:
                    self.warmup_seconds = time.perf_counter() - self._t_start
                    self._wi.warmup.set(self.warmup_seconds)
                drain_span = self._tracer.span("sharded_bfs.drain", drain=drains)
                with drain_span:
                    with self._phase("device"):
                        res = exe(*args)
                        if self._attr is not None:
                            self._attr.fence(res)
                    dstats = self._pull(res["drain_stats"])  # (n, 10)
                    drain_generated = int(dstats[:, 1].sum())
                    drain_new = int(dstats[:, 2].sum())
                    self._state_count += drain_generated
                    self._unique_count += drain_new
                    # Drains only run tier-empty: every fresh is L0-resident.
                    self._l0_count += drain_new
                    self._max_depth = max(
                        self._max_depth, int(dstats[:, 3].max())
                    )
                    # Aggregate span per drain (per-wave host exits are the
                    # cost the drain amortizes away); the final unconsumed
                    # wave is accounted by _consume_final below.
                    self._wi.drains.inc()
                    self._wi.waves.inc(int(dstats[:, 4].max()))
                    comms_extra = self._consume_comms(
                        res["comms_acc"], self._F_loc * self._A
                    )
                    if self._fleet_on:
                        comms_extra.update(
                            self._consume_fleet(
                                res["fleet_acc"],
                                waves=int(dstats[:, 4].max()),
                            )
                        )
                    self._wi.record(
                        drain_span,
                        frontier=self._G,
                        generated=drain_generated,
                        n_new=drain_new,
                        occupancy=self._l0_count / (self._n * self._cap_loc),
                        capacity=self._n * self._cap_loc,
                        max_depth=self._max_depth,
                        count_wave=False,
                        observe=False,
                        waves=int(dstats[:, 4].max()),
                        # Live pending states across all rings — the monitor's
                        # progress fit reads this, not the capacity `frontier`.
                        ring_count=int(dstats[:, 5].sum()),
                        **comms_extra,
                    )
                pool, head, count = res["pool"], res["head"], res["count"]
                if self._sieve:
                    fin = res["final"]
                    self._sieve_dev = (
                        fin["sieve_cache"], fin["sieve_bloom"]
                    )
                ring_est = int(dstats[:, 5].max())
                if self._cov is not None:
                    # Every drain wave (final included — see
                    # _deep_drain_local's cov_acc note), mesh-summed.
                    self._cov.consume_device(
                        np.asarray(
                            self._pull(res["cov_acc"])
                        ).sum(axis=0),
                        self._cov_layout,
                        max_depth=self._max_depth,
                    )
                # The whole drain's parent-fp stream: one (n, 6, Ll) transfer,
                # sliced per device by its log_n.
                max_log = int(dstats[:, 0].max())
                if max_log:
                    pack = self._pull(res["log_pack"][:, :, :max_log])
                    for d in range(n):
                        ln = int(dstats[d, 0])
                        if ln:
                            self._wave_log.append(
                                (
                                    fp64_pairs(pack[d, 0, :ln], pack[d, 1, :ln]),
                                    fp64_pairs(pack[d, 2, :ln], pack[d, 3, :ln]),
                                )
                            )
                            if self._symmetry_enabled:
                                self._key_log.append(
                                    fp64_pairs(pack[d, 4, :ln], pack[d, 5, :ln])
                                )
                lanes0 = self._exchange_lanes
                with self._tracer.span("sharded_bfs.wave", drain=drains) as sp:
                    table, pool, head, count, ring_est = self._consume_final(
                        res, dstats, table, pool, head, count, ring_est,
                        depth_cap, span=sp,
                    )
                    # The drain span counted this wave's exchange; only an
                    # overflow retry's is this span's.
                    sp.set(**self._ci.exchange(self._exchange_lanes - lanes0))
        self._record_placement(table)
        self._audit_table(table)

    def _consume_final(
        self, res, dstats, table, pool, head, count, ring_est, depth_cap,
        span=None,
    ):
        """Applies the drain's final (unconsumed) wave host-side: counters,
        discoveries, parent-fp log, ring push of the exchanged rows, and
        the table-growth overflow retry. ``span`` (a wave span covering
        this consume) gets the per-wave args the monitor reads — without
        it the final wave's uniques are invisible to the progress
        estimator and SSE stream (registry counters alone don't stream)."""
        props = self._properties
        n = self._n
        final = res["final"]
        table = final["table"]
        self._state_count += int(dstats[:, 7].sum())
        self._max_depth = max(self._max_depth, int(dstats[:, 9].max()))
        if props:
            hit = self._pull(res["prop_hit"])
            phi = self._pull(res["prop_hi"])
            plo = self._pull(res["prop_lo"])
            for i, p in enumerate(props):
                if p.name in self._discoveries_fp:
                    continue
                for d in range(n):
                    if hit[d, i]:
                        self._discoveries_fp[p.name] = fp_to_int(
                            phi[d, i], plo[d, i]
                        )
                        break
        # Log + count the final wave's fresh rows (generator side).
        n_new = dstats[:, 6]
        total_new = int(n_new.sum())
        self._unique_count += total_new
        self._l0_count += total_new
        self._wi.unique.inc(total_new)
        self._wi.generated.inc(int(dstats[:, 7].sum()))
        self._wi.wave_new.observe(total_new)
        if total_new:
            B = self._F_loc * self._A
            hi = self._pull(final["new_hi"]).reshape(n, B)
            lo = self._pull(final["new_lo"]).reshape(n, B)
            phi_ = self._pull(final["parent_hi"]).reshape(n, B)
            plo_ = self._pull(final["parent_lo"]).reshape(n, B)
            sel = np.zeros((n, B), bool)
            for d in range(n):
                sel[d, : int(n_new[d])] = True
            self._wave_log.append(
                (fp64_pairs(hi[sel], lo[sel]), fp64_pairs(phi_[sel], plo_[sel]))
            )
            if self._symmetry_enabled:
                khi = self._pull(final["new_khi"]).reshape(n, B)
                klo = self._pull(final["new_klo"]).reshape(n, B)
                self._key_log.append(fp64_pairs(khi[sel], klo[sel]))
            # Push the exchanged rows into the rings (device-side; the
            # exchange already balanced them round-robin).
            recv_per_dev = final["recv_mask"].shape[0] // n
            # Grow until the received rows provably fit: recv_per_dev is
            # n*ceil(B/n) and can exceed a single doubling of a small ring
            # (ring_push would silently wrap and overwrite queued states).
            while ring_est + recv_per_dev > self._PCl:
                ring_est = int(self._pull(count).max())
                if ring_est + recv_per_dev <= self._PCl:
                    break
                pool, head, count = self._grow_rings(pool, head, count)
            rows = dict(final["recv"])
            rows["mask"] = final["recv_mask"]
            out = self._jit_ring_push(pool, head, count, rows)
            pool, count = out["pool"], out["count"]
            ring_est += recv_per_dev
        # Overflow retry: grow the table and re-expand the saved frontier
        # through the wave path (fresh rows land in the host pool).
        retry_new = 0
        if int(dstats[:, 8].sum()):
            fr = res["frontier"]
            while True:
                table = self._grow_table(table, self._cap_loc * 2)
                # Through the AOT cache: the grown-shape compile is
                # measured into warmup, and the executable is shared with
                # the wave path.
                wave = self._call_wave(table, fr, depth_cap)
                table = wave["table"]
                if self._cov is not None:
                    # Retry of the drain's final frontier: its eval-based
                    # slices already rode cov_acc; only the newly-claimed
                    # fresh lanes accumulate.
                    self._cov.consume_device(
                        np.asarray(self._pull(wave["cov"])).sum(axis=0),
                        self._cov_layout,
                        first_attempt=False,
                    )
                harvested = self._harvest(wave)
                self._wi.unique.inc(harvested)
                retry_new += harvested
                if not int(self._pull(wave["overflow"]).sum()):
                    break
        if span is not None:
            gen = int(dstats[:, 7].sum())
            nn = total_new + retry_new
            span.set(
                frontier=self._G,
                generated=gen,
                new_unique=nn,
                # Clamped: the overflow retry's harvest rides nn but its
                # regeneration is already inside gen — a skewed split must
                # not stream an impossible negative rate.
                dedup_hit_rate=(max(0.0, (gen - nn) / gen) if gen else 0.0),
                occupancy=self._l0_count / (self._n * self._cap_loc),
                max_depth=self._max_depth,
                # The drain span already tallied this wave; live pending
                # (ring residue after the push) rides for the monitor's
                # frontier fit.
                waves=0,
                live_lanes=ring_est,
            )
        if self._cov is not None:
            self._cov.emit_wave_span()
        return table, pool, head, count, ring_est

    def _rings_pool_batches(self, pool, head, count):
        """The whole pending frontier in deep mode, as host row-batches:
        any host-pool leftovers plus the rings exported into one batch
        (the shape ``save_checkpoint``/``checkpoint_payload`` take)."""
        exported = self._jit_ring_export(pool, head, count)
        mask = self._pull(exported["mask"])
        batch = {
            k: (
                jax.tree_util.tree_map(lambda x: self._pull(x)[mask], v)
                if k == "states"
                else self._pull(v)[mask]
            )
            for k, v in exported.items()
            if k != "mask"
        }
        return list(self._pool) + [batch]

    def _checkpoint_rings(self, pool, head, count):
        """Deep-mode checkpoint: exports the rings into one host row-batch
        and saves it alongside any host-pool leftovers. Async mode
        defers the pickle + rename to the pipeline worker, same as the
        wave path (deep drains carry no verdicts, so the barrier is
        instant)."""
        self._save_checkpoint_maybe_async(
            self._rings_pool_batches(pool, head, count)
        )

    def _seed(self):
        """Fingerprints + dedup-inserts the initial states; returns the
        sharded visited table and fills the host pool."""
        n, G = self._n, self._G
        model = self._model
        init = model.packed_init_states()
        n0 = jax.tree_util.tree_leaves(init)[0].shape[0]
        width = max(G, n * _pow2ceil((n0 + n - 1) // n))

        def pad0(x):
            return np.pad(
                np.asarray(x), [(0, width - n0)] + [(0, 0)] * (x.ndim - 1)
            )

        init_np = jax.tree_util.tree_map(pad0, init)
        hi, lo = (np.asarray(a) for a in self._jit_fp_batch(init_np))
        if self._symmetry_enabled:
            khi, klo = (np.asarray(a) for a in self._jit_key_batch(init_np))
        else:
            khi, klo = hi, lo
        in_range = np.arange(width) < n0
        bound = np.asarray(self._jit_within(init_np))
        valid = in_range & bound

        table = self._new_table()
        while True:
            out = self._jit_insert(
                table,
                *(self._put_sharded(a) for a in (khi, klo, valid)),
            )
            if not int(self._pull(out["overflow"]).sum()):
                break
            self._cap_loc *= 2
            table = self._new_table()
        table = out["table"]
        fresh = self._pull(out["fresh"])
        self._state_count = int(valid.sum())
        self._unique_count = int(fresh.sum())
        self._l0_count = self._unique_count
        # Seed the cumulative counters too (init states skip the waves).
        self._wi.generated.inc(self._state_count)
        self._wi.unique.inc(self._unique_count)
        if self._cov is not None:
            self._cov.record_seed(self._unique_count)
        child64 = fp64_pairs(hi, lo)
        self._wave_log.append((child64[fresh], np.zeros((fresh.sum(),), np.uint64)))
        if self._symmetry_enabled:
            self._key_log.append(fp64_pairs(khi, klo)[valid])
        if self._live_enabled:
            # Analysis roots: condition-false VALID init states (the
            # only legal counterexample starting points).
            from ..checker.device_liveness import seed_root_mask

            root_mask = np.asarray(
                jax.jit(
                    lambda s, v: seed_root_mask(
                        self._conditions, self._ebit, s, v
                    )
                )(init_np, jnp.asarray(valid))
            )
            self._live_store.add_roots(child64[valid], root_mask[valid])

        self._pool_append(
            {
                "states": jax.tree_util.tree_map(lambda x: x[fresh], init_np),
                "hi": hi[fresh],
                "lo": lo[fresh],
                "ebits": np.full((int(fresh.sum()),), self._ebits0, np.uint32),
                "depth": np.ones((int(fresh.sum()),), np.int32),
            }
        )
        return table

    # -- checkpoint/resume (parity with TpuBfsChecker; SURVEY §5) ----------

    def save_checkpoint(self, path, pool) -> None:
        """Atomically serializes counters, discoveries, the parent-pointer
        map, and the host frontier pool. The visited set is not stored —
        it is exactly the parent map's keys, and the per-shard tables are
        rebuilt from them on resume (keys re-route by ``hi % n``, so a
        checkpoint restores onto a mesh of any size).

        Worker-internal (called between chunks, when no chunk is in
        flight): the explicit ``pool`` argument mirrors ``TpuBfsChecker``'s
        queue parameter — calling this from another thread mid-run would
        race the worker's pool mutation and could snapshot an in-flight
        chunk out of existence."""
        payload = self.checkpoint_payload(pool)
        # Multi-controller: every process builds the identical payload;
        # exactly one writes the file.
        if jax.process_index() == 0:
            atomic_pickle(path, payload)

    def checkpoint_payload(self, pool) -> dict:
        """The checkpoint as an in-memory payload dict (the exact object
        ``save_checkpoint`` pickles); the preempt/resume path passes it
        straight to a new checker's ``resume_from=``."""
        self._ingest_wave_log()
        children, parents = self._store.export()
        payload = {
            **checkpoint_header(
                "sharded",
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
            "cap_loc": self._cap_loc,
            "n_shards": self._n,
            "pool": [
                jax.tree_util.tree_map(np.asarray, batch) for batch in pool
            ],
        }
        if self._symmetry_enabled:
            payload["keys"] = (
                np.concatenate(self._key_log)
                if self._key_log
                else np.zeros((0,), np.uint64)
            )
        if self._tiers and self._tier_active():
            # Out-of-core: every shard's runs + Bloom filters ride the
            # checkpoint (CRC-validated on restore); the shard tables
            # rebuild as "known keys not in any run".
            payload["storage"] = [t.export_state() for t in self._tiers]
        if self._live_enabled:
            # v3 payload extension (see checker/tpu.py): the liveness
            # edge relation + roots/terminals round-trip with the run.
            payload["liveness"] = self._live_store.export_state()
            payload["version"] = 3
        return payload

    def _restore(self, path):
        if isinstance(path, dict):
            # In-memory resume (preempt/resume): the payload dict itself.
            payload = path
        else:
            import pickle

            with open(path, "rb") as f:
                payload = pickle.load(f)
        validate_checkpoint_header(
            payload,
            "sharded",
            "single-device TpuBfs checkpoints do not carry the frontier "
            "pool this restore needs",
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
        # Visited-set keys == the original fps unless symmetry was on.
        keys = children
        if self._symmetry_enabled:
            keys = payload["keys"]
            self._key_log.append(keys)
        for batch in payload["pool"]:
            self._pool_append(batch)

        # Liveness edge store must round-trip with the run (see
        # checker/tpu.py for why mode mismatches are refused).
        live_state = payload.get("liveness")
        if self._live_enabled and live_state is None:
            raise ValueError(
                "liveness='device' cannot resume a checkpoint written "
                "without it: pre-checkpoint edges were never logged, so "
                "the final verdict would be unsound"
            )
        if live_state is not None:
            if not self._live_enabled:
                raise ValueError(
                    "checkpoint carries a liveness edge store; resume "
                    "with liveness='device' (dropping it would discard "
                    "the soundness the original run paid for)"
                )
            self._live_store.load_state(live_state)

        # Out-of-core checkpoints carry per-shard run lists. Same mesh
        # width: load each store as written. Different width (elastic
        # restore): re-partition the runs' keys by owner under the
        # CURRENT mesh so per-shard host budgets stay balanced — probe
        # correctness never depended on the partitioning (union probe).
        n = self._n
        storage_state = payload.get("storage")
        if storage_state:
            if not self._tiers:
                # Restored without budget knobs: hold the runs anyway
                # (unbounded shard tables from here on, probes correct).
                from ..storage import StorageInstruments, TieredVisitedStore

                self._si = StorageInstruments(
                    "sharded_bfs", registry=self._registry
                )
                self._tiers = [
                    TieredVisitedStore(
                        instruments=self._si, shard=d, tracer=self._tracer
                    )
                    for d in range(n)
                ]
            if len(storage_state) == n:
                for t, s in zip(self._tiers, storage_state):
                    t.load_state(s)
            else:
                from ..storage.runs import FingerprintRun

                allk = [
                    FingerprintRun.from_state(r).decode_all()
                    for s in storage_state
                    for r in list(s.get("l1", [])) + list(s.get("l2", []))
                ]
                allk = np.unique(np.concatenate(allk))
                owner = ((allk >> np.uint64(32)) % np.uint64(n)).astype(
                    np.int64
                )
                for d in range(n):
                    self._tiers[d].evict(allk[owner == d])

        # Rebuild the sharded visited set by claim-inserting the L0 keys
        # (all known keys minus the tiers' runs) through the normal
        # routed insert — each key lands on its owner shard under the
        # *current* mesh, so shard count may differ from the writer's.
        if payload["n_shards"] == n:
            # Same mesh width: start at the writer's shard capacity so the
            # rebuild needs no growth rounds.
            self._cap_loc = max(self._cap_loc, payload["cap_loc"])
        insert_keys = keys
        if self._tiers and self._tier_active():
            insert_keys = keys[~self._probe_tiers(keys)]
        need = _pow2ceil(
            max(int(len(insert_keys) / (_MAX_LOAD * n)), self._cap_loc)
        )
        self._cap_loc = need
        if self._max_cap_loc is not None:
            self._cap_loc = min(self._cap_loc, self._max_cap_loc)
        table = self._new_table()
        hi = (insert_keys >> np.uint64(32)).astype(np.uint32)
        lo = (insert_keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        W = n * (1 << 13)
        if self._max_cap_loc is not None:
            # A batch must fit one freshly-evicted shard under the load
            # cap even if every key routes there.
            W = min(W, n * max(1, int(self._max_cap_loc * _MAX_LOAD) // n))
        for start in range(0, len(insert_keys), W):
            bh = hi[start : start + W]
            bl = lo[start : start + W]
            m = len(bh)
            if m < W:
                bh = np.pad(bh, (0, W - m))
                bl = np.pad(bl, (0, W - m))
            valid = np.arange(W) < m
            while True:
                out = self._jit_insert(
                    table,
                    *(self._put_sharded(a) for a in (bh, bl, valid)),
                )
                table = out["table"]
                self._l0_count += int(self._pull(out["fresh"]).sum())
                if not int(self._pull(out["overflow"]).sum()):
                    break
                table = self._grow_table(table, self._cap_loc * 2)
        return table

    def _harvest_liveness(self, wave) -> None:
        """Absorbs one wave attempt's per-shard condition-false edge
        rows into the host store (sync harvest or async verdict worker
        — FIFO keeps absorb order deterministic). Runs even on
        zero-fresh waves: cycle-closing edges point at already-visited
        states, which is exactly the n_new == 0 case."""
        if not self._live_enabled:
            return
        from ..ops.edge_store import EDGE_COLS

        ln = np.asarray(self._pull(wave["live_n"]))
        if not int(ln.sum()):
            return
        cols = {
            c: np.asarray(self._pull(wave[f"live_{c}"]))
            for c in EDGE_COLS
        }
        W = cols["phi"].shape[0] // self._n
        sel = np.zeros((self._n * W,), bool)
        for d in range(self._n):
            sel[d * W : d * W + int(ln[d])] = True
        self._live_store.absorb(**{c: cols[c][sel] for c in EDGE_COLS})

    def _harvest(self, wave):
        """Pulls each device's compacted fresh rows into the host pool;
        returns the global fresh count surviving the tier probe
        (telemetry). Out-of-core mode filters here: L0-fresh rows whose
        key lives in an evicted run are stale — never re-counted,
        re-logged, or re-pooled — so the run stays bit-identical to the
        unbounded one."""
        n_new = self._pull(wave["n_new"])
        total = int(n_new.sum())
        self._l0_count += total
        if not total:
            return total
        survivors, n_stale = self._harvest_rows(wave, n_new)
        self._wave_stale += n_stale
        return survivors

    def _harvest_rows(self, wave, n_new, overlapped=False):
        """Pull + probe + log + re-pool one wave's compacted fresh
        rows. ONE site for the sync harvest and the async verdict job —
        the key selection, stale gather, and row order must never
        diverge between them (the bit-identical guarantee).
        ``overlapped`` picks the attribution ledger (worker-thread time
        is shadowed, not serial wall). Returns
        ``(survivors, n_stale)``."""
        hi = self._pull(wave["new_hi"])
        # Per-device candidate-lane width of THIS wave (bucketed chunks
        # dispatch below G, so the width is the wave's, not the config's).
        B = hi.shape[0] // self._n
        lo = self._pull(wave["new_lo"])
        ebits = self._pull(wave["new_ebits"])
        depth = self._pull(wave["new_depth"])
        phi = self._pull(wave["parent_hi"])
        plo = self._pull(wave["parent_lo"])
        states = jax.tree_util.tree_map(self._pull, wave["new_states"])
        sel = np.zeros((self._n * B,), bool)
        for d in range(self._n):
            sel[d * B : d * B + int(n_new[d])] = True
        child64 = fp64_pairs(hi, lo)
        par64 = fp64_pairs(phi, plo)
        key64 = None
        if self._symmetry_enabled:
            key64 = fp64_pairs(
                self._pull(wave["new_khi"]), self._pull(wave["new_klo"])
            )
        idx = np.flatnonzero(sel)
        n_stale = 0
        if self._tiers and self._tier_active():
            phase = self._phase_overlapped if overlapped else self._phase
            with phase("host_probe"):
                keys = (key64 if key64 is not None else child64)[idx]
                stale = self._probe_tiers(keys)
            n_stale = int(stale.sum())
            idx = idx[~stale]
        survivors = len(idx)
        self._unique_count += survivors
        if not survivors:
            return 0, n_stale
        self._wave_log.append((child64[idx], par64[idx]))
        if self._symmetry_enabled:
            self._key_log.append(key64[idx])
        self._pool_append(
            {
                "states": jax.tree_util.tree_map(lambda x: x[idx], states),
                "hi": hi[idx],
                "lo": lo[idx],
                "ebits": ebits[idx].astype(np.uint32),
                "depth": depth[idx].astype(np.int32),
            }
        )
        return survivors, n_stale

    def _record_wave_metrics(
        self, span, frontier, generated, n_new, bucket=None,
        compaction_ratio=None, live_lanes=None, stale=None, warm=None,
        state=None, comms=None, exchange_lanes=None,
    ):
        """One host-visible wave's telemetry (the shared bundle does the
        recording; occupancy is the shard tables' resident load — under
        tiering the global unique count outgrows the devices).
        ``stale``/``warm``/``state`` (= (l0, total capacity, max_depth))
        are point-in-time captures the async verdict job passes in — by
        verdict time the live fields describe a later wave (a deferred
        eviction even resets l0 to 0); the synchronous path reads the
        live fields."""
        extra = {}
        if live_lanes is not None:
            # Live (pre-padding) pending rows: the monitor's frontier fit
            # reads this over the dispatch-width `frontier` when present.
            extra["live_lanes"] = live_lanes
        # Exchange ledger args (comms_lanes, sieve kill/FP counts...) ride
        # the wave span for the attribution report and gap_report. The
        # async verdict passes its capture; the sync path reads the last
        # dispatch's (growth retries overwrite — last attempt's is the
        # one whose rows this span's n_new describes).
        cm = comms if comms is not None else self._last_comms
        if cm:
            extra.update(cm)
        if exchange_lanes is not None:
            # Every attempt's exchange, growth retries included.
            extra.update(self._ci.exchange(exchange_lanes))
        if state is not None:
            l0, capacity, depth = state
        else:
            l0, capacity, depth = (
                self._l0_count, self._n * self._cap_loc, self._max_depth
            )
        if self._si is not None:
            self._si.set_l0(l0)
            extra["storage_stale"] = (
                stale if stale is not None else self._wave_stale
            )
            # Worker-exact: tier mutations are FIFO-ordered, so at this
            # job's position the tier state matches the synchronous
            # path's.
            extra["storage_fps"] = sum(t.total_fps for t in self._tiers)
        steady = (
            warm if warm is not None else self.warmup_seconds is not None
        )
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
            compaction_ratio=compaction_ratio,
            **extra,
        )

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
        hi, lo = self._jit_fp_single(self._model.pack_state(host_state))
        return fp_to_int(hi, lo)

    def _ingest_wave_log(self):
        with self._ingest_lock:
            while self._ingested < len(self._wave_log):
                children, parents = self._wave_log[self._ingested]
                self._store.insert_batch(children, parents)
                self._ingested += 1

    def _reconstruct(self, fp: int) -> Path:
        self._ingest_wave_log()
        chain = self._store.chain(fp)
        return Path.from_fingerprints(self._model, chain, fp_of=self._host_fp)

    # -- Checker surface ---------------------------------------------------

    @property
    def pipeline(self) -> str:
        """The expansion pipeline this backend dispatches. The sharded
        wave always materializes the candidate grid (the fps wave has no
        sharded counterpart yet), but the property must exist so
        bench.py's measured-policy mismatch gate is not silently inert
        for sharded legs (``getattr(checker, "pipeline", None)`` =>
        never flags)."""
        return "materialize"

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
        with self._tracer.span("sharded_bfs.paths"):
            out = {
                name: self._reconstruct(fp)
                for name, fp in list(self._discoveries_fp.items())
            }
            out = self._with_device_liveness(out)
            return self._with_lassos(
                out,
                self._done_event.is_set(),
                set(self._discoveries_fp) | set(self._live_paths),
            )

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

    supports_preempt = True

    def request_preempt(self) -> None:
        """Suspend at the next wave/drain boundary into an in-memory
        checkpoint payload (``preempt_payload()``); resume with
        ``resume_from=<payload>``. Same contract as
        ``TpuBfsChecker.request_preempt``."""
        self._preempt_event.set()

    def state_digest(self) -> dict:
        digest = super().state_digest()
        digest.update(
            shards=self._n,
            table_capacity_per_shard=getattr(self, "_cap_loc", None),
            frontier_per_device=self._F_loc,
            warmup_seconds=getattr(self, "warmup_seconds", None),
            checkpoint_path=self._checkpoint_path,
            preempted=self.preempted,
            wave_kernel=self._wave_kernel,
            sieve=self._sieve,
        )
        if self.wave_kernel_reason is not None:
            digest["wave_kernel_reason"] = self.wave_kernel_reason
        if self._sieve:
            digest["comm_sieve"] = {
                "cache_slots": self._sieve_slots,
                "bloom_bits": self._sieve_bits,
            }
        if self._si is not None:
            try:
                digest["storage"] = self._si.bench_stats()
            except Exception:  # noqa: BLE001 - mid-crash best effort
                digest["storage"] = None
        return digest
