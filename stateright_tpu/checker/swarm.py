"""Swarm verification engine: device-width randomized walks for state
spaces beyond the store.

``checker/tpu_simulation.py`` already walks N vmapped lanes, but it is
host-paced: small ``steps_per_call`` round trips, no visited sampling,
no restart dedup, no preemption, no service integration. This module is
the GPUexplore-style swarm mode (PAPERS: "On the Scalability of the
GPUexplore Explicit-State Model Checker") built for state spaces the
PR 5 tiered store cannot enumerate:

- **One long fused scan per wave.** The entire walk loop — per-walk
  threefry PRNG streams (``fold_in(PRNGKey(seed), lane)``), restart /
  boundary / depth / terminal handling, per-lane cycle detection against
  the walk's own trace buffer, property evaluation, and per-property
  discovery capture — runs inside one jitted ``lax.scan`` of
  ``wave_steps`` steps (thousands, not 64). The host touches the device
  once per wave: a single stats pull.
- **A device hash-table sample of walk fingerprints** (``ops/hashset``,
  the duplicate-tolerant scatter-claim insert): every sampled step and
  every restart claim-inserts its fingerprint, which (a) dedups restarts
  (``swarm.restarts_deduped`` counts walks re-entering already-sampled
  states) and (b) yields an honest unique-coverage *estimate* —
  ``unique_state_count()`` is the number of distinct sampled
  fingerprints, reported as a lower bound once the fixed-capacity table
  saturates (``sample_saturated``). The walk dynamics never read the
  table, so the sample is pure observation: results are bit-identical
  at any ``sample_capacity``.
- **Run-anywhere determinism.** The stop decision (every property
  discovered, or ``target_state_count`` reached) is evaluated INSIDE
  the scan and freezes the carry at the exact step it fires, so the
  same seed produces bit-identical discoveries, walk counts, and
  coverage estimates regardless of ``wave_steps`` chunking, across
  preempt/resume (the checkpoint-v3 ``swarm`` payload slice carries the
  PRNG keys and walk buffers verbatim), and packed-vs-solo (a packed
  tenant's slot computes exactly the solo carry under ``vmap``).
- **Frontier-seeded hybrid mode.** ``seeds=`` accepts a packed-state
  pool — e.g. ``frontier_seeds_from_payload`` applied to a
  budget-exhausted ``TpuBfsChecker`` preempt payload — and walk
  restarts draw from that pool instead of the init states: the
  exhaustive run maps the space it can afford, the swarm hunts beyond
  its live frontier. Seeded discoveries replay from their seed state
  (the path *fragment* past the frontier; the prefix lives in the
  exhaustive run's store).

``SwarmEngine`` is the shared multi-tenant kernel (max_tenants slots
over one stacked dispatch — walks are lane-independent, so tenant
packing is exact by ``vmap`` semantics); ``SwarmChecker`` is the solo
``Checker`` facade ``spawn_swarm`` returns; ``SwarmPackedEngine`` is
the service packer's engine (admit / step / drop / release — the
``TenantPackedEngine`` protocol).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batch import BatchableModel
from ..core.path import Path
from ..ops.fingerprint import fp_to_int
from ..ops.hashset import hashset_insert_unsorted, hashset_new
from ..telemetry import get_tracer, metrics_registry
from ..utils.faults import TenantFaultError, fault_point
from .base import Checker
from .tpu import checkpoint_header, validate_checkpoint_header
from .tpu_simulation import (
    capture_discoveries,
    walk_kernel_surface,
    walk_lane_step,
)

__all__ = [
    "SwarmChecker",
    "SwarmEngine",
    "SwarmPackedEngine",
    "frontier_seeds_from_payload",
]

# Runtime "no cap/target" sentinels (per-tenant scalars in the carry, so
# one compiled wave serves every tenant's depth cap and state target).
_NO_CAP = np.int32(2**31 - 1)
_NO_TARGET = np.int32(-1)

# Shared wave executables across engines of one zoo configuration: the
# second same-shape swarm job (and every preempted job's next
# incarnation) compiles nothing. Keyed on the AOT namespace plus every
# shape-determining knob; entries hold the jitted stacked-wave fn.
# Bounded like the service's model cache — a long-lived service fed
# many distinct configurations must not pin executables forever.
_WAVE_FN_CACHE: Dict[tuple, object] = {}
_WAVE_FN_CACHE_MAX = 32


def frontier_seeds_from_payload(model, payload: dict):
    """Extracts the LIVE frontier states from a ``TpuBfsChecker``
    checkpoint/preempt payload as a swarm restart-seed pool (stacked
    packed states, numpy leaves). This is the hybrid handoff: a
    budget-exhausted exhaustive run's pending frontier becomes the
    swarm's restart distribution, so walks start where enumeration
    stopped instead of re-rolling the shallow region it already
    certified."""
    if payload.get("kind") not in ("tpu_bfs",):
        raise ValueError(
            f"frontier seeds need a tpu_bfs payload, got kind="
            f"{payload.get('kind')!r}"
        )
    if payload.get("model") != type(model).__name__:
        raise ValueError(
            f"payload was written by model {payload.get('model')!r}, "
            f"seeding walks of {type(model).__name__!r} would mix state "
            "spaces"
        )
    parts = []
    for chunk in payload.get("chunks", ()):
        mask = np.asarray(chunk["mask"]).astype(bool)
        if not mask.any():
            continue
        parts.append(
            jax.tree_util.tree_map(
                lambda x: np.asarray(x)[mask], chunk["states"]
            )
        )
    if not parts:
        raise ValueError(
            "payload has no live frontier lanes to seed from (the run "
            "finished; there is nothing beyond the store to hunt)"
        )
    return jax.tree_util.tree_map(
        lambda *xs: np.concatenate(xs, axis=0), *parts
    )


class _WalkKernel:
    """The pure compute core: everything the jitted wave closes over —
    model, conditions, seed pool, shapes — and NOTHING else. Kept
    separate from ``SwarmEngine`` so the shared-executable cache pins
    only this (the model and seeds it genuinely needs), never the
    engine's multi-MB device carry or its metric instruments."""

    def __init__(self, model, *, lanes, wave_steps, max_trace_len,
                 sample_capacity, sample_stride, seeds,
                 coverage_layout):
        if not isinstance(model, BatchableModel):
            raise TypeError(
                f"the swarm engine requires a BatchableModel; "
                f"{type(model).__name__} does not implement the packed "
                "protocol"
            )
        if sample_capacity & (sample_capacity - 1):
            raise ValueError("sample_capacity must be a power of two")
        self._model = model
        (
            self._properties,
            self._conditions,
            self._ebit,
            self._ebits0,
        ) = walk_kernel_surface(model)
        self._A = model.packed_action_count()
        self._P = len(self._properties)
        self._L = int(lanes)
        self._K = int(wave_steps)
        self._D = int(max_trace_len)
        self._cap = int(sample_capacity)
        self._stride = max(1, int(sample_stride))
        self._cov_layout = coverage_layout
        if coverage_layout is not None:
            try:
                ants = list(model.packed_antecedents())
            except Exception:  # noqa: BLE001 - optional hook
                ants = [None] * self._P
            self._cov_antecedents = ants
        self._fp_fn = model.packed_fingerprint

        # Restart-seed pool: the model's init states by default, or the
        # hybrid frontier pool. Closed over by the jit as a constant.
        if seeds is None:
            seeds = model.packed_init_states()
            self._seeded = False
        else:
            self._seeded = True
        self._seeds = jax.tree_util.tree_map(jnp.asarray, seeds)
        self._n_seeds = int(
            jax.tree_util.tree_leaves(self._seeds)[0].shape[0]
        )
        if self._n_seeds < 1:
            raise ValueError("the restart-seed pool is empty")
        # Host mirrors for seeded-path replay: fp -> host state of each
        # seed, so a discovery whose walk started mid-space can still be
        # replayed into a concrete Path fragment. The digest pins the
        # pool's CONTENT in cache keys and checkpoint payloads — a
        # same-shape but different pool must never be substituted (the
        # walk sequence would silently diverge).
        self._seed_host = jax.tree_util.tree_map(np.asarray, self._seeds)
        from hashlib import blake2b

        h = blake2b(digest_size=8)
        for leaf in jax.tree_util.tree_leaves(self._seed_host):
            arr = np.asarray(leaf)
            h.update(str(arr.shape).encode())
            h.update(str(arr.dtype).encode())
            h.update(arr.tobytes())
        self.seeds_digest = h.hexdigest()

    # -- carry shape ------------------------------------------------------

    def _blank_tenant(self):
        L, D, P = self._L, self._D, self._P
        inits = self._model.packed_init_states()
        return {
            "lanes": {
                "state": jax.tree_util.tree_map(
                    lambda x: jnp.zeros((L,) + x.shape[1:], x.dtype), inits
                ),
                "depth": jnp.zeros((L,), jnp.int32),
                "ebits": jnp.zeros((L,), jnp.uint32),
                "done": jnp.ones((L,), bool),  # all lanes restart on step 1
                "thi": jnp.zeros((L, D), jnp.uint32),
                "tlo": jnp.zeros((L, D), jnp.uint32),
                "key": jnp.zeros((L, 2), jnp.uint32),
            },
            "table": hashset_new(self._cap),
            "disc": {
                "found": jnp.zeros((P,), bool),
                "hi": jnp.zeros((P, D), jnp.uint32),
                "lo": jnp.zeros((P, D), jnp.uint32),
                "len": jnp.zeros((P,), jnp.int32),
            },
            "stats": {
                "step": jnp.int32(0),
                "count": jnp.int32(0),
                "max_depth": jnp.int32(0),
                "walks": jnp.int32(0),
                "restarts": jnp.int32(0),
                "restart_dups": jnp.int32(0),
                "overflow": jnp.int32(0),
                "sample_unique": jnp.int32(0),
                "sample_sat": jnp.bool_(False),
                # Free slots are born stopped: the wave freezes them.
                "stopped": jnp.bool_(True),
            },
            "depth_cap": jnp.int32(_NO_CAP),
            "target": jnp.int32(_NO_TARGET),
            **(
                {"cov": jnp.zeros((self._cov_layout.size,), jnp.int32)}
                if self._cov_layout is not None
                else {}
            ),
        }

    # -- the fused walk kernel ----------------------------------------------

    def _lane_step(self, state, depth, ebits, done, thi, tlo, key,
                   depth_cap):
        """One walk step for a single lane (vmapped over L); the body is
        the ``walk_lane_step`` core shared with ``TpuSimulationChecker``
        — the swarm passes the runtime depth cap and its restart pool,
        and consumes the truncation/restart/coverage outputs the
        simulation checker's scan drops."""
        return walk_lane_step(
            self, self._seeds, self._n_seeds, state, depth, ebits, done,
            thi, tlo, key, depth_cap,
        )

    def _tenant_step(self, c):
        """One fused step for a whole tenant (lane vmap + sample insert
        + discovery capture + in-scan stop). The stop flag freezes the
        carry exactly: chunking into waves can never change results."""
        i32 = jnp.int32
        stats = c["stats"]
        stopped = stats["stopped"]

        out = jax.vmap(
            self._lane_step, in_axes=(0, 0, 0, 0, 0, 0, 0, None)
        )(
            c["lanes"]["state"],
            c["lanes"]["depth"],
            c["lanes"]["ebits"],
            c["lanes"]["done"],
            c["lanes"]["thi"],
            c["lanes"]["tlo"],
            c["lanes"]["key"],
            c["depth_cap"],
        )

        # Sample the visited multiset: every ``sample_stride``-th step
        # plus every restart (restart dedup must never be strided away).
        sample = out["write"] & (
            ((stats["step"] % i32(self._stride)) == 0) | out["restarted"]
        )
        table, fresh, found, pending = hashset_insert_unsorted(
            c["table"], out["hi"], out["lo"], sample
        )

        # SATURATING step counter: the count is carried across waves
        # (the in-scan stop needs it), so past ~2.15B lane-steps it
        # pins at INT32_MAX instead of wrapping negative — targets are
        # validated < 2^31 at admission, so the stop logic never needs
        # the saturated range. (tpu_simulation.py dodges this by
        # zeroing per call; a fused scan cannot.)
        count_inc = stats["count"] + out["counted"].sum(dtype=i32)
        new_stats = {
            "step": stats["step"] + 1,
            "count": jnp.where(
                count_inc < stats["count"],
                jnp.int32(2**31 - 1),
                count_inc,
            ),
            "max_depth": jnp.maximum(
                stats["max_depth"], out["path_len"].max()
            ),
            "walks": stats["walks"] + out["done"].sum(dtype=i32),
            "restarts": stats["restarts"]
            + out["restarted"].sum(dtype=i32),
            "restart_dups": stats["restart_dups"]
            + (out["restarted"] & found).sum(dtype=i32),
            "overflow": stats["overflow"]
            + out["truncated"].sum(dtype=i32),
            "sample_unique": stats["sample_unique"]
            + fresh.sum(dtype=i32),
            "sample_sat": stats["sample_sat"] | pending.any(),
        }

        disc = c["disc"]
        P = self._P
        if P:
            disc = capture_discoveries(disc, out, P)
            all_found = disc["found"].all()
        else:
            all_found = jnp.bool_(False)
        target = c["target"]
        new_stats["stopped"] = all_found | (
            (target >= 0) & (new_stats["count"] >= target)
        )

        new_c = {
            "lanes": {
                k: out[k]
                for k in (
                    "state", "depth", "ebits", "done", "thi", "tlo", "key"
                )
            },
            "table": table,
            "disc": disc,
            "stats": new_stats,
            "depth_cap": c["depth_cap"],
            "target": c["target"],
        }
        if self._cov_layout is not None:
            new_c["cov"] = c["cov"] + self._cov_layout.wave_reduce(
                eval_mask=out["counted"],
                cvalid=out["cvalid"],
                fresh=out["advanced"],
                lane_action=out["choice"],
                new_depth=out["depth"],
                exercised=[
                    out["exercised"][:, i] for i in range(self._P)
                ],
            )
        # Freeze-on-stop: a stopped tenant's slot passes through
        # untouched (PRNG keys included), so results are independent of
        # how many extra wave steps the fleet runs past its stop.
        return jax.tree_util.tree_map(
            lambda old, new: jnp.where(stopped, old, new), c, new_c
        )

    def _tenant_wave(self, c):
        return jax.lax.scan(
            lambda carry, _: (self._tenant_step(carry), None),
            c,
            None,
            length=self._K,
        )[0]



class SwarmEngine:
    """The shared device kernel: ``max_tenants`` walk fleets advance in
    one stacked jitted dispatch. Tenant slots are independent lane
    blocks — admission writes a slot's carry, a wave advances every
    non-stopped slot by ``wave_steps`` fused steps, and a drop reads the
    slot back out as a checkpoint-v3 payload slice. Because slots never
    interact (separate PRNG streams, separate sample tables, per-tenant
    stop flags), a tenant's results are bit-identical solo or packed.
    """

    def __init__(
        self,
        model,
        *,
        lanes: int = 1024,
        wave_steps: int = 1024,
        max_trace_len: int = 256,
        sample_capacity: int = 1 << 15,
        sample_stride: int = 1,
        max_tenants: int = 1,
        seeds=None,
        coverage_layout=None,
        aot_cache: Optional[str] = None,
        tracer=None,
        registry=None,
    ):
        self._k = _WalkKernel(
            model, lanes=lanes, wave_steps=wave_steps,
            max_trace_len=max_trace_len,
            sample_capacity=sample_capacity,
            sample_stride=sample_stride, seeds=seeds,
            coverage_layout=coverage_layout,
        )
        k = self._k
        # Mirrored views of the kernel's static facts (one source of
        # truth; the engine adds only mutable run state on top).
        self._model = k._model
        self._properties = k._properties
        self._cov_layout = k._cov_layout
        self._fp_fn = k._fp_fn
        self._seeded = k._seeded
        self._seeds = k._seeds
        self._seed_host = k._seed_host
        self._n_seeds = k._n_seeds
        self._A, self._P = k._A, k._P
        self._L, self._K, self._D = k._L, k._K, k._D
        self._cap, self._stride = k._cap, k._stride
        self._T = max(1, int(max_tenants))
        self._tracer = tracer if tracer is not None else get_tracer()
        self._registry = (
            registry if registry is not None else metrics_registry()
        )
        self._wave_calls = 0

        # Engine-level instruments (per-tenant registries get their own
        # families from the views).
        reg = self._registry
        self._m_waves = reg.counter("swarm.wave_calls")
        self._m_steps = reg.counter("swarm.walk_steps")
        self._m_walks = reg.counter("swarm.walks_completed")
        self._m_restarts = reg.counter("swarm.restarts")
        self._m_restart_dups = reg.counter("swarm.restarts_deduped")
        self._m_overflow = reg.counter("swarm.trace_overflow")
        self._m_unique = reg.counter("swarm.unique_sample")
        self._g_sat = reg.gauge("swarm.sample_saturated")
        self._g_occ = reg.gauge("swarm.sample_occupancy")
        self._h_hit_depth = reg.histogram("swarm.hit_depth")

        self._wave_fn = self._build_wave_fn(aot_cache)
        self._carry = self._blank_carry()
        # Last pulled per-tenant stats (numpy), refreshed each wave.
        self._stats_host = jax.device_get(self._carry["stats"])
        self._disc_found_host = np.asarray(self._carry["disc"]["found"])
        self.warmup_seconds: Optional[float] = None

    # -- carry construction -------------------------------------------------

    def _blank_carry(self):
        one = self._k._blank_tenant()
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x[None], (self._T,) + x.shape
            ).copy(),
            one,
        )

    def fresh_tenant_carry(self, seed: int, depth_cap=None, target=None):
        """A new tenant slot's carry: per-walk threefry streams derived
        from ``fold_in(PRNGKey(seed), lane)`` — independent of slot
        index and fleet width, which is the packed-vs-solo bit-identity
        story."""
        c = self._k._blank_tenant()
        base = jax.random.PRNGKey(int(seed))
        c["lanes"]["key"] = jax.vmap(
            lambda i: jax.random.fold_in(base, i)
        )(jnp.arange(self._L)).astype(jnp.uint32)
        c["stats"]["stopped"] = jnp.bool_(False)
        if depth_cap is not None:
            if not 0 < int(depth_cap) < 2**31:
                raise ValueError(
                    f"target_max_depth={depth_cap} out of the int32 "
                    "range the walk carry uses"
                )
            c["depth_cap"] = jnp.int32(int(depth_cap))
        if target is not None:
            if not 0 < int(target) < 2**31:
                # int32 would silently wrap a >=2^31 target negative —
                # which the in-scan stop reads as NO target at all.
                raise ValueError(
                    f"target_state_count={target} exceeds the int32 "
                    "walk counter; split the budget across resumed "
                    "runs"
                )
            c["target"] = jnp.int32(int(target))
        return c

    def write_slot(self, t: int, tenant_carry) -> None:
        self._carry = jax.tree_util.tree_map(
            lambda full, one: full.at[t].set(one), self._carry, tenant_carry
        )
        # The written slot's stats/found flags are already in
        # tenant_carry: update the host mirrors in place (fresh copies —
        # run_wave's delta baseline may still reference the old arrays)
        # instead of a fleet-wide blocking device pull per admit/drop.
        stats = {}
        for k, arr in self._stats_host.items():
            arr = np.array(arr)
            arr[t] = np.asarray(tenant_carry["stats"][k])
            stats[k] = arr
        self._stats_host = stats
        found = np.array(self._disc_found_host)
        found[t] = np.asarray(tenant_carry["disc"]["found"])
        self._disc_found_host = found

    def read_slot(self, t: int):
        return jax.tree_util.tree_map(
            lambda x: np.asarray(x[t]), self._carry
        )

    def clear_slot(self, t: int) -> None:
        self.write_slot(t, self._k._blank_tenant())

    def _build_wave_fn(self, aot_cache):
        # The cached fn closes over the KERNEL (model + seed pool —
        # never the engine's carry or instruments), so the key pins the
        # model by IDENTITY — a config digest cannot distinguish models
        # whose packed shapes match but whose transition logic differs
        # (e.g. ShardedKv guarded vs unguarded); the cache entry's
        # closure keeps the model alive so the id stays stable — and
        # the seeds by CONTENT (they are data: content-equal pools are
        # interchangeable, and the service's per-namespace model cache
        # makes same-config engines share one instance, which is where
        # the compile-free second job comes from).
        key = None
        if aot_cache is not None:
            k = self._k
            key = (
                aot_cache, id(self._model), k.seeds_digest,
                self._T, self._L, self._D, self._K, self._cap,
                self._stride, self._A, self._P,
                self._cov_layout is not None,
            )
            fn = _WAVE_FN_CACHE.get(key)
            if fn is not None:
                return fn
        fn = jax.jit(jax.vmap(self._k._tenant_wave))
        if key is not None:
            _WAVE_FN_CACHE[key] = fn
            while len(_WAVE_FN_CACHE) > _WAVE_FN_CACHE_MAX:
                _WAVE_FN_CACHE.pop(next(iter(_WAVE_FN_CACHE)))
        return fn

    # -- wave dispatch ------------------------------------------------------

    def run_wave(self) -> None:
        """One stacked wave: every non-stopped tenant advances by
        ``wave_steps`` fused steps; one stats pull lands the per-tenant
        deltas and feeds the engine instruments plus the monitor's wave
        stream."""
        fault_point("swarm.wave")
        self._wave_calls += 1
        prev = self._stats_host
        warm = self.warmup_seconds is None
        t0 = time.perf_counter()
        with self._tracer.span(
            "swarm.wave", call=self._wave_calls, tenants=self._T,
            lanes=self._L, wave_steps=self._K,
        ) as sp:
            self._carry = self._wave_fn(self._carry)
            stats = jax.device_get(self._carry["stats"])
            self._disc_found_host = np.asarray(self._carry["disc"]["found"])
            d_steps = int(stats["count"].sum() - prev["count"].sum())
            d_unique = int(
                stats["sample_unique"].sum() - prev["sample_unique"].sum()
            )
            live = int((~stats["stopped"]).sum()) * self._L
            sp.set(
                states=d_steps,
                generated=d_steps,
                new_unique=d_unique,
                live_lanes=live,
                max_depth=int(stats["max_depth"].max()),
            )
        if warm:
            self.warmup_seconds = time.perf_counter() - t0
        self._stats_host = stats
        self._m_waves.inc()
        self._m_steps.inc(d_steps)
        self._m_unique.inc(max(0, d_unique))
        for field, counter in (
            ("walks", self._m_walks),
            ("restarts", self._m_restarts),
            ("restart_dups", self._m_restart_dups),
            ("overflow", self._m_overflow),
        ):
            counter.inc(max(0, int(stats[field].sum() - prev[field].sum())))
        self._g_sat.set(int(stats["sample_sat"].any()))
        self._g_occ.set(
            float(stats["sample_unique"].max()) / float(self._cap)
        )

    # -- per-tenant host views ---------------------------------------------

    def tenant_stats(self, t: int) -> dict:
        """The slot's cumulative host-visible numbers (idempotent reads
        of the last pull — a missed absorb self-heals next wave)."""
        s = self._stats_host
        return {k: v[t].item() for k, v in s.items()}

    def tenant_found_names(self, t: int) -> List[str]:
        flags = self._disc_found_host[t]
        return [
            p.name for i, p in enumerate(self._properties) if flags[i]
        ]

    def tenant_discoveries_fps(self, t: int):
        """Pulls the slot's discovery trace buffers and materializes
        fp lists per discovered property (empty walks — a seed already
        out of boundary — settle the property with no path, matching
        the host simulation semantics)."""
        disc = jax.tree_util.tree_map(
            lambda x: np.asarray(x[t]), self._carry["disc"]
        )
        fps: Dict[str, List[int]] = {}
        empty = set()
        hi = disc["hi"].astype(np.uint64)
        lo = disc["lo"].astype(np.uint64)
        for i, p in enumerate(self._properties):
            if not disc["found"][i]:
                continue
            n = int(disc["len"][i])
            if n == 0:
                empty.add(p.name)
                continue
            fps[p.name] = (
                (hi[i, :n] << np.uint64(32)) | lo[i, :n]
            ).tolist()
        return fps, empty

    def export_slot_payload(self, t: int, seed: int, run_state: dict):
        """The slot as a checkpoint-v3 payload slice: standard header +
        the ``swarm`` extension carrying PRNG keys and walk buffers
        verbatim. Resuming (solo or into a later pack) continues the
        exact walk sequence — bit-identical to an uninterrupted run."""
        slot = self.read_slot(t)
        stats = {k: v.item() for k, v in slot["stats"].items()}
        payload = {
            **checkpoint_header("swarm", self._model, self._A, False),
            "version": 3,
            "state_count": int(stats["count"]),
            "unique_count": int(stats["sample_unique"]),
            "max_depth": int(stats["max_depth"]),
            "swarm": {
                "slot": slot,
                "seed": int(seed),
                "lanes": self._L,
                "max_trace_len": self._D,
                "sample_capacity": self._cap,
                "sample_stride": self._stride,
                "seeded": self._seeded,
                # Pool CONTENT, not just the flag: resuming into a
                # same-shape but different restart pool would silently
                # diverge the walk sequence.
                "seeds_digest": self._k.seeds_digest,
                **run_state,
            },
        }
        return payload

    def restore_slot_carry(self, payload: dict):
        """Validates a swarm payload against this engine's model and
        shapes and returns the tenant carry it froze."""
        validate_checkpoint_header(
            payload,
            "swarm",
            "exhaustive checkpoints carry a frontier queue, not walk "
            "buffers; use frontier_seeds_from_payload for the hybrid "
            "handoff instead",
            self._model,
            self._A,
            False,
        )
        sw = payload["swarm"]
        for knob, mine in (
            ("lanes", self._L),
            ("max_trace_len", self._D),
            ("sample_capacity", self._cap),
            ("sample_stride", self._stride),
            ("seeded", self._seeded),
            ("seeds_digest", self._k.seeds_digest),
        ):
            if sw.get(knob) != mine:
                raise ValueError(
                    f"swarm payload {knob}={sw.get(knob)!r} does not "
                    f"match this engine ({mine!r}); the walk sequence "
                    "would diverge from the original run"
                )
        # Coverage is a carry-SHAPE knob too (the cov vector is a slot
        # leaf): refuse a flag mismatch explicitly instead of failing
        # with an opaque pytree/KeyError inside write_slot.
        had_cov = "cov" in sw["slot"]
        want_cov = self._cov_layout is not None
        if had_cov != want_cov:
            raise ValueError(
                f"swarm payload coverage={had_cov} does not match this "
                f"engine (coverage={want_cov}); resume with the same "
                "coverage setting the run was spawned with"
            )
        return jax.tree_util.tree_map(jnp.asarray, sw["slot"])


class SwarmChecker(Checker):
    """The solo swarm run ``spawn_swarm`` returns: one engine slot, a
    worker thread driving waves until every property has a discovery or
    ``target_state_count`` is reached (reference simulation semantics),
    with preempt/resume and the full Checker surface."""

    supports_preempt = True
    # Honest capability surface (the PR 12 pattern): swarm jobs pack —
    # lane blocks over one shared dispatch (``SwarmPackedEngine``).
    supports_packing = True
    packing_reason = None

    def __init__(
        self,
        options,
        seed: int,
        lanes: int = 1024,
        wave_steps: int = 1024,
        max_trace_len: Optional[int] = None,
        sample_capacity: int = 1 << 15,
        sample_stride: int = 1,
        seeds=None,
        resume_from=None,
        coverage: bool = False,
        run_id=None,
        aot_cache: Optional[str] = None,
    ):
        model = options.model
        if not isinstance(model, BatchableModel):
            raise TypeError(
                f"spawn_swarm requires a BatchableModel; "
                f"{type(model).__name__} does not implement the packed "
                "protocol"
            )
        if options._symmetry is not None:
            raise NotImplementedError(
                "symmetry-aware cycle detection is host-only; use "
                "spawn_simulation for symmetric models"
            )
        if options._visitor is not None:
            raise NotImplementedError(
                "per-state visitors replay O(depth²) host paths; use "
                "spawn_simulation for visitor-driven runs"
            )
        self._model = model
        self._properties = model.properties()
        self.run_id = run_id
        self._registry = metrics_registry(run_id) if run_id else None
        self._tracer = get_tracer(run_id)
        self._seed = int(seed)
        self._depth_cap = options._target_max_depth
        self._target = options._target_state_count
        # Trace-buffer depth: an explicit ``max_trace_len``, else the
        # user's depth cap (capped walks are then a semantic bound),
        # else the default. The cap itself is a RUNTIME scalar in the
        # carry — one buffer shape serves every cap, which is what keeps
        # solo and service-packed runs bit-identical. Walks hitting the
        # buffer below the cap are TRUNCATED and counted
        # (``swarm.trace_overflow``).
        D = max_trace_len or (self._depth_cap or 512)

        cov_layout = None
        if coverage:
            from ..telemetry.coverage import DeviceCoverage

            cov_layout = DeviceCoverage(
                model.packed_action_count(), len(self._properties)
            )
        if isinstance(seeds, dict) and "chunks" in seeds:
            seeds = frontier_seeds_from_payload(model, seeds)
        self._engine = SwarmEngine(
            model,
            lanes=lanes,
            wave_steps=wave_steps,
            max_trace_len=D,
            sample_capacity=sample_capacity,
            sample_stride=sample_stride,
            max_tenants=1,
            seeds=seeds,
            coverage_layout=cov_layout,
            aot_cache=aot_cache,
            tracer=self._tracer,
            registry=self.metrics(),
        )
        if coverage:
            self._init_coverage(
                "swarm", True, model.packed_action_count()
            )
            self._cov_last = np.zeros(
                (cov_layout.size,), np.int64
            )
        if resume_from is not None:
            carry = self._engine.restore_slot_carry(resume_from)
            if coverage:
                # The restored carry's cov vector is CUMULATIVE over the
                # pre-preempt run, and the previous incarnation already
                # consumed it into this run_id's registry — baseline the
                # delta here or resume double-counts the whole prefix.
                self._cov_last = np.asarray(
                    carry["cov"], dtype=np.int64
                )
        else:
            carry = self._engine.fresh_tenant_carry(
                self._seed,
                depth_cap=self._depth_cap,
                target=self._target,
            )
        self._engine.write_slot(0, carry)

        self._state_count = 0
        self._max_depth = 0
        self._unique_sample = 0
        self._sample_saturated = False
        self._trace_overflows = 0
        self._discoveries_fps: Dict[str, List[int]] = {}
        self._empty_discoveries: set = set()
        self._found_names: List[str] = []
        self._preempt_event = threading.Event()
        self._done_event = threading.Event()
        self._error: Optional[BaseException] = None
        self._jit_fp_single = jax.jit(model.packed_fingerprint)

        self._handles = [
            threading.Thread(target=self._run, name="swarm", daemon=True)
        ]
        self._handles[0].start()

    @property
    def warmup_seconds(self):
        return self._engine.warmup_seconds

    # -- worker loop --------------------------------------------------------

    def _run(self):
        try:
            self._explore()
        except BaseException as e:  # noqa: BLE001 - via worker_error
            self._error = e
            self._abort_attribution()
        finally:
            self._finalize_coverage(set(self._discoveries_fps))
            self._done_event.set()

    def _absorb_stats(self):
        s = self._engine.tenant_stats(0)
        self._state_count = int(s["count"])
        self._max_depth = int(s["max_depth"])
        self._unique_sample = int(s["sample_unique"])
        self._sample_saturated = bool(s["sample_sat"])
        self._trace_overflows = int(s["overflow"])
        self._found_names = self._engine.tenant_found_names(0)
        if self._cov is not None:
            vec = np.asarray(
                self._engine._carry["cov"][0], dtype=np.int64
            )
            delta = vec - self._cov_last
            self._cov_last = vec
            self._cov.consume_device(
                delta, self._engine._cov_layout,
                first_attempt=True, max_depth=self._max_depth,
            )
            self._cov.emit_wave_span()
        return s

    def _explore(self):
        if not self._properties and self._target is None:
            return
        while True:
            self._engine.run_wave()
            s = self._absorb_stats()
            if self._preempt_event.is_set() and not s["stopped"]:
                self._preempt_payload = self._engine.export_slot_payload(
                    0, self._seed, {}
                )
                return
            if s["stopped"]:
                fps, empty = self._engine.tenant_discoveries_fps(0)
                self._discoveries_fps = fps
                self._empty_discoveries = empty
                for name, trail in fps.items():
                    self._engine._h_hit_depth.observe(len(trail))
                return

    # -- path reconstruction ------------------------------------------------

    def _host_fp(self, host_state) -> int:
        hi, lo = self._jit_fp_single(self._model.pack_state(host_state))
        return fp_to_int(hi, lo)

    _seed_fp_map = None

    def _replay(self, fps: List[int]) -> Path:
        if not self._engine._seeded:
            return Path.from_fingerprints(
                self._model, fps, fp_of=self._host_fp
            )
        # Seeded walks start mid-space: find the seed whose fingerprint
        # opens the trail and replay the fragment from there. The
        # fp -> seed-index map is one vmapped fingerprint pass, built on
        # first replay.
        if self._seed_fp_map is None:
            hi, lo = jax.jit(jax.vmap(self._engine._fp_fn))(
                self._engine._seeds
            )
            fps64 = (
                np.asarray(hi).astype(np.uint64) << np.uint64(32)
            ) | np.asarray(lo).astype(np.uint64)
            fp_map: Dict[int, int] = {}
            for i, f in enumerate(fps64.tolist()):
                fp_map.setdefault(int(f), i)
            self._seed_fp_map = fp_map
        idx = self._seed_fp_map.get(int(fps[0]))
        if idx is None:
            raise RuntimeError(
                "seeded discovery trail does not start at any seed "
                "state (the seed pool changed between run and replay?)"
            )
        packed = jax.tree_util.tree_map(
            lambda x: x[idx], self._engine._seed_host
        )
        state = self._model.unpack_state(packed)
        return _path_from_state(self._model, state, fps, self._host_fp)

    # -- Checker surface ----------------------------------------------------

    def model(self):
        return self._model

    def state_count(self) -> int:
        return self._state_count

    def unique_state_count(self) -> int:
        # The honest coverage estimate: distinct sampled walk
        # fingerprints — a LOWER bound once the sample table saturates
        # (``sample_saturated`` / ``coverage_estimate()``), never the
        # reference's total-count approximation.
        return self._unique_sample

    def coverage_estimate(self) -> dict:
        """The unique-coverage sample: distinct fingerprints observed,
        whether the fixed-capacity table saturated (the estimate is then
        a lower bound), and the raw walk-step total for context."""
        return {
            "unique_sample": self._unique_sample,
            "saturated": self._sample_saturated,
            "walk_steps": self._state_count,
            "sample_capacity": self._engine._cap,
        }

    def max_depth(self) -> int:
        return self._max_depth

    def discoveries(self) -> Dict[str, Path]:
        return {
            name: self._replay(fps)
            for name, fps in list(self._discoveries_fps.items())
        }

    def _discovery_names(self) -> List[str]:
        return list(self._found_names)

    def handles(self) -> List[threading.Thread]:
        handles, self._handles = self._handles, []
        return handles

    def is_done(self) -> bool:
        return self._done_event.is_set()

    def worker_error(self) -> Optional[BaseException]:
        return self._error

    def request_preempt(self) -> None:
        self._preempt_event.set()

    def state_digest(self) -> dict:
        digest = super().state_digest()
        digest["swarm"] = {
            "lanes": self._engine._L,
            "wave_steps": self._engine._K,
            "sample": self.coverage_estimate(),
            "trace_overflows": self._trace_overflows,
        }
        return digest


def _path_from_state(model, start_state, fps: List[int], fp_of) -> Path:
    """``Path.from_fingerprints`` from an arbitrary start state (the
    hybrid mode's seeded walks do not begin at an init state)."""
    if fp_of(start_state) != fps[0]:
        raise ValueError("start state does not match the trail head")
    output = []
    last_state = start_state
    for next_fp in fps[1:]:
        found = None
        for a, s in model.next_steps(last_state):
            if fp_of(s) == next_fp:
                found = (a, s)
                break
        if found is None:
            raise RuntimeError(
                f"seeded walk replay diverged at fingerprint {next_fp}"
            )
        output.append((last_state, found[0]))
        last_state = found[1]
    output.append((last_state, None))
    return Path(output)


class _TenantWalkView(Checker):
    """A packed swarm tenant's Checker-shaped view: cumulative counts,
    discovery names, and (once the tenant stops) full discovery paths —
    what the service's ``_finalize`` consumes."""

    supports_preempt = True
    supports_packing = True
    packing_reason = None

    def __init__(self, pack: "SwarmPackedEngine", key: str, slot: int,
                 run_id=None):
        self._pack = pack
        self._key = key
        self._slot = slot
        self._model = pack._engine._model
        self.run_id = run_id
        self._registry = metrics_registry(run_id) if run_id else None
        self._tracer = get_tracer(run_id)
        self._stats: dict = {}
        self._found: List[str] = []
        self._fps: Dict[str, List[int]] = {}
        self._stopped = False
        self._last = {}
        reg = self.metrics()
        self._m = {
            "count": reg.counter("swarm.walk_steps"),
            "walks": reg.counter("swarm.walks_completed"),
            "restarts": reg.counter("swarm.restarts"),
            "restart_dups": reg.counter("swarm.restarts_deduped"),
            "overflow": reg.counter("swarm.trace_overflow"),
            "sample_unique": reg.counter("swarm.unique_sample"),
        }

    @property
    def warmup_seconds(self):
        return self._pack._engine.warmup_seconds

    def _prime(self, stats: dict, found_names: List[str]) -> None:
        """Admission-time baseline: a RESUMED slot's cumulative totals
        were already recorded into this run's registry by the previous
        incarnation — seed ``_last`` so only post-admission deltas
        count (a fresh slot's zeros make this a no-op)."""
        self._stats = stats
        self._found = found_names
        self._stopped = bool(stats.get("stopped"))
        for field in self._m:
            self._last[field] = int(stats.get(field, 0))

    def _absorb(self, stats: dict, found_names: List[str]) -> None:
        self._stats = stats
        self._found = found_names
        self._stopped = bool(stats.get("stopped"))
        for field, counter in self._m.items():
            cur = int(stats.get(field, 0))
            prev = self._last.get(field, 0)
            if cur > prev:
                counter.inc(cur - prev)
                self._last[field] = cur

    def _finish(self, fps: Dict[str, List[int]]) -> None:
        self._fps = fps
        self._stopped = True

    @property
    def _trace_overflows(self) -> int:
        return int(self._stats.get("overflow", 0))

    def model(self):
        return self._model

    def state_count(self) -> int:
        return int(self._stats.get("count", 0))

    def unique_state_count(self) -> int:
        return int(self._stats.get("sample_unique", 0))

    def coverage_estimate(self) -> dict:
        return {
            "unique_sample": self.unique_state_count(),
            "saturated": bool(self._stats.get("sample_sat", False)),
            "walk_steps": self.state_count(),
            "sample_capacity": self._pack._engine._cap,
        }

    def max_depth(self) -> int:
        return int(self._stats.get("max_depth", 0))

    def _host_fp(self, host_state) -> int:
        hi, lo = self._pack._jit_fp_single(
            self._model.pack_state(host_state)
        )
        return fp_to_int(hi, lo)

    def discoveries(self) -> Dict[str, Path]:
        return {
            name: Path.from_fingerprints(
                self._model, fps, fp_of=self._host_fp
            )
            for name, fps in list(self._fps.items())
        }

    def _discovery_names(self) -> List[str]:
        return list(self._found)

    def handles(self) -> List[threading.Thread]:
        return []

    def is_done(self) -> bool:
        return self._stopped

    def worker_error(self) -> Optional[BaseException]:
        return None

    def state_digest(self) -> dict:
        digest = super().state_digest()
        digest["swarm"] = {"packed": True, **self.coverage_estimate()}
        return digest


class SwarmPackedEngine:
    """The service packer's swarm engine: up to ``max_tenants`` swarm
    jobs co-schedule onto ONE stacked wave dispatch. Implements the
    ``TenantPackedEngine`` protocol (admit / step / drop / release /
    free_slots / live_count / faulted_keys / fault_error / close) so
    ``CheckService._run_packed_slice`` drives it unchanged. Walk fleets
    are lane-independent, so per-tenant verdicts are bit-identical to
    solo runs by construction — no salting required."""

    def __init__(
        self,
        model,
        *,
        lanes: int = 1024,
        wave_steps: int = 1024,
        max_trace_len: int = 256,
        sample_capacity: int = 1 << 15,
        sample_stride: int = 1,
        max_tenants: int = 8,
        aot_cache: Optional[str] = None,
    ):
        self._engine = SwarmEngine(
            model,
            lanes=lanes,
            wave_steps=wave_steps,
            max_trace_len=max_trace_len,
            sample_capacity=sample_capacity,
            sample_stride=sample_stride,
            max_tenants=max_tenants,
            aot_cache=aot_cache,
        )
        self._jit_fp_single = jax.jit(model.packed_fingerprint)
        self._slots: List[Optional[str]] = [None] * self._engine._T
        self._views: Dict[str, _TenantWalkView] = {}
        self._seeds: Dict[str, int] = {}
        self._reported: set = set()
        self._faulted: Dict[str, BaseException] = {}

    # -- the TenantPackedEngine protocol ------------------------------------

    def free_slots(self) -> int:
        return sum(1 for s in self._slots if s is None)

    def live_count(self) -> int:
        # A stopped-but-not-yet-REPORTED tenant still counts live: its
        # completion may have been rolled back by a same-wave peer
        # fault, and the service's drive loop gates on this count — an
        # early zero would strand the finished job in JOB_RUNNING.
        return sum(
            1
            for jid in self._slots
            if jid is not None
            and not (
                self._views[jid]._stopped and jid in self._reported
            )
        )

    def faulted_keys(self):
        return list(self._faulted)

    def fault_error(self, key: str):
        return self._faulted.get(key)

    def admit(self, job_id: str, run_id=None, *, seed: int = 0,
              depth_cap=None, target_state_count=None,
              resume_from=None) -> _TenantWalkView:
        """Claims a lane-block slot: fresh walks from ``seed``, or a
        suspended job's exact carry (``resume_from`` = the standard
        swarm payload — resumes from a solo run or an earlier pack
        bit-identically)."""
        try:
            slot = self._slots.index(None)
        except ValueError:
            raise RuntimeError("no free swarm lane slots") from None
        if resume_from is not None:
            carry = self._engine.restore_slot_carry(resume_from)
            seed = int(resume_from["swarm"].get("seed", seed))
        else:
            carry = self._engine.fresh_tenant_carry(
                seed, depth_cap=depth_cap, target=target_state_count
            )
        self._engine.write_slot(slot, carry)
        self._slots[slot] = job_id
        self._seeds[job_id] = int(seed)
        view = _TenantWalkView(self, job_id, slot, run_id=run_id)
        view._prime(
            self._engine.tenant_stats(slot),
            self._engine.tenant_found_names(slot),
        )
        self._views[job_id] = view
        self._reported.discard(job_id)
        self._faulted.pop(job_id, None)
        return view

    def step(self) -> List[str]:
        """One shared wave for every live tenant; returns the job ids
        that finished this wave (stopped, discoveries materialized).
        A per-tenant harvest fault raises ``TenantFaultError`` so the
        service drops ONLY that tenant (its slot carry is intact — the
        payload slice resumes it from this very wave boundary) while
        survivors keep walking."""
        self._engine.run_wave()
        done: List[str] = []
        try:
            for slot, jid in enumerate(self._slots):
                if jid is None or jid in self._faulted:
                    continue
                view = self._views[jid]
                try:
                    fault_point("swarm.tenant.verdict", tenant=jid)
                    stats = self._engine.tenant_stats(slot)
                    view._absorb(
                        stats, self._engine.tenant_found_names(slot)
                    )
                    if stats["stopped"] and jid not in self._reported:
                        fps, _empty = (
                            self._engine.tenant_discoveries_fps(slot)
                        )
                        view._finish(fps)
                        self._reported.add(jid)
                        done.append(jid)
                except Exception as e:  # noqa: BLE001 - blast radius
                    self._faulted[jid] = e
                    raise TenantFaultError(jid, e) from e
        except BaseException:
            # The raised fault discards this wave's ``done`` list, so
            # the completions it carried must become re-reportable —
            # a finished tenant left in _reported but never RETURNED
            # would sit in JOB_RUNNING forever (the finish harvest is
            # idempotent, so the next step() re-reports it exactly).
            for jid in done:
                self._reported.discard(jid)
            raise
        return done

    def drop(self, job_id: str, discard: bool = False):
        """Releases the tenant's slot; unless ``discard``, hands back
        its payload slice (resumable solo or into a later pack)."""
        slot = self._slots.index(job_id)
        payload = None
        if not discard:
            payload = self._engine.export_slot_payload(
                slot, self._seeds.get(job_id, 0), {}
            )
        self._engine.clear_slot(slot)
        self._slots[slot] = None
        self._views.pop(job_id, None)
        self._seeds.pop(job_id, None)
        self._faulted.pop(job_id, None)
        self._reported.discard(job_id)
        return payload

    def release(self, job_id: str) -> None:
        """Frees a COMPLETED tenant's slot (the service calls this
        after harvesting the verdict) — exactly a discard-drop, shared
        so the slot/view/seed bookkeeping lives in one place."""
        self.drop(job_id, discard=True)

    def close(self) -> None:
        """Nothing persistent to tear down — the engine is carry +
        executables, both process-cached."""
