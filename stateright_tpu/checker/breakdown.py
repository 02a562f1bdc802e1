"""Per-wave cost attribution for the device BFS — bench instrumentation.

``TpuBfsChecker._wave`` is one fused jit on purpose (every host round
trip costs a dispatch and a sync); a fused kernel cannot say where
wave time goes. This module mirrors the wave pipeline as SEPARATELY
jitted stages — expand / properties / fingerprint / sort-dedup / insert /
compact — drives a few real waves to reach a representative frontier,
then times each stage with ``block_until_ready`` and pulls XLA's compiled
``cost_analysis`` (FLOPs, bytes accessed) per stage. The stage split adds
dispatch overhead the fused wave does not pay, so the fused wave is timed
too and reported alongside (stage sums exceeding the fused time = the
overhead, not a lie).

Occupancy-adaptive dispatch is mirrored as well: the representative
frontier's live lanes are counted, compacted to a dense prefix, and the
wave is attributed at the smallest ladder bucket holding them — the exact
dispatch the checker runs. ``fused_wave_ms`` is therefore the bucketed
wave; ``fused_wave_fixed_ms`` keeps the fixed-F_max figure the pre-bucket
rounds reported (their ratio is the dispatch win), ``bucket_fused_ms``
times every ladder rung, and ``compact_ms`` prices the compaction pass
the bucketed dispatch adds.

The output feeds ``bench.py``'s breakdown fields: per-stage milliseconds,
bytes-per-state, and a roofline attainment figure against the chip's HBM
peak — the judgeability half of VERDICT r03 #1. The reference's analog is
its ``ReportData`` throughput surface (``/root/reference/src/report.rs:
10-98``), which has no per-phase attribution at all.

Symmetry-reduced models are not supported (none of the bench legs use
symmetry; the key_fn cost would need its own stage).
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp

from ..core.batch import BatchableModel
from ..ops.fingerprint import fingerprint_state
from ..ops.hashset import (
    hashset_insert,
    hashset_insert_unsorted,
    hashset_new,
)

_U32_MAX = jnp.uint32(0xFFFFFFFF)

# Chip peaks for roofline attainment, keyed on the exact jax
# Device.device_kind (Google Cloud documentation, "TPU v5e", "TPU v5p",
# "TPU v4"). v5e reports "TPU v5 lite". The BFS is integer/memory-bound,
# so HBM attainment is the meaningful axis; the FLOP figure is reported
# for completeness only.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
    "TPU v5": {"hbm_gbps": 2765.0, "bf16_tflops": 459.0},
    "TPU v4": {"hbm_gbps": 1200.0, "bf16_tflops": 275.0},
}


def device_peak(platform: str, kind: str):
    """The peak entry for a device, by exact ``device_kind``. Non-TPU
    backends have no roofline (None); a TPU kind missing from the table
    raises rather than borrowing another chip's peak."""
    if platform != "tpu":
        return None
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no peak for TPU device_kind {kind!r}: add it to DEVICE_PEAKS "
            "with its source"
        ) from None


def _memory_traffic(compiled) -> float:
    """Post-fusion HBM traffic estimate of one call: arguments read +
    outputs written + temp buffers written-then-read. This is what the
    chip's HBM actually moves — XLA's op-level ``bytes accessed``
    (``_cost``) counts every pre-fusion elementwise op as a full
    round-trip, overstating fused compute chains by an order of
    magnitude, which round 4's roofline math inherited."""
    try:
        ma = compiled.memory_analysis()
        return float(
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + 2 * ma.temp_size_in_bytes
        )
    except Exception:
        return 0.0


def _cost(compiled) -> Dict[str, float]:
    """FLOPs + bytes from a compiled executable's cost analysis (best
    effort: some backends return None or a list)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }


def _time_stage(fn, args, iters: int) -> float:
    """Median-of-iters seconds for one blocked stage call."""
    out = fn(*args)
    jax.block_until_ready(out)  # warmup/compile
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2]


def measure_wave_breakdown(
    model: BatchableModel,
    frontier_capacity: int = 1 << 11,
    table_capacity: int = 1 << 20,
    warmup_waves: int = 6,
    iters: int = 20,
    wave_dedup: str | None = None,
    bucket_ladder: int | None = None,
    wave_kernel: str = "staged",
) -> Dict:
    """Stage-split timings + cost analysis on a representative wave.

    Runs the staged pipeline for ``warmup_waves`` real waves from the
    model's initial states (so the measured frontier holds real states at
    a realistic fill), compacts the live lanes and selects the ladder
    bucket exactly like the checker's dispatch, then times each stage at
    that bucket. Returns a dict of per-stage seconds, the bucketed and
    fixed-width fused-wave seconds, per-rung fused times, per-wave
    cost-analysis totals, and roofline attainment when the device peak is
    known.

    ``wave_dedup`` must match the configuration being attributed
    (``TpuBfsChecker``'s knob): "sort" measures the sort_dedup + sorted
    insert stages; "scatter" replaces both with the single
    duplicate-tolerant ``insert`` stage the scatter path actually runs —
    attributing a sort the measured rate never executes would mislead
    the next optimization round. None resolves to the same backend
    default the checker uses (``default_wave_dedup``). ``bucket_ladder``
    mirrors the checker knob (None = the default ladder, 0 = fixed
    width).

    ``wave_kernel="fused"`` attributes the Pallas wave megakernel
    (``ops/pallas_wave.py``) instead of the staged stage split: the
    whole wave is ONE dispatch, so ``stages_ms`` holds a single
    ``wave_kernel`` entry and ``dispatches_per_wave`` drops to 1 (the
    staged split reports its stage count there — the dispatch-overhead
    collapse the megakernel buys, rendered by ``bench.py
    --megakernel``). The fused kernel fixes the sorted-dedup
    discipline, so ``wave_dedup="scatter"`` is rejected, and
    ``table_capacity`` is tile-rounded like the checker does.
    """
    from .tpu import (
        _AUTO_BUCKET_MIN_F,
        _DEFAULT_BUCKET_STEPS,
        bucket_for,
        bucket_ladder_widths,
        default_wave_dedup,
        supports_expand_fps,
    )

    if wave_kernel not in ("staged", "fused"):
        raise ValueError(
            f"wave_kernel must be 'staged' or 'fused': {wave_kernel!r}"
        )
    if wave_kernel == "fused":
        if wave_dedup == "scatter":
            raise ValueError(
                "wave_kernel='fused' fixes the sorted-dedup discipline; "
                "attribute wave_dedup='scatter' with wave_kernel='staged'"
            )
        wave_dedup = "sort"
        from ..ops.pallas_hashset import round_table_capacity

        table_capacity = round_table_capacity(table_capacity)
    if wave_dedup is None:
        wave_dedup = default_wave_dedup(jax.default_backend())
    if wave_dedup not in ("sort", "scatter"):
        raise ValueError(f"wave_dedup must be 'sort' or 'scatter': {wave_dedup!r}")
    F = 1 << (frontier_capacity - 1).bit_length()
    if bucket_ladder is None:
        # Mirror the checker's auto rule so the attributed dispatch is
        # the dispatched dispatch.
        bucket_ladder = (
            _DEFAULT_BUCKET_STEPS if F >= _AUTO_BUCKET_MIN_F else 0
        )
    ladder = bucket_ladder_widths(F, bucket_ladder)
    A = model.packed_action_count()
    conditions = model.packed_conditions()
    fp_fn = model.packed_fingerprint
    # Attribute the pipeline the checker's auto policy runs: models
    # providing the fps hooks get the fingerprint-only wave (expand_fps /
    # insert / materialize), everything else the materializing wave.
    # Every stage below is shape-polymorphic in the frontier width
    # (widths are taken from the inputs), so one definition serves every
    # ladder rung.
    use_fps = supports_expand_fps(model)
    if wave_kernel == "fused":
        # The fused megakernel materializes the candidate grid in VMEM
        # scratch — the checker refuses expand_fps under it; mirror.
        use_fps = False

    def expand(states, mask):
        cand, cvalid = jax.vmap(model.packed_expand)(states)
        cvalid = cvalid & mask[:, None]
        cvalid = cvalid & jax.vmap(jax.vmap(model.packed_within_boundary))(cand)
        return cand, cvalid

    def props(states, mask):
        if not conditions:
            return jnp.zeros((1,), bool)
        return jnp.stack([jax.vmap(c)(states) & mask for c in conditions])

    def fingerprint(cand):
        flat = jax.tree_util.tree_map(
            lambda x: x.reshape((-1,) + x.shape[2:]), cand
        )
        return jax.vmap(fp_fn)(flat)

    def sort_dedup(chi, clo, flat_valid):
        b = chi.shape[0]
        shi = jnp.where(flat_valid, chi, _U32_MAX)
        slo = jnp.where(flat_valid, clo, _U32_MAX)
        shi, slo, sidx = jax.lax.sort(
            (shi, slo, jnp.arange(b, dtype=jnp.int32)), num_keys=2
        )
        uniq = jnp.concatenate(
            [jnp.ones((1,), bool), (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1])]
        )
        return shi, slo, sidx, flat_valid[sidx] & uniq

    def insert(table, shi, slo, active):
        return hashset_insert(table, shi, slo, active)

    def insert_scatter(table, chi, clo, flat_valid):
        return hashset_insert_unsorted(table, chi, clo, flat_valid)

    def compact_refs(fresh, sidx):
        """Width-compacted source references of the fresh lanes — the
        wave's next-frontier selection (beyond-width fresh lanes go to
        later segments/chunks in the real checker). Shared slot math for
        both pipelines."""
        b = fresh.shape[0]
        f_out = b // A
        pos = jnp.cumsum(fresh.astype(jnp.int32)) - 1
        out_slot = jnp.where(fresh & (pos < f_out), pos, f_out)
        src_idx = jnp.zeros((f_out,), jnp.int32).at[out_slot].set(
            sidx, mode="drop"
        )
        taken = jnp.zeros((f_out,), bool).at[out_slot].set(fresh, mode="drop")
        return src_idx, taken

    def compact(cand, sidx, fresh):
        flat = jax.tree_util.tree_map(
            lambda x: x.reshape((-1,) + x.shape[2:]), cand
        )
        src_idx, taken = compact_refs(fresh, sidx)
        new_states = jax.tree_util.tree_map(lambda x: x[src_idx], flat)
        return new_states, taken

    def expand_fps(states, mask):
        hi, lo, v = jax.vmap(model.packed_expand_fps)(states)
        v = v & mask[:, None]
        return hi.reshape(-1), lo.reshape(-1), v.reshape(-1)

    def materialize(states, src_idx):
        """One frontier-width segment of fresh-child materialization (the
        real pipeline runs ceil(n_new / width) of these per wave)."""
        parents = jax.tree_util.tree_map(lambda x: x[src_idx // A], states)
        return jax.vmap(model.packed_take)(parents, src_idx % A)

    def compact_dispatch(states, mask):
        """The checker's pre-dispatch live-lane compaction (_compact_chunk):
        a stable cumsum scatter of the frontier rows to a dense prefix —
        the overhead the bucketed dispatch adds over fixed width."""
        f_in = mask.shape[0]
        pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
        dest = jnp.where(mask, pos, f_in)

        def scat(x):
            z = jnp.zeros((f_in,) + x.shape[1:], x.dtype)
            return z.at[dest].set(x, mode="drop")

        out = jax.tree_util.tree_map(scat, states)
        new_mask = jnp.arange(f_in, dtype=jnp.int32) < mask.sum(
            dtype=jnp.int32
        )
        return out, new_mask

    def fused(table, states, mask):
        # The props result is returned (not dropped) so XLA cannot
        # dead-code-eliminate the predicate out of the fused timing.
        b = mask.shape[0] * A
        pv = props(states, mask)
        if use_fps:
            chi, clo, cvalid = expand_fps(states, mask)
            if wave_dedup == "scatter":
                table, fresh, _found, _pending = insert_scatter(
                    table, chi, clo, cvalid
                )
                sidx = jnp.arange(b, dtype=jnp.int32)
            else:
                shi, slo, sidx, active = sort_dedup(chi, clo, cvalid)
                table, fresh, _found, _pending = insert(
                    table, shi, slo, active
                )
            src_idx, taken = compact_refs(fresh, sidx)
            new_states = materialize(states, src_idx)
            return table, new_states, taken, pv.any()
        cand, cvalid = expand(states, mask)
        cvalid = cvalid.reshape(b)  # (F, A) grid -> flat lanes, like _wave
        chi, clo = fingerprint(cand)
        if wave_dedup == "scatter":
            table, fresh, _found, _pending = insert_scatter(
                table, chi, clo, cvalid
            )
            sidx = jnp.arange(b, dtype=jnp.int32)
        else:
            shi, slo, sidx, active = sort_dedup(chi, clo, cvalid)
            table, fresh, _found, _pending = insert(table, shi, slo, active)
        new_states, taken = compact(cand, sidx, fresh)
        return table, new_states, taken, pv.any()

    j_expand = jax.jit(expand)
    j_props = jax.jit(props)
    j_fp = jax.jit(fingerprint)
    j_sort = jax.jit(sort_dedup)
    j_insert = jax.jit(insert)
    j_insert_scatter = jax.jit(insert_scatter)
    j_compact = jax.jit(compact)
    j_fused = jax.jit(fused)
    j_expand_fps = jax.jit(expand_fps)
    j_materialize = jax.jit(materialize)
    j_refs = jax.jit(compact_refs)
    j_compact_dispatch = jax.jit(compact_dispatch)

    # Seed: initial states padded to the frontier width.
    init = model.packed_init_states()
    n0 = min(jax.tree_util.tree_leaves(init)[0].shape[0], F)
    states = jax.tree_util.tree_map(
        lambda x: jnp.zeros((F,) + x.shape[1:], x.dtype).at[:n0].set(x[:F]),
        init,
    )
    mask = jnp.arange(F) < n0
    table = hashset_new(table_capacity)
    # Claim the init states so wave 1 doesn't re-find them.
    ihi, ilo = jax.vmap(fp_fn)(states)
    shi0, slo0, _ = jax.lax.sort(
        (jnp.where(mask, ihi, _U32_MAX), jnp.where(mask, ilo, _U32_MAX),
         jnp.arange(F, dtype=jnp.int32)),
        num_keys=2,
    )
    uniq0 = jnp.concatenate(
        [jnp.ones((1,), bool), (shi0[1:] != shi0[:-1]) | (slo0[1:] != slo0[:-1])]
    )
    table, _, _, _ = hashset_insert(
        table, shi0, slo0, (jnp.arange(F) < n0) & uniq0
    )

    for _ in range(warmup_waves):
        nxt = j_fused(table, states, mask)
        if not bool(nxt[2].any()):
            break  # space exhausted; measure on the last non-empty wave
        table, states, mask = nxt[0], nxt[1], nxt[2]

    live = int(mask.sum())
    frontier_fill = live / F
    # The checker's dispatch: compact live lanes to a dense prefix, pick
    # the smallest ladder bucket that holds them, slice the frontier to it.
    bucket = bucket_for(ladder, max(1, live))
    c_states, c_mask = j_compact_dispatch(states, mask)
    states_w = jax.tree_util.tree_map(lambda x: x[:bucket], c_states)
    mask_w = c_mask[:bucket]
    B = bucket * A

    materialize_segments = None
    if use_fps:
        fhi, flo, fvalid = j_expand_fps(states_w, mask_w)
        stages = {
            "expand_fps": (j_expand_fps, (states_w, mask_w)),
            "properties": (j_props, (states_w, mask_w)),
        }
        if wave_dedup == "scatter":
            _, fresh_f, _, _ = j_insert_scatter(table, fhi, flo, fvalid)
            sidx_f = jnp.arange(B, dtype=jnp.int32)
            stages["insert"] = (
                j_insert_scatter,
                (table, fhi, flo, fvalid),
            )
        else:
            shi, slo, sidx_f, active_f = j_sort(fhi, flo, fvalid)
            fresh_f = active_f
            stages["sort_dedup"] = (j_sort, (fhi, flo, fvalid))
            stages["insert"] = (j_insert, (table, shi, slo, active_f))
        src_idx_f, _ = j_refs(fresh_f, sidx_f)
        n_new_rep = int(fresh_f.sum())
        # The checker materializes fresh lanes in frontier-width segments;
        # the timed stage is ONE segment, and the per-wave totals scale by
        # the representative wave's segment count.
        materialize_segments = max(1, -(-n_new_rep // bucket))
        stages["materialize"] = (j_materialize, (states_w, src_idx_f))
    else:
        cand, cvalid = j_expand(states_w, mask_w)
        cvalid = cvalid.reshape(B)  # flat lanes, matching the fused wave
        chi, clo = j_fp(cand)

        stages = {
            "expand": (j_expand, (states_w, mask_w)),
            "properties": (j_props, (states_w, mask_w)),
            "fingerprint": (j_fp, (cand,)),
        }
        if wave_dedup == "scatter":
            _, fresh_sc, _, _ = j_insert_scatter(table, chi, clo, cvalid)
            stages["insert"] = (j_insert_scatter, (table, chi, clo, cvalid))
            stages["compact"] = (
                j_compact,
                (cand, jnp.arange(B, dtype=jnp.int32), fresh_sc),
            )
        else:
            shi, slo, sidx, active = j_sort(chi, clo, cvalid)
            stages["sort_dedup"] = (j_sort, (chi, clo, cvalid))
            stages["insert"] = (j_insert, (table, shi, slo, active))
            stages["compact"] = (j_compact, (cand, sidx, active))
    staged_dispatches = len(stages)
    if wave_kernel == "fused":
        # The whole wave is ONE Pallas dispatch: replace the stage table
        # with the single wave_kernel stage the checker actually runs.
        from ..ops.pallas_wave import FusedWaveSpec, fused_wave

        props_list = list(model.properties())
        if len(conditions) != len(props_list):
            raise ValueError(
                "packed_conditions() must align 1:1 with properties(): "
                f"{len(conditions)} != {len(props_list)}"
            )
        eventually = [
            i
            for i, p in enumerate(props_list)
            if getattr(p.expectation, "value", None) == "eventually"
        ]
        ebit = tuple((pi, b) for b, pi in enumerate(eventually))
        spec = FusedWaveSpec(
            expand=model.packed_expand,
            within_boundary=model.packed_within_boundary,
            fp_fn=fp_fn,
            conditions=tuple(conditions),
            expectations=tuple(
                p.expectation.value for p in props_list
            ),
            ebit=ebit,
            action_count=A,
            interpret=jax.default_backend() != "tpu",
        )
        hi_w, lo_w = jax.vmap(fp_fn)(states_w)
        ebits_w = jnp.full(
            (bucket,), sum(1 << b for _pi, b in ebit), jnp.uint32
        )
        depth_w = jnp.zeros((bucket,), jnp.int32)

        def mega(table, states, hi, lo, ebits, depth, mask):
            return fused_wave(
                spec, table, states, hi, lo, ebits, depth, mask,
                jnp.int32(2**31 - 1),
            )

        j_mega = jax.jit(mega)
        stages = {
            "wave_kernel": (
                j_mega,
                (table, states_w, hi_w, lo_w, ebits_w, depth_w, mask_w),
            )
        }
    out = {
        "frontier_capacity": F,
        "action_count": A,
        "frontier_fill": round(frontier_fill, 4),
        "live_lanes": live,
        "bucket": bucket,
        "bucket_ladder": ladder,
        "compaction_ratio": round(live / bucket, 4) if bucket else 0.0,
        "device": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "wave_dedup": wave_dedup,
        "wave_kernel": wave_kernel,
        # Kernel launches one wave pays: the staged split's stage count
        # vs the megakernel's single dispatch — the overhead collapse
        # bench.py --megakernel renders.
        "dispatches_per_wave": (
            1 if wave_kernel == "fused" else staged_dispatches
        ),
        "table_capacity": table_capacity,
        "stages_ms": {},
        "stage_cost": {},
    }
    total_bytes = 0.0
    total_flops = 0.0
    if materialize_segments is not None:
        # materialize stage numbers are per bucket-wide segment; totals
        # below scale them by this count (the representative wave's real
        # cost).
        out["materialize_segments_per_wave"] = materialize_segments
        out["pipeline"] = "fps"
    for name, (fn, args) in stages.items():
        scale = (
            materialize_segments
            if name == "materialize" and materialize_segments
            else 1
        )
        out["stages_ms"][name] = round(
            _time_stage(fn, args, iters) * 1e3 * scale, 4
        )
        cost = _cost(fn.lower(*args).compile())
        if cost:
            cost = {k: v * scale for k, v in cost.items()}
            out["stage_cost"][name] = cost
            total_bytes += cost["bytes"]
            total_flops += cost["flops"]
    # The compaction pass the bucketed dispatch adds (full-width frontier
    # in, dense prefix out) — the overhead the tier-1 micro-benchmark
    # budget-tests against the fixed-width wave.
    out["compact_ms"] = round(
        _time_stage(j_compact_dispatch, (states, mask), iters) * 1e3, 4
    )
    # THE dispatched wave: fused at the selected bucket (acceptance
    # metric), alongside the fixed-width wave the pre-bucket rounds
    # measured and the full per-rung ladder.
    out["fused_wave_ms"] = round(
        _time_stage(j_fused, (table, states_w, mask_w), iters) * 1e3, 4
    )
    out["fused_wave_fixed_ms"] = round(
        _time_stage(j_fused, (table, states, mask), iters) * 1e3, 4
    )
    bucket_fused = {}
    for w in ladder:
        if w == bucket:
            bucket_fused[str(w)] = out["fused_wave_ms"]
        elif w == F:
            bucket_fused[str(w)] = out["fused_wave_fixed_ms"]
        else:
            bucket_fused[str(w)] = round(
                _time_stage(
                    j_fused,
                    (
                        table,
                        jax.tree_util.tree_map(
                            lambda x: x[:w], c_states
                        ),
                        c_mask[:w],
                    ),
                    iters,
                )
                * 1e3,
                4,
            )
    out["bucket_fused_ms"] = bucket_fused
    fused_compiled = j_fused.lower(table, states_w, mask_w).compile()
    fused_traffic = _memory_traffic(fused_compiled)

    # Normalize: candidates processed per dispatched wave is the honest
    # denominator for "bytes per state" (every candidate lane in the
    # bucket is fingerprinted/sorted whether or not it turns out fresh).
    out["candidates_per_wave"] = B
    out["candidates_per_wave_fixed"] = F * A
    if total_bytes:
        # Op-level (pre-fusion) accounting: an upper bound that charges
        # every elementwise op a full memory round-trip.
        out["bytes_per_candidate"] = round(total_bytes / B, 1)
        out["flops_per_candidate"] = round(total_flops / B, 1)
    if fused_traffic:
        # Post-fusion buffer traffic of the ONE fused executable the
        # checker actually runs per wave — the honest HBM figure for
        # roofline math (BASELINE.md north-star feasibility).
        out["hbm_bytes_per_candidate"] = round(fused_traffic / B, 1)
        out["fused_wave_hbm_bytes"] = fused_traffic
    peak = device_peak(out["device"], out["device_kind"])
    if peak and (fused_traffic or total_bytes):
        # Roofline: the time HBM alone would need for the wave's traffic,
        # over the measured fused time. Post-fusion traffic when the
        # backend reports it (op-level bytes otherwise). Low attainment =
        # dispatch/latency bound (small waves) or compute-bound stages.
        ideal_s = (fused_traffic or total_bytes) / (peak["hbm_gbps"] * 1e9)
        out["hbm_peak_gbps"] = peak["hbm_gbps"]
        out["hbm_roofline_attainment"] = round(
            ideal_s / (out["fused_wave_ms"] / 1e3), 4
        )
    return out


def measure_pipeline_choice(
    model: BatchableModel,
    frontier_capacity: int = 1 << 10,
    table_capacity: int = 1 << 16,
    wave_dedup: str | None = None,
    warmup_waves: int = 4,
    iters: int = 5,
) -> Dict:
    """expand_fps as a MEASURED policy: times one calibration wave under
    each expansion pipeline — ``fps`` (fingerprint-only expansion +
    fresh-lane materialization) and ``materialize`` (the full F × A
    candidate grid) — on the same representative frontier, so bench.py
    can compare the configured pipeline against the timed winner instead
    of trusting the auto rule (VERDICT r05: abd3o 2.5× and scr4 26% CPU
    regressions landed silently under auto-fps).

    Returns ``{"supported": False}`` when the model has no fps hooks
    (one pipeline exists; nothing to compare), else ``fps_ms`` /
    ``materialize_ms`` (median-of-iters, compile excluded) and
    ``measured_faster``. Both pipelines run the same dedup/insert
    (``wave_dedup``: the checker's knob, None = backend default), so the
    delta is the expansion strategy itself.
    """
    from .tpu import default_wave_dedup, supports_expand_fps

    out: Dict = {"supported": bool(supports_expand_fps(model))}
    if not out["supported"]:
        return out
    if wave_dedup is None:
        wave_dedup = default_wave_dedup(jax.default_backend())
    if wave_dedup not in ("sort", "scatter"):
        raise ValueError(
            f"wave_dedup must be 'sort' or 'scatter': {wave_dedup!r}"
        )
    F = 1 << (frontier_capacity - 1).bit_length()
    A = model.packed_action_count()
    B = F * A
    fp_fn = model.packed_fingerprint

    def _dedup_insert(table, chi, clo, cvalid):
        if wave_dedup == "scatter":
            table, fresh, _found, _p = hashset_insert_unsorted(
                table, chi, clo, cvalid
            )
            return table, fresh, jnp.arange(B, dtype=jnp.int32)
        shi = jnp.where(cvalid, chi, _U32_MAX)
        slo = jnp.where(cvalid, clo, _U32_MAX)
        shi, slo, sidx = jax.lax.sort(
            (shi, slo, jnp.arange(B, dtype=jnp.int32)), num_keys=2
        )
        uniq = jnp.concatenate(
            [jnp.ones((1,), bool),
             (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1])]
        )
        active = cvalid[sidx] & uniq
        table, fresh, _found, _p = hashset_insert(table, shi, slo, active)
        return table, fresh, sidx

    def _next_refs(fresh, sidx):
        pos = jnp.cumsum(fresh.astype(jnp.int32)) - 1
        out_slot = jnp.where(fresh & (pos < F), pos, F)
        src_idx = jnp.zeros((F,), jnp.int32).at[out_slot].set(
            sidx, mode="drop"
        )
        taken = jnp.zeros((F,), bool).at[out_slot].set(fresh, mode="drop")
        return src_idx, taken

    def mat_wave(table, states, mask):
        cand, cvalid = jax.vmap(model.packed_expand)(states)
        cvalid = cvalid & mask[:, None]
        cvalid = cvalid & jax.vmap(
            jax.vmap(model.packed_within_boundary)
        )(cand)
        flat = jax.tree_util.tree_map(
            lambda x: x.reshape((B,) + x.shape[2:]), cand
        )
        cvalid = cvalid.reshape(B)
        chi, clo = jax.vmap(fp_fn)(flat)
        table, fresh, sidx = _dedup_insert(table, chi, clo, cvalid)
        src_idx, taken = _next_refs(fresh, sidx)
        new_states = jax.tree_util.tree_map(lambda x: x[src_idx], flat)
        return table, new_states, taken

    def fps_wave(table, states, mask):
        chi_g, clo_g, cvalid = jax.vmap(model.packed_expand_fps)(states)
        cvalid = (cvalid & mask[:, None]).reshape(B)
        chi, clo = chi_g.reshape(B), clo_g.reshape(B)
        table, fresh, sidx = _dedup_insert(table, chi, clo, cvalid)
        src_idx, taken = _next_refs(fresh, sidx)
        parents = jax.tree_util.tree_map(
            lambda x: x[src_idx // A], states
        )
        new_states = jax.vmap(model.packed_take)(parents, src_idx % A)
        return table, new_states, taken

    j_mat = jax.jit(mat_wave)
    j_fps = jax.jit(fps_wave)

    # Seed + advance to a representative frontier through the
    # materializing wave (both pipelines then time on the SAME frontier
    # against the SAME table — the comparison is expansion-only).
    init = model.packed_init_states()
    n0 = min(jax.tree_util.tree_leaves(init)[0].shape[0], F)
    states = jax.tree_util.tree_map(
        lambda x: jnp.zeros((F,) + x.shape[1:], x.dtype).at[:n0].set(x[:F]),
        init,
    )
    mask = jnp.arange(F) < n0
    table = hashset_new(table_capacity)
    ihi, ilo = jax.vmap(fp_fn)(states)
    shi0, slo0, _ = jax.lax.sort(
        (jnp.where(mask, ihi, _U32_MAX), jnp.where(mask, ilo, _U32_MAX),
         jnp.arange(F, dtype=jnp.int32)),
        num_keys=2,
    )
    uniq0 = jnp.concatenate(
        [jnp.ones((1,), bool),
         (shi0[1:] != shi0[:-1]) | (slo0[1:] != slo0[:-1])]
    )
    table, _, _, _ = hashset_insert(table, shi0, slo0, mask & uniq0)
    for _ in range(warmup_waves):
        nxt = j_mat(table, states, mask)
        if not bool(nxt[2].any()):
            break
        table, states, mask = nxt

    out["frontier_capacity"] = F
    out["live_lanes"] = int(mask.sum())
    out["wave_dedup"] = wave_dedup
    out["materialize_ms"] = round(
        _time_stage(j_mat, (table, states, mask), iters) * 1e3, 4
    )
    out["fps_ms"] = round(
        _time_stage(j_fps, (table, states, mask), iters) * 1e3, 4
    )
    out["measured_faster"] = (
        "fps" if out["fps_ms"] <= out["materialize_ms"] else "materialize"
    )
    return out
