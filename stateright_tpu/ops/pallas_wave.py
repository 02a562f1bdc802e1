"""Fused Pallas wave megakernel: one kernel per BFS wave.

The staged wave (``checker/tpu.TpuBfsChecker._wave``) is one jit but ~5
logical XLA stages — expand, fingerprint, sort-dedup, visited-set insert,
compact/properties/coverage — each materializing its intermediates through
HBM, and (with ``hashset_impl="pallas"``) a separate Pallas dispatch for the
insert. BENCH_r11 measured the consequence: device utilization 0.10,
``gap_share`` 0.57 — per-stage dispatch overhead and HBM round-trips
dominate, and no host/device overlap fixes that. GPUexplore's answer
(PAPERS: "On the Scalability of the GPUexplore Explicit-State Model
Checker") is to run the entire BFS iteration inside a single kernel against
a fast-memory hash table; this module is that design on the TPU memory
hierarchy.

One ``pl.pallas_call`` grids over the visited table's ``TILE_ROWS``-row
tiles. Grid step ``t`` sweeps table tile ``t`` as a VMEM-resident
partition while the next tile's window is double-buffered in via async
DMA; the wave-wide compute rides the first and last steps:

- **prologue** (step 0): expand the F × A action grid, boundary-filter,
  fingerprint, sort-dedup (the staged path's exact stable
  ``lax.sort``), evaluate property conditions, and compute each tile's
  contiguous key range (``searchsorted`` over the monotone homes) into
  scratch. The model's packed callables close over device arrays
  (action tables, hash constants); the whole prologue goes through
  ``jax.closure_convert`` and the hoisted constants ride in as extra
  VMEM operands, since a Pallas kernel cannot capture array constants;
- **every step**: wait for tile ``t``'s window DMA, patch the
  ``MAX_PROBES``-row apron from the previous tile's buffer (tile ``t``'s
  window was prefetched *before* tile ``t-1``'s claims were written
  back, and the two windows overlap by exactly the apron), start the
  prefetch of tile ``t+1`` into the opposite parity buffer, then
  probe/claim this tile's keys in VMEM (``probe_claim``
  — the same claim semantics as the staged insert) and write the
  window back;
- **epilogue** (last step): prefix-compact the fresh lanes, evaluate
  properties, reduce coverage, and emit the consolidated stats vector.

The output dict is bit-identical to the staged wave's — same sort, same
first-occurrence dedup winner, same claim order, same compaction — so
every consumer (deep drain, checkpointing, tiered store, AOT cache)
composes unchanged. ``interpret=True`` (forced off-TPU) runs the real
kernel logic on CPU for tier-1/CI. On a TPU the kernel is compiled and
Mosaic refuses it: the in-kernel ``lax.sort``/gathers have no lowering
yet ("Shape mismatch in input, indices and output";
``tests/test_chip_compile.py`` pins it, README "Fused wave megakernel").
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .hashset import MAX_PROBES
from .pallas_hashset import TILE_ROWS

__all__ = ["FusedWaveSpec", "fused_wave"]

# numpy scalar: folds into jaxprs as a literal, never a captured constant.
_U32_MAX = np.uint32(0xFFFFFFFF)
# One table window: the tile plus the probe apron reaching into the next
# tile (open addressing probes at most MAX_PROBES rows past the home).
_WIN = TILE_ROWS + MAX_PROBES
# Keys resolved per inner chunk.
_KC = 256


def probe_claim(window, kh, kl, local):
    """Resolve one key against a VMEM table window: compare its
    ``MAX_PROBES``-row probe window at ``local``, claim the first empty
    slot when no match precedes it, and return ``(can_claim,
    is_found)``. Sequential per-key use makes the claim race-free — the
    next key observes this write in VMEM immediately, which is exact
    CAS-free open addressing. The claim is a masked whole-probe-window
    rewrite (a vector store — Mosaic handles dynamic scalar stores to
    VMEM poorly)."""
    from jax.experimental import pallas as pl

    rows_hi = window[pl.ds(local, MAX_PROBES), 0]
    rows_lo = window[pl.ds(local, MAX_PROBES), 1]
    idx = jax.lax.broadcasted_iota(
        jnp.int32, (MAX_PROBES, 1), 0
    ).reshape(MAX_PROBES)
    big = jnp.int32(MAX_PROBES)
    empty = (rows_hi == 0) & (rows_lo == 0)
    match = (rows_hi == kh) & (rows_lo == kl)
    first_empty = jnp.min(jnp.where(empty, idx, big))
    first_match = jnp.min(jnp.where(match, idx, big))
    is_found = first_match < first_empty
    can_claim = (first_empty < big) & ~is_found
    claim = can_claim & (idx == first_empty)
    window[pl.ds(local, MAX_PROBES), 0] = jnp.where(claim, kh, rows_hi)
    window[pl.ds(local, MAX_PROBES), 1] = jnp.where(claim, kl, rows_lo)
    return can_claim, is_found


@dataclasses.dataclass(frozen=True, eq=False)
class FusedWaveSpec:
    """Everything the fused wave closes over, bundled so the kernel stays
    checker-agnostic. ``expectations`` carry the property kinds as strings
    (``"always" | "sometimes" | "eventually"``) and ``ebit`` the
    (property index → eventually bit) pairs — the ops layer must not
    import checker/core enums. ``cov_layout`` is a
    ``telemetry.coverage.DeviceCoverage`` (or None); ``cov_antecedents``
    align with properties when coverage is on."""

    expand: Callable
    within_boundary: Callable
    fp_fn: Callable
    conditions: Tuple[Callable, ...]
    expectations: Tuple[str, ...]
    ebit: Tuple[Tuple[int, int], ...]
    action_count: int
    cov_layout: Any = None
    cov_antecedents: Tuple[Optional[Callable], ...] = ()
    interpret: bool = True


def fused_wave(spec: FusedWaveSpec, table, states, hi, lo, ebits, depth,
               mask, depth_cap):
    """One fused wave. Same arguments and output dict as the staged
    ``TpuBfsChecker._wave`` (materializing, no symmetry/fps/liveness —
    the checker refuses those combinations up front), traced inside the
    caller's jit."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    A = spec.action_count
    F = hi.shape[0]
    B = F * A
    P = len(spec.conditions)
    ebit = dict(spec.ebit)
    cov = spec.cov_layout
    capacity = table.shape[0] - MAX_PROBES
    cap_bits = capacity.bit_length() - 1
    assert capacity == (1 << cap_bits), "capacity must be a power of two"
    assert capacity % TILE_ROWS == 0, (
        f"capacity must be a multiple of TILE_ROWS={TILE_ROWS} "
        "(round_table_capacity)"
    )
    n_tiles = capacity // TILE_ROWS
    n_stats = 4 + (1 if P else 0)

    state_leaves, state_tree = jax.tree_util.tree_flatten(states)
    n_state = len(state_leaves)
    cand_struct = jax.eval_shape(jax.vmap(spec.expand), states)[0]
    cand_leaf_structs, cand_tree = jax.tree_util.tree_flatten(cand_struct)
    n_cand = len(cand_leaf_structs)
    cand_flat_shapes = [
        ((B,) + s.shape[2:], s.dtype) for s in cand_leaf_structs
    ]

    def prologue(dcap, hi_v, lo_v, ebits_v, depth_v, mask_u, *sleaves):
        """The wave-wide compute ahead of the table sweep, as a pure
        function of the kernel inputs — every model closure (expand,
        boundary, fingerprint, conditions, coverage antecedents) lives
        here so ``closure_convert`` can hoist their captured arrays."""
        states_v = jax.tree_util.tree_unflatten(state_tree, list(sleaves))
        eval_mask = (mask_u != 0) & (depth_v < dcap)
        cond_vals = [jax.vmap(c)(states_v) for c in spec.conditions]
        ebits_after = ebits_v
        for pi, b in ebit.items():
            ebits_after = jnp.where(
                cond_vals[pi],
                ebits_after & ~jnp.uint32(1 << b),
                ebits_after,
            )
        cand, cvalid = jax.vmap(spec.expand)(states_v)
        cvalid = cvalid & eval_mask[:, None]
        cvalid = cvalid & jax.vmap(jax.vmap(spec.within_boundary))(cand)
        terminal = eval_mask & ~cvalid.any(axis=1)
        cond_mat = (
            jnp.stack([c.astype(jnp.uint32) for c in cond_vals])
            if P
            else jnp.zeros((0, F), jnp.uint32)
        )
        # Coverage exercise masks need the frontier states, so they are
        # computed here (not in the epilogue) and parked in scratch.
        ex_mat = jnp.zeros((0, F), jnp.uint32)
        if cov is not None and P:
            exercised = []
            for pi in range(P):
                kind = spec.expectations[pi]
                if kind == "always":
                    ant = (
                        spec.cov_antecedents[pi]
                        if spec.cov_antecedents
                        else None
                    )
                    exercised.append(
                        eval_mask & jax.vmap(ant)(states_v)
                        if ant is not None
                        else eval_mask
                    )
                elif kind == "sometimes":
                    exercised.append(eval_mask & cond_vals[pi])
                else:  # eventually: met == the unmet bit already cleared
                    eb = ebit[pi]
                    exercised.append(
                        eval_mask
                        & (((ebits_after >> jnp.uint32(eb)) & 1) == 0)
                    )
            ex_mat = jnp.stack([e.astype(jnp.uint32) for e in exercised])
        cand_flat = jax.tree_util.tree_map(
            lambda x: x.reshape((B,) + x.shape[2:]), cand
        )
        cvalid_flat = cvalid.reshape(B)
        chi, clo = jax.vmap(spec.fp_fn)(cand_flat)
        # The staged path's exact stable dedup sort: invalid lanes sink
        # to the all-ones sentinel, first occurrence of each (hi, lo)
        # wins.
        shi = jnp.where(cvalid_flat, chi, _U32_MAX)
        slo = jnp.where(cvalid_flat, clo, _U32_MAX)
        shi, slo, sidx = jax.lax.sort(
            (shi, slo, jnp.arange(B, dtype=jnp.int32)), num_keys=2
        )
        uniq = jnp.concatenate(
            [
                jnp.ones((1,), bool),
                (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1]),
            ]
        )
        active = (cvalid_flat[sidx] & uniq).astype(jnp.uint32)
        # Per-tile key ranges: homes are monotone in the sorted keys (top
        # cap_bits of hi), so each tile's keys form a contiguous range.
        # Sentinel lanes home into the last tile, masked by ``active``.
        homes = (shi >> jnp.uint32(32 - cap_bits)).astype(jnp.int32)
        bounds = jnp.arange(1, n_tiles + 1, dtype=jnp.int32) * TILE_ROWS
        starts = jnp.concatenate(
            [
                jnp.zeros((1,), jnp.int32),
                jnp.searchsorted(homes, bounds).astype(jnp.int32),
            ]
        )
        return (
            ebits_after,
            eval_mask.astype(jnp.uint32),
            terminal.astype(jnp.uint32),
            cond_mat,
            ex_mat,
            cvalid_flat.astype(jnp.uint32),
            chi,
            clo,
            shi,
            slo,
            sidx,
            active,
            starts,
        ) + tuple(jax.tree_util.tree_leaves(cand_flat))

    # A Pallas kernel cannot capture array constants (the model's packed
    # callables close over action tables, hash coefficient vectors, …);
    # stage the prologue to a jaxpr, hoist its constants, and feed them
    # in as ordinary VMEM operands, rank-1-padded. (``jax.closure_convert``
    # is not enough: it only hoists consts that are AD-perturbable
    # tracers, and the model's concrete arrays stay baked in.)
    dcap = jnp.asarray(depth_cap, jnp.int32)
    mask_u = mask.astype(jnp.uint32)
    closed = jax.make_jaxpr(prologue)(
        dcap, hi, lo, ebits, depth, mask_u, *state_leaves
    )
    consts = closed.consts
    n_args = 6 + n_state

    def prologue_conv(*args_and_consts):
        from jax.core import eval_jaxpr

        return eval_jaxpr(
            closed.jaxpr,
            args_and_consts[n_args:],
            *args_and_consts[:n_args],
        )

    const_shapes = [jnp.shape(c) for c in consts]
    const_ops = [jnp.reshape(c, (1,) + jnp.shape(c)) for c in consts]
    n_const = len(const_ops)

    def kernel(*refs):
        dcap_ref = refs[0]
        srefs = refs[1 : 1 + n_state]
        o = 1 + n_state
        hi_ref, lo_ref, ebits_ref, depth_ref, mask_ref = refs[o : o + 5]
        o += 5
        const_refs = refs[o : o + n_const]
        o += n_const + 1  # + the aliased table input (DMA via the output)
        out_table = refs[o]
        o += 1
        new_srefs = refs[o : o + n_cand]
        o += n_cand
        (new_hi_ref, new_lo_ref, new_ebits_ref, new_depth_ref,
         parent_hi_ref, parent_lo_ref) = refs[o : o + 6]
        o += 6
        if P:
            hit_ref, prop_hi_ref, prop_lo_ref = refs[o : o + 3]
            o += 3
        if cov is not None:
            cov_ref = refs[o]
            o += 1
        stats_ref = refs[o]
        o += 1
        cand_refs = refs[o : o + n_cand]
        o += n_cand
        (chi_s, clo_s, shi_s, slo_s, cvalid_s, active_s, fresh_s,
         pending_s) = refs[o : o + 8]
        o += 8
        sidx_s, ebits_after_s, evalm_s, term_s = refs[o : o + 4]
        o += 4
        if P:
            cond_s = refs[o]
            o += 1
        if cov is not None and P:
            ex_s = refs[o]
            o += 1
        starts_s = refs[o]
        o += 1
        win_a, win_b, sem_a, sem_b, sem_out = refs[o : o + 5]

        t = pl.program_id(0)

        @pl.when(t == 0)
        def _prologue():
            const_vals = [
                r[...].reshape(s)
                for r, s in zip(const_refs, const_shapes)
            ]
            outs = prologue_conv(
                dcap_ref[0],
                hi_ref[...],
                lo_ref[...],
                ebits_ref[...],
                depth_ref[...],
                mask_ref[...],
                *[r[...] for r in srefs],
                *const_vals,
            )
            (ebits_after, evalm, term, cond_mat, ex_mat, cvalid_u, chi,
             clo, shi, slo, sidx, active, starts) = outs[:13]
            ebits_after_s[...] = ebits_after
            evalm_s[...] = evalm
            term_s[...] = term
            if P:
                cond_s[...] = cond_mat
            if cov is not None and P:
                ex_s[...] = ex_mat
            cvalid_s[...] = cvalid_u
            chi_s[...] = chi
            clo_s[...] = clo
            shi_s[...] = shi
            slo_s[...] = slo
            sidx_s[...] = sidx
            active_s[...] = active
            fresh_s[...] = jnp.zeros((B,), jnp.uint32)
            pending_s[...] = jnp.zeros((B,), jnp.uint32)
            starts_s[...] = starts
            for ref, leaf in zip(cand_refs, outs[13:]):
                ref[...] = leaf

            # Kick off tile 0's window DMA (parity buffer A).
            @pl.when(starts_s[1] > starts_s[0])
            def _first_dma():
                pltpu.make_async_copy(
                    out_table.at[pl.ds(0, _WIN)], win_a, sem_a
                ).start()

        s = starts_s[t]
        e = starts_s[t + 1]
        even = t % 2 == 0
        # Tile t-1 processed ⇒ its claims into THIS tile's first
        # MAX_PROBES rows (the window overlap) postdate our window
        # prefetch; the freshest copy of those rows lives in the previous
        # parity buffer's apron.
        tm1 = jnp.maximum(t - 1, 0)
        patch_needed = (t > 0) & (starts_s[t] > starts_s[tm1])

        def wait_and_patch(buf, prev_buf, sem):
            pltpu.make_async_copy(
                out_table.at[pl.ds(t * TILE_ROWS, _WIN)], buf, sem
            ).wait()

            @pl.when(patch_needed)
            def _patch():
                buf[pl.ds(0, MAX_PROBES), :] = prev_buf[
                    pl.ds(TILE_ROWS, MAX_PROBES), :
                ]

        @pl.when(e > s)
        def _wait():
            @pl.when(even)
            def _a():
                wait_and_patch(win_a, win_b, sem_a)

            @pl.when(~even)
            def _b():
                wait_and_patch(win_b, win_a, sem_b)

        # Prefetch tile t+1 into the opposite parity buffer — after the
        # apron patch above consumed that buffer's previous contents.
        nxt = t + 1

        @pl.when(nxt < n_tiles)
        def _prefetch():
            @pl.when(starts_s[nxt + 1] > starts_s[nxt])
            def _issue():
                src = out_table.at[pl.ds(nxt * TILE_ROWS, _WIN)]

                @pl.when(even)  # next tile is odd parity
                def _b():
                    pltpu.make_async_copy(src, win_b, sem_b).start()

                @pl.when(~even)
                def _a():
                    pltpu.make_async_copy(src, win_a, sem_a).start()

        def sweep(buf):
            base = t * TILE_ROWS
            shift = jnp.uint32(32 - cap_bits)

            def chunk_body(c, _):
                k0 = s + c * _KC

                def key_body(k, _):
                    i = k0 + k

                    @pl.when((i < e) & (active_s[i] != 0))
                    def _one_key():
                        kh = shi_s[i]
                        kl = slo_s[i]
                        local = (kh >> shift).astype(jnp.int32) - base
                        can_claim, is_found = probe_claim(
                            buf, kh, kl, local
                        )
                        fresh_s[i] = can_claim.astype(jnp.uint32)
                        pending_s[i] = (~is_found & ~can_claim).astype(
                            jnp.uint32
                        )

                jax.lax.fori_loop(0, _KC, key_body, None)
                return 0

            n_chunks = (e - s + _KC - 1) // _KC
            jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
            dma_out = pltpu.make_async_copy(
                buf, out_table.at[pl.ds(base, _WIN)], sem_out
            )
            dma_out.start()
            dma_out.wait()

        @pl.when(e > s)
        def _sweep():
            @pl.when(even)
            def _a():
                sweep(win_a)

            @pl.when(~even)
            def _b():
                sweep(win_b)

        @pl.when(t == n_tiles - 1)
        def _epilogue():
            fresh = fresh_s[...] != 0
            sidx = sidx_s[...]
            chi = chi_s[...]
            clo = clo_s[...]
            ebits_after = ebits_after_s[...]
            depth_v = depth_ref[...]
            mask_v = mask_ref[...] != 0
            eval_mask = evalm_s[...] != 0
            terminal = term_s[...] != 0
            hi_v = hi_ref[...]
            lo_v = lo_ref[...]

            pos = jnp.cumsum(fresh.astype(jnp.int32)) - 1
            out_slot = jnp.where(fresh, pos, B)
            zi = jnp.zeros((B,), jnp.int32)
            zu = jnp.zeros((B,), jnp.uint32)
            src_idx = zi.at[out_slot].set(sidx, mode="drop")
            parent_row = sidx // A
            new_hi_ref[...] = zu.at[out_slot].set(chi[sidx], mode="drop")
            new_lo_ref[...] = zu.at[out_slot].set(clo[sidx], mode="drop")
            new_ebits_ref[...] = zu.at[out_slot].set(
                ebits_after[parent_row], mode="drop"
            )
            new_depth_ref[...] = zi.at[out_slot].set(
                depth_v[parent_row] + 1, mode="drop"
            )
            parent_hi_ref[...] = zu.at[out_slot].set(
                hi_v[parent_row], mode="drop"
            )
            parent_lo_ref[...] = zu.at[out_slot].set(
                lo_v[parent_row], mode="drop"
            )
            for out_ref, cref in zip(new_srefs, cand_refs):
                out_ref[...] = cref[...][src_idx]

            generated = (cvalid_s[...] != 0).sum(dtype=jnp.int32)
            n_new = fresh.sum(dtype=jnp.int32)
            overflow = (pending_s[...] != 0).sum(dtype=jnp.int32)
            max_depth = jnp.max(jnp.where(mask_v, depth_v, 0))

            hits = []
            if P:
                fhis, flos = [], []
                for i in range(P):
                    kind = spec.expectations[i]
                    cv = cond_s[i, :] != 0
                    if kind == "always":
                        h = eval_mask & ~cv
                    elif kind == "sometimes":
                        h = eval_mask & cv
                    else:  # eventually: unmet bit at a terminal state
                        b = ebit[i]
                        h = terminal & (
                            ((ebits_after >> jnp.uint32(b)) & 1) == 1
                        )
                    idx = jnp.argmax(h)
                    hits.append(h.any())
                    fhis.append(hi_v[idx])
                    flos.append(lo_v[idx])
                hit_ref[...] = jnp.stack(hits).astype(jnp.int32)
                prop_hi_ref[...] = jnp.stack(fhis)
                prop_lo_ref[...] = jnp.stack(flos)

            if cov is not None:
                exercised = (
                    [ex_s[i, :] != 0 for i in range(P)] if P else []
                )
                cov_ref[...] = cov.wave_reduce(
                    eval_mask=eval_mask,
                    cvalid=(cvalid_s[...] != 0).reshape(F, A),
                    fresh=fresh,
                    lane_action=sidx % A,
                    new_depth=depth_v[sidx // A] + 1,
                    exercised=exercised,
                    uniq_fp=None,
                    uniq_key=None,
                )

            stats = [generated, n_new, overflow, max_depth]
            if P:
                stats.append(jnp.stack(hits).any().astype(jnp.int32))
            stats_ref[...] = jnp.stack(
                [x.astype(jnp.int32) for x in stats]
            )

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    out_shape = [jax.ShapeDtypeStruct(table.shape, table.dtype)]
    out_shape += [
        jax.ShapeDtypeStruct(shape, dtype)
        for shape, dtype in cand_flat_shapes
    ]
    out_shape += [
        jax.ShapeDtypeStruct((B,), jnp.uint32),  # new hi
        jax.ShapeDtypeStruct((B,), jnp.uint32),  # new lo
        jax.ShapeDtypeStruct((B,), jnp.uint32),  # new ebits
        jax.ShapeDtypeStruct((B,), jnp.int32),  # new depth
        jax.ShapeDtypeStruct((B,), jnp.uint32),  # parent hi
        jax.ShapeDtypeStruct((B,), jnp.uint32),  # parent lo
    ]
    if P:
        out_shape += [
            jax.ShapeDtypeStruct((P,), jnp.int32),
            jax.ShapeDtypeStruct((P,), jnp.uint32),
            jax.ShapeDtypeStruct((P,), jnp.uint32),
        ]
    if cov is not None:
        out_shape.append(jax.ShapeDtypeStruct((cov.size,), jnp.int32))
    out_shape.append(jax.ShapeDtypeStruct((n_stats,), jnp.int32))

    scratch = [
        pltpu.VMEM(shape, dtype) for shape, dtype in cand_flat_shapes
    ]
    scratch += [pltpu.VMEM((B,), jnp.uint32) for _ in range(8)]
    scratch += [
        pltpu.VMEM((B,), jnp.int32),  # sidx
        pltpu.VMEM((F,), jnp.uint32),  # ebits_after
        pltpu.VMEM((F,), jnp.uint32),  # eval_mask
        pltpu.VMEM((F,), jnp.uint32),  # terminal
    ]
    if P:
        scratch.append(pltpu.VMEM((P, F), jnp.uint32))
    if cov is not None and P:
        scratch.append(pltpu.VMEM((P, F), jnp.uint32))
    scratch += [
        pltpu.SMEM((n_tiles + 1,), jnp.int32),  # per-tile key ranges
        pltpu.VMEM((_WIN, 2), jnp.uint32),  # window, even tiles
        pltpu.VMEM((_WIN, 2), jnp.uint32),  # window, odd tiles
        pltpu.SemaphoreType.DMA,
        pltpu.SemaphoreType.DMA,
        pltpu.SemaphoreType.DMA,
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[vmem] * (n_state + 5 + n_const) + [any_],
        out_specs=[any_] + [vmem] * (len(out_shape) - 1),
        scratch_shapes=scratch,
    )
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(out_shape),
        # Table operand index counts the scalar-prefetch arg.
        input_output_aliases={1 + n_state + 5 + n_const: 0},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=spec.interpret,
    )(
        dcap.reshape((1,)),
        *state_leaves,
        hi,
        lo,
        ebits,
        depth,
        mask_u,
        *const_ops,
        table,
    )

    o = 0
    out_table = res[o]
    o += 1
    new_states = jax.tree_util.tree_unflatten(
        cand_tree, list(res[o : o + n_cand])
    )
    o += n_cand
    new_hi, new_lo, new_ebits, new_depth, parent_hi, parent_lo = res[
        o : o + 6
    ]
    o += 6
    if P:
        prop_hit, prop_hi, prop_lo = res[o : o + 3]
        o += 3
    if cov is not None:
        cov_vec = res[o]
        o += 1
    stats = res[o]

    out = {
        "table": out_table,
        "generated": stats[0],
        "n_new": stats[1],
        "overflow": stats[2],
        "max_depth": stats[3],
        "new": {
            "hi": new_hi,
            "lo": new_lo,
            "ebits": new_ebits,
            "depth": new_depth,
            "states": new_states,
        },
        "parent_hi": parent_hi,
        "parent_lo": parent_lo,
        "stats": stats,
    }
    if P:
        out["prop_hit"] = prop_hit != 0
        out["prop_hi"] = prop_hi
        out["prop_lo"] = prop_lo
    if cov is not None:
        out["cov"] = cov_vec
    return out
