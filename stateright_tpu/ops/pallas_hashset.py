"""Pallas TPU kernel for the visited-set insert (tile-sweep open addressing).

The XLA path (``ops/hashset.py``) probes with per-round full-table gathers:
every probe round issues a B-lane random gather + scatter + re-read over the
whole HBM-resident table. This kernel exploits two structural facts the
checkers guarantee:

1. keys arrive **sorted** (the wave dedup sorts them), and
2. the home slot is **monotone in the key** (top bits of ``hi`` —
   ``ops/hashset._home``),

so the batch touches the table in a single left-to-right sweep. The kernel
grids over fixed-size table *tiles*; per tile it DMAs one window (tile +
``MAX_PROBES`` apron) HBM→VMEM, resolves every key homed in the tile against
VMEM (probe window compare + first-empty claim, sequentially per key — which
is exact CAS-free open addressing, since within one batch the keys are
processed in order), and DMAs the window back before the next tile starts.
Tiles no key homes into are skipped. Per-tile key ranges arrive via
``PrefetchScalarGridSpec`` from a ``searchsorted`` over the (monotone) homes.

Layout, as the TPU's tiling accepts it:

- the table is handed to the kernel as two lane-dense planes, ``hi`` and
  ``lo``, each ``(rows / 128, 128)``. A probe window of ``MAX_PROBES`` (=
  128) rows spans at most two plane rows; the kernel loads the aligned
  16-row block that holds them and masks by flat position, so every
  VMEM access is a full vector load or store at an 8-aligned row;
- per-key keys, flags and outputs are read and written as scalars in
  SMEM, staged ``_KC`` keys at a time by DMA from and to HBM (SMEM holds
  1 MiB, far less than a wave's batch).

Cost: the checkers own the table as ``(rows, 2)``, so every call splits
it into the two planes and stacks them back: two whole-table HBM copies,
O(capacity) per call, whatever the batch. Inside the kernel untouched
tiles are skipped, but the call as a whole is not a faster insert than
``hashset_insert`` until the table is held as planes where this kernel
owns it (ROADMAP A2). Not timed on the chip.

Semantics match ``hashset_insert`` exactly (same contract, same claim/fresh/
found/pending outcomes) — property-tested against it in
``tests/test_pallas_hashset.py`` — EXCEPT that duplicate in-batch keys are
also handled (second occurrence reports ``found``), which is a superset of
the wave-unique contract.

Reference analog: the ``DashMap`` visited set at
``/root/reference/src/checker/bfs.rs:28-29``; SURVEY §7-5c calls for exactly
this "insert-heavy open-addressing in Pallas" design.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .hashset import MAX_PROBES

__all__ = [
    "pallas_hashset_insert",
    "round_table_capacity",
    "TILE_ROWS",
]

# Table rows per grid step. 2048 rows × (2×4B) = 16KB window DMA (+ apron).
TILE_ROWS = 2048
# Keys staged through SMEM per chunk (a multiple of _LANES).
_KC = 512
_LANES = 128
_TILE_VROWS = TILE_ROWS // _LANES
# Plane rows one window DMA moves: the tile plus the probe apron.
_WIN_VROWS = (TILE_ROWS + MAX_PROBES) // _LANES
# The aligned block a probe loads: a probe starting in plane row r spans
# rows r and r + 1, which both lie in the 16 rows from r // 8 * 8.
_BLOCK_VROWS = 16
_SCRATCH_VROWS = (_TILE_VROWS - 1) // 8 * 8 + _BLOCK_VROWS


def round_table_capacity(capacity: int) -> int:
    """The smallest power-of-two multiple of ``TILE_ROWS`` that holds
    ``capacity`` rows — the admissible table size for the tile-sweep
    kernels (``pallas_hashset_insert`` and the fused wave megakernel,
    ``ops/pallas_wave.py``), which grid over ``TILE_ROWS``-row table
    tiles. ``TILE_ROWS`` is itself a power of two, so every power of two
    at or above it is tile-aligned; callers report the adjustment
    instead of refusing admission."""
    c = max(int(capacity), TILE_ROWS)
    return 1 << (c - 1).bit_length()


def _copy_all(pairs, sems):
    """Start one DMA per ``(src, dst)`` pair, then wait for all of them."""
    from jax.experimental.pallas import tpu as pltpu

    copies = [
        pltpu.make_async_copy(src, dst, sems.at[j])
        for j, (src, dst) in enumerate(pairs)
    ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def _insert_kernel(
    starts_ref,  # scalar-prefetch: (n_tiles + 1,) int32 key-range bounds
    cap_bits_ref,  # scalar-prefetch: (1,) int32 log2(capacity)
    key_hi_ref,  # HBM (Bp / 128, 128) uint32, sorted
    key_lo_ref,  # HBM (Bp / 128, 128) uint32
    active_ref,  # HBM (Bp / 128, 128) uint32 0/1
    hi_ref,  # HBM (rows / 128, 128) uint32 table plane, aliased output
    lo_ref,  # HBM (rows / 128, 128) uint32 table plane, aliased output
    out_hi_ref,  # alias of hi_ref
    out_lo_ref,  # alias of lo_ref
    fresh_ref,  # HBM (Bp / 128, 128) uint32 out
    found_ref,  # HBM (Bp / 128, 128) uint32 out
    pending_ref,  # HBM (Bp / 128, 128) uint32 out
    k_hi, k_lo, k_act,  # SMEM (_KC / 128, 128) key staging
    f_fresh, f_found, f_pend,  # SMEM (_KC / 128, 128) flag staging
    win_hi,  # VMEM (_SCRATCH_VROWS, 128) table window, hi plane
    win_lo,  # VMEM (_SCRATCH_VROWS, 128) table window, lo plane
    sems,
):
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    s = starts_ref[t]
    e = starts_ref[t + 1]
    shift = (32 - cap_bits_ref[0]).astype(jnp.uint32)

    @pl.when(e > s)
    def _process_tile():
        base = t * TILE_ROWS
        window = pl.ds(pl.multiple_of(t * _TILE_VROWS, _TILE_VROWS), _WIN_VROWS)
        in_win = pl.ds(0, _WIN_VROWS)
        _copy_all(
            ((out_hi_ref.at[window], win_hi.at[in_win]),
             (out_lo_ref.at[window], win_lo.at[in_win])),
            sems,
        )
        flat = (
            jax.lax.broadcasted_iota(jnp.int32, (_BLOCK_VROWS, _LANES), 0)
            * _LANES
            + jax.lax.broadcasted_iota(jnp.int32, (_BLOCK_VROWS, _LANES), 1)
        )
        big = jnp.int32(MAX_PROBES)

        def key_body(k, k0):
            i = k0 + k
            r, c = k // _LANES, k % _LANES
            mine = (i >= s) & (i < e)

            @pl.when(mine)
            def _clear():
                f_fresh[r, c] = jnp.uint32(0)
                f_found[r, c] = jnp.uint32(0)
                f_pend[r, c] = jnp.uint32(0)

            @pl.when(mine & (k_act[r, c] != 0))
            def _one_key():
                kh = k_hi[r, c]
                kl = k_lo[r, c]
                local = (kh >> shift).astype(jnp.int32) - base
                r0 = pl.multiple_of(local // _LANES // 8 * 8, 8)
                rows = pl.ds(r0, _BLOCK_VROWS)
                blk_hi = win_hi[rows, :]
                blk_lo = win_lo[rows, :]
                # Probe position of each block element; the probe window
                # is positions [0, MAX_PROBES).
                idx = flat + (r0 * _LANES - local)
                probe = (idx >= 0) & (idx < big)
                empty = probe & (blk_hi == 0) & (blk_lo == 0)
                match = probe & (blk_hi == kh) & (blk_lo == kl)
                first_empty = jnp.min(jnp.where(empty, idx, big))
                first_match = jnp.min(jnp.where(match, idx, big))
                is_found = first_match < first_empty
                can_claim = (first_empty < big) & ~is_found
                claim = can_claim & (idx == first_empty)
                win_hi[rows, :] = jnp.where(claim, kh, blk_hi)
                win_lo[rows, :] = jnp.where(claim, kl, blk_lo)
                f_fresh[r, c] = can_claim.astype(jnp.uint32)
                f_found[r, c] = is_found.astype(jnp.uint32)
                f_pend[r, c] = (~is_found & ~can_claim).astype(jnp.uint32)

            return k0

        # Chunks start on a 128-key row, so a chunk may begin with keys of
        # the previous tile: the flag chunk is read back first, and those
        # keys keep the flags their own tile wrote.
        k_base = s // _LANES * _LANES

        def chunk_body(ci, _):
            k0 = k_base + ci * _KC
            keys = pl.ds(k0 // _LANES, _KC // _LANES)
            flags = (
                (f_fresh, fresh_ref.at[keys]),
                (f_found, found_ref.at[keys]),
                (f_pend, pending_ref.at[keys]),
            )
            _copy_all(
                ((key_hi_ref.at[keys], k_hi), (key_lo_ref.at[keys], k_lo),
                 (active_ref.at[keys], k_act))
                + tuple((dst, src) for src, dst in flags),
                sems,
            )
            jax.lax.fori_loop(0, _KC, key_body, k0)
            _copy_all(flags, sems)
            return 0

        jax.lax.fori_loop(0, (e - k_base + _KC - 1) // _KC, chunk_body, 0)
        _copy_all(
            ((win_hi.at[in_win], out_hi_ref.at[window]),
             (win_lo.at[in_win], out_lo_ref.at[window])),
            sems,
        )


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_hashset_insert(
    table: jax.Array,
    key_hi: jax.Array,
    key_lo: jax.Array,
    active: jax.Array,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Drop-in for ``hashset_insert`` when keys are sorted ascending by
    (hi, lo). Returns ``(table, fresh, found, pending)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = table.shape[0]
    capacity = rows - MAX_PROBES
    cap_bits = capacity.bit_length() - 1
    assert capacity == (1 << cap_bits), "capacity must be a power of two"
    assert capacity % TILE_ROWS == 0, (
        f"capacity must be a multiple of TILE_ROWS={TILE_ROWS}"
    )
    n_tiles = capacity // TILE_ROWS
    B = key_hi.shape[0]

    # Homes are monotone in the sorted keys, so each tile's keys form a
    # contiguous range found by searchsorted. Inactive lanes must not
    # extend ranges: sorted order puts the u32max sentinels last; they map
    # into the final tile and are masked by ``active`` inside the kernel.
    homes = (key_hi >> jnp.uint32(32 - cap_bits)).astype(jnp.int32)
    bounds = jnp.arange(1, n_tiles + 1, dtype=jnp.int32) * TILE_ROWS
    starts = jnp.concatenate(
        [
            jnp.zeros((1,), jnp.int32),
            jnp.searchsorted(homes, bounds).astype(jnp.int32),
        ]
    )
    # The last chunk may reach _KC keys past the batch.
    Bp = -(-(B + _KC) // _LANES) * _LANES

    def lanes(x):
        return jnp.pad(x, (0, Bp - B)).reshape(Bp // _LANES, _LANES)

    planes = [table[:, j].reshape(rows // _LANES, _LANES) for j in (0, 1)]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    staging = pltpu.SMEM((_KC // _LANES, _LANES), jnp.uint32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=[hbm] * 5,
        out_specs=[hbm] * 5,
        scratch_shapes=[
            *[staging] * 6,
            pltpu.VMEM((_SCRATCH_VROWS, _LANES), jnp.uint32),
            pltpu.VMEM((_SCRATCH_VROWS, _LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((6,)),
        ],
    )
    plane = jax.ShapeDtypeStruct(planes[0].shape, jnp.uint32)
    flag = jax.ShapeDtypeStruct((Bp // _LANES, _LANES), jnp.uint32)
    out_hi, out_lo, fresh, found, pending = pl.pallas_call(
        _insert_kernel,
        grid_spec=grid_spec,
        out_shape=(plane, plane, flag, flag, flag),
        # table planes (arg idx incl. 2 prefetch args)
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(
        starts,
        jnp.full((1,), cap_bits, jnp.int32),
        lanes(key_hi),
        lanes(key_lo),
        lanes(active.astype(jnp.uint32)),
        *planes,
    )
    out_table = jnp.stack([out_hi.reshape(rows), out_lo.reshape(rows)], axis=1)

    def flags(x):
        return x.reshape(Bp)[:B] != 0

    return out_table, flags(fresh), flags(found), flags(pending)
