"""Occupancy-adaptive wave dispatch: bucketed/compacted checker must be
bit-identical to the fixed-width path.

Equivalence strategy: the bucket ladder only changes how many padding
lanes the expand grid carries — the dispatched live-lane sequence is
identical (ring pops and chunk compaction are stable, FIFO order is
preserved) — so unique/total counts, depths, discovery fingerprints, and
the golden WriteReporter strings must all match the ``bucket_ladder=0``
(fixed-width) dispatch exactly, for both the materializing and the
fingerprint-only (``expand_fps``) pipelines.
"""

import io
import re

import jax
import jax.numpy as jnp
import pytest

from stateright_tpu import WriteReporter
from stateright_tpu.checker.tpu import (
    _MIN_BUCKET,
    bucket_for,
    bucket_ladder_widths,
)
from stateright_tpu.models.linearizable_register import AbdModelCfg
from stateright_tpu.models.raft import RaftModelCfg
from stateright_tpu.models.single_copy_register import SingleCopyModelCfg
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.ops.hashset import hashset_new
from stateright_tpu.telemetry import metrics_registry


# -- ladder unit semantics -------------------------------------------------


def test_ladder_widths_descending_pow2():
    assert bucket_ladder_widths(2048, 4) == [2048, 1024, 512, 256, 128]
    assert bucket_ladder_widths(64, 4) == [64, 32, 16, 8]
    assert bucket_ladder_widths(64, 0) == [64]
    # The floor is one tile: rungs never go below _MIN_BUCKET.
    assert bucket_ladder_widths(16, 6) == [16, 8]
    assert min(bucket_ladder_widths(4096, 10)) >= _MIN_BUCKET


def test_bucket_for_picks_smallest_holding_rung():
    widths = [2048, 1024, 512, 256, 128]
    assert bucket_for(widths, 1) == 128
    assert bucket_for(widths, 128) == 128
    assert bucket_for(widths, 129) == 256
    assert bucket_for(widths, 1024) == 1024
    assert bucket_for(widths, 2048) == 2048
    # Beyond the widest rung: the widest rung is the cap.
    assert bucket_for(widths, 100_000) == 2048


# -- equivalence suite -----------------------------------------------------


def _golden(checker):
    out = io.StringIO()
    checker.report(WriteReporter(out))
    # The wall-clock field is the only permitted difference.
    return re.sub(r"sec=\d+", "sec=_", out.getvalue())


def _run_pair(model_fn, **kw):
    """Runs the same model bucketed (full ladder, forced — the default
    only auto-engages at production frontier sizes) and fixed-width;
    returns both finished checkers."""
    bucketed = (
        model_fn().checker().spawn_tpu_bfs(bucket_ladder=4, **kw).join()
    )
    fixed = (
        model_fn().checker().spawn_tpu_bfs(bucket_ladder=0, **kw).join()
    )
    assert bucketed.worker_error() is None
    assert fixed.worker_error() is None
    return bucketed, fixed


def _assert_identical(bucketed, fixed):
    assert bucketed.unique_state_count() == fixed.unique_state_count()
    assert bucketed.state_count() == fixed.state_count()
    assert bucketed.max_depth() == fixed.max_depth()
    assert bucketed._discoveries_fp == fixed._discoveries_fp
    assert _golden(bucketed) == _golden(fixed)


def test_bucketed_identical_2pc():
    """Materializing pipeline (2pc has no fps hooks), deep drain. Also
    asserts the bucketed run leaves the per-rung dispatch counters plus
    the compaction/fill gauges in the registry (the bench leg JSON reads
    them)."""
    metrics_registry().reset()
    b, f = _run_pair(
        lambda: TwoPhaseSys(3),
        frontier_capacity=64,
        table_capacity=1 << 10,
        drain_log_factor=1,  # frequent drain exits exercise rung changes
    )
    assert b.unique_state_count() == 288
    _assert_identical(b, f)
    snap = metrics_registry().snapshot()
    dispatch = {
        int(k.rsplit(".", 1)[1]): v
        for k, v in snap.items()
        if k.startswith("tpu_bfs.bucket_dispatch.")
    }
    assert dispatch, "bucketed run must record per-rung dispatch counts"
    assert all(w in bucket_ladder_widths(64, 4) for w in dispatch)
    assert 0.0 < snap["tpu_bfs.compaction_ratio"] <= 1.0
    assert 0.0 < snap["tpu_bfs.frontier_fill"] <= 1.0
    assert snap["tpu_bfs.wave_bucket"] in bucket_ladder_widths(64, 4)


def test_bucketed_identical_2pc_wave_at_a_time():
    """The chunk path (max_drain_waves=1) with per-chunk compaction."""
    b, f = _run_pair(
        lambda: TwoPhaseSys(3),
        frontier_capacity=64,
        table_capacity=1 << 10,
        max_drain_waves=1,
    )
    assert b.unique_state_count() == 288
    _assert_identical(b, f)


@pytest.mark.slow
@pytest.mark.parametrize("expand_fps", [None, False])
def test_bucketed_identical_abd(expand_fps):
    """ABD register (fps-capable): both the fingerprint-only wave
    (expand_fps=None resolves to on) and the forced materializing wave."""
    b, f = _run_pair(
        lambda: AbdModelCfg(2, 2).into_model(),
        frontier_capacity=256,
        table_capacity=1 << 13,
        drain_log_factor=1,
        expand_fps=expand_fps,
    )
    assert b.unique_state_count() == 544
    _assert_identical(b, f)


@pytest.mark.slow
def test_bucketed_identical_property_violation():
    """A property-violating model: the falsifiable ``stable leader``
    liveness property must be discovered at the SAME counterexample
    fingerprint (the golden reporter compares the replayed paths)."""
    b, f = _run_pair(
        lambda: RaftModelCfg(
            server_count=3, max_term=1, lossy=True
        ).into_model(),
        frontier_capacity=128,
        table_capacity=1 << 13,
        drain_log_factor=1,  # frequent drain exits exercise rung changes
    )
    assert "stable leader" in b._discoveries_fp
    _assert_identical(b, f)


def test_bucketed_identical_single_copy_fps():
    """Fast-lane coverage of the fingerprint-only pipeline: the 93-state
    single-copy register (fps-capable) at a tiny frontier; the slow lane
    re-checks fps on/off at scale on the ABD register."""
    b, f = _run_pair(
        lambda: SingleCopyModelCfg(2, 1).into_model(),
        frontier_capacity=64,
        table_capacity=1 << 10,
        drain_log_factor=1,
    )
    assert b.unique_state_count() == 93
    assert b._use_fps  # the pipeline under test really is the fps wave
    _assert_identical(b, f)


# -- dispatch overhead budget (tier-1 micro-benchmark) ---------------------


def test_bucket_dispatch_overhead_under_budget():
    """What the adaptive dispatch adds to a wave, counted: one dispatch
    per chunk, and a compaction only when the chosen rung is narrower
    than the chunk — a FULL frontier selects the widest rung and skips
    compaction, so the dispatch can be always-on. (Counts, not CPU
    wall-clock: a CPU timing carries no speed meaning, ROADMAP C8.)"""
    model = TwoPhaseSys(5)
    checker = model.checker().spawn_tpu_bfs(
        frontier_capacity=512, table_capacity=1 << 14
    ).join()
    assert checker.worker_error() is None
    F = checker._F_max

    # A synthetic FULL frontier (every lane live) of real packed states.
    init = model.packed_init_states()
    states = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(
            x[:1], (F,) + x.shape[1:]
        ).astype(x.dtype),
        init,
    )
    hi, lo = jax.vmap(checker._fp_fn)(states)

    def chunk(live):
        return {
            "states": states,
            "hi": hi,
            "lo": lo,
            "ebits": jnp.zeros((F,), jnp.uint32),
            "depth": jnp.ones((F,), jnp.int32),
            "mask": jnp.arange(F) < live,
        }

    compactions, dispatches = [], []
    compact, record = checker._compact_chunk, checker._record_dispatch

    def counting_compact(c, width):
        compactions.append(width)
        return compact(c, width)

    def counting_record(width, live):
        dispatches.append((width, live))
        return record(width, live)

    checker._compact_chunk = counting_compact
    checker._record_dispatch = counting_record
    depth_cap = jnp.int32((1 << 31) - 1)
    narrow = F // 4 - 3
    rung = bucket_for(checker._buckets, narrow)
    assert rung < F
    for live in (F, narrow, F):
        checker._last_dispatch = None
        _out, dispatched = checker._call_wave(
            hashset_new(1 << 14), chunk(live), depth_cap
        )
        assert dispatched["hi"].shape[0] == (F if live == F else rung)
    assert dispatches == [(F, F), (rung, narrow), (F, F)]
    assert compactions == [rung]


# -- checkpoint/resume under donation (regression) -------------------------


def test_deep_drain_checkpoint_roundtrip_with_donation(tmp_path):
    """The ring-export/checkpoint path must keep NON-donated copies: a
    checkpoint written mid-run (the pool ring exported between donated
    drain calls) must resume to the exact full space. Guards the
    donation audit — a donated export would either crash (deleted
    buffer) or corrupt the resumed frontier. (The wave-at-a-time
    checkpoint flavor is covered by tests/test_checkpoint.py, which now
    also runs under donation.)"""
    ckpt = tmp_path / "bucketed_deep.ckpt"
    first = TwoPhaseSys(4).checker().spawn_tpu_bfs(
        frontier_capacity=64,
        table_capacity=1 << 10,
        checkpoint_path=str(ckpt),
        checkpoint_every_chunks=2,  # caps waves-per-drain at 2
        drain_log_factor=1,
    ).join()
    assert first.worker_error() is None
    assert first.unique_state_count() == 1568
    assert ckpt.exists()
    resumed = (
        TwoPhaseSys(4)
        .checker()
        .spawn_tpu_bfs(frontier_capacity=64, resume_from=str(ckpt))
        .join()
    )
    assert resumed.worker_error() is None
    # The checkpoint may already cover the whole space; the resumed run
    # must land on exactly the full count either way.
    assert resumed.unique_state_count() == 1568
    resumed.assert_properties()
