"""Compiles for a described TPU v5e, without the chip (on-chip guide §2,
rehearsal 3): the kernels of the main path at the widths ``chip_smoke.py``
runs them, refused here exactly as the chip's compiler would refuse them.

The topology is described only inside the module-scoped fixture, never at
import time: one process at a time may load the TPU's library, and every
xdist worker imports this file. Code that asks ``jax.default_backend()``
still sees the CPU here, so each test steers it itself (the chip's
``wave_dedup="sort"``, materializing wave and ``interpret=False``).

Also here: ``chip_smoke.py``'s phase function on the CPU at a tiny size.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import bench
import chip_smoke
from stateright_tpu.checker.tpu import TpuBfsChecker
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.ops.hashset import MAX_PROBES

# The fused megakernel's in-kernel sort and gathers have no Mosaic
# lowering (ROADMAP A2).
FUSED_REFUSAL = "Shape mismatch in input, indices and output"


@pytest.fixture(scope="module")
def cache_off():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(cache_off):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _idle_checker(monkeypatch, model, **spawn):
    """A configured TpuBfsChecker whose worker does nothing: its jitted
    device functions, ready to lower against described shapes."""
    monkeypatch.setattr(TpuBfsChecker, "_run", lambda self: None)
    return model.checker().spawn_tpu_bfs(**spawn).join()


def _spec(sharding):
    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    return spec


def _table(checker, sharding):
    return jax.ShapeDtypeStruct(
        (checker._capacity + MAX_PROBES, 2), jnp.uint32, sharding=sharding
    )


@pytest.mark.parametrize("leg", ["2pc", "paxos3"])
def test_staged_drain_compiles_at_smoke_width(leg, one_chip, monkeypatch):
    """The deep drain — the staged XLA wave in its device loop, the
    program ``chip_smoke.py`` dispatches — at the leg's full width."""
    spec = bench._leg_specs()[leg]
    checker = _idle_checker(
        monkeypatch, spec["model"](), **spec["spawn"],
        wave_dedup="sort", expand_fps=False,
    )
    width = checker._F_max
    assert width == spec["spawn"]["frontier_capacity"]
    to_chip = _spec(one_chip)
    pool = jax.tree_util.tree_map(
        to_chip, jax.eval_shape(lambda: checker._pool_zero(checker._pool_capacity))
    )
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    undiscovered = jax.ShapeDtypeStruct(
        (len(checker._properties),), jnp.bool_, sharding=one_chip
    )
    args = (_table(checker, one_chip), pool, i32, i32, undiscovered, i32, i32)
    compiled = (
        jax.jit(lambda *a: checker._deep_drain(width, *a), donate_argnums=(0, 1))
        .lower(*args)
        .compile()
    )
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_sharded_drain_compiles_for_four_chips(topo, monkeypatch):
    """The sharded deep drain over the four chips of a v5e host, at the
    widths ``chip_smoke.py --chips 4`` and the ``2pc7.sharded4`` cell run
    (2pc-7, 2,048 lanes and 2^18 rows per chip): key exchange and row
    balance by ``all_to_all``, the drain's exit by ``psum``."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from stateright_tpu.ops.ring import ring_rows
    from stateright_tpu.parallel.sharded import ShardedTpuBfsChecker

    monkeypatch.setattr(
        ShardedTpuBfsChecker, "_run", lambda self: self._done_event.set()
    )
    mesh = Mesh(np.array(topo.devices), ("fp",))
    model = TwoPhaseSys(7)
    checker = ShardedTpuBfsChecker(
        model.checker(), mesh=mesh,
        frontier_per_device=chip_smoke.SHARDED_FRONTIER_PER_DEVICE,
        table_capacity_per_device=chip_smoke.SHARDED_TABLE_PER_DEVICE,
    )
    shard, rep = NamedSharding(mesh, P("fp")), NamedSharding(mesh, P())

    def s(shape, dtype, sharding=shard):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = jax.tree_util.tree_map(
        lambda x: s(x.shape, x.dtype),
        jax.eval_shape(lambda: ring_rows(model, 4 * checker._PCl)),
    )
    i32 = s((), jnp.int32, rep)
    args = (
        s((4, checker._cap_loc + MAX_PROBES, 2), jnp.uint32), pool,
        s((4,), jnp.int32), s((4,), jnp.int32),
        s((len(checker._properties),), jnp.bool_, rep), i32, i32,
    )
    compiled = checker._jit_deep_drain.lower(*args).compile()
    assert "all-to-all" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


@pytest.mark.parametrize("batch", [4096, 1 << 17])
def test_pallas_insert_compiles(batch, one_chip):
    """The tile-sweep insert at a 2^20-row table: keys staged through
    SMEM, so the batch is bounded by HBM, not by SMEM's 1 MiB."""
    from stateright_tpu.ops.pallas_hashset import pallas_hashset_insert

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = (
        jax.jit(pallas_hashset_insert, static_argnames=("interpret",))
        .lower(
            s(((1 << 20) + MAX_PROBES, 2), jnp.uint32),
            s((batch,), jnp.uint32),
            s((batch,), jnp.uint32),
            s((batch,), jnp.bool_),
        )
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(strict=True, raises=ValueError, reason=FUSED_REFUSAL)
def test_fused_wave_compiles(one_chip, monkeypatch):
    """The fused megakernel on 2pc-3 (F=256, table 2^14), compiled as on
    the chip. Mosaic refuses its in-kernel gather; the day it compiles,
    this strict xfail fails and ROADMAP A2 moves on."""
    checker = _idle_checker(
        monkeypatch, TwoPhaseSys(3),
        frontier_capacity=256, table_capacity=1 << 14, wave_kernel="fused",
    )
    checker._fused_spec = dataclasses.replace(
        checker._fused_spec, interpret=False
    )
    F = checker._F_max
    to_chip = _spec(one_chip)
    ring = jax.tree_util.tree_map(
        to_chip, jax.eval_shape(lambda: checker._pool_zero(F))
    )
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    args = (
        _table(checker, one_chip), ring["states"], ring["hi"], ring["lo"],
        ring["ebits"], ring["depth"],
        jax.ShapeDtypeStruct((F,), jnp.bool_, sharding=one_chip), i32,
    )
    try:
        jax.jit(checker._wave, donate_argnums=(0,)).lower(*args).compile()
    except ValueError as e:
        if FUSED_REFUSAL not in str(e):
            pytest.fail(f"fused wave refused for another reason: {e}")
        raise


def test_smoke_phase_on_cpu():
    """chip_smoke's phase function end to end on 2pc-3 (288 states); the
    platform check lives in main(), which this test does not call."""
    checker = chip_smoke.run_phase(
        "2pc check 3", TwoPhaseSys(3),
        dict(frontier_capacity=64, table_capacity=1 << 12), 288,
    )
    assert checker.unique_state_count() == 288
    with pytest.raises(AssertionError, match="288 unique states != 289"):
        chip_smoke.run_phase(
            "2pc check 3", TwoPhaseSys(3),
            dict(frontier_capacity=64, table_capacity=1 << 12), 289,
        )


def test_smoke_sharded_phase_on_cpu():
    """The same phase spread over two devices (``devices=2``), as
    ``chip_smoke.py --chips 4`` spreads 2pc check 7 over four."""
    spawn = dict(devices=2, frontier_per_device=32,
                 table_capacity_per_device=1 << 11)
    checker = chip_smoke.run_phase("2pc check 3", TwoPhaseSys(3), spawn, 288)
    assert len({d for d, _ in checker.visited_placement}) == 2


def test_smoke_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
