"""Checker builder: configuration + spawn entry points for every backend.

Reference: ``CheckerBuilder`` at ``/root/reference/src/checker.rs:64-267``.
New in this framework: ``spawn_tpu_bfs`` (device frontier-expansion BFS) and
``spawn_tpu_simulation`` (vmapped random-walk lanes).
"""

from __future__ import annotations

from typing import Callable, Optional

from ..core.visitor import CheckerVisitor, FnVisitor


def default_representative(state):
    """The ``symmetry()`` default. A named sentinel so device checkers can
    tell it apart from a user-supplied ``symmetry_fn`` (whose custom
    equivalence they cannot honor — they reduce by the full permutation
    group instead, which would over-merge under a partial symmetry)."""
    return state.representative()


class CheckerBuilder:
    def __init__(self, model):
        self.model = model
        self._symmetry: Optional[Callable] = None
        self._target_state_count: Optional[int] = None
        self._target_max_depth: Optional[int] = None
        self._thread_count: int = 1
        self._visitor: Optional[CheckerVisitor] = None
        self._complete_liveness: bool = False
        self._liveness_budget_states: Optional[int] = None
        self._liveness_deadline_s: Optional[float] = None

    # -- configuration -----------------------------------------------------

    def symmetry(self) -> "CheckerBuilder":
        """Enables symmetry reduction: host checkers dedup on
        ``state.representative()``; device checkers use orbit-proper
        minimum-fingerprint keys (see ``core/batch.py``)."""
        return self.symmetry_fn(default_representative)

    def symmetry_fn(self, representative: Callable) -> "CheckerBuilder":
        self._symmetry = representative
        return self

    def complete_liveness(self, budget_states: Optional[int] = None,
                          deadline_s: Optional[float] = None,
                          ) -> "CheckerBuilder":
        """Opt-in cycle-aware ``eventually`` checking (beyond the
        reference, whose semantics miss counterexamples that loop —
        documented FIXMEs at ``src/checker/bfs.rs:285-305``): after
        exploration, every undiscovered ``eventually`` property gets a
        host-side lasso search over the condition-false region
        (``checker/liveness.py``). Costs O(|condition-false region|) host
        time/memory, hence opt-in; the default semantics stay
        reference-exact. Honored by the exhaustive checkers
        (bfs/dfs/tpu_bfs/sharded_tpu_bfs), which refuse capped runs
        (``target_state_count``/``target_max_depth``) under this flag —
        the lasso search cannot honor caps.

        ``budget_states`` / ``deadline_s`` bound the pass: properties it
        cannot certify within the budget report an honest
        ``inconclusive`` outcome (reporter line, ``liveness.inconclusive``
        metric, ``liveness_report()``) instead of stalling
        ``discoveries()`` for unbounded host minutes. For sound verdicts
        WITHOUT the O(region) cost, prefer the device checkers'
        ``liveness="device"`` spawn knob (README "Trustworthy
        liveness")."""
        self._complete_liveness = True
        self._liveness_budget_states = budget_states
        self._liveness_deadline_s = deadline_s
        return self

    def target_state_count(self, count: int) -> "CheckerBuilder":
        """The checker may exceed this number, but will never generate fewer
        states if more exist."""
        self._target_state_count = count if count > 0 else None
        return self

    def target_max_depth(self, depth: int) -> "CheckerBuilder":
        self._target_max_depth = depth if depth > 0 else None
        return self

    def threads(self, thread_count: int) -> "CheckerBuilder":
        self._thread_count = thread_count
        return self

    def visitor(self, visitor) -> "CheckerBuilder":
        """A function or CheckerVisitor run on each evaluated state's path."""
        if not isinstance(visitor, CheckerVisitor):
            visitor = FnVisitor(visitor)
        self._visitor = visitor
        return self

    # -- spawns ------------------------------------------------------------

    def spawn_bfs(self):
        """Breadth-first host checker; shortest paths when single-threaded."""
        from .bfs import BfsChecker

        return BfsChecker(self)

    def spawn_dfs(self):
        """Depth-first host checker; dramatically less memory than BFS."""
        from .dfs import DfsChecker

        return DfsChecker(self)

    def spawn_on_demand(self):
        """Lazy checker that only computes states when asked (Explorer)."""
        from .on_demand import OnDemandChecker

        return OnDemandChecker(self)

    def spawn_simulation(self, seed: int, chooser=None):
        """Random-walk checking for state spaces too large to exhaust."""
        from .simulation import SimulationChecker, UniformChooser

        return SimulationChecker(self, seed, chooser or UniformChooser())

    def spawn_tpu_bfs(self, devices: Optional[int] = None, **kwargs):
        """TPU-accelerated BFS: vmapped frontier expansion + device-resident
        fingerprint set. Requires the model to implement ``BatchableModel``
        (or be convertible via ``stateright_tpu.models.packing``).
        ``wave_kernel="fused"`` runs the whole wave body — expand,
        fingerprint, sort-dedup, the VMEM tile-sweep insert, compaction,
        properties, coverage — as one Pallas dispatch per wave instead
        of the staged XLA chain (README "Fused wave megakernel");
        bit-identical to ``wave_kernel="staged"`` with
        ``wave_dedup="sort"``, interpreted off-TPU.

        ``devices=n`` spreads the check over the first ``n`` devices, as
        upstream's ``threads(n)`` spreads it over workers: the sharded
        checker (``spawn_sharded_tpu_bfs``) over ``default_mesh(n)``, its
        visited set partitioned by fingerprint. Its arguments are the
        sharded checker's (``frontier_per_device``,
        ``table_capacity_per_device``, ...); one the sharded checker does
        not take raises ``ValueError`` naming it."""
        if devices is None:
            from .tpu import TpuBfsChecker

            return TpuBfsChecker(self, **kwargs)
        import inspect

        from ..parallel.base_mesh import default_mesh
        from ..parallel.sharded import ShardedTpuBfsChecker

        taken = inspect.signature(ShardedTpuBfsChecker).parameters
        refused = sorted(k for k in kwargs if k == "mesh" or k not in taken)
        if refused:
            raise ValueError(
                f"spawn_tpu_bfs(devices={devices}) runs the sharded checker, "
                f"which takes no {', '.join(refused)} (the mesh comes from "
                "devices; per-device widths are frontier_per_device and "
                "table_capacity_per_device)"
            )
        return ShardedTpuBfsChecker(
            self, mesh=default_mesh(devices), **kwargs
        )

    def spawn_sharded_tpu_bfs(self, mesh=None, **kwargs):
        """Multi-device BFS over a ``jax.sharding.Mesh``: the visited set is
        sharded by fingerprint range and candidate keys ride an all-to-all;
        states never leave the device that generated them."""
        from ..parallel.sharded import ShardedTpuBfsChecker

        return ShardedTpuBfsChecker(self, mesh=mesh, **kwargs)

    def spawn_tpu_simulation(self, seed: int, lanes: int = 1024, **kwargs):
        """TPU-accelerated simulation: N vmapped random-walk lanes."""
        from .tpu_simulation import TpuSimulationChecker

        return TpuSimulationChecker(self, seed, lanes, **kwargs)

    def spawn_swarm(self, seed: int, **kwargs):
        """Swarm verification: the entire randomized-walk loop runs
        device-resident — per-walk threefry PRNG streams, restart/
        boundary/depth/terminal handling, property evaluation, and
        discovery capture fused into one long jitted scan per wave —
        with a device hash-table sample of walk fingerprints for an
        honest unique-coverage estimate. For state spaces too large
        even for the tiered store; preemptible, packable, and
        seed-deterministic (README "Swarm verification"). Pass
        ``seeds=`` (a packed-state pool, or a budget-exhausted
        ``spawn_tpu_bfs`` preempt payload) for the frontier-seeded
        hybrid mode. Reference simulation semantics: the run ends when
        every property has a discovery or ``target_state_count`` is
        reached — a model with a HOLDING ``always`` property needs a
        walk-step target or it samples forever."""
        from .swarm import SwarmChecker

        return SwarmChecker(self, seed, **kwargs)

    def serve(self, address):
        """Starts the interactive Explorer web service (blocks)."""
        from .explorer import serve

        return serve(self, address)
