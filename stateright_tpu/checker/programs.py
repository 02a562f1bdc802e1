"""Where a device engine's compiled programs come from.

Every engine (the one-chip checker, the sharded checker, the tenant-packed
engine) runs its programs as ahead-of-time executables obtained through
one :class:`Programs`. It looks in one order: the executables this check
already holds, the process-wide shared cache (``aot_cache``), the disk
store (``aot_store``, for the wave and the drain), and a compile last.
Each first use in a check is one ``<prefix>.exe`` span (see
``telemetry/executables.py``) whose ``source`` is ``memory``, ``disk``,
``persistent_cache`` or ``compile``; a compile of the wave or the drain is
also a ``<prefix>.compile`` span inside the engine's compile phase.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict

import jax
from jax.api_util import shaped_abstractify

from ..telemetry.executables import exe_span

# Custom ``symmetry_fn`` runs key on fp(model.packed_representative(s)) —
# a third key space, tagged separately in checkpoints.
CUSTOM_REP_SCHEME = "custom-representative-v1"

# The kinds whose compiles count as compiles (the ``<prefix>.compile`` span
# and the compile phase) and whose executables the disk store keeps.
STEPS = frozenset(("wave", "drain"))

# -- cross-checker AOT executable sharing (checking-as-a-service) -----------
#
# One resident process serving many jobs must never recompile a wave shape
# a previous job already built: the programs are pure XLA programs (model
# constants baked in at trace time), so two engine INSTANCES whose traces
# are provably identical can share them. "Provably" is the caller's
# namespace (e.g. the service's model-zoo entry name) ANDed with a full
# trace signature — model digest, property list, key scheme, pipeline,
# ladder, capacities — so a namespace collision between genuinely
# different configurations still misses instead of corrupting.
_AOT_LOCK = threading.Lock()
_AOT_CACHES: Dict[tuple, dict] = {}


def shared_aot_cache(namespace: str, signature: tuple) -> dict:
    """The process-global executable dict for one (namespace, signature)
    — get-or-create, so every engine built with the same
    ``aot_cache=namespace`` and an identical trace signature probes and
    populates the same cache."""
    key = (namespace, signature)
    with _AOT_LOCK:
        return _AOT_CACHES.setdefault(key, {})


def clear_shared_aot_caches() -> None:
    """Drops every shared executable (tests / memory reclamation)."""
    with _AOT_LOCK:
        _AOT_CACHES.clear()


class Programs:
    """One engine's executables, by ``(kind,) + key``.

    ``prefix`` names the engine's spans; ``signature`` is everything its
    traces close over that a key does not hold. With ``aot_cache`` the
    dict is the shared one for ``(aot_cache, (prefix,) + signature)``: the
    namespace asserts the semantic equivalence the signature cannot see
    (property conditions closing over values outside the packed arrays),
    so it is refused where the signature names ``CUSTOM_REP_SCHEME`` (the
    traced key function is caller code). ``aot_store`` (a path or an
    ``AotDiskStore``) keeps the wave and the drain on disk under the same
    namespace, and so requires it. ``compile_phase()`` is the context a
    step compile runs in; ``on_compile(kind, seconds)`` is told of every
    compile, to charge it to the engine's warm-up."""

    def __init__(self, prefix, tracer, *, signature, on_compile,
                 aot_cache=None, aot_store=None, registry=None,
                 compile_phase=contextlib.nullcontext):
        self._exe_name = f"{prefix}.exe"
        self._compile_name = f"{prefix}.compile"
        self._tracer = tracer
        self._compile_phase = compile_phase
        self._on_compile = on_compile
        # What this check holds: a later lookup of one is a reuse, not an
        # acquisition.
        self.held = set()
        self.cache = {}
        self.disk = None
        if aot_cache is not None:
            if CUSTOM_REP_SCHEME in signature:
                raise ValueError(
                    "aot_cache cannot be shared under a custom "
                    "symmetry_fn: the traced key function is caller "
                    "code the cache signature cannot compare"
                )
            self.cache = shared_aot_cache(aot_cache, (prefix,) + signature)
        if aot_store is not None:
            if aot_cache is None:
                raise ValueError(
                    "aot_store requires aot_cache=<namespace>: the disk "
                    "entries inherit the namespace's semantic-equivalence "
                    "assertion (see shared_aot_cache)"
                )
            from ..storage.persist import AotDiskStore

            if not isinstance(aot_store, AotDiskStore):
                aot_store = AotDiskStore(aot_store)
            self.disk = aot_store.binding(
                aot_cache, (prefix,) + signature, registry=registry
            )

    def obtain(self, kind, key, jit_fn, args, **span_args):
        """The compiled ``jit_fn`` for ``args`` under ``(kind,) + key``: the
        one this check holds, else the shared cache's, else (a step) the
        disk store's, else one lowered and compiled now, through the
        persistent compilation cache."""
        full = (kind,) + key
        exe = self.cache.get(full)
        if exe is not None and full in self.held:
            return exe
        disk = self.disk if kind in STEPS else None
        with exe_span(self._tracer, self._exe_name, kind, **span_args) as sp:
            if exe is not None:
                sp.set(source="memory")
                if disk is not None:
                    # Warm memory, cold disk: a later fresh process still
                    # finds the artifact.
                    disk.ensure(kind, key, exe)
            elif disk is not None:
                # A fenced hit loads outside the compile phase: a warm
                # start records no compile.
                t0 = time.perf_counter()
                exe = disk.load(kind, key)
                if exe is not None:
                    sp.set(
                        source="disk",
                        build_ms=(time.perf_counter() - t0) * 1e3,
                    )
            if exe is None:
                exe = self._compile(kind, jit_fn, args, span_args)
                if disk is not None:
                    disk.save(kind, key, exe)
            self.cache[full] = exe
        self.held.add(full)
        return exe

    def _compile(self, kind, jit_fn, args, span_args):
        t0 = time.perf_counter()
        if kind in STEPS:
            with self._tracer.span(
                self._compile_name, kind=kind, **span_args
            ), self._compile_phase():
                exe = jit_fn.lower(*args).compile()
        else:
            exe = jit_fn.lower(*args).compile()
        self._on_compile(kind, time.perf_counter() - t0)
        return exe

    def jit(self, kind, fn, static_argnums=(), **jit_kwargs):
        """``jax.jit(fn, ...)`` run as executables this obtains."""
        return AotJit(
            self.obtain, kind, fn, static_argnums=static_argnums,
            **jit_kwargs,
        )


def aval_key(x) -> tuple:
    """Shape, dtype and weak type of one argument leaf: what a compiled
    executable is specialised to."""
    a = shaped_abstractify(x)
    return (a.shape, a.dtype, a.weak_type)


class AotJit:
    """A ``jax.jit`` run as ahead-of-time executables that ``obtain(kind,
    key, jit_fn, args)`` supplies. The key holds the arguments at the
    ``static_argnums`` positions (baked into the executable, so the call
    drops them), the pytree structure of the others and each of their
    leaves' ``aval_key``: callers whose ``obtain`` looks in one dict share
    executables."""

    __slots__ = ("_obtain", "_kind", "_jit", "_static")

    def __init__(self, obtain, kind, fn, static_argnums=(), **jit_kwargs):
        self._obtain = obtain
        self._kind = kind
        self._jit = jax.jit(fn, static_argnums=static_argnums, **jit_kwargs)
        self._static = frozenset(static_argnums)

    def __call__(self, *args):
        static = self._static
        if static:
            fixed = tuple(a for i, a in enumerate(args) if i in static)
            dyn = tuple(a for i, a in enumerate(args) if i not in static)
        else:
            fixed, dyn = (), args
        leaves, tree = jax.tree_util.tree_flatten(dyn)
        key = (fixed, tree, tuple(map(aval_key, leaves)))
        return self._obtain(self._kind, key, self._jit, args)(*dyn)
