"""Executable acquisition as spans.

A check obtains an executable when it meets a program at a new argument
signature: it takes a compiled program from an ahead-of-time cache, or
traces and lowers the function and then compiles it or loads it from the
persistent compilation cache. Each acquisition is one span (the device
checker's ``tpu_bfs.exe``) with ``kind`` (the program), ``source``
(``memory``, ``disk``, ``persistent_cache`` or ``compile``), ``trace_ms``
(trace and lowering) and ``build_ms`` (compile or load); a call that
reuses an executable records none.

JAX reports trace, lowering and compile durations, and persistent-cache
hits, through ``jax.monitoring`` on the calling thread; the build meter
sums them per thread, so a span can say what its acquisition cost and
where the executable came from.
"""

from __future__ import annotations

import contextlib
import threading

import jax

_TRACE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_meter = threading.local()
_meter_lock = threading.Lock()
_meter_installed = False


def _meter_duration(event, secs, **_):
    if event == _BUILD_EVENT:
        _meter.build = getattr(_meter, "build", 0.0) + secs
    elif event in _TRACE_EVENTS:
        _meter.trace = getattr(_meter, "trace", 0.0) + secs


def _meter_event(event, **_):
    if event == _CACHE_HIT_EVENT:
        _meter.hits = getattr(_meter, "hits", 0) + 1


def install_build_meter() -> None:
    """Registers the meter's ``jax.monitoring`` listeners, once per
    process."""
    global _meter_installed
    with _meter_lock:
        if not _meter_installed:
            jax.monitoring.register_event_duration_secs_listener(
                _meter_duration
            )
            jax.monitoring.register_event_listener(_meter_event)
            _meter_installed = True


def build_meter():
    """(trace + lowering seconds, compile-or-load seconds, persistent-cache
    hits) reported by JAX on this thread so far."""
    return (
        getattr(_meter, "trace", 0.0),
        getattr(_meter, "build", 0.0),
        getattr(_meter, "hits", 0),
    )


def acquired(before, after) -> dict:
    """``source``, ``trace_ms`` and ``build_ms`` of an acquisition from
    the build meter's readings around it."""
    (t0, b0, h0), (t1, b1, h1) = before, after
    if h1 > h0:
        source = "persistent_cache"
    elif b1 > b0:
        source = "compile"
    else:
        source = "memory"
    return {
        "source": source,
        "trace_ms": (t1 - t0) * 1e3,
        "build_ms": (b1 - b0) * 1e3,
    }


@contextlib.contextmanager
def exe_span(tracer, name, kind, **args):
    """One acquisition as a ``name`` span; ``source``, ``trace_ms`` and
    ``build_ms`` come from the build meter unless the caller has set them
    on the span."""
    install_build_meter()
    before = build_meter()
    with tracer.span(name, kind=kind, **args) as sp:
        yield sp
        found = acquired(before, build_meter())
        sp.set(**{k: v for k, v in found.items() if k not in sp.args})
