"""TPU random-walk checker: N vmapped simulation lanes in lockstep.

The host ``SimulationChecker`` rolls one trace per thread
(reference design: ``/root/reference/src/checker/simulation.rs``); here L
lanes advance together under one jitted ``lax.scan`` — per step each lane

1. restarts from a uniformly chosen initial state if its trace ended;
2. mirrors the host trace loop *in order*: depth-cap abort (no
   ``eventually`` discoveries), boundary exit (trace excludes the current
   state), on-device fingerprint + cycle check against the lane's own
   trace buffer (trace includes the current state), property evaluation,
   then a uniform choice among valid transitions (terminal exit when none);
3. on a first property hit anywhere in the batch, snapshots that lane's
   fingerprint trace into a per-property discovery buffer — the host
   replays it into a ``Path`` exactly like the other device checkers.

Like the reference, simulation only returns when every property has a
discovery or ``target_state_count`` is reached, and ``unique_state_count``
is approximated by the total count. Cycle-detection symmetry reduction is
host-only (use ``spawn_simulation`` for symmetric models); traces longer
than the lane buffer (``max_trace_len``) are aborted like a depth-cap.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batch import BatchableModel
from ..core.model import Expectation
from ..core.path import Path
from ..ops.fingerprint import fingerprint_state, fp_to_int
from ..telemetry import get_tracer, metrics_registry
from .base import Checker

_NEG_INF = -1e30


def walk_lane_step(k, seeds, n_seeds, state, depth, ebits, done, thi, tlo,
                   key, depth_cap):
    """One walk step for a single lane — the shared trace-loop core of
    ``TpuSimulationChecker`` and the swarm kernel (``checker/swarm.py``),
    vmapped over lanes by each caller. Mirrors the host
    ``SimulationChecker`` loop *in order*: restart from the seed pool,
    depth cap, boundary exit, fingerprint + own-trace cycle check,
    property evaluation, uniform choice among valid transitions.

    ``k`` supplies the packed-model surface (``_model``/``_fp_fn``/
    ``_conditions``/``_ebit``/``_ebits0``/``_properties``/``_A``/``_D``
    and, when the swarm runs with coverage, ``_cov_layout``/
    ``_cov_antecedents``). ``depth_cap`` is a runtime scalar so one
    compiled shape serves every cap (the simulation checker pins it to
    its buffer depth ``D``). Returns the superset of per-step outputs;
    each caller's scan consumes its subset and XLA drops the rest —
    keeping ONE copy of the walk semantics is what guarantees the two
    walkers can never silently diverge."""
    model = k._model
    A, D = k._A, k._D
    key, k_init, k_act = jax.random.split(key, 3)

    # Restart ended lanes from a uniformly chosen seed state.
    init_idx = jax.random.randint(k_init, (), 0, n_seeds)
    restarted = done
    state = jax.tree_util.tree_map(
        lambda fresh, cur: jnp.where(done, fresh[init_idx], cur),
        seeds,
        state,
    )
    depth = jnp.where(done, 0, depth)
    ebits = jnp.where(done, k._ebits0, ebits)

    cap = jnp.minimum(jnp.int32(D), depth_cap)
    capped = depth >= cap
    # A cap hit BELOW the user's depth target (or with no target at
    # all) is a trace-buffer truncation, not a semantic bound — the
    # honest-overflow counter the reporter warns on.
    truncated = capped & (jnp.int32(D) < depth_cap)
    in_bounds = model.packed_within_boundary(state)
    boundary_end = ~capped & ~in_bounds

    hi, lo = k._fp_fn(state)
    slots = jnp.arange(D, dtype=jnp.int32)
    seen = slots < depth
    cycle = (seen & (thi == hi) & (tlo == lo)).any()
    # Record the current fingerprint (host appends before cycle break,
    # so cycle/terminal/property traces include the current state).
    write = ~capped & ~boundary_end
    thi = jnp.where(write & (slots == depth), hi, thi)
    tlo = jnp.where(write & (slots == depth), lo, tlo)
    cycle_end = write & cycle

    eval_ok = write & ~cycle
    cond_vals = [c(state) for c in k._conditions]
    ebits_after = ebits
    for pi, b in k._ebit.items():
        ebits_after = jnp.where(
            eval_ok & cond_vals[pi],
            ebits_after & ~jnp.uint32(1 << b),
            ebits_after,
        )

    # Uniform choice among valid transitions.
    aids = jnp.arange(A, dtype=jnp.int32)
    cand, cvalid = jax.vmap(lambda a: model.packed_step(state, a))(aids)
    cvalid = cvalid & eval_ok
    terminal = eval_ok & ~cvalid.any()
    logits = jnp.where(cvalid, 0.0, _NEG_INF)
    choice = jax.random.categorical(k_act, logits)
    advanced = eval_ok & ~terminal
    state = jax.tree_util.tree_map(
        lambda c, cur: jnp.where(advanced, c[choice], cur), cand, state
    )

    ebits_end = boundary_end | cycle_end | terminal
    done = capped | ebits_end
    # Trace length as the host's fingerprint_path would have it (capped
    # and out-of-boundary exits happen before the host appends).
    path_len = jnp.where(capped | boundary_end, depth, depth + 1)
    depth = jnp.where(advanced, depth + 1, depth)

    cov_layout = getattr(k, "_cov_layout", None)
    per_prop = []
    exercised = []
    for i, p in enumerate(k._properties):
        if p.expectation == Expectation.ALWAYS:
            hit = eval_ok & ~cond_vals[i]
        elif p.expectation == Expectation.SOMETIMES:
            hit = eval_ok & cond_vals[i]
        else:
            b = k._ebit[i]
            hit = ebits_end & (((ebits_after >> jnp.uint32(b)) & 1) == 1)
        per_prop.append(hit)
        if cov_layout is not None:
            if p.expectation == Expectation.ALWAYS:
                ant = k._cov_antecedents[i]
                exercised.append(
                    eval_ok & ant(state) if ant is not None else eval_ok
                )
            elif p.expectation == Expectation.SOMETIMES:
                exercised.append(eval_ok & cond_vals[i])
            else:
                eb = k._ebit[i]
                exercised.append(
                    eval_ok
                    & (((ebits_after >> jnp.uint32(eb)) & 1) == 0)
                )
    hits = (
        jnp.stack(per_prop) if per_prop else jnp.zeros((0,), bool)
    )

    out = {
        "state": state,
        "depth": depth,
        "ebits": ebits_after,
        "done": done,
        "thi": thi,
        "tlo": tlo,
        "key": key,
        "counted": eval_ok,
        "hits": hits,
        "path_len": path_len,
        "capped": capped,
        "hi": hi,
        "lo": lo,
        "write": write,
        "restarted": restarted,
        "truncated": truncated,
    }
    if cov_layout is not None:
        out["cvalid"] = cvalid
        out["choice"] = choice
        out["advanced"] = advanced
        out["exercised"] = (
            jnp.stack(exercised)
            if exercised
            else jnp.zeros((0,), bool)
        )
    return out


def walk_kernel_surface(model):
    """The packed walk-kernel contract both walkers build at init:
    aligned condition callables, the eventually-property bit map, and
    the all-pending ebits seed. One copy so the eventually-bit encoding
    ``walk_lane_step`` consumes can never diverge between them. Returns
    ``(properties, conditions, ebit, ebits0)``."""
    properties = model.properties()
    conditions = model.packed_conditions()
    if len(conditions) != len(properties):
        raise ValueError(
            "packed_conditions() must align 1:1 with properties(): "
            f"{len(conditions)} != {len(properties)}"
        )
    eventually = [
        i
        for i, p in enumerate(properties)
        if p.expectation == Expectation.EVENTUALLY
    ]
    if len(eventually) > 32:
        raise ValueError("at most 32 eventually properties supported")
    ebit: Dict[int, int] = {pi: b for b, pi in enumerate(eventually)}
    ebits0 = np.uint32(sum(1 << b for b in ebit.values()))
    return properties, conditions, ebit, ebits0


def capture_discoveries(disc, out, P):
    """First-hit discovery capture shared by both walkers: for each of
    the P properties with a hit anywhere in the batch this step,
    snapshot the hitting lane's fingerprint trace into the per-property
    discovery buffers exactly once — the first step that hits wins, and
    later hits leave the recorded trace untouched. One copy for the
    same reason as ``walk_lane_step``: a tie-break or trace-length
    change must not silently diverge the walkers' discovery traces."""
    hits = out["hits"]  # (L, P) after the callers' lane vmap
    for i in range(P):
        lane = jnp.argmax(hits[:, i])
        any_hit = hits[:, i].any()
        found_now = any_hit & ~disc["found"][i]
        disc = {
            "found": disc["found"].at[i].set(disc["found"][i] | any_hit),
            "hi": disc["hi"].at[i].set(
                jnp.where(found_now, out["thi"][lane], disc["hi"][i])
            ),
            "lo": disc["lo"].at[i].set(
                jnp.where(found_now, out["tlo"][lane], disc["lo"][i])
            ),
            "len": disc["len"].at[i].set(
                jnp.where(found_now, out["path_len"][lane], disc["len"][i])
            ),
        }
    return disc


class TpuSimulationChecker(Checker):
    # Honest capability surface (the PR 12 convention): the host-paced
    # step loop has no resumable payload and no shared-dispatch packing
    # — ``spawn_swarm`` is the device-resident walker that has both.
    supports_preempt = False
    supports_packing = False
    packing_reason = (
        "host-paced step loop (spawn_swarm is the packable walker)"
    )

    def __init__(
        self,
        options,
        seed: int,
        lanes: int = 1024,
        steps_per_call: int = 64,
        max_trace_len: Optional[int] = None,
    ):
        model = options.model
        if not isinstance(model, BatchableModel):
            raise TypeError(
                f"spawn_tpu_simulation requires a BatchableModel; "
                f"{type(model).__name__} does not implement the packed protocol"
            )
        if options._symmetry is not None:
            raise NotImplementedError(
                "symmetry-aware cycle detection is host-only; use "
                "spawn_simulation for symmetric models"
            )
        self._model = model
        (
            self._properties,
            self._conditions,
            self._ebit,
            self._ebits0,
        ) = walk_kernel_surface(model)
        self._A = model.packed_action_count()
        self._L = lanes
        self._K = steps_per_call
        self._depth_cap = options._target_max_depth
        self._D = max_trace_len or (self._depth_cap or 512)
        if self._depth_cap is not None:
            self._D = min(self._D, self._depth_cap)
        self._target_state_count = options._target_state_count
        if options._visitor is not None:
            raise NotImplementedError(
                "per-state visitors replay O(depth²) host paths; use "
                "spawn_simulation for visitor-driven runs"
            )
        self._seed = seed

        self._state_count = 0
        self._max_depth = 0
        # Trace-buffer truncation honesty: a lane hitting the buffer
        # limit D BELOW the user's depth cap (or with no cap at all) was
        # silently aborted — counted per step call and warned about at
        # run end, so truncation is never mistaken for absence.
        self._trace_overflows = 0
        self._buffer_truncates = (
            self._depth_cap is None or self._D < self._depth_cap
        )
        self._discoveries_fps: Dict[str, List[int]] = {}
        self._empty_discoveries: set = set()
        self._done_event = threading.Event()
        self._error: Optional[BaseException] = None

        self._fp_fn = model.packed_fingerprint
        self._jit_steps = jax.jit(self._run_steps)
        self._jit_fp_single = jax.jit(self._fp_fn)

        self._handles = [
            threading.Thread(target=self._run, name="tpu-sim", daemon=True)
        ]
        self._handles[0].start()

    # -- device kernel -----------------------------------------------------

    def _lane_step(self, inits, n_init, state, depth, ebits, done, thi, tlo, key):
        """One host-loop iteration for a single lane (vmapped); the body
        is the ``walk_lane_step`` core shared with the swarm kernel. The
        cap is pinned to the buffer depth D — the host-side
        ``_buffer_truncates`` flag decides whether hitting it was a
        semantic bound or a truncation."""
        return walk_lane_step(
            self, inits, n_init, state, depth, ebits, done, thi, tlo,
            key, jnp.int32(self._D),
        )

    def _run_steps(self, carry):
        inits = self._model.packed_init_states()
        n_init = jax.tree_util.tree_leaves(inits)[0].shape[0]
        P = len(self._properties)

        def body(c, _):
            lanes, stats, disc = c
            out = jax.vmap(
                lambda s, d, e, dn, th, tl, k: self._lane_step(
                    inits, n_init, s, d, e, dn, th, tl, k
                )
            )(
                lanes["state"],
                lanes["depth"],
                lanes["ebits"],
                lanes["done"],
                lanes["thi"],
                lanes["tlo"],
                lanes["key"],
            )
            lanes = {
                k: out[k]
                for k in ("state", "depth", "ebits", "done", "thi", "tlo", "key")
            }
            stats = {
                "count": stats["count"] + out["counted"].sum(dtype=jnp.int32),
                "max_depth": jnp.maximum(
                    stats["max_depth"], out["path_len"].max()
                ),
                "overflow": stats["overflow"]
                + out["capped"].sum(dtype=jnp.int32),
            }
            if P:
                disc = capture_discoveries(disc, out, P)
            return (lanes, stats, disc), None

        carry, _ = jax.lax.scan(body, carry, None, length=self._K)
        return carry

    # -- host loop ---------------------------------------------------------

    def _run(self):
        try:
            self._explore()
        except BaseException as e:  # noqa: BLE001 - surfaced via worker_error
            self._error = e
        finally:
            self._done_event.set()

    def _fresh_carry(self):
        L, D, P = self._L, self._D, len(self._properties)
        inits = self._model.packed_init_states()
        lanes = {
            "state": jax.tree_util.tree_map(
                lambda x: jnp.zeros((L,) + x.shape[1:], x.dtype), inits
            ),
            "depth": jnp.zeros((L,), jnp.int32),
            "ebits": jnp.zeros((L,), jnp.uint32),
            "done": jnp.ones((L,), bool),  # all lanes restart on step one
            "thi": jnp.zeros((L, D), jnp.uint32),
            "tlo": jnp.zeros((L, D), jnp.uint32),
            "key": jax.vmap(
                lambda i: jax.random.fold_in(jax.random.PRNGKey(self._seed), i)
            )(jnp.arange(L)),
        }
        stats = {
            "count": jnp.int32(0),
            "max_depth": jnp.int32(0),
            "overflow": jnp.int32(0),
        }
        disc = {
            "found": jnp.zeros((P,), bool),
            "hi": jnp.zeros((P, D), jnp.uint32),
            "lo": jnp.zeros((P, D), jnp.uint32),
            "len": jnp.zeros((P,), jnp.int32),
        }
        return (lanes, stats, disc)

    def _explore(self):
        props = self._properties
        if not props:
            return
        carry = self._fresh_carry()
        tracer = get_tracer()
        reg = metrics_registry()
        m_calls = reg.counter("tpu_sim.step_calls")
        m_states = reg.counter("tpu_sim.states_visited")
        # Shared family with checker/swarm.py — the truncation signal
        # reads the same whichever walker produced it.
        m_overflow = reg.counter("swarm.trace_overflow")
        # The device counter is int32 (jnp.int64 needs x64 mode) and would
        # wrap after ~2.15B counted lane-steps if carried across calls, so
        # each _jit_steps call counts from zero and the host accumulates.
        count = 0
        calls = 0
        while True:
            calls += 1
            with tracer.span(
                "tpu_sim.steps", call=calls, lanes=self._L,
                steps_per_call=self._K,
            ) as sp:
                carry = self._jit_steps(carry)
                lanes, stats, disc = carry
                step_count = int(stats["count"])
                sp.set(states=step_count)
            m_calls.inc()
            m_states.inc(step_count)
            count += step_count
            self._state_count = count
            self._max_depth = max(self._max_depth, int(stats["max_depth"]))
            if self._buffer_truncates:
                overflow = int(stats["overflow"])
                if overflow:
                    m_overflow.inc(overflow)
                    self._trace_overflows += overflow
            carry = (
                lanes,
                {
                    "count": jnp.int32(0),
                    "max_depth": stats["max_depth"],
                    "overflow": jnp.int32(0),
                },
                disc,
            )
            found = np.asarray(disc["found"])
            if found.any():
                hi = np.asarray(disc["hi"]).astype(np.uint64)
                lo = np.asarray(disc["lo"]).astype(np.uint64)
                lens = np.asarray(disc["len"])
                for i, p in enumerate(props):
                    if found[i] and p.name not in self._discoveries_fps:
                        n = int(lens[i])
                        if n == 0:
                            # A lane whose trace ended before visiting any
                            # state (out-of-boundary init) has no path to
                            # report; count it as settled so the run can end,
                            # but surface no (empty) Path.
                            self._empty_discoveries.add(p.name)
                            continue
                        self._empty_discoveries.discard(p.name)
                        fps = ((hi[i, :n] << np.uint64(32)) | lo[i, :n]).tolist()
                        self._discoveries_fps[p.name] = fps
            settled = set(self._discoveries_fps) | self._empty_discoveries
            if len(settled) == len(props):
                return
            if (
                self._target_state_count is not None
                and self._target_state_count <= count
            ):
                return
            # Like the host checker, keep sampling until discoveries or the
            # target are reached — no other exit (reference-parity).

    # -- path reconstruction ----------------------------------------------

    def _host_fp(self, host_state) -> int:
        hi, lo = self._jit_fp_single(self._model.pack_state(host_state))
        return fp_to_int(hi, lo)

    # -- Checker surface ---------------------------------------------------

    def model(self):
        return self._model

    def state_count(self) -> int:
        return self._state_count

    def unique_state_count(self) -> int:
        # Like the reference, approximated by the total count.
        return self._state_count

    def max_depth(self) -> int:
        return self._max_depth

    def discoveries(self) -> Dict[str, Path]:
        return {
            name: Path.from_fingerprints(self._model, fps, fp_of=self._host_fp)
            for name, fps in list(self._discoveries_fps.items())
        }

    def handles(self) -> List[threading.Thread]:
        handles, self._handles = self._handles, []
        return handles

    def is_done(self) -> bool:
        return self._done_event.is_set()

    def worker_error(self) -> Optional[BaseException]:
        return self._error
