"""Warm-start persistence: the disk AOT executable store and the
finished-run seed store (ISSUE 19, ROADMAP item 3).

Two independent planes share this module because they share the same
discipline — **refuse, never mis-execute**:

- **``AotDiskStore``** — a content-addressed store of serialized XLA
  executables under ``CheckService(service_dir=)/aot/``. Entries are
  keyed by the checker's full AOT trace signature (the exact tuple that
  keys the in-memory ``shared_aot_cache``) plus the per-dispatch shape
  key, and every artifact carries a *fence* — jax version, backend,
  device kind/count, and the ids of the devices it was compiled for,
  which it is loaded onto — verified on load. A fence mismatch or a torn/
  corrupt pickle is counted (``aot_cache.refused_stale`` /
  ``aot_cache.refused_corrupt``) and treated as a miss: the caller
  recompiles, it never runs a stale binary. Unlike jax's own persistent
  compilation cache (``utils/compile_cache.py``, keyed on HLO inside
  ``jit.__call__``), this store round-trips *AOT* ``Compiled`` objects
  (``jax.experimental.serialize_executable``), so the checkers' explicit
  ``lower().compile()`` sites — the attribution engine's compile
  detectors — can skip the compile entirely: a disk hit records **zero**
  compile phase, which is the whole point.

- **``SeedStore``** — finished-run seeds under ``service_dir/seeds/``:
  the run's completion checkpoint with its visited keys rewritten as
  sorted ``FingerprintRun``s (+ per-run Bloom filters — the PR 5/PR 17
  codec), keyed by a *model-structure signature* derived from the packed
  model (per-action jaxpr digests + property/boundary/fingerprint
  digests + the init/params digest). On resubmission of a compatible
  model the service attaches the seed as ``resume_from=``: the tiered
  store's L1 is loaded from the runs (CRC-validated per run — the
  O(verify) cost), the L0 insert set is empty, the frontier queue is
  empty, and the run completes without exploring. The structural diff
  admits exactly one edit class beyond bit-identity — removal of
  provably dead actions (coverage ``fired == 0`` in the seeding run,
  every surviving digest unchanged) — and anything else falls back to a
  full recheck, so soundness never depends on the diff.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
from contextlib import contextmanager
from typing import List, Optional, Tuple

import numpy as np

from ..telemetry.metrics import metrics_registry
from ..utils.faults import fault_point
from .runs import FingerprintRun

__all__ = [
    "AotDiskStore",
    "AotDiskBinding",
    "aot_fence",
    "SeedStore",
    "model_structure_signature",
    "build_seed_artifact",
    "seed_compatibility",
    "adapt_seed_checkpoint",
]

AOT_FORMAT = "stateright-aotx-v1"
SEED_FORMAT = "stateright-warmstart-seed-v1"


def _digest(*parts) -> str:
    h = hashlib.blake2b(digest_size=16)
    for p in parts:
        h.update(repr(p).encode())
        h.update(b"\x1f")
    return h.hexdigest()


def _atomic_write_bytes(path: str, blob: bytes) -> None:
    """tmp + rename in the artifact's directory — a torn write leaves a
    stray tmp file, never a half-length artifact under the final name."""
    d = os.path.dirname(path)
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


# ---------------------------------------------------------------------------
# Disk AOT executable store
# ---------------------------------------------------------------------------


def aot_fence() -> dict:
    """The environment key an AOT artifact is only valid under. XLA
    serialized executables are not portable across jax versions,
    backends, or device topologies — a mismatch must refuse the entry,
    never deserialize-and-hope."""
    import jax

    try:
        devs = jax.devices()
        kind = devs[0].device_kind if devs else "none"
        count = len(devs)
    except Exception:
        kind, count = "unknown", 0
    return {
        "format": AOT_FORMAT,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": kind,
        "device_count": count,
    }


class AotDiskStore:
    """Content-addressed serialized-executable files under ``root``.

    The store itself is namespace/signature-agnostic; checkers attach
    through :meth:`binding`, which closes over their cache namespace +
    full trace signature and counts hits/misses/refusals into the run's
    metrics registry (``aot_cache.*`` family)."""

    def __init__(self, root: str):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)
        self._fence = None

    def fence(self) -> dict:
        if self._fence is None:
            self._fence = aot_fence()
        return self._fence

    def entry_path(self, namespace, signature, kind: str, key) -> str:
        return os.path.join(
            self.root, f"{_digest(namespace, signature, kind, key)}.aotx"
        )

    def load_entry(
        self, namespace, signature, kind: str, key
    ) -> Tuple[Optional[object], str]:
        """``(executable, outcome)`` — outcome in ``{"hit", "miss",
        "stale", "corrupt"}``. Anything but a verified fence match is a
        miss variant; the artifact is never executed on doubt."""
        path = self.entry_path(namespace, signature, kind, key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None, "miss"
        try:
            entry = pickle.loads(blob)
            if not isinstance(entry, dict) or entry.get("format") != AOT_FORMAT:
                return None, "corrupt"
        except Exception:
            return None, "corrupt"
        fence = dict(entry.get("fence") or {})
        device_ids = fence.pop("execution_devices", None)
        if fence != self.fence() or not device_ids:
            return None, "stale"
        try:
            import jax
            from jax.experimental import serialize_executable

            by_id = {d.id: d for d in jax.devices()}
            exe = serialize_executable.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                execution_devices=[by_id[i] for i in device_ids],
            )
        except Exception:
            # Same-fence deserialization failure: an XLA build drift the
            # fence could not see. Refuse as stale, recompile.
            return None, "stale"
        return exe, "hit"

    def save_entry(self, namespace, signature, kind: str, key, exe) -> bool:
        """Best-effort persist (a full disk must never fail a run).

        The blob is round-trip verified before it is written: XLA
        executables that were themselves loaded from jax's persistent
        compilation cache serialize without their symbol payloads
        (deserialize fails with "Symbols not found" on this jax line),
        and persisting one would poison every later cold-process load
        into a stale-refusal + recompile. Refusing the save keeps the
        store all-loadable; the cold process just sees a plain miss."""
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(exe)
            # The devices the executable was compiled for: loaded onto any
            # other set (by default, every local device), it refuses to run.
            devices = exe.runtime_executable().local_devices()
            serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=devices
            )
            blob = pickle.dumps(
                {
                    "format": AOT_FORMAT,
                    "fence": {
                        **self.fence(),
                        "execution_devices": [d.id for d in devices],
                    },
                    "namespace": namespace,
                    "kind": kind,
                    "payload": payload,
                    "in_tree": in_tree,
                    "out_tree": out_tree,
                },
                protocol=pickle.HIGHEST_PROTOCOL,
            )
            _atomic_write_bytes(
                self.entry_path(namespace, signature, kind, key), blob
            )
            return True
        except Exception:
            return False

    def binding(self, namespace, signature, registry=None) -> "AotDiskBinding":
        return AotDiskBinding(self, namespace, signature, registry=registry)


class AotDiskBinding:
    """One checker's view of the store: namespace + trace signature
    fixed, per-outcome counters in the run's registry (the ``aot_cache``
    metric family ``bench.py --service`` reads back per job)."""

    def __init__(self, store: AotDiskStore, namespace, signature,
                 registry=None):
        reg = registry if registry is not None else metrics_registry()
        self._store = store
        self._namespace = namespace
        self._signature = signature
        self.disk_hit = reg.counter("aot_cache.disk_hit")
        self.disk_miss = reg.counter("aot_cache.disk_miss")
        self.refused_stale = reg.counter("aot_cache.refused_stale")
        self.refused_corrupt = reg.counter("aot_cache.refused_corrupt")
        self.saved = reg.counter("aot_cache.saved")
        self.save_refused = reg.counter("aot_cache.save_refused")
        self._known = set()  # keys confirmed present on disk

    def ensure(self, kind: str, key, exe) -> None:
        """Backfills the disk tier for an executable served from the
        in-memory shared cache: a warm process must still leave
        artifacts a cold process can reuse. At most one existence probe
        per key per binding, and no counter churn on the common
        already-persisted path."""
        k = (kind, key)
        if k in self._known:
            return
        self._known.add(k)
        if os.path.exists(
            self._store.entry_path(self._namespace, self._signature,
                                   kind, key)
        ):
            return
        self.save(kind, key, exe)

    def load(self, kind: str, key):
        exe, outcome = self._store.load_entry(
            self._namespace, self._signature, kind, key
        )
        if outcome == "hit":
            self.disk_hit.inc()
            self._known.add((kind, key))
        elif outcome == "stale":
            self.refused_stale.inc()
        elif outcome == "corrupt":
            self.refused_corrupt.inc()
        else:
            self.disk_miss.inc()
        return exe

    def save(self, kind: str, key, exe) -> bool:
        ok = self._store.save_entry(
            self._namespace, self._signature, kind, key, exe
        )
        if ok:
            self.saved.inc()
            self._known.add((kind, key))
        else:
            # Unserializable executable or IO failure — either way the
            # artifact is not on disk; count it so bench/report readers
            # can tell "nothing saved" from "nothing to save".
            self.save_refused.inc()
        return ok


# ---------------------------------------------------------------------------
# Model-structure signature (the seed key + the diff's evidence)
# ---------------------------------------------------------------------------


def _jaxpr_digest(fn, *protos) -> str:
    """Digest of a traced function's jaxpr *and* its closed-over
    constants — the jaxpr printer elides large literals (shape/dtype
    only), so two models differing only in a constant table would
    otherwise collide."""
    import jax

    closed = jax.make_jaxpr(fn)(*protos)
    h = hashlib.blake2b(digest_size=16)
    h.update(str(closed.jaxpr).encode())
    for c in closed.consts:
        arr = np.asarray(c)
        h.update(repr((arr.shape, str(arr.dtype))).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def _state_prototype(model):
    """One unbatched abstract state (``ShapeDtypeStruct`` pytree) from
    the stacked init batch, plus a digest of the init *values* (the
    params half of the signature: two configurations with identical
    transition structure but different initial contents must not share
    seeds)."""
    import jax

    init = model.packed_init_states()
    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(init)]
    h = hashlib.blake2b(digest_size=16)
    for arr in leaves:
        h.update(repr((arr.shape, str(arr.dtype))).encode())
        h.update(arr.tobytes())
    proto = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.asarray(x).shape[1:],
                                       np.asarray(x).dtype),
        init,
    )
    return proto, h.hexdigest()


@contextmanager
def _concrete_switch():
    """Routes ``lax.switch`` calls whose index is still a *concrete*
    array straight to the selected branch for the duration of the
    signature trace. jax's own short-circuit cannot fire inside
    ``make_jaxpr`` — its dtype-convert/clamp prelude stages the index
    into a tracer before the concreteness check — so without this, every
    per-action trace would embed ALL branches and any single-branch edit
    would perturb every digest. Models that pass the raw action id to
    ``lax.switch`` get genuinely per-branch digests; models that
    arithmetic on the id first (staging it) fall through to the original
    switch and land on the conservative full-recheck path, which is the
    sound direction to degrade."""
    import jax

    orig = jax.lax.switch

    def switch(index, branches, *operands, **kwargs):
        try:
            from jax._src.core import is_concrete

            concrete = not kwargs and is_concrete(index)
        except Exception:  # noqa: BLE001 - shortcut is best-effort
            concrete = False
        if concrete:
            i = max(0, min(len(branches) - 1, int(index)))
            return branches[i](*operands)
        return orig(index, branches, *operands, **kwargs)

    jax.lax.switch = switch
    try:
        yield
    finally:
        jax.lax.switch = orig


def model_structure_signature(model) -> dict:
    """The ``(model-structure, params)`` signature incremental
    re-checking keys on. Per-action digests trace ``packed_step`` with a
    *concrete* action id — models that dispatch through ``lax.switch``
    on the raw id trace only that action's branch (see
    ``_concrete_switch``), so an edit to one action perturbs one digest;
    models that blend the id arithmetically perturb every digest and
    land on the conservative full-recheck path, which is the sound
    direction to fail in."""
    from ..ops.fingerprint import FP_SCHEME
    from ..telemetry.coverage import coverage_action_labels

    import jax.numpy as jnp

    proto, init_digest = _state_prototype(model)
    A = int(model.packed_action_count())
    with _concrete_switch():
        actions = [
            _jaxpr_digest(
                lambda s, _a=jnp.asarray(a, jnp.int32): (
                    model.packed_step(s, _a)
                ),
                proto,
            )
            for a in range(A)
        ]
    conditions = model.packed_conditions()
    properties = [
        [p.name, str(p.expectation), _jaxpr_digest(cond, proto)]
        for p, cond in zip(model.properties(), conditions)
    ]
    boundary = _jaxpr_digest(model.packed_within_boundary, proto)
    fingerprint = _jaxpr_digest(model.packed_fingerprint, proto)
    sig = {
        "format": 1,
        "fp_scheme": FP_SCHEME,
        "model": type(model).__name__,
        "action_count": A,
        "labels": coverage_action_labels(model, A),
        "init": init_digest,
        "actions": actions,
        "properties": properties,
        "boundary": boundary,
        "fingerprint": fingerprint,
    }
    sig["digest"] = _digest(
        sig["fp_scheme"], sig["model"], sig["init"], sig["actions"],
        sig["properties"], sig["boundary"], sig["fingerprint"],
    )
    # The lookup key must survive the edits the diff can judge, so it
    # excludes actions/properties: same class + same init + same
    # fingerprint scheme → same seed file.
    sig["family"] = _digest(sig["fp_scheme"], sig["model"], sig["init"])
    return sig


# ---------------------------------------------------------------------------
# Seed build / compatibility / adaptation
# ---------------------------------------------------------------------------


def build_seed_artifact(structure: dict, payload: dict,
                        coverage: Optional[dict] = None,
                        spawn_sig: Optional[str] = None) -> dict:
    """A finished run's checkpoint payload rewritten for reseeding: all
    visited keys (device L0 + any evicted tiers) merged into ONE sorted
    ``FingerprintRun`` riding the payload's ``storage`` slot, so a
    restore probes the run (Bloom + CRC — O(verify)) and inserts
    nothing, and the empty ``chunks`` queue completes immediately."""
    if payload.get("chunks"):
        raise ValueError(
            "seed artifacts require a completed run (non-empty frontier "
            "queue means the verdict is not final)"
        )
    keys = (
        payload["keys"] if payload.get("symmetry") else payload["children"]
    )
    parts = [np.asarray(keys, np.uint64).ravel()]
    storage = payload.get("storage") or {}
    for st in list(storage.get("l1", ())) + list(storage.get("l2", ())):
        parts.append(FingerprintRun.from_state(st).decode_all())
    merged = np.unique(np.concatenate(parts))
    ckpt = dict(payload)
    ckpt["chunks"] = []
    ckpt["storage"] = {
        "seq": 0,
        "l1": [FingerprintRun.build(merged).to_state()] if len(merged) else [],
        "l2": [],
    }
    actions_meta = None
    table = ((coverage or {}).get("actions") or {}).get("table")
    if table is not None:
        actions_meta = [
            {
                "label": label,
                "fired": int((table.get(label) or {}).get("fired", 0)),
                "fresh": int((table.get(label) or {}).get("fresh", 0)),
            }
            for label in structure.get("labels", [])
        ]
    return {
        "format": SEED_FORMAT,
        "structure": structure,
        "spawn_sig": spawn_sig,
        "actions_meta": actions_meta,
        "checkpoint": ckpt,
        "counts": {
            "unique": int(payload.get("unique_count", 0)),
            "states": int(payload.get("state_count", 0)),
            "max_depth": int(payload.get("max_depth", 0)),
            "runs": len(ckpt["storage"]["l1"]),
            "keys": int(len(merged)),
        },
    }


def seed_compatibility(artifact: dict, structure: dict) -> dict:
    """Judges whether ``artifact`` (a stored seed) may seed a run of the
    model described by ``structure``. Returns ``{compatible, mode,
    reason, invalidated_uniques, removed}``.

    Admitted modes:

    - ``exact`` — structure digests identical (the unchanged-model
      resubmit).
    - ``dead_action_removal`` — the new action list is the old one with
      some actions deleted, every survivor's digest unchanged (in
      order), properties/boundary/fingerprint/init unchanged, and every
      deleted action has ``fired == 0`` in the seeding run's coverage
      table: the action never produced a candidate anywhere in the
      reachable space, so deleting it provably leaves the state space,
      the counts, and every verdict untouched. The inverted
      action→uniques attribution (``fresh``) is exactly the count of
      states the edit would invalidate — the proof obligation is that
      it is zero.

    Everything else is ``compatible: False`` with the reason; the caller
    must fall back to a full recheck.
    """
    old = artifact.get("structure") or {}
    if old.get("format") != structure.get("format"):
        return _verdict(False, "format", "signature format mismatch")
    if old.get("digest") == structure.get("digest"):
        return _verdict(True, "exact", None)
    for field in ("fp_scheme", "model", "init", "boundary", "fingerprint"):
        if old.get(field) != structure.get(field):
            return _verdict(
                False, "full",
                f"{field} changed; no sound incremental reuse",
            )
    if old.get("properties") != structure.get("properties"):
        return _verdict(
            False, "full",
            "property set changed; prior verdicts do not transfer",
        )
    meta = artifact.get("actions_meta")
    if not meta:
        return _verdict(
            False, "full",
            "seeding run recorded no coverage; cannot prove removed "
            "actions dead (resubmit with coverage to enable the "
            "action-diff path)",
        )
    old_actions = list(old.get("actions", ()))
    new_actions = list(structure.get("actions", ()))
    if len(new_actions) > len(old_actions):
        return _verdict(
            False, "full", "actions added; new transitions may reach "
            "states the seed never explored",
        )
    # Align: each old action either matches the next new digest or was
    # removed. Any digest drift → not provable → full recheck.
    removed: List[int] = []
    j = 0
    for i, d in enumerate(old_actions):
        if j < len(new_actions) and new_actions[j] == d:
            j += 1
        else:
            removed.append(i)
    if j != len(new_actions):
        return _verdict(
            False, "full",
            "action bodies changed; the edit is not a pure removal of "
            "unchanged actions",
        )
    invalidated = 0
    for i in removed:
        row = meta[i] if i < len(meta) else None
        if row is None:
            return _verdict(
                False, "full",
                f"removed action {i} has no coverage row",
            )
        if row.get("fired", 1) != 0:
            # The inverted attribution: this action's uniques (and
            # their descendants) are the affected states. Non-zero ⇒
            # not provably dead ⇒ full recheck.
            invalidated = int(row.get("fresh", 0))
            return _verdict(
                False, "full",
                f"removed action {i} ({row.get('label')}) fired "
                f"{row.get('fired')} times in the seeding run "
                f"({row.get('fresh')} uniques attributed); removal is "
                "not provably dead",
                invalidated=invalidated, removed=removed,
            )
    return _verdict(
        True, "dead_action_removal", None, invalidated=0, removed=removed
    )


def _verdict(compatible, mode, reason, invalidated=0, removed=()):
    return {
        "compatible": bool(compatible),
        "mode": mode,
        "reason": reason,
        "invalidated_uniques": int(invalidated),
        "removed": list(removed),
    }


def adapt_seed_checkpoint(artifact: dict, model) -> dict:
    """The seed's checkpoint payload re-headered for ``model`` — needed
    on the dead-action-removal path, where the packed-model digest
    (action count) legitimately changed. Only call after
    ``seed_compatibility`` admitted the pair; the rewrite is what the
    compatibility proof licenses."""
    from ..checker.tpu import packed_model_digest

    ckpt = dict(artifact["checkpoint"])
    ckpt["model"] = type(model).__name__
    ckpt["model_digest"] = packed_model_digest(
        model, int(model.packed_action_count())
    )
    return ckpt


# ---------------------------------------------------------------------------
# Seed store
# ---------------------------------------------------------------------------


class SeedStore:
    """Finished-run seeds under ``root`` (``service_dir/seeds/``), one
    file per model family, atomically replaced on each completed run.
    ``load`` validates everything it will hand to a restore — format,
    pickle integrity, every FingerprintRun's CRC — and refuses with a
    reason instead of letting a torn artifact reach the checker."""

    def __init__(self, root: str):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)

    def path_for(self, family: str) -> str:
        return os.path.join(self.root, f"{family}.seed")

    def save(self, artifact: dict) -> Optional[str]:
        family = (artifact.get("structure") or {}).get("family")
        if not family:
            return None
        path = self.path_for(family)
        try:
            _atomic_write_bytes(
                path, pickle.dumps(artifact, protocol=pickle.HIGHEST_PROTOCOL)
            )
        except OSError:
            return None
        return path

    def load(self, family: str) -> Tuple[Optional[dict], Optional[str]]:
        """``(artifact, refusal_reason)`` — exactly one is None."""
        path = self.path_for(family)
        if not os.path.exists(path):
            return None, "no seed for this model family"
        try:
            # Injection seam: a torn read / failing disk surfaces here,
            # before any artifact content is trusted.
            fault_point("warmstart.seed_load")
            with open(path, "rb") as f:
                artifact = pickle.load(f)
            if (
                not isinstance(artifact, dict)
                or artifact.get("format") != SEED_FORMAT
            ):
                return None, "unrecognized seed format"
            storage = (artifact.get("checkpoint") or {}).get("storage") or {}
            for st in list(storage.get("l1", ())) + list(
                storage.get("l2", ())
            ):
                # The O(verify) half: every run's structure + CRC checks
                # here; corruption refuses the seed, never a wrong
                # visited set.
                FingerprintRun.from_state(st)
            return artifact, None
        except Exception as e:
            return None, f"seed artifact refused: {type(e).__name__}: {e}"
