#!/usr/bin/env python
"""Expansion probe: does the default device expand a model's states as
the CPU does?

    python scripts/expand_probe.py --leg paxos3 --sample 16
    python scripts/expand_probe.py --leg paxos3 --sample 16 --ref .scratch/p3.npz

A BFS on the CPU backend walks the bench leg's whole state space with
the wave's own expansion (``packed_expand``, ``packed_within_boundary``,
``packed_fingerprint``) and keeps every state whose fingerprint's low
word is 0 mod ``--sample``, with each candidate's validity and
fingerprint as the reference. The default device then expands that
sample through both of the checker's waves: the materializing
``packed_expand`` and, where the model has it, the fingerprint-only
``packed_expand_fps``. Every candidate's validity and fingerprint is
compared with the reference.

Prints one ``probe {...}`` line per wave and exits non-zero on any
mismatch. ``--ref PATH`` loads the reference from PATH if it exists and
otherwise writes it there, so the CPU BFS can run once off the chip.

This is how a wrong state count on the chip was traced to single
traced-index ``.at[...]`` updates (``models/paxos.py`` ``dec_bcast``,
the fps working set's ``eused`` in ``actor/packed.py``; PR 21): a
miscounting run names no state, this probe names the action.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH = 2048


def _batches(states, n):
    """``states`` in ``BATCH``-row slices, the last padded with row 0."""
    import numpy as np

    for s in range(0, n, BATCH):
        b = {k: v[s:s + BATCH] for k, v in states.items()}
        m = len(next(iter(b.values())))
        if m < BATCH:
            b = {
                k: np.concatenate([v, np.repeat(v[:1], BATCH - m, 0)])
                for k, v in b.items()
            }
        yield s, m, b


def _wave_fns(model):
    import jax

    def expand(states):
        cand, cv = jax.vmap(model.packed_expand)(states)
        cv = cv & jax.vmap(jax.vmap(model.packed_within_boundary))(cand)
        hi, lo = jax.vmap(jax.vmap(model.packed_fingerprint))(cand)
        return cand, cv, hi, lo

    def expand_fps(states):
        hi, lo, cv = jax.vmap(model.packed_expand_fps)(states)
        return cv, hi, lo

    return jax.jit(expand), jax.jit(expand_fps)


def reference(model, sample):
    """CPU BFS of the whole space; returns the sampled states and their
    candidates' validity, fingerprints and the states' depths."""
    import jax
    import numpy as np

    cpu = jax.devices("cpu")[0]
    expand, _ = _wave_fns(model)
    fp1 = jax.jit(jax.vmap(model.packed_fingerprint))

    def key(hi, lo):
        return (hi.astype(np.uint64) << 32) | lo.astype(np.uint64)

    init = {k: np.asarray(v) for k, v in model.packed_init_states().items()}
    ih, il = jax.device_get(fp1(jax.device_put(init, cpu)))
    ik = key(ih, il)
    _, first = np.unique(ik, return_index=True)
    frontier = {k: v[first] for k, v in init.items()}
    seen = set(ik[first].tolist())
    picked = {k: [] for k in frontier}
    ref = {"hi": [], "lo": [], "cv": [], "depth": []}
    depth = 0
    while len(next(iter(frontier.values()))):
        nxt = {k: [] for k in frontier}
        for _, m, b in _batches(frontier, len(next(iter(frontier.values())))):
            on_cpu = jax.device_put(b, cpu)
            cand, cv, hi, lo = jax.device_get(expand(on_cpu))
            _, plo = jax.device_get(fp1(on_cpu))
            cand = {k: v[:m] for k, v in cand.items()}
            cv, hi, lo, plo = cv[:m], hi[:m], lo[:m], plo[:m]
            pick = plo % sample == 0
            for k in picked:
                picked[k].append(b[k][:m][pick])
            for name, v in (("hi", hi), ("lo", lo), ("cv", cv)):
                ref[name].append(v[pick])
            ref["depth"].append(np.full(int(pick.sum()), depth))
            u, idx = np.unique(key(hi, lo)[cv], return_index=True)
            new = np.fromiter((x not in seen for x in u.tolist()), bool, len(u))
            seen.update(u[new].tolist())
            for k in nxt:
                nxt[k].append(cand[k][cv][idx[new]])
        frontier = {k: np.concatenate(v) for k, v in nxt.items()}
        depth += 1
    out = {"s_" + k: np.concatenate(v) for k, v in picked.items()}
    out.update({"r_" + k: np.concatenate(v) for k, v in ref.items()})
    out["unique"] = np.asarray(len(seen))
    return out


def compare(model, ref):
    """Expand the sampled states on the default device through each wave;
    one result dict per wave."""
    import jax
    import numpy as np

    from stateright_tpu.checker.tpu import supports_expand_fps

    dev = jax.devices()[0]
    states = {k[2:]: v for k, v in ref.items() if k.startswith("s_")}
    rcv, rhi, rlo = ref["r_cv"], ref["r_hi"], ref["r_lo"]
    n = len(rcv)
    expand, expand_fps = _wave_fns(model)
    waves = {"packed_expand": lambda b: expand(b)[1:]}
    if supports_expand_fps(model):
        waves["packed_expand_fps"] = expand_fps
    results = []
    for wave, fn in waves.items():
        t0 = time.perf_counter()
        parts = [
            [x[:m] for x in jax.device_get(fn(jax.device_put(b, dev)))]
            for _, m, b in _batches(states, n)
        ]
        cv, hi, lo = (np.concatenate(x) for x in zip(*parts))
        valid_bad = cv != rcv
        fp_bad = cv & rcv & ((hi != rhi) | (lo != rlo))
        bad = valid_bad | fp_bad
        results.append(
            {
                "wave": wave,
                "device": dev.device_kind,
                "states": int(n),
                "candidates": int(rcv.sum()),
                "valid_mismatch": int(valid_bad.sum()),
                "fp_mismatch": int(fp_bad.sum()),
                "per_action": bad.sum(axis=0).tolist(),
                "first_bad": [
                    [int(i), int(a)] for i, a in np.argwhere(bad)[:8]
                ],
                "seconds": time.perf_counter() - t0,
            }
        )
    return results


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--leg", default="paxos3", help="a bench._leg_specs leg")
    parser.add_argument(
        "--sample", type=int, default=16,
        help="keep states whose fingerprint low word is 0 mod this",
    )
    parser.add_argument("--ref", help="reference .npz: loaded if present, else written")
    args = parser.parse_args(argv)

    import numpy as np

    from bench import _leg_specs

    spec = _leg_specs()[args.leg]
    model = spec["model"]()
    if args.ref and os.path.exists(args.ref):
        ref = dict(np.load(args.ref))
    else:
        t0 = time.perf_counter()
        ref = reference(model, args.sample)
        print(
            "reference "
            + json.dumps(
                {
                    "leg": args.leg,
                    "unique": int(ref["unique"]),
                    "sampled": int(len(ref["r_cv"])),
                    "seconds": time.perf_counter() - t0,
                }
            ),
            flush=True,
        )
        if args.ref:
            np.savez_compressed(args.ref, **ref)
    pinned = spec.get("expected")
    if pinned is not None and int(ref["unique"]) != pinned:
        print(
            f"reference BFS found {int(ref['unique'])} states, the leg pins "
            f"{pinned}",
            file=sys.stderr,
        )
        return 1
    results = compare(model, ref)
    for r in results:
        print("probe " + json.dumps(r), flush=True)
    return int(any(r["valid_mismatch"] or r["fp_mismatch"] for r in results))


if __name__ == "__main__":
    sys.exit(main())
