"""Micro-bench: XLA scatter-claim insert vs the Pallas tile-sweep kernel
on the current default device. Usage::

    python -m stateright_tpu.ops.bench_hashset [log2_capacity] [batch] [--json]

Feeds both paths identical sorted batches at the checkers' target load
factor and prints keys/sec for each. Decides whether runs should pass
``hashset_impl="pallas"`` to the TPU checkers (``checker/tpu.py`` — the
default stays "xla" until the Pallas path measures faster on hardware).
``--json`` prints ONE machine-readable line instead.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp


def main():
    args = [a for a in sys.argv[1:] if a != "--json"]
    as_json = "--json" in sys.argv
    log2_cap = int(args[0]) if len(args) > 0 else 20
    batch = int(args[1]) if len(args) > 1 else 1 << 15
    cap = 1 << log2_cap
    rounds = max(1, int(cap * 0.5) // batch)  # fill to ~50% load

    from .hashset import hashset_insert, hashset_new
    from .pallas_hashset import pallas_hashset_insert

    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"
    print(f"device={dev.platform} cap=2^{log2_cap} batch={batch} "
          f"rounds={rounds} interpret={interpret}", file=sys.stderr)

    rng = np.random.default_rng(0)

    def batches():
        for _ in range(rounds):
            hi = rng.integers(0, 1 << 32, batch, np.uint64).astype(np.uint32)
            lo = rng.integers(1, 1 << 32, batch, np.uint64).astype(np.uint32)
            order = np.lexsort((lo, hi))
            yield jnp.asarray(hi[order]), jnp.asarray(lo[order])

    ones = jnp.ones((batch,), bool)
    results = {}

    for name, fn in (
        ("xla", lambda t, h, l: hashset_insert(t, h, l, ones)),
        (
            "pallas",
            lambda t, h, l: pallas_hashset_insert(
                t, h, l, ones, interpret=interpret
            ),
        ),
    ):
        data = list(batches())
        table = hashset_new(cap)
        # Warm up compile on the first batch shape.
        out = fn(table, *data[0])
        jax.block_until_ready(out[0])
        table = hashset_new(cap)
        t0 = time.perf_counter()
        lanes = 0
        fresh_total = jnp.zeros((), jnp.int32)
        pend_total = jnp.zeros((), jnp.int32)
        for h, l in data:
            table, fresh, _found, pend = fn(table, h, l)
            lanes += batch
            fresh_total = fresh_total + fresh.sum(dtype=jnp.int32)
            pend_total = pend_total + pend.sum(dtype=jnp.int32)
        jax.block_until_ready(table)
        dt = time.perf_counter() - t0
        fresh_n = int(fresh_total)
        results[name] = {
            "lanes_per_s": round(lanes / dt, 1),
            "inserts_per_s": round(fresh_n / dt, 1),
            "fresh": fresh_n,
            "pending": int(pend_total),
        }
        out_line = (
            f"{name}: {lanes} lanes in {dt:.3f}s = {lanes/dt:,.0f} lanes/s, "
            f"{fresh_n/dt:,.0f} effective inserts/s "
            f"(fresh={fresh_n} pending={int(pend_total)})"
        )
        print(out_line, file=sys.stderr if as_json else sys.stdout)

    if as_json:
        print(
            json.dumps(
                {
                    "device": dev.platform,
                    "compiled": not interpret,
                    "cap_log2": log2_cap,
                    "batch": batch,
                    **results,
                    "winner": max(
                        results, key=lambda k: results[k]["lanes_per_s"]
                    ),
                }
            )
        )


if __name__ == "__main__":
    main()
