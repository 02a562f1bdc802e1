"""The control: the plain reference put in the program's place with one
guarantee of the configuration broken, run through the benchmark's own
window and comparison, which has to find it not correct.

    python3 benchmark/control.py --workload paxos3.d15 --seed 5 --seconds 30 --break fifo

``--break fifo`` pops the newest state first instead of the oldest, so
depths are no longer exact BFS depths (the guarantee the device ring's
FIFO order keeps); under a depth cap that changes which states are
reached. ``--break key32`` keys the visited set by a 32-bit hash of the
state instead of the state, so colliding states are lost (the step a
smaller fingerprint would take). Runs on the host's CPU; the benchmark's
own runs never run it.
"""

import argparse
import hashlib
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def key32(state):
    return hashlib.blake2b(repr(state).encode(), digest_size=4).digest()


class ReferenceSystem:
    """Answers a check with the reference under a broken guarantee."""

    def __init__(self, config, broken):
        import harness

        self.model = harness.load_reference(config)
        self.broken = broken

    def check(self, cap):
        from harness import Check
        from reference.bfs import bfs

        t0 = time.perf_counter()
        r = bfs(self.model, depth_cap=cap, paths=True,
                lifo=self.broken == "fifo",
                key=key32 if self.broken == "key32" else None)
        t1 = time.perf_counter()
        return Check(cap, t0, t1, r["unique"], r["states"], r["max_depth"],
                     discoveries=r["discovery_paths"])


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--break", dest="broken", choices=("fifo", "key32"),
                   required=True)
    args = p.parse_args(argv)
    args.trace = 0
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.dirname(HERE))
    import harness
    import run

    cell = harness.load_cell(args.workload)
    info = {"platform": "cpu", "kind": "cpu", "count": 1}
    return run.measure(args, cell, info, {},
                       system=ReferenceSystem(cell.config, args.broken))


if __name__ == "__main__":
    sys.exit(main())
