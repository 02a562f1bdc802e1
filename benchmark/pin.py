"""Recompute a configuration's pinned answers.

    python3 benchmark/pin.py benchmark/configs/paxos3.json [--program]

For every depth cap the configuration pins, prints the plain reference's
answer (``reference/bfs.py``) beside the pinned one, and with
``--program`` the program's own host checker (``spawn_bfs``) too, as a
second witness. Exits non-zero on any difference. Runs on the CPU.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def program_answer(config, cap):
    from harness import build_model

    builder = build_model(config).checker()
    if cap is not None:
        builder = builder.target_max_depth(cap)
    c = builder.spawn_bfs().join()
    return {
        "unique": c.unique_state_count(),
        "states": c.state_count(),
        "max_depth": c.max_depth(),
        "discoveries": {k: len(p) for k, p in c.discoveries().items()},
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("config")
    p.add_argument("--program", action="store_true",
                   help="also run the program's host BfsChecker")
    args = p.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.dirname(HERE))
    import harness
    from reference.bfs import bfs

    with open(args.config) as f:
        config = json.load(f)
    model = harness.load_reference(config)
    ok = True
    for key, pin in config["pins"].items():
        cap = None if key == "none" else int(key)
        t0 = time.perf_counter()
        ref = bfs(model, depth_cap=cap)
        line = {"cap": key, "reference": ref,
                "reference_s": round(time.perf_counter() - t0, 3)}
        ok &= ref == pin
        if args.program:
            t0 = time.perf_counter()
            line["program"] = program_answer(config, cap)
            line["program_s"] = round(time.perf_counter() - t0, 3)
            ok &= line["program"] == pin
        print(json.dumps(line), flush=True)
    print("pins hold" if ok else "PINS DIFFER")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
