"""The plain reference checker: a breadth-first search over a reference model.

It follows upstream stateright's ``BfsChecker`` semantics, written out
without anything of the checker under test:

- init states have depth 1; a state at depth ``>= depth_cap`` is counted
  but neither evaluated nor expanded;
- ``states`` counts init states plus every successor generated by an
  expanded state (duplicates included), ``unique`` the distinct states;
- a ``sometimes`` property is discovered by a state where it holds, an
  ``always`` property by a state where it fails; the reference records the
  shallowest depth at which each is discovered;
- ``max_depth`` is the deepest depth taken off the queue.

``key`` maps a state to what the visited set stores (the state itself by
default), and ``lifo`` pops the newest state first instead of the oldest.
Both exist for the control (``benchmark/control.py``), which breaks the
visited set's exactness or the frontier's FIFO order on purpose.
"""

from __future__ import annotations

from collections import deque


def bfs(model, depth_cap=None, key=None, lifo=False, paths=False):
    """The reference answer; with ``paths``, each discovery's path of
    states as well (``discovery_paths``)."""
    key = key or (lambda s: s)
    inits = model.init_states()
    seen = {}
    queue = deque()
    for s in inits:
        k = key(s)
        if k not in seen:
            seen[k] = (None, s) if paths else None
            queue.append((s, k, 1))
    states = len(inits)
    max_depth = 0
    found = {}
    found_at = {}
    props = model.properties()
    while queue:
        s, k, depth = queue.pop() if lifo else queue.popleft()
        max_depth = max(max_depth, depth)
        if depth_cap is not None and depth >= depth_cap:
            continue
        for name, kind, holds in props:
            if name not in found and holds(s) == (kind == "sometimes"):
                found[name] = depth
                found_at[name] = k
        for t in model.successors(s):
            states += 1
            kt = key(t)
            if kt not in seen:
                seen[kt] = (k, t) if paths else None
                queue.append((t, kt, depth + 1))
    out = {
        "unique": len(seen),
        "states": states,
        "max_depth": max_depth,
        "discoveries": found,
    }
    if paths:
        out["discovery_paths"] = {}
        for name, k in found_at.items():
            path = []
            while k is not None:
                k, s = seen[k]
                path.append(s)
            out["discovery_paths"][name] = path[::-1]
    return out


def replay(model, path_states, prop_name):
    """Why a discovery path fails in the reference, or None when it holds.

    ``path_states`` are reference states. The path has to start at an init
    state, take only reference transitions, and end in a state that
    discovers ``prop_name``."""
    if not path_states:
        return "empty path"
    if path_states[0] not in model.init_states():
        return "first state is not an init state"
    for i in range(1, len(path_states)):
        if path_states[i] not in model.successors(path_states[i - 1]):
            return f"step {i} is not a transition"
    for name, kind, holds in model.properties():
        if name == prop_name:
            if holds(path_states[-1]) != (kind == "sometimes"):
                return "last state does not discover the property"
            return None
    return f"unknown property {prop_name!r}"
