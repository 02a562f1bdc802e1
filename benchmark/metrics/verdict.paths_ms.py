"""Discovery paths rebuilt on the host: the self time of the
``tpu_bfs.paths`` spans in the window (``discoveries()``, less the
``tpu_bfs.exe`` spans nested in it) over the window's checks, in ms per
check."""


def exe_inside(span, exes):
    """Microseconds of ``exes`` on the span's thread within its interval."""
    lo, hi = span["ts"], span["ts"] + span["dur"]
    return sum(
        e["dur"] for e in exes
        if e["tid"] == span["tid"]
        and lo <= e["ts"] and e["ts"] + e["dur"] <= hi
    )


def read(run):
    paths = run.window_spans("tpu_bfs.paths")
    if not paths or not run.checks:
        return None
    exes = run.window_spans("tpu_bfs.exe")
    self_us = sum(e["dur"] - exe_inside(e, exes) for e in paths)
    return self_us / 1e3 / len(run.checks)
