"""Executables each check obtains: the summed duration of the
``tpu_bfs.exe`` spans in the window (a per-instance jit's first call per
shape, an AOT-cache or disk lookup, a compile) over the window's checks,
in ms per check. None where the program records no ``tpu_bfs.check``
span (it predates per-check tracing)."""


def read(run):
    if not run.window_spans("tpu_bfs.check") or not run.checks:
        return None
    total_us = sum(e["dur"] for e in run.window_spans("tpu_bfs.exe"))
    return total_us / 1e3 / len(run.checks)
