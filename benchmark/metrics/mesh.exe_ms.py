"""Executables each sharded check obtains: the summed duration of the
``sharded_bfs.exe`` spans in the window (a shared AOT-cache hit, a disk
load, a compile) over the window's checks, in ms per check. None where
the program records no ``sharded_bfs.check`` span."""


def read(run):
    if not run.window_spans("sharded_bfs.check") or not run.checks:
        return None
    total_us = sum(e["dur"] for e in run.window_spans("sharded_bfs.exe"))
    return total_us / 1e3 / len(run.checks)
