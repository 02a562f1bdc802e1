"""Host time between deep drains: the mean self time of the
``tpu_bfs.drain_exit`` spans in the window (a drain's result to the next
drain's dispatch, less the ``tpu_bfs.exe`` and ``tpu_bfs.table_grow``
spans nested in it: executables obtained, and the table's growth, whose
rehash runs on the device), in ms per exit."""

NESTED = ("tpu_bfs.exe", "tpu_bfs.table_grow")


def nested_us(span, inner):
    """Microseconds of the span's interval that ``inner`` spans on its
    thread cover (their union: a rehash's own ``tpu_bfs.exe`` lies inside
    its ``tpu_bfs.table_grow``)."""
    lo, hi = span["ts"], span["ts"] + span["dur"]
    covered, end = 0.0, lo
    for a, b in sorted(
        (e["ts"], e["ts"] + e["dur"]) for e in inner
        if e["tid"] == span["tid"] and lo <= e["ts"] and e["ts"] + e["dur"] <= hi
    ):
        if b > end:
            covered += b - max(a, end)
            end = b
    return covered


def read(run):
    exits = run.window_spans("tpu_bfs.drain_exit")
    if not exits:
        return None
    inner = [e for name in NESTED for e in run.window_spans(name)]
    self_us = sum(e["dur"] - nested_us(e, inner) for e in exits)
    return self_us / 1e3 / len(exits)
