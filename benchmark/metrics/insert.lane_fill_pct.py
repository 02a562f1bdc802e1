"""Live lanes over issued lanes of the visited-set insert's probe rounds:
100 x sum(``insert_lanes_live``) / sum(``insert_lanes_issued``) over the
window's ``tpu_bfs.drain`` and ``tpu_bfs.wave`` spans. None where the spans
carry no insert counters (a program that does not count them)."""


def read(run):
    live = issued = 0
    for name in ("tpu_bfs.drain", "tpu_bfs.wave"):
        for e in run.window_spans(name):
            a = e.get("args", {})
            if "insert_lanes_issued" in a:
                live += a["insert_lanes_live"]
                issued += a["insert_lanes_issued"]
    return 100.0 * live / issued if issued else None
