"""How often a check's executable acquisitions hit the shared AOT cache:
100 x the window's ``tpu_bfs.exe`` spans whose ``source`` is ``memory``
(an executable another check under the namespace already built) over all
of the window's ``tpu_bfs.exe`` spans. None where the window has none."""


def read(run):
    spans = run.window_spans("tpu_bfs.exe")
    if not spans:
        return None
    hits = sum(1 for e in spans if e.get("args", {}).get("source") == "memory")
    return 100.0 * hits / len(spans)
