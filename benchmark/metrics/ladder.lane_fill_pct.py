"""Live lanes over dispatched lanes, over the deep drains in the window:
sum(compaction_ratio x waves x bucket) / sum(waves x bucket) of the
``tpu_bfs.drain`` spans."""


def read(run):
    num = den = 0.0
    for e in run.window_spans("tpu_bfs.drain"):
        a = e.get("args", {})
        ratio, waves, bucket = a.get("compaction_ratio"), a.get("waves"), a.get("bucket")
        if ratio is None or not waves or not bucket:
            continue
        num += ratio * waves * bucket
        den += waves * bucket
    return 100.0 * num / den if den else None
