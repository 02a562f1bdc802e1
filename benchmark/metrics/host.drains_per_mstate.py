"""Host exits of the deep drain: ``tpu_bfs.drain`` spans in the window per
million unique states of its checks."""


def read(run):
    drains = len(run.window_spans("tpu_bfs.drain"))
    unique = sum(c.unique for c in run.checks)
    if not drains or not unique:
        return None
    return drains / (unique / 1e6)
