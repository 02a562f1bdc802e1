"""Bytes the sharded checker's key exchange carried per unique state: the
sum of ``exchange_bytes`` over the window's ``sharded_bfs.drain`` and
``sharded_bfs.wave`` spans (key lanes every all-to-all carried, summed over
devices, from the device's own count) over the unique states of the
window's checks. None where no span carries ``exchange_bytes``."""


def read(run):
    nbytes, found = 0, False
    for name in ("sharded_bfs.drain", "sharded_bfs.wave"):
        for e in run.window_spans(name):
            a = e.get("args", {})
            if "exchange_bytes" in a:
                nbytes += a["exchange_bytes"]
                found = True
    unique = sum(c.unique for c in run.checks)
    if not found or unique <= 0:
        return None
    return nbytes / unique
