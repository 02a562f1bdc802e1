"""The traced checks' algorithm bytes at the HBM bandwidth of every chip
of the cell, over the busy time of the traced window (the union of every
device's operations, ``tracing.py``).

Algorithm bytes are ``wave_roofline``'s, from the configuration's byte
constants: each unique state written to the frontier once and read once,
one visited-set probe per generated state, one insert and one parent-log
entry per unique state. On one chip this reads as ``wave_roofline``."""


def read(run):
    d = run.device
    if not d or d["busy_s"] <= 0:
        return None
    traced = [
        c for c in run.checks
        if c.t_spawn >= d["t_from"] and c.t_end <= d["t_to"]
    ]
    if not traced:
        return None
    k = run.cell.config["roofline"]
    nbytes = sum(
        c.unique * (2 * k["state_bytes"] + k["table_access_bytes"] + k["log_entry_bytes"])
        + c.states * k["table_access_bytes"]
        for c in traced
    )
    peak = run.cell.chips * run.peaks["hbm_bytes_per_s"]
    return 100.0 * nbytes / peak / d["busy_s"]
