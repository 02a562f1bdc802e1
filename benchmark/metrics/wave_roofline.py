"""The traced checks' algorithm bytes at the chip's HBM bandwidth, over the
device's busy time in the traced window.

Algorithm bytes count the same work whatever implements it: each unique
state is written to the frontier once and read once (2 x state bytes),
each generated candidate makes one visited-set probe, and each unique
state one insert and one parent-log entry. The byte constants are fixed
in the configuration file, never read from the program."""


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
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / d["busy_s"]
