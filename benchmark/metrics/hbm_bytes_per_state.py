"""The device's peak bytes in use over the unique states of one check."""


def read(run):
    if not run.checks or not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / max(c.unique for c in run.checks)
