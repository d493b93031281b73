"""Peak device memory of the fullest chip, in GB (10**9 bytes)."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
