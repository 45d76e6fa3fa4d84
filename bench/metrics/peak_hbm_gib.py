"""Peak HBM in use over the whole run (publish, restores, serving), from the
device's ``memory_stats``, in GiB."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 2**30
