"""Peak device memory in use over the run (``memory_stats``), in GB."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
