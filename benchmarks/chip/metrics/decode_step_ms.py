"""Device time per run of the batcher's compiled decode step, from the trace."""


def read(run):
    s = run.trace.per_run_s("decode") if run.trace else None
    return None if s is None else 1e3 * s
