"""Output tokens served per second of the window. A request's tokens are
spread evenly over the time it was being served, and the part of that
time inside the window counts."""
import latency_stats


def read(run):
    t0, t1 = run.window
    spans = [(r.start, r.end, len(r.tokens)) for r in run.requests if r.tokens is not None]
    return latency_stats.prorated(spans, t0, t1) / (t1 - t0)
