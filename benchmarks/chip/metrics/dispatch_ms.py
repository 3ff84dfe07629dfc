"""Platform overhead per request: the wall time from invoke to output,
less the span of the ``generate`` vertex's body; median over the requests
served while the profiler ran."""
import latency_stats


def read(run):
    t0, t1 = run.traced
    own = [1e3 * (r.end - r.start - r.body_s) for r in run.requests
           if r.tokens is not None and t0 <= r.start and r.end <= t1]
    return latency_stats.percentile(own, 50) if own else None
