"""Median time from when a request was due to when its output returned,
over every request due in the window (open loop only)."""
import latency_stats


def latencies_ms(run):
    return [1e3 * (r.end - r.due) if r.tokens is not None else float("inf")
            for r in run.requests]


def read(run):
    if run.cell.traffic["loop"] != "open" or not run.requests:
        return None
    return latency_stats.percentile(latencies_ms(run), 50)
