"""Useful model operations served while the profiler ran (real prompt and
output tokens, no padding; ``costs.py``) over the traced window's length
(the extent of the harness's spans in the trace) times the chip's peak."""
import costs


def read(run):
    if run.trace is None or not run.steps or not run.peak:
        return None
    m = run.cell.model
    flops = sum(costs.prefill_flops(m, s.prompt_len) if s.kind == "prefill"
                else costs.decode_flops(m, s.positions) for s in run.steps)
    return 100.0 * flops / (run.trace.window_s * run.cell.chips * run.peak["flops_per_s"])
