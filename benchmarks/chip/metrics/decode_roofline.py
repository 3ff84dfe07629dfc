"""Share of its roofline that the decode step reaches: the least time the
chip could take for the steps' operations and bytes (``costs.py``, from
the sizes and each step's active slots and positions), over the device
time the steps took. Memory-bound at the served sizes."""
import costs


def read(run):
    device_s = run.trace.per_run_s("decode") if run.trace else None
    steps = [s for s in run.steps if s.kind == "decode"]
    if device_s is None or not steps or not run.peak:
        return None
    m = run.cell.model
    least = sum(costs.roofline_s(costs.decode_flops(m, s.positions),
                                 costs.decode_bytes(m, s.positions), run.peak)
                for s in steps) / len(steps)
    return 100.0 * least / device_s
