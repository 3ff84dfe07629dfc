"""Mean share of the batcher's slots that hold a request at each decode
step, counted by the harness's wrapper of the step."""


def read(run):
    steps = [s for s in run.steps if s.kind == "decode"]
    if not steps or not run.num_slots:
        return None
    return 100.0 * sum(s.active for s in steps) / (len(steps) * run.num_slots)
