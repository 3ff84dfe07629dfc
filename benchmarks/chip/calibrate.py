#!/usr/bin/env python3
"""Readings that set a cell's numbers, taken once on the chip by hand.

    python3 benchmarks/chip/calibrate.py limits --workload hymba-chat \\
        --seeds 1,2,3 --control-seeds 1,2,3 --seconds 10
    python3 benchmarks/chip/calibrate.py knee --workload hymba-chat \\
        --rates 2,2.5,3 --seconds 20 --seed 1
    python3 benchmarks/chip/calibrate.py stalls --workload hymba-chat \\
        --seeds 1,2,3 --seconds 50 --dump stalls.txt

``limits`` makes one full run of the cell per seed, in this one process,
and prints the program's ``served_gap`` (the lower reading of its limit)
and, on the control seeds, the same number for the reference computed in
the configuration's control precision in the program's place (the upper
reading). ``knee`` runs an open-loop cell at each rate, its traffic
otherwise as the cell's file has it. ``stalls`` runs a cell on each seed
with a watchdog over the host (``WatchedSpans``) and prints the longest
stretches without a span edge. None is run by the benchmark itself.
"""
from __future__ import annotations

import argparse
import contextlib
import faulthandler
import json
import os
import resource
import sys
import time
from typing import List

import bench


def _floats(text):
    return [float(x) for x in text.split(",") if x]


def limits(args) -> None:
    cell = bench.load_cell(bench.ROOT, args.workload)
    control = cell.config["check"]["control"]
    seeds = [int(x) for x in args.seeds.split(",")]
    with_control = {int(x) for x in args.control_seeds.split(",") if x}
    for seed in seeds:
        r = bench.run(bench.ROOT, args.workload, seed, args.seconds, False,
                      control=control if seed in with_control else "")
        c = r["checks"]
        print("LIMITS " + json.dumps({
            "workload": args.workload, "seed": seed, "correct": r["correct"],
            "served_gap": c["served_gap"]["value"],
            "control_gap": c.get("control_gap", {}).get("value"),
            "metrics": {k: v["value"] for k, v in r["metrics"].items()}}), flush=True)


def knee(args) -> None:
    mix = bench.load_cell(bench.ROOT, args.workload).traffic
    for rate in _floats(args.rates):
        r = bench.run(bench.ROOT, args.workload, args.seed, args.seconds, False,
                      traffic=dict(mix, rate_per_s=rate))
        print("KNEE " + json.dumps({"rate_per_s": rate, "correct": r["correct"],
                                    "attempted": r["attempted"],
                                    "metrics": {k: v["value"] for k, v in r["metrics"].items()}}),
              flush=True)


class WatchedSpans(bench.Spans):
    """The harness's spans with a watchdog over the window: whenever no
    span has opened or closed for ``stall_s`` outside an idle wait,
    ``faulthandler`` writes the stack of every thread to ``dump`` (from its
    own thread, so it also fires while the main thread holds the
    interpreter), and again every ``stall_s`` while it lasts. Each edge
    records the main thread's own CPU seconds, page faults and context
    switches, and the whole process's CPU seconds, to tell a host that
    computes from one that waits or is not scheduled."""

    stall_s = 0.3
    dump = None
    edges: List[tuple] = []
    window_t0 = None   # host clock when the window opened; None outside it
    opened = 0.0       # host clock when the last window opened

    def __init__(self):
        super().__init__()
        self.open: List[str] = []

    def _edge(self, what: str) -> None:
        ru = resource.getrusage(resource.RUSAGE_THREAD)
        cpu = os.times()
        self.edges.append((time.perf_counter(), ru.ru_utime + ru.ru_stime, ru.ru_majflt,
                           ru.ru_minflt, ru.ru_nvcsw, ru.ru_nivcsw, cpu.user + cpu.system, what))
        if WatchedSpans.window_t0 is None or "wait" in self.open:
            faulthandler.cancel_dump_traceback_later()
        else:
            faulthandler.dump_traceback_later(self.stall_s, repeat=True, file=self.dump)

    @contextlib.contextmanager
    def __call__(self, name: str):
        self.open.append(name)
        self._edge("open " + name)
        try:
            with super().__call__(name):
                yield
        finally:
            self.open.remove(name)
            self._edge("close " + name)


def _watched(drive):
    """``drive`` with the watchdog armed from the window's opening to its end."""
    def run_window(*args, **kw):
        WatchedSpans.window_t0 = WatchedSpans.opened = time.perf_counter()
        try:
            return drive(*args, **kw)
        finally:
            WatchedSpans.window_t0 = None
            faulthandler.cancel_dump_traceback_later()
    return run_window


def stalls(args) -> None:
    WatchedSpans.stall_s = args.stall_ms / 1e3
    WatchedSpans.dump = open(args.dump, "w")
    bench.Spans = WatchedSpans
    bench.drive_open, bench.drive_closed = _watched(bench.drive_open), _watched(bench.drive_closed)
    for seed in [int(x) for x in args.seeds.split(",")]:
        WatchedSpans.edges = edges = []
        r = bench.run(bench.ROOT, args.workload, seed, args.seconds, False)
        edges = [e for e in edges if e[0] >= WatchedSpans.opened]
        gaps = [(b[0] - a[0], a, b) for a, b in zip(edges, edges[1:])
                if not (a[7] == "open wait" and b[7] == "close wait")]
        out = [{"gap_ms": 1e3 * g, "at_s": a[0] - WatchedSpans.opened, "from": a[7], "to": b[7],
                "thread_cpu_ms": 1e3 * (b[1] - a[1]), "majflt": b[2] - a[2],
                "minflt": b[3] - a[3], "vol_switches": b[4] - a[4],
                "invol_switches": b[5] - a[5], "process_cpu_ms": 1e3 * (b[6] - a[6])}
               for g, a, b in sorted(gaps, key=lambda x: -x[0])[:6]]
        print("STALLS " + json.dumps({"seed": seed, "correct": r["correct"],
                                      "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                                      "longest": out}), flush=True)
        WatchedSpans.dump.write(f"=== end of seed {seed}\n")
        WatchedSpans.dump.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("limits", "knee", "stalls"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--stall-ms", type=float, default=300.0)
    ap.add_argument("--dump", default="stalls.txt")
    args = ap.parse_args(argv)
    {"limits": limits, "knee": knee, "stalls": stalls}[args.mode](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
