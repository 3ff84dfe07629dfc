"""From a profiler trace (``.xplane.pb``) to device busy time, per-program
device time, and where the device sat idle.

Device work is read from each TPU's plane: its "XLA Ops" line holds one
event per operation, its "XLA Modules" line one event per run of a
program. The runs of the prefill and the decode step are those whose HLO
module name is that of the batcher's own ``Compiled`` objects. Where two
roles share a module name (both of the batcher's steps are jitted
lambdas), each program id takes the role of the harness's host span
(``prefill_step`` or ``decode_step``) that most often opened nearest to
the start of one of its runs: a program runs just after its call is
issued, and the serving path waits for each step before the next.

The window is the extent of the harness's own host spans
(``TraceAnnotation``), and each idle gap inside it is put down to the
innermost of those spans that was open at its middle.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


def find_trace(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def module_name(compiled) -> str:
    """HLO module name of a ``jax.stages.Compiled``."""
    return compiled.runtime_executable().hlo_modules()[0].name


def _op_name(text: str) -> str:
    """``fusion.155`` from an XLA Ops event named by its HLO instruction."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _self_times(ops, runs, by_op) -> None:
    """Add each operation's own device time (less the operations nested in
    it, as a loop holds its body) to ``by_op``, keyed by the role (or the
    module name) of the program run that holds it and the operation's
    name. ``runs`` are (start, end, label), sorted by start."""
    starts = [r[0] for r in runs]
    entries: List[list] = []
    stack: List[list] = []
    for a, b, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            stack[-1][2] -= b - a
        k = bisect.bisect_right(starts, a) - 1
        module = runs[k][2] if k >= 0 and a <= runs[k][1] else "?"
        entry = [a, b, b - a, f"{module}/{name}"]
        stack.append(entry)
        entries.append(entry)
    for _, _, own, key in entries:
        by_op[key] += own


def _module_of(ev, st) -> Tuple[str, object]:
    """(module name, program id) of a module-run event."""
    name = st.get("hlo_module") or ev.name.split("(")[0]
    pid = st.get("program_id")
    if pid is None and ev.name.endswith(")") and "(" in ev.name:
        pid = ev.name[ev.name.rindex("(") + 1:-1]
    return name, pid


@dataclass
class Reduction:
    busy_s: float
    window_s: float
    programs: Dict[str, Tuple[int, float]]          # role -> (runs, device seconds)
    ops: List[Tuple[str, float]]                    # device time by operation
    idle: List[Tuple[str, float]]                   # idle time by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def per_run_s(self, role: str) -> Optional[float]:
        n, total = self.programs.get(role, (0, 0.0))
        return total / n if n else None

    def breakdown(self) -> Dict[str, list]:
        return {"device_ops": [[n, s] for n, s in self.ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle[:10]]}


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _stats(ev) -> Dict:
    try:
        return dict(ev.stats)
    except Exception:  # an event whose stats the reader cannot convert
        return {}


def reduce(path: str, programs: Dict[str, str], spans: Sequence[str]) -> Reduction:
    """Reduce one trace. ``programs`` maps a role ("prefill", "decode") to
    the HLO module name of the ``Compiled`` object whose runs it names;
    ``spans`` are the names of the harness's host spans, outermost first."""
    from jax.profiler import ProfileData

    names = set(programs.values())
    data = ProfileData.from_file(path)
    planes = list(data.planes)
    tpus = [p for p in planes if p.name.startswith("/device:TPU:") and p.name[12:].isdigit()]
    if not tpus:
        raise ValueError("the trace holds no TPU plane")

    host_spans: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name in spans:
                    host_spans[ev.name].append((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
    edges = [t for name in ("invoke", "wait") for iv in host_spans.get(name, []) for t in iv]
    if not edges:
        raise ValueError("the trace holds none of the harness's host spans")
    lo, hi = min(edges), max(edges)

    op_sets: List[List[Tuple[float, float]]] = []
    by_op: Dict[str, float] = defaultdict(float)
    execs: List[Tuple[object, float, float]] = []   # (program id, start, seconds)
    planes_ops = []
    for p in tpus:
        ops, runs_here = [], []
        for line in p.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    b = a + ev.duration_ns * 1e-9
                    if b > lo and a < hi:
                        ops.append((a, b, _op_name(ev.name)))
            elif line.name == "XLA Modules":
                for ev in line.events:
                    name, pid = _module_of(ev, _stats(ev))
                    a = ev.start_ns * 1e-9
                    runs_here.append((a, a + ev.duration_ns * 1e-9, name, pid))
                    if name in names and lo <= a <= hi:
                        execs.append((pid, a, ev.duration_ns * 1e-9))
        op_sets.append([(a, b) for a, b, _ in ops])
        planes_ops.append((ops, sorted(runs_here, key=lambda r: r[0])))

    busy_each, gaps_each = [], []
    for ops in op_sets:
        u = _clip(_union(ops), lo, hi)
        busy_each.append(sum(b - a for a, b in u))
        edges_u = [lo] + [t for iv in u for t in iv] + [hi]
        gaps_each.append([(edges_u[i], edges_u[i + 1]) for i in range(0, len(edges_u), 2)
                          if edges_u[i + 1] > edges_u[i]])
    busy = sum(busy_each) / len(busy_each) if busy_each else 0.0

    # idle time by the innermost harness span open at each gap's middle;
    # spans of one name do not overlap, as the harness is one thread
    named = [(name, sorted(host_spans.get(name, []))) for name in reversed(spans)]
    idle: Dict[str, float] = defaultdict(float)
    for gaps in gaps_each:
        for a, b in gaps:
            mid = 0.5 * (a + b)
            label = "none"
            for name, iv in named:
                k = bisect.bisect_right(iv, (mid, float("inf"))) - 1
                if k >= 0 and iv[k][0] <= mid <= iv[k][1]:
                    label = name
                    break
            idle[label] += (b - a) / len(gaps_each)
    roles = _roles(execs, host_spans, programs)
    for ops, runs_here in planes_ops:
        _self_times(ops, [(a, b, roles.get(pid, name)) for a, b, name, pid in runs_here], by_op)
    runs: Dict[str, List[float]] = defaultdict(list)
    for pid, role in roles.items():
        runs[role] += [d for p, _, d in execs if p == pid]
    programs_out = {role: (len(v), float(sum(v))) for role, v in runs.items()}
    return Reduction(
        busy_s=float(busy), window_s=float(hi - lo), programs=programs_out,
        ops=sorted(by_op.items(), key=lambda kv: -kv[1]),
        idle=sorted(idle.items(), key=lambda kv: -kv[1]))


def _roles(execs, host_spans, programs) -> Dict[object, str]:
    """Program id -> role, by the host span of each role ("<role>_step")
    whose opening lies nearest to the start of the program's runs, in most
    of them. Nearest, not last before: the host's and the device's clocks
    in a trace agree only to some tens of microseconds."""
    opened = sorted((a, role) for role in programs
                    for a, _ in host_spans.get(f"{role}_step", []))
    starts = [a for a, _ in opened]
    votes: Dict[object, Dict[str, int]] = defaultdict(lambda: defaultdict(int))
    for pid, a, _ in execs:
        k = bisect.bisect_left(starts, a)
        near = [j for j in (k - 1, k) if 0 <= j < len(starts)]
        if near:
            j = min(near, key=lambda j: abs(starts[j] - a))
            votes[pid][opened[j][1]] += 1
    out = {}
    for pid, v in votes.items():
        role = max(v, key=v.get)
        if 2 * v[role] > sum(v.values()):
            out[pid] = role
    return out
