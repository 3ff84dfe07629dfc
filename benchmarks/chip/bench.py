#!/usr/bin/env python3
"""Serve one benchmark cell on the chip and print its metrics.

    python3 benchmarks/chip/bench.py --workload hymba-chat --seed 7 \\
        --seconds 40 --trace 0

One process, which holds the chip, does in order:

1. places JAX's compile cache (``repro.launch.serve.use_compile_cache``);
2. stops with exit code 3, printing no result, unless JAX sees a TPU and
   as many chips as the cell asks for;
3. builds the cell's model from ``configs/<config>.json``, its weights
   drawn on the device from ``--seed`` in one jitted call;
4. builds the ``ContinuousBatcher`` and compiles its two steps;
5. deploys the adapter's composition (``adapters/<entry>.py``) once;
6. serves warm-up requests through it, so that every program the window
   runs is compiled or loaded before the window;
7. serves the traffic of ``traffic/<mix>.json`` for ``--seconds``: an
   open loop times each request from when it was due, a closed loop keeps
   a fixed number of clients waiting on replies; one thread sends and
   serves, as the serving path takes one request at a time;
8. compares what was served with the plain reference (``check.py``);
9. prints the result as the last line of stdout.

``--trace 1`` runs the profiler over the window and reports the cell's
per-layer metrics (``metrics/<name>.py``) instead of its end-to-end ones.
Everything a cell needs is found by name, so a cell is added with data
files and a ``workloads`` entry in ``BENCHMARK.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import traceback
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(HERE)]

# after the window closes, requests due in it are still waited for this long
GRACE_S = 60.0
# a traced run traces this many seconds in the middle of its window:
# writing the trace takes about six seconds for each second traced
TRACE_S = 10.0


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: Path

    @property
    def model(self) -> Dict:
        return self.config["model"]


def load_cell(root: Path, workload: str) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``, with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench_dir = root / bench["paths"][0]
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"error: no workload {workload!r}; known: {sorted(by_name)}")
    w = by_name[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())

    def listed(m):
        return workload in m.get("workloads", [workload])

    longest = traffic["prompt"]["max"] + traffic["output"]["max"]
    if longest > config["serving"]["cache_len"]:
        raise SystemExit(f"error: {workload}: a request of {longest} tokens does not fit "
                         f"the {config['serving']['cache_len']}-token cache")
    e2e = [m for m in bench["end_to_end"] if listed(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer, bench_dir)


def load_module(path: Path):
    name = f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    """One request as the client saw it (host clock, perf_counter seconds)."""

    index: int
    prompt: List[int]
    max_new: int
    due: float
    start: float = 0.0
    end: float = 0.0
    body_s: float = 0.0            # the generate vertex's own span
    tokens: Optional[List[int]] = None
    error: Optional[str] = None


@dataclasses.dataclass
class Step:
    """One call of a batcher step, as the harness's wrapper saw it."""

    kind: str                      # "prefill" | "decode"
    t: float
    active: int = 1                # slots holding a request
    positions: tuple = ()          # decode: position of each active slot's token
    prompt_len: int = 0            # prefill: real tokens of the prompt


@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    peak: Dict
    setup_s: float = 0.0
    window: tuple = (0.0, 0.0)
    requests: List[Served] = dataclasses.field(default_factory=list)
    steps: List[Step] = dataclasses.field(default_factory=list)
    memory_peak_bytes: int = 0
    trace: Optional[object] = None   # trace_reduce.Reduction of a traced run
    traced: tuple = (float("-inf"), float("inf"))  # host clock while the profiler ran
    num_slots: int = 0


class Spans:
    """Host spans around the harness's calls into each layer: written into
    the profiler's trace as ``TraceAnnotation``s and kept on the host clock."""

    def __init__(self):
        import jax
        self._annotate = jax.profiler.TraceAnnotation
        self.last: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with self._annotate(name):
            try:
                yield
            finally:
                self.last[name] = time.perf_counter() - t0


class Tracer:
    """The profiler over ``seconds`` in the middle of the window, started
    and stopped at request boundaries; a no-op when ``trace_dir`` is None.
    The middle, not the start: an open loop's queue is empty when the
    window opens, so its first seconds are idler than the rest. Longer
    than some ten seconds, the trace drops device events. Writing the
    trace stops the serving loop for about a minute."""

    def __init__(self, jax, trace_dir: Optional[str], seconds: float, window: float):
        self.jax, self.dir, self.seconds = jax, trace_dir, seconds
        self.offset = max(0.0, (window - seconds) / 2)
        self.on = False
        self.started_at = self.stopped_at = float("inf")
        self.paused_s = 0.0   # the serving loop stood still while the profiler started or stopped

    def tick(self, t0: float) -> None:
        now = time.perf_counter()
        if self.dir is not None and not self.on and self.started_at == float("inf") \
                and now >= t0 + self.offset:
            options = self.jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the harness's spans are TraceAnnotations
            self.jax.profiler.start_trace(self.dir, profiler_options=options)
            self.on = True
            self.started_at = time.perf_counter()
            self.paused_s += self.started_at - now
        elif self.on and now >= self.started_at + self.seconds:
            self.stop()

    def stop(self) -> None:
        if self.on:
            self.stopped_at = time.perf_counter()
            self.jax.profiler.stop_trace()
            self.paused_s += time.perf_counter() - self.stopped_at
            self.on = False


def wrap_steps(batcher, spans: Spans, steps: List[Step]) -> None:
    """Annotate and count the batcher's two step callables, on this instance."""
    prefill, decode = batcher.prefill_step, batcher.decode_step
    admitted = [0]

    def prefill_step(*args, **kw):
        # admission is first come, first served: the k-th prefill is the
        # k-th request submitted
        req = batcher.all_requests[admitted[0]]
        admitted[0] += 1
        steps.append(Step("prefill", time.perf_counter(),
                          prompt_len=min(len(req.prompt), batcher.cache_len)))
        with spans("prefill_step"):
            return prefill(*args, **kw)

    def decode_step(*args, **kw):
        live = [r for r in batcher.slots if r is not None]
        pos = tuple(min(len(r.prompt), batcher.cache_len) + len(r.generated) - 1 for r in live)
        steps.append(Step("decode", time.perf_counter(), active=len(live), positions=pos))
        with spans("decode_step"):
            return decode(*args, **kw)

    batcher.prefill_step, batcher.decode_step = prefill_step, decode_step


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
def serve_one(serve: Callable, spans: Spans, r: Served) -> None:
    r.start = time.perf_counter()
    try:
        with spans("invoke"):
            r.tokens = serve(r.prompt, r.max_new)
        r.body_s = spans.last.get("generate", 0.0)
    except Exception:  # a request that fails is counted, and the run goes on
        r.error = traceback.format_exc()
        print(f"request {r.index} failed:\n{r.error}", file=sys.stderr)
    r.end = time.perf_counter()


def drive_open(serve, spans, reqs, seconds, tracer) -> tuple:
    """Requests due on the wall clock, served one after another: a request
    that comes due while another is served waits for it, and is timed from
    when it was due. Returns (window, served, how late each request was
    sent after an idle wait, in seconds)."""
    t0 = time.perf_counter()
    late: List[float] = []
    out: List[Served] = []
    for q in reqs:
        tracer.tick(t0)
        r = Served(q.index, q.prompt.tolist(), q.max_new, t0 + q.due)
        if r.due > time.perf_counter():
            with spans("wait"):
                time.sleep(r.due - time.perf_counter())
            late.append(time.perf_counter() - r.due)
        # the profiler's pause to write its trace is the harness's, not the server's
        if time.perf_counter() > t0 + seconds + GRACE_S + tracer.paused_s:
            r.error = "never served: still queued a minute after the window closed"
            r.start = r.end = float("inf")
        else:
            serve_one(serve, spans, r)
        out.append(r)
    return (t0, t0 + seconds), out, late


def drive_closed(serve, spans, stream, clients, seconds, tracer) -> tuple:
    """``clients`` clients, each waiting on its reply before it sends again;
    none sends once the window has closed."""
    t0 = time.perf_counter()
    t1 = t0 + seconds
    waiting = []
    for _ in range(clients):
        q = next(stream)
        waiting.append(Served(q.index, q.prompt.tolist(), q.max_new, t0))
    out: List[Served] = []
    while waiting and time.perf_counter() < t1:
        tracer.tick(t0)
        r = waiting.pop(0)
        serve_one(serve, spans, r)
        out.append(r)
        q = next(stream)
        waiting.append(Served(q.index, q.prompt.tolist(), q.max_new, r.end))
    return (t0, t1), out, []


def longest_gaps(requests: List[Served], steps: List[Step], t0: float, n: int = 3) -> List[str]:
    """The ``n`` longest stretches of host time inside a request between
    two of its landmarks (invoke, each step call, output), as
    "<ms>@<s into the window>:<from>-<to>"."""
    ts = sorted(steps, key=lambda st: st.t)
    out = []
    for r in requests:
        if r.tokens is None:
            continue
        marks = [(r.start, "invoke")] + [(st.t, st.kind) for st in ts
                                         if r.start <= st.t <= r.end] + [(r.end, "output")]
        out += [(b[0] - a[0], a[0] - t0, f"{a[1]}-{b[1]}") for a, b in zip(marks, marks[1:])]
    return [f"{1e3 * g:.1f}@{at:.1f}:{what}" for g, at, what in sorted(out, reverse=True)[:n]]


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------
def device_info(jax, chips: int, require_tpu: bool) -> Dict:
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def memory_peak(jax, chips: int) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, fault: Optional[Callable] = None,
        control: str = "", traffic: Optional[Dict] = None) -> Dict:
    """One run of a cell; returns the result line's object. For tests and
    ``calibrate.py`` only: ``fault`` is called with the batcher before the
    window, to break it; ``control`` names a lower precision in which the
    reference is also run in the program's place (``checks.control_gap``);
    ``traffic`` stands in for the cell's traffic file."""
    setup: Dict[str, float] = {}
    t = time.perf_counter()
    from repro.launch.serve import CacheEvents, use_compile_cache

    cache_dir = use_compile_cache()
    import jax
    import numpy as np

    import check
    import latency_stats
    import trace_reduce
    import traffic as traffic_gen
    import weights
    from repro.config import ModelConfig
    from repro.models.common import is_spec
    from repro.models.model import build
    from repro.serving.batching import ContinuousBatcher
    setup["imports_s"] = time.perf_counter() - t

    t = time.perf_counter()
    cell = load_cell(root, workload)
    if traffic is not None:
        cell.traffic = traffic
    device = device_info(jax, cell.chips, require_tpu)
    peaks = json.loads((cell.bench_dir / "peaks.json").read_text())["devices"]
    if require_tpu and device["kind"] not in peaks:
        raise SystemExit(f"error: no peaks for device kind {device['kind']!r} in peaks.json")
    run = Run(cell, seed, seconds, peaks.get(device["kind"], {}))
    setup["devices_s"] = time.perf_counter() - t

    model, serving, mix = cell.model, cell.config["serving"], cell.traffic
    t = time.perf_counter()
    cfg = ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in model.items()})
    api = build(cfg)
    with CacheEvents() as events:
        params = weights.make_params(api.param_template, cfg.dtype, seed, is_leaf=is_spec)
        jax.block_until_ready(params)
        setup["weights_s"] = time.perf_counter() - t
        batcher = ContinuousBatcher(api, params, num_slots=serving["slots"],
                                    cache_len=serving["cache_len"])
        setup.update({f"compile_{k}_s": v for k, v in batcher.compile().items()})
        t = time.perf_counter()
        programs = {"prefill": trace_reduce.module_name(batcher.prefill_step),
                    "decode": trace_reduce.module_name(batcher.decode_step)}
        spans = Spans()
        steps: List[Step] = []
        wrap_steps(batcher, spans, steps)
        adapter = load_module(cell.bench_dir / "adapters" / f"{serving['adapter']}.py")
        serve = adapter.deploy(batcher, spans)
        setup["deploy_s"] = time.perf_counter() - t
        t = time.perf_counter()
        rng = np.random.default_rng([seed, 1])
        for n in (mix["prompt"]["max"], mix["prompt"]["min"]):
            serve(rng.integers(0, cfg.vocab_size, n, dtype=np.int32), 3)
        setup["warmup_s"] = time.perf_counter() - t
    if fault is not None:
        fault(batcher)
    run.num_slots = batcher.num_slots
    del steps[:]

    t = time.perf_counter()
    if mix["loop"] == "open":
        reqs = traffic_gen.open_loop(mix, seed, seconds, cfg.vocab_size)
    else:
        stream = traffic_gen.closed_loop(mix, seed, cfg.vocab_size)
    compiles = []
    listener = lambda event, duration, **kw: compiles.append(event) \
        if event == "/jax/core/compile/backend_compile_duration" else None
    jax.monitoring.register_event_duration_secs_listener(listener)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    tracer = Tracer(jax, trace_dir, TRACE_S, seconds)
    # what set-up left behind is not scanned again by the collector in the
    # window, as a server that has started would arrange
    gc.collect()
    gc.freeze()
    setup["requests_s"] = time.perf_counter() - t
    run.setup_s = time.perf_counter() - T_START
    try:
        if mix["loop"] == "open":
            run.window, run.requests, late = drive_open(serve, spans, reqs, seconds, tracer)
        else:
            run.window, run.requests, late = drive_closed(
                serve, spans, stream, int(mix["clients"]), seconds, tracer)
    finally:
        tracer.stop()
        jax.monitoring.unregister_event_duration_listener(listener)
        gc.unfreeze()
    gaps = longest_gaps(run.requests, steps, run.window[0])
    if trace:
        run.traced = (tracer.started_at, tracer.stopped_at)
        run.steps = [s for s in steps if run.traced[0] <= s.t < run.traced[1]]
    else:
        run.steps = steps
    after = time.perf_counter()

    if trace:
        run.trace = trace_reduce.reduce(trace_reduce.find_trace(trace_dir), programs,
                                        spans=("invoke", "generate", "prefill_step",
                                               "decode_step", "wait"))
        shutil.rmtree(trace_dir, ignore_errors=True)
    run.memory_peak_bytes = memory_peak(jax, cell.chips)

    # free the served model before the reference runs on the same chip
    del batcher, params, serve, adapter
    gc.collect()

    attempted = len(run.requests)
    failed = sum(r.tokens is None for r in run.requests)
    chk = cell.config["check"]
    sample = check.draw_sample(run.requests, seed, chk["sample_requests"])
    t = time.perf_counter()
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    ref_times: Dict[str, float] = {}
    shape = (chk["sample_requests"], longest, chk["sample_requests"] * mix["output"]["max"])
    read = check.served_gap(model, seed, sample, serving["cache_len"], shape, control, ref_times) \
        if sample else {"served_gap": float("inf")}
    reference_s = time.perf_counter() - t
    checks = {"served_gap": {"value": read["served_gap"], "limit": chk["served_gap_limit"]},
              "failed_requests": {"value": failed, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    if control:
        checks["control_gap"] = {"value": read.get("control_gap"),
                                 "limit": chk["served_gap_limit"]}

    late_ms = sorted(1e3 * x for x in late) or [0.0]
    lat_ms = [1e3 * (r.end - r.due) if r.tokens is not None else float("inf")
              for r in run.requests]
    busy = [r.end - r.start for r in run.requests if r.tokens is not None] or [0.0]
    print(f"cell={cell.name} seed={seed} seconds={seconds} trace={int(trace)} "
          f"cache_dir={cache_dir} cache_hits={events.counts['hits']} "
          f"cache_misses={events.counts['misses']}")
    print("setup " + " ".join(f"{k}={v:.6f}" for k, v in setup.items())
          + f" before_s={run.setup_s - sum(setup.values()):.6f} total_s={run.setup_s:.6f}")
    print(f"window requests={attempted} failed={failed} "
          f"served_after_close_s={after - run.window[1]:.6f} compiles_in_window={len(compiles)} "
          f"sent_late_ms_p50={late_ms[len(late_ms) // 2]:.6f} "
          f"sent_late_ms_max={late_ms[-1]:.6f} "
          f"service_ms_mean={1e3 * sum(busy) / len(busy):.6f} "
          f"latency_ms_p90={latency_stats.percentile(lat_ms, 90) if lat_ms else 0:.6f} "
          f"longest_host_gaps={','.join(gaps)}")
    if trace:
        # a trace that dropped device events counts fewer runs than the host issued
        print("trace " + " ".join(
            f"{role}_runs={run.trace.programs.get(role, (0, 0.0))[0]}/"
            f"{sum(s.kind == role for s in run.steps)}" for role in programs)
            + f" started_s={run.traced[0] - run.window[0]:.6f}"
            f" window_s={run.trace.window_s:.6f} busy_s={run.trace.busy_s:.6f}")
    print(f"check sample_requests={len(sample)} "
          f"sample_tokens={sum(len(d.tokens) for d in sample)} reference_s={reference_s:.6f} "
          + " ".join(f"{k}={v:.6f}" for k, v in ref_times.items()))

    metric_list = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in metric_list:
        value = load_module(cell.bench_dir / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["memory_peak_bytes"] = run.memory_peak_bytes
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}={c['value']} limit={c['limit']}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
