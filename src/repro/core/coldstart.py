# det-lint: file waive[wall-clock] reason=real-exec cold-start measurement; wall time here IS the measurement, not a model
"""Cold-start backends: three real code paths with Table-1-style phases.

The paper's four isolation backends (CHERI/rWasm/process/KVM) are CPU
hardware mechanisms with no TPU analogue (DESIGN.md SS2). What *does*
transfer is the cold-start cost structure, which we reproduce with real
work on this platform:

  dandelion  -- Dandelion's own path: bind a memory context + load the
                function binary from the RAM code cache (disk on a cache
                miss) + set up the I/O descriptor structure. No compile,
                no deserialize: this is the 100s-of-us path.
  snapshot   -- Firecracker-snapshot analogue: the function's AOT-compiled
                executable is deserialized from its serialized snapshot on
                every cold start (jax serialize_executable round trip).
                ms-scale.
  microvm    -- Firecracker full-boot analogue: trace+lower+compile the
                function on the critical path. 100ms-scale.

Phases mirror Table 1: marshal requests / load from disk / transfer input
/ execute(-setup) / get+send output. ``measure`` runs the real path k
times and returns median phase durations; the virtual-time engines then
consume these profiles (with seeded lognormal jitter) so thousand-RPS
sweeps stay faithful to measured costs.
"""
from __future__ import annotations

import pickle
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from repro.core import tracing
from repro.core.context import MemoryContext
from repro.core.items import Item, SetDict
from repro.core.registry import ComputeFunction, FunctionRegistry

BACKENDS = ("dandelion", "snapshot", "microvm")


@dataclass
class ColdStartBreakdown:
    """Per-phase seconds (Table 1 rows)."""

    marshal: float = 0.0
    load: float = 0.0
    transfer: float = 0.0
    execute_setup: float = 0.0
    output: float = 0.0

    @property
    def total(self) -> float:
        return self.marshal + self.load + self.transfer + self.execute_setup + self.output

    def us(self) -> Dict[str, float]:
        return {
            "marshal_us": self.marshal * 1e6,
            "load_us": self.load * 1e6,
            "transfer_us": self.transfer * 1e6,
            "execute_setup_us": self.execute_setup * 1e6,
            "output_us": self.output * 1e6,
            "total_us": self.total * 1e6,
        }


class _AotCache:
    """Serialized-executable store for the snapshot/microvm backends."""

    def __init__(self):
        self._snapshots: Dict[str, bytes] = {}

    def snapshot_blob(self, cf: ComputeFunction) -> bytes:
        if cf.name in self._snapshots:
            return self._snapshots[cf.name]
        if cf.jax_fn is None:
            raise ValueError(f"{cf.name}: snapshot backend needs a jax payload")
        import jax
        from jax.experimental import serialize_executable

        compiled = jax.jit(cf.jax_fn).lower(*cf.abstract_args).compile()
        blob = serialize_executable.serialize(compiled)
        self._snapshots[cf.name] = pickle.dumps(blob)
        return self._snapshots[cf.name]


_AOT = _AotCache()


def _marshal(inputs: SetDict) -> Dict[str, Any]:
    """Build the low-level descriptor structure the function sees (SS4.1)."""
    return {
        name: [(it.key, it.nbytes) for it in items]
        for name, items in inputs.items()
    }


def cold_start(
    registry: FunctionRegistry,
    name: str,
    inputs: SetDict,
    *,
    backend: str = "dandelion",
    cached: bool = True,
    tracker=None,
    modeled: bool = False,
) -> Tuple[MemoryContext, ColdStartBreakdown, Callable[[], SetDict]]:
    """Run the real cold-start path. Returns (context, phases, run_fn).

    ``run_fn()`` executes the function body against the prepared context
    and writes outputs back into it (timed separately by the caller).

    ``modeled=True`` is the simulator fast path for tasks whose durations
    come from a calibrated ``ColdStartProfile``: the phase breakdown is
    not consumed, so the real disk read / AOT deserialize / compile work
    is skipped (memory is committed by size, page-identical), and the
    payload executes through the registry's content-addressed memo —
    each distinct ``(fn, input digest)`` body runs once, repeated trace
    events reuse the outputs. Dataflow and committed-memory accounting
    stay byte-identical with the measured path.
    """
    cf = registry.get(name)
    bd = ColdStartBreakdown()

    if modeled:
        ctx = MemoryContext(capacity=cf.context_bytes, tracker=tracker)
        # code + input-set pages commit as one collapsed tracker record
        # (accounting-identical; see MemoryContext.bulk_load)
        ctx.bulk_load(len(cf.code), inputs)
        memo = registry.memo

        def run_modeled() -> SetDict:
            out = memo.run(cf, ctx.inputs) if memo is not None else cf.fn(ctx.inputs)
            ctx.write_sets_bulk(out, into="outputs")
            return out

        return ctx, bd, run_modeled

    # the spans open outside each phase's clock reads: the breakdown does
    # not time them
    tracing.count("sandbox.boots")
    with tracing.span("sandbox.marshal"):
        t0 = time.perf_counter()
        desc = _marshal(inputs)
        bd.marshal = time.perf_counter() - t0

    with tracing.span("sandbox.load"):
        t0 = time.perf_counter()
        code = registry.load_code(name, cached=cached)
        bd.load = time.perf_counter() - t0

    with tracing.span("sandbox.transfer"):
        t0 = time.perf_counter()
        ctx = MemoryContext(capacity=cf.context_bytes, tracker=tracker)
        ctx.load_code(code)
        for set_name, items in inputs.items():
            ctx.write_set(set_name, items)
        bd.transfer = time.perf_counter() - t0

    with tracing.span("sandbox.setup"):
        t0 = time.perf_counter()
        runner: Callable[[], SetDict]
        if backend == "dandelion":
            fn = cf.fn
            runner = lambda: fn(ctx.inputs)
        elif backend == "snapshot":
            blob = _AOT.snapshot_blob(cf)
            from jax.experimental import serialize_executable

            compiled = serialize_executable.deserialize_and_load(
                *pickle.loads(blob)
            )
            runner = _jax_runner(cf, compiled, ctx)
        elif backend == "microvm":
            if cf.jax_fn is None:
                raise ValueError(f"{name}: microvm backend needs a jax payload")
            import jax

            # fresh closure per boot: defeats the jit cache, so every cold
            # start really pays trace + lower + compile (the full-boot analogue)
            payload = cf.jax_fn
            fresh = lambda *a: payload(*a)  # noqa: E731
            compiled = jax.jit(fresh).lower(*cf.abstract_args).compile()
            runner = _jax_runner(cf, compiled, ctx)
        else:
            raise ValueError(f"unknown backend {backend!r}; known {BACKENDS}")
        bd.execute_setup = time.perf_counter() - t0

    def run_and_collect() -> SetDict:
        out = runner()
        with tracing.span("sandbox.output"):
            t1 = time.perf_counter()
            for sname, items in out.items():
                ctx.write_set(sname, items, into="outputs")
            bd.output = time.perf_counter() - t1
        return out

    return ctx, bd, run_and_collect


def _jax_runner(cf: ComputeFunction, compiled, ctx: MemoryContext):
    """Adapt an AOT-compiled jax payload to the SetDict interface: arrays
    are taken positionally from the first input set."""

    def run() -> SetDict:
        args = []
        for items in ctx.inputs.values():
            for it in items:
                if hasattr(it.data, "shape"):
                    args.append(it.data)
        args = args[: len(cf.abstract_args)]
        result = compiled(*args)
        leaves = result if isinstance(result, (tuple, list)) else [result]
        return {"out": [Item(np.asarray(x)) for x in leaves]}

    return run


def measure(
    registry: FunctionRegistry,
    name: str,
    inputs: SetDict,
    *,
    backend: str = "dandelion",
    cached: bool = True,
    samples: int = 7,
    execute: bool = True,
) -> Tuple[ColdStartBreakdown, float]:
    """Median phase breakdown over ``samples`` real runs.

    Returns (breakdown, execute_seconds). Set ``execute=False`` to measure
    only sandbox creation (Fig. 5's workload).
    """
    phases = []
    exec_times = []
    for _ in range(samples):
        ctx, bd, run = cold_start(
            registry, name, inputs, backend=backend, cached=cached
        )
        if execute:
            t0 = time.perf_counter()
            run()
            exec_times.append(time.perf_counter() - t0 - bd.output)
        phases.append(bd)
        ctx.free()
    med = lambda xs: float(np.median(xs))
    out = ColdStartBreakdown(
        marshal=med([p.marshal for p in phases]),
        load=med([p.load for p in phases]),
        transfer=med([p.transfer for p in phases]),
        execute_setup=med([p.execute_setup for p in phases]),
        output=med([p.output for p in phases]),
    )
    return out, (med(exec_times) if exec_times else 0.0)


class CodeCache:
    """Per-node RAM code-cache residency model (SS5 two-level code store).

    The ``FunctionRegistry`` owns the *global* disk/RAM store; this class
    models which function binaries are resident in ONE worker node's RAM,
    which is what locality-aware routing cares about: a request lands
    "warm" only on a node that has already loaded the composition's
    functions. LRU over a bounded number of entries, with hit/miss
    counters the control plane exports through ``tracing.RoutingStats``.
    """

    def __init__(self, capacity_entries: int = 256):
        if capacity_entries <= 0:
            raise ValueError("code cache needs capacity >= 1 entry")
        self.capacity_entries = capacity_entries
        self._lru: "OrderedDict[str, None]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def resident(self, fn_name: str) -> bool:
        return fn_name in self._lru

    def warm_fraction(self, fn_names) -> float:
        """Fraction of ``fn_names`` resident — the routing affinity score."""
        names = list(fn_names)
        if not names:
            return 0.0
        return sum(1 for n in names if n in self._lru) / len(names)

    def warm(self, fn_name: str) -> None:
        """Seed residency without counting a hit or a miss.

        Used by P2P artifact prefetch (``core.artifacts``): the binary
        arrived over a modeled transfer, not a disk load, so the next
        ``touch`` must be a warm hit and hit/miss rates must reflect only
        real request traffic.
        """
        already = fn_name in self._lru
        self._lru[fn_name] = None
        self._lru.move_to_end(fn_name)
        if not already:
            while len(self._lru) > self.capacity_entries:
                self._lru.popitem(last=False)
                self.evictions += 1

    def touch(self, fn_name: str) -> bool:
        """Record a code load; returns True on a RAM hit (no disk read)."""
        hit = fn_name in self._lru
        if hit:
            self._lru.move_to_end(fn_name)
            self.hits += 1
        else:
            self.misses += 1
            self._lru[fn_name] = None
            while len(self._lru) > self.capacity_entries:
                self._lru.popitem(last=False)
                self.evictions += 1
        return hit

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def __len__(self) -> int:
        return len(self._lru)


@dataclass
class ColdStartProfile:
    """Calibrated per-(function, backend) profile consumed by the
    virtual-time engines: deterministic base + seeded lognormal jitter.

    ``cold_setup_s`` is the extra, deliberately jitter-free setup charged
    when the task runs without resident state (``Task.cold_setup`` set by
    the dispatcher: a weight-store miss, or — for functions no store
    handles — a code-residency miss): for ordinary functions a disk code
    load, for serving functions the model-weight load + compile term
    priced from the HLO cost models
    (``repro.launch.hlo_analysis.weight_coldstart_estimate``). Zero by
    default, so existing profiles and their RNG draw order are untouched
    (the cross-PR byte-identity contract)."""

    setup_s: float            # marshal+load+transfer+execute_setup+output
    execute_s: float
    jitter_sigma: float = 0.08
    cold_setup_s: float = 0.0  # added when the task is not cached/resident

    def sample(self, rng: np.random.Generator) -> Tuple[float, float]:
        j1 = float(rng.lognormal(0.0, self.jitter_sigma))
        j2 = float(rng.lognormal(0.0, self.jitter_sigma))
        return self.setup_s * j1, self.execute_s * j2


@dataclass(frozen=True)
class TransferProfile:
    """Deterministic per-link model for cross-node data movement.

    When a composition vertex is placed on a different node than one of
    its producers (cross-node scheduling, ``cluster.CrossNodePlacer``),
    the producing node's comm engine is charged one transfer task per
    crossing edge. ``charge(nbytes)`` splits the cost into

      * ``cpu_s`` — protocol/copy CPU that occupies the sender's comm
        slot (cooperative, like HTTP protocol handling);
      * ``io_s`` — wire time (link latency + bytes/bandwidth) during
        which the slot is free for other green tasks.

    Deliberately jitter-free: given the same placements and payload
    bytes, transfer durations are byte-stable run to run (the same
    determinism contract as the modeled comm-protocol CPU)."""

    latency_s: float = 100e-6       # per-message link latency
    bandwidth_bps: float = 1.25e9   # wire rate in bytes/sec (~10 GbE)
    cpu_per_byte_s: float = 1e-10   # sender-side protocol/copy CPU
    min_cpu_s: float = 2e-6         # floor, matches http.MIN_COMM_CPU_S

    def charge(self, nbytes: int) -> Tuple[float, float]:
        """(cpu_s, io_s) for moving ``nbytes`` over this link."""
        cpu_s = self.min_cpu_s + nbytes * self.cpu_per_byte_s
        io_s = self.latency_s + nbytes / self.bandwidth_bps
        return cpu_s, io_s


def profile_from_measurement(
    registry: FunctionRegistry,
    name: str,
    inputs: SetDict,
    backend: str = "dandelion",
    cached: bool = True,
) -> ColdStartProfile:
    bd, exec_s = measure(registry, name, inputs, backend=backend, cached=cached)
    return ColdStartProfile(setup_s=bd.total, execute_s=exec_s)
