"""The span-and-counter recorder (``repro.core.tracing``): free when off,
nested records and counters when on, and the sandbox's spans on one
platform invocation."""
import math
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro import sdk
from repro.core import FunctionRegistry, Item, cold_start, tracing

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
SANDBOX = ("sandbox.marshal", "sandbox.load", "sandbox.transfer", "sandbox.setup",
           "sandbox.run", "sandbox.output")


@pytest.fixture
def recorder():
    tracing.enable()
    tracing.take()
    yield tracing
    tracing.disable()
    tracing.take()


def test_off_records_nothing_and_imports_no_jax():
    code = (
        "import sys\n"
        "from repro.core import tracing\n"
        "with tracing.span('a', rid=1) as s:\n"
        "    with tracing.span('b'):\n"
        "        tracing.count('c', 3)\n"
        "assert tracing.span('x') is s is tracing.span('y')\n"
        "assert tracing.take() == ([], {})\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
    )
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr


def test_on_spans_nest_by_parent_index(recorder):
    with tracing.span("outer", rid=7):
        with tracing.span("inner"):
            with tracing.span("leaf"):
                pass
        with tracing.span("inner"):
            pass
    with tracing.span("next"):
        pass
    records, counters = tracing.take()
    assert [(r.name, r.parent) for r in records] == [
        ("outer", -1), ("inner", 0), ("leaf", 1), ("inner", 0), ("next", -1)]
    assert records[0].attrs == {"rid": 7} and counters == {}
    for r in records:
        assert r.start <= r.end
        if r.parent >= 0:
            p = records[r.parent]
            assert p.start <= r.start and r.end <= p.end


def test_a_span_on_another_thread_has_its_own_parents(recorder):
    seen = []

    def other():
        with tracing.span("worker"):
            seen.append(True)

    with tracing.span("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    records, _ = tracing.take()
    parents = {r.name: r.parent for r in records}
    assert seen and parents == {"main": -1, "worker": -1}


def test_counters_add_up_and_take_clears(recorder):
    for n in (1, 2, 3):
        tracing.count("steps")
        tracing.count("slots", n)
    with tracing.span("open") as s:
        assert math.isnan(tracing.take()[0][0].end)
    assert s.record.end >= s.record.start
    tracing.count("steps")
    records, counters = tracing.take()
    assert records == [] and counters == {"steps": 1}
    tracing.disable()
    tracing.count("steps")
    with tracing.span("gone"):
        pass
    assert tracing.take() == ([], {})


def _platform_with_one_function():
    def double(inputs):
        with tracing.span("body"):
            x = np.frombuffer(inputs["x"][0].data, np.int32)
            return {"y": [Item((2 * x).tobytes())]}

    fn = sdk.declare("double", double, inputs=("x",), outputs=("y",), memoize=False)
    with sdk.composition("twice") as app:
        app.output("y", fn(x=app.input("x")).y)
    platform = sdk.Platform(node=sdk.NodeSpec(num_slots=2, comm_slots=1))
    platform.deploy(app)
    return platform, app


def test_one_invocation_records_each_sandbox_span_once(recorder):
    platform, app = _platform_with_one_function()
    tracing.take()
    h = platform.invoke(app, {"x": [Item(np.arange(4, dtype=np.int32).tobytes())]})
    y = np.frombuffer(h.result()["y"][0].data, np.int32)
    assert y.tolist() == [0, 2, 4, 6]
    records, counters = tracing.take()
    names = [r.name for r in records if r.name.startswith("sandbox.")]
    assert sorted(names) == sorted(SANDBOX)
    by = {r.name: i for i, r in enumerate(records)}
    # the boot's phases and the body's run share one parent; the body's
    # own spans and the output write are inside the run
    top = {records[by[n]].parent for n in SANDBOX[:5]}
    assert len(top) == 1
    assert records[by["sandbox.output"]].parent == by["sandbox.run"]
    assert records[by["body"]].parent == by["sandbox.run"]
    assert records[by["sandbox.run"]].attrs == {"fn": "double"}
    assert counters == {"sandbox.boots": 1}


def test_the_breakdown_is_timed_inside_its_spans(recorder):
    reg = FunctionRegistry()
    reg.register_function("ident", lambda inputs: {"out": list(inputs["x"])})
    ctx, bd, run = cold_start(reg, "ident", {"x": [Item(np.ones(8, np.int32))]})
    run()
    records, counters = tracing.take()
    spans = {r.name: r.end - r.start for r in records}
    phases = {"sandbox.marshal": bd.marshal, "sandbox.load": bd.load,
              "sandbox.transfer": bd.transfer, "sandbox.setup": bd.execute_setup,
              "sandbox.output": bd.output}
    for name, seconds in phases.items():
        assert 0 <= seconds <= spans[name], name
    assert bd.total == pytest.approx(sum(phases.values()))
    assert counters == {"sandbox.boots": 1}
    ctx.free()


def test_fig10_rows_identical_with_the_recorder_on(monkeypatch):
    """The recorder touches no event loop or virtual time: the fig10 rows
    (the byte-identity contract) read the same with it on."""
    import importlib

    monkeypatch.setenv("FIG10_DURATION_S", "30")
    mod = importlib.import_module("benchmarks.fig10_azure_trace")
    ref = mod.run()
    tracing.enable()
    try:
        got = mod.run()
    finally:
        tracing.disable()
        tracing.take()
    assert got == ref
