"""Whole runs of the chip benchmark at smoke size on the CPU, through the
harness's test-only path (``bench.run(..., require_tpu=False)``): a cell
added from data files alone runs end to end, and a run whose timed path is
broken underneath reports ``correct`` false."""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import pytest  # noqa: E402

import arch  # noqa: E402
import bench  # noqa: E402
import smoke_cells  # noqa: E402

SMOKE = [c["smoke"]["name"] for c in smoke_cells.configs()]


@pytest.fixture(scope="module")
def no_cache():
    import jax

    # CPU executables read back from a persistent cache only warn; keep it out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def root(tmp_path_factory, no_cache):
    return smoke_cells.make_root(tmp_path_factory.mktemp("chip"))


def _metric_names(root, cell, key):
    b = json.loads((root / "BENCHMARK.json").read_text())
    return {m["name"] for m in b[key] if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", SMOKE)
def test_a_cell_added_as_data_runs_end_to_end(root, cell):
    r = bench.run(root, cell, 2**33 + 3, 2.0, False, require_tpu=False)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == _metric_names(root, cell, "end_to_end")
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["checks"]["served_gap"]["value"] <= r["checks"]["served_gap"]["limit"]


def _recorded_trace(monkeypatch):
    """A CPU's trace holds no TPU plane: the recorded TPU trace stands in."""
    import trace_reduce

    path = str(HERE / "fixtures" / "tiny_trace.xplane.pb")
    expect = json.loads((HERE / "fixtures" / "tiny_trace.json").read_text())
    reduce = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "find_trace", lambda trace_dir: path)
    monkeypatch.setattr(trace_reduce, "reduce",
                        lambda p, programs, spans: reduce(p, expect["programs"], expect["spans"]))


def test_a_traced_run_reports_the_per_layer_metrics(root, monkeypatch):
    _recorded_trace(monkeypatch)
    r = bench.run(root, "hymba-smoke", 11, 2.0, True, require_tpu=False)
    assert r["correct"] is True
    names = _metric_names(root, "hymba-smoke", "per_layer")
    # no roofline or utilization without the chip's peaks
    assert set(r["metrics"]) == {n for n in names if not n.startswith(("decode_roofline", "mfu"))}
    assert r["metrics"]["batch_occupancy.latency"]["value"] == pytest.approx(100.0 / 8)
    assert r["metrics"]["dispatch_ms"]["value"] > 0
    assert 0 < r["device"]["busy_s"] < r["device"]["window_s"]
    assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]


def _state_unchanged(batcher):
    step = batcher.decode_step

    def decode_step(params, cache, tokens):
        logits, _ = step(params, cache, tokens)
        return logits, cache
    batcher.decode_step = decode_step


def _token_altered(batcher):
    step = batcher.decode_step

    def decode_step(params, cache, tokens):
        logits, cache = step(params, cache, tokens)
        return logits.at[:, 7].add(1e3), cache
    batcher.decode_step = decode_step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
@pytest.mark.parametrize("cell", SMOKE)
def test_a_broken_timed_path_is_not_correct(root, cell, fault):
    r = bench.run(root, cell, 5, 2.0, False, require_tpu=False, fault=fault)
    assert r["correct"] is False
    assert r["checks"]["served_gap"]["value"] > r["checks"]["served_gap"]["limit"]


# a new architecture's reference and counts, as new files: here the
# defaults, each call written to a log beside the file
WRAPPED_REFERENCE = """
from pathlib import Path
from reference import model as _default

def logits_at(*args, **kw):
    with open(Path(__file__).with_name("calls.log"), "a") as f:
        f.write("reference.logits_at\\n")
    return _default.logits_at(*args, **kw)
"""
WRAPPED_COSTS = """
from pathlib import Path
import costs as _default

def _recorded(name):
    def call(*args, **kw):
        with open(Path(__file__).with_name("calls.log"), "a") as f:
            f.write(f"costs.{name}\\n")
        return getattr(_default, name)(*args, **kw)
    return call

for _name in ("param_count", "param_bytes", "prefill_flops", "decode_flops",
              "decode_bytes", "roofline_s"):
    globals()[_name] = _recorded(_name)
"""


def test_a_new_architecture_is_new_files_and_entries(tmp_path, no_cache, monkeypatch):
    root = smoke_cells.make_root(tmp_path)
    bench_dir = root / "benchmarks" / "chip"
    (bench_dir / "newarch").mkdir()
    (bench_dir / "newarch" / "reference.py").write_text(WRAPPED_REFERENCE)
    (bench_dir / "newarch" / "costs.py").write_text(WRAPPED_COSTS)
    like = "hymba-smoke"
    conf = json.loads((bench_dir / "configs" / f"{like}.json").read_text())
    conf.update(name="newarch-smoke",
                arch={"reference": "newarch/reference.py", "costs": "newarch/costs.py"})
    (bench_dir / "configs" / "newarch-smoke.json").write_text(json.dumps(conf))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "newarch-smoke", "source": "smoke size", "reduced": [],
                         "file": "benchmarks/chip/configs/newarch-smoke.json", "why": "CPU test"})
    b["workloads"].append({"name": "newarch-smoke", "config": "newarch-smoke", "traffic": like,
                           "chips": 1, "why": "CPU test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append("newarch-smoke")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    # this host's device stands in for the chip in the table of peaks, so
    # that the roofline and utilization readers have something to read
    peaks = json.loads((bench_dir / "peaks.json").read_text())
    import jax
    peaks["devices"][jax.devices()[0].device_kind] = peaks["devices"]["TPU v5 lite"]
    (bench_dir / "peaks.json").write_text(json.dumps(peaks))
    _recorded_trace(monkeypatch)

    cell = bench.load_cell(root, "newarch-smoke")
    new = bench_dir.resolve() / "newarch"
    assert arch.path("reference", cell.config, cell.bench_dir) == new / "reference.py"
    assert arch.path("costs", cell.config, cell.bench_dir) == new / "costs.py"
    r = bench.run(root, "newarch-smoke", 2**33 + 17, 2.0, True, require_tpu=False)
    assert r["correct"] is True, r["checks"]
    assert {"decode_roofline.latency", "mfu.latency"} <= set(r["metrics"])
    calls = set((bench_dir / "newarch" / "calls.log").read_text().split())
    assert {"reference.logits_at", "costs.decode_flops", "costs.decode_bytes",
            "costs.prefill_flops", "costs.roofline_s"} <= calls
