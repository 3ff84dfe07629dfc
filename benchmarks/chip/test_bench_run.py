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

import bench  # noqa: E402
import smoke_cells  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax

    # CPU executables read back from a persistent cache only warn; keep it out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield smoke_cells.make_root(tmp_path_factory.mktemp("chip"))
    jax.config.update("jax_enable_compilation_cache", enabled)


def _metric_names(root, cell, key):
    b = json.loads((root / "BENCHMARK.json").read_text())
    return {m["name"] for m in b[key] if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", ["hymba-smoke", "granite-smoke"])
def test_a_cell_added_as_data_runs_end_to_end(root, cell):
    r = bench.run(root, cell, 2**33 + 3, 2.0, False, require_tpu=False)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == _metric_names(root, cell, "end_to_end")
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["checks"]["served_gap"]["value"] <= r["checks"]["served_gap"]["limit"]


def test_a_traced_run_reports_the_per_layer_metrics(root, monkeypatch):
    import trace_reduce

    # a CPU's trace holds no TPU plane: the recorded TPU trace stands in
    path = str(HERE / "fixtures" / "tiny_trace.xplane.pb")
    expect = json.loads((HERE / "fixtures" / "tiny_trace.json").read_text())
    reduce = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "find_trace", lambda trace_dir: path)
    monkeypatch.setattr(trace_reduce, "reduce",
                        lambda p, programs, spans: reduce(p, expect["programs"], expect["spans"]))
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
@pytest.mark.parametrize("cell", ["hymba-smoke", "granite-smoke"])
def test_a_broken_timed_path_is_not_correct(root, cell, fault):
    r = bench.run(root, cell, 5, 2.0, False, require_tpu=False, fault=fault)
    assert r["correct"] is False
    assert r["checks"]["served_gap"]["value"] > r["checks"]["served_gap"]["limit"]
