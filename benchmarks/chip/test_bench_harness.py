"""The chip benchmark's harness on the CPU: traffic, arithmetic, trace
reduction, the manifest, and the refusal to run without a TPU."""
import contextlib
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(HERE)]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import arch  # noqa: E402
import latency_stats  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text()) for c in BENCH["configs"]}
CHAT = json.loads((HERE / "traffic" / "chat.json").read_text())
BATCH = json.loads((HERE / "traffic" / "batch-gen.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# --- traffic ---------------------------------------------------------------
def test_open_loop_replays_one_schedule_and_the_seed_draws_the_ids():
    a = traffic.open_loop(CHAT, 2**33 + 5, 30, 32001)
    b = traffic.open_loop(CHAT, 2**33 + 5, 30, 32001)
    c = traffic.open_loop(CHAT, 2**33 + 6, 30, 32001)
    assert [(r.due, r.max_new, len(r.prompt)) for r in a] == \
        [(r.due, r.max_new, len(r.prompt)) for r in c]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    other = traffic.open_loop(dict(CHAT, schedule_seed=CHAT["schedule_seed"] + 1), 1, 30, 32001)
    assert [r.due for r in other] != [r.due for r in a]


def test_open_loop_lengths_and_arrivals_fill_the_window_in_range():
    reqs = traffic.open_loop(CHAT, 1, 30, 32001)
    assert len(reqs) == round(CHAT["rate_per_s"] * 30)
    assert reqs[0].due == 0.0 and all(0 <= r.due < 30 for r in reqs)
    plens = [len(r.prompt) for r in reqs]
    assert CHAT["prompt"]["min"] <= min(plens) and max(plens) <= CHAT["prompt"]["max"]
    assert all(CHAT["output"]["min"] <= r.max_new <= CHAT["output"]["max"] for r in reqs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 32001 for r in reqs)
    assert np.mean(np.diff([r.due for r in reqs])) == pytest.approx(1 / CHAT["rate_per_s"], rel=0.1)


def test_lognormal_lengths_have_the_stated_median():
    x = traffic.lengths(CHAT["prompt"], 1001)
    assert x[500] == CHAT["prompt"]["median"]
    assert list(x) == sorted(x)


def test_closed_loop_blocks_hold_the_same_work():
    stream = traffic.closed_loop(BATCH, 77, 49152)
    k = BATCH["clients"]
    blocks = [[next(stream) for _ in range(k)] for _ in range(3)]
    keys = [sorted(len(r.prompt) for r in b) for b in blocks]
    assert keys[0] == keys[1] == keys[2]
    outs = [sorted(r.max_new for r in b) for b in blocks]
    assert outs[0] == outs[1] == outs[2]
    assert [r.index for b in blocks for r in b] == list(range(3 * k))


# --- arithmetic ----------------------------------------------------------------
def test_percentiles_count_every_request():
    lat = [100.0] * 8 + [200.0, float("inf")]
    assert latency_stats.percentile(lat, 50) == 100.0
    assert latency_stats.percentile(lat, 90) == float("inf")
    assert latency_stats.percentile(list(range(1, 101)), 90) == pytest.approx(90.1)


def test_tokens_prorated_to_the_window():
    spans = [(0.0, 2.0, 10), (2.0, 4.0, 10), (9.0, 11.0, 20), (12.0, 13.0, 5)]
    # all of the first two, half of the third, none of the fourth
    assert latency_stats.prorated(spans, 0.0, 10.0) == pytest.approx(30.0)
    assert latency_stats.prorated([(5.0, 5.0, 3)], 0.0, 10.0) == 3


@pytest.mark.parametrize("config", list(CONFIGS))
def test_costs_count_the_programs_parameters(config):
    from repro.config import ModelConfig
    from repro.models.model import build

    costs, m = arch.costs(CONFIGS[config]), CONFIGS[config]["model"]
    api = build(ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in m.items()}))
    assert costs.param_count(m) == api.param_count()
    assert costs.param_bytes(m) == api.param_bytes()
    # a decode step moves at least its weights, and more with more context
    assert costs.decode_bytes(m, [10]) < costs.decode_bytes(m, [1000]) < costs.decode_bytes(m, [10, 1000])
    # a one-token prompt is the work of one decode step at position 0
    assert costs.prefill_flops(m, 1) == costs.decode_flops(m, [0]) < costs.decode_flops(m, [1000])
    assert costs.decode_flops(m, [5, 5]) == 2 * costs.decode_flops(m, [5])


# (param_count, param_bytes) and, at each set of positions, (prefill_flops
# of a prompt as long as the last position + 1, decode_flops, decode_bytes),
# as the counts read before a configuration could name its own
POSITIONS = ([0], [10, 1000], [5, 517, 1023, 1024, 2047], list(range(0, 2048, 97)))
COUNTED = {
    "hymba-1.5b": ((1589773120, 3179555840), [
        (3187920000.0, 3187920000.0, 3193986048.0),
        (3191207120000.0, 6582688000.0, 3249785856.0),
        (6651124944000.0, 16694716800.0, 3402730240.0),
        (6617978928000.0, 73643187200.0, 4198809856.0)]),
    "granite-8b-18l": ((4127457280, 8254914560), [
        (8254685184.0, 8254685184.0, 8255062016.0),
        (8007890141184.0, 16807231488.0, 8329674752.0),
        (16699537489920.0, 42634739712.0, 8595980288.0),
        (16614993592320.0, 188211167232.0, 9910181888.0)]),
}


@pytest.mark.parametrize("config", list(COUNTED))
def test_the_counts_reached_by_name_are_the_ones_read_before(config):
    costs, m = arch.costs(CONFIGS[config]), CONFIGS[config]["model"]
    params, per_positions = COUNTED[config]
    assert (costs.param_count(m), costs.param_bytes(m)) == params
    for pos, counted in zip(POSITIONS, per_positions):
        assert (costs.prefill_flops(m, pos[-1] + 1), costs.decode_flops(m, pos),
                costs.decode_bytes(m, pos)) == counted, pos


@pytest.mark.parametrize("config", list(COUNTED))
def test_a_configuration_without_arch_takes_the_default_modules(config):
    conf = CONFIGS[config]
    assert "arch" not in conf and not set(conf["model"]) & {"arch", "smoke"}
    assert arch.path("reference", conf) == HERE / "reference" / "model.py"
    assert arch.path("costs", conf) == HERE / "costs.py"
    assert callable(arch.reference(conf).logits_at) and callable(arch.costs(conf).roofline_s)
    with pytest.raises(ValueError, match="refernce"):
        arch.path("costs", dict(conf, arch={"refernce": "reference/other.py"}))
    # a named module that lies outside the benchmark's directory is refused
    for outside in ("../../src/repro/config/model.py", str(HERE.parent / "chip_other" / "costs.py"),
                    "reference/../../chip_other/costs.py"):
        with pytest.raises(ValueError, match="outside"):
            arch.path("costs", dict(conf, arch={"costs": outside}))
    assert arch.path("costs", dict(conf, arch={"costs": "reference/../costs.py"})) == HERE / "costs.py"


class _Spans:
    last = {}

    def __call__(self, name):
        return contextlib.nullcontext()


class _Tracer:
    def __init__(self, paused_s):
        self.paused_s = paused_s

    def tick(self, t0):
        pass


@pytest.mark.parametrize("paused_s,unserved", [(0.0, 2), (10.0, 0)])
def test_open_loop_waits_a_minute_past_the_close_and_out_the_profilers_pause(
        monkeypatch, paused_s, unserved):
    import bench

    monkeypatch.setattr(bench, "GRACE_S", 0.05)

    def serve(prompt, max_new):
        time.sleep(0.04)
        return [1] * max_new

    reqs = [traffic.Request(i, 0.0, np.zeros(4, np.int32), 2) for i in range(4)]
    _, out, _ = bench.drive_open(serve, _Spans(), reqs, 0.0, _Tracer(paused_s))
    assert sum(r.error is not None for r in out) == unserved
    assert all(r.tokens == [1, 1] for r in out if r.error is None)


# --- trace reduction -------------------------------------------------------------
def _fixture():
    path = HERE / "fixtures" / "tiny_trace.xplane.pb"
    expect = json.loads((HERE / "fixtures" / "tiny_trace.json").read_text())
    return str(path), expect


def test_trace_reduce_on_a_recorded_tpu_trace():
    path, expect = _fixture()
    red = trace_reduce.reduce(path, expect["programs"], expect["spans"])
    for role, runs in expect["runs"].items():
        assert red.programs[role][0] == runs, role
        assert red.per_run_s(role) > 0
    assert 0 < red.busy_s < red.window_s
    assert red.busy_s == pytest.approx(expect["busy_s"], rel=1e-9)
    assert red.window_s == pytest.approx(expect["window_s"], rel=1e-9)
    out = red.breakdown()
    assert 0 < len(out["device_ops"]) <= 10 and 0 < len(out["idle_gaps"]) <= 10
    idle = sum(s for _, s in red.idle)
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)


def test_trace_reduce_names_programs_by_the_span_that_issued_them():
    execs = [(7, 1.0, 0.1), (9, 1.5, 0.01), (9, 1.6, 0.01), (7, 3.0, 0.1), (9, 3.5, 0.01)]
    spans = {"prefill_step": [(0.9, 0.95), (2.9, 2.95)],
             "decode_step": [(1.45, 1.46), (1.55, 1.56), (3.45, 3.46)]}
    roles = trace_reduce._roles(execs, spans, {"prefill": "m", "decode": "m"})
    assert roles == {7: "prefill", 9: "decode"}


def test_module_name_is_the_compiled_programs_own():
    import jax
    import jax.numpy as jnp

    def prefill_like(x):
        return x * 2

    c = jax.jit(prefill_like).lower(jnp.ones(3)).compile()
    assert trace_reduce.module_name(c) == "jit_prefill_like"


# --- the manifest --------------------------------------------------------------
def test_manifest_names_units_and_files():
    b = BENCH
    assert b["command"][1] == "benchmarks/chip/bench.py" and b["paths"] == ["benchmarks/chip"]
    metrics = b["end_to_end"] + b["per_layer"]
    for entry in b["configs"] + b["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert (HERE / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["reduced"] == c["reduced"] and conf["source"] == c["source"]
        for k in c["reduced"]:
            assert not re.search(r"(_dim|_rank|d_model|d_ff|head|state|expand|vocab)", k), k
    names = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in names and all(m["bound"] <= 0.25 for m in b["end_to_end"])
    for m in b["per_layer"]:
        assert m["moves"] in names and "\n" not in m["layer"]
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        e2e = [m["name"] for m in b["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        per = [m for m in b["per_layer"] if w["name"] in m.get("workloads", [])]
        assert per and all(m["moves"] in e2e for m in per)
    assert len(json.dumps(b)) < 64 * 1024


@pytest.mark.parametrize("config", list(CONFIGS))
def test_every_request_of_the_traffic_fits_the_cache(config):
    import bench

    for w in BENCH["workloads"]:
        if w["config"] == config:
            cell = bench.load_cell(ROOT, w["name"])
            assert cell.model["dtype"] == "bfloat16"
            assert cell.config["check"]["served_gap_limit"] > 0


# --- refusal -------------------------------------------------------------------
def test_bench_refuses_a_host_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run([sys.executable, str(HERE / "bench.py"), "--workload", "hymba-chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[-1].startswith("{")
