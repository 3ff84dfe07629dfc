"""The plain reference against the served model (``repro.models``) at smoke
sizes on the CPU, and the control: the reference in float8 in the
program's place must read far worse than the program does.

Tolerances, with their reasons:
  * float32 model, prefill: the same weights and the same mathematics in
    float32 on one backend, so the last position's logits agree to
    float32 rounding (2e-5 of their scale);
  * float32 model, decode through the slot cache: the program keeps keys
    and values in a bfloat16 cache whatever the model's type, so the
    logits carry bfloat16 rounding of every cached key and value: a
    relative L2 gap under 2e-2 (0.3-0.4 % measured);
  * bfloat16 model (as served): relative L2 under 5e-2, and greedy tokens
    whose logits lie within 0.05 of the reference's best (the smoke runs
    read under 0.01).
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import arch  # noqa: E402
import check  # noqa: E402
import smoke_cells  # noqa: E402
import weights  # noqa: E402

CONFIGS = {c["name"]: c for c in smoke_cells.configs()}
SEED = 2**32 + 123


def _model(config, dtype):
    """The configuration at the smoke size its file gives."""
    return smoke_cells.model(CONFIGS[config], dtype=dtype)


def _serve(m, prompts, max_new):
    """Serve ``prompts`` through the program's batcher; return the logits it
    computed for each served token, in order, and the served tokens."""
    from repro.config import ModelConfig
    from repro.models.common import is_spec
    from repro.models.model import build
    from repro.serving.batching import ContinuousBatcher, Request

    cfg = ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in m.items()})
    api = build(cfg)
    params = weights.make_params(api.param_template, cfg.dtype, SEED, is_leaf=is_spec)
    b = ContinuousBatcher(api, params, num_slots=4, cache_len=160)
    seen = []
    prefill, decode = b.prefill_step, b.decode_step

    def prefill_step(*a, **k):
        logits, cache = prefill(*a, **k)
        seen.append(np.asarray(logits[0], np.float32))
        return logits, cache

    def decode_step(params, cache, tokens):
        logits, cache = decode(params, cache, tokens)
        seen.append(np.asarray(logits[0], np.float32))
        return logits, cache

    b.prefill_step, b.decode_step = prefill_step, decode_step
    out = []
    for i, p in enumerate(prompts):
        seen.clear()
        b.submit(Request(i, list(p), max_new_tokens=max_new))
        toks = b.run_to_completion()[i]
        out.append((np.stack(seen[:len(toks)]), np.asarray(toks)))
    return out


def _reference(config, m, prompts, served, numerics="float32"):
    """The configuration's own reference (``arch.py``) at ``prompts`` with
    their served tokens."""
    seqs = [np.concatenate([p, t[:-1]]).astype(np.int32) for p, t in zip(prompts, served)]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(t)) for p, t in zip(prompts, served)]
    return arch.reference(CONFIGS[config]).logits_at(m, SEED, seqs, rows, numerics)


PROMPTS = [np.random.default_rng(i).integers(0, 256, n).astype(np.int32)
           for i, n in enumerate((5, 40, 70))]  # inside and past the 32-token window


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("config", list(CONFIGS))
def test_float32_model_matches_the_reference(config):
    m = _model(config, "float32")
    served = _serve(m, PROMPTS, 12)
    for p, (logits, toks) in zip(PROMPTS, served):
        ref = _reference(config, m, [p], [toks])
        scale = np.abs(ref).max()
        assert np.abs(logits[0] - ref[0]).max() <= 2e-5 * scale       # prefill
        assert _rel(logits[1:], ref[1:]) < 2e-2                        # decode via the cache


@pytest.mark.parametrize("config", list(CONFIGS))
def test_served_bfloat16_model_matches_the_reference(config):
    m = _model(config, "bfloat16")
    served = _serve(m, PROMPTS, 12)
    for p, (logits, toks) in zip(PROMPTS, served):
        ref = _reference(config, m, [p], [toks])
        assert _rel(logits, ref) < 5e-2
        assert check.gaps(ref, toks).max() < 0.05


@pytest.mark.parametrize("config", list(CONFIGS))
def test_the_control_reads_far_worse_than_the_program(config):
    m = _model(config, "bfloat16")
    served = _serve(m, PROMPTS, 24)
    toks = [t for _, t in served]
    ref = _reference(config, m, PROMPTS, toks)
    low = _reference(config, m, PROMPTS, toks, "float8_e4m3fn")
    program = check.gaps(ref, np.concatenate(toks)).max()
    control = check.gaps(ref, low.argmax(-1)).max()
    assert control >= 3 * max(program, 1e-3), (program, control)


def test_weights_depend_on_the_seed_name_and_layer_only():
    key = weights.seed_key(SEED)
    a = weights.draw_layer(key, "blocks/attn/wq", 1, (8, 4), "normal")
    b = weights.draw_layer(key, "blocks/attn/wq", 1, (8, 4), "normal")
    c = weights.draw_layer(key, "blocks/attn/wq", 2, (8, 4), "normal")
    d = weights.draw_layer(weights.seed_key(SEED + 1), "blocks/attn/wq", 1, (8, 4), "normal")
    assert np.array_equal(a, b) and not np.array_equal(a, c) and not np.array_equal(a, d)
    assert float(np.abs(a).max()) <= 2.0 / np.sqrt(8) + 1e-6


def _program(m):
    from repro.config import ModelConfig
    from repro.models.model import build

    return build(ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in m.items()}))


def _leaf_by_leaf(template, dtype, seed):
    """Every leaf drawn alone: one stacked layer at a time, as the parent
    drew every leaf, or, for an expert leaf, one expert of one layer at a
    time from the layer's key folded with the expert's index."""
    from repro.models.common import is_spec

    key = weights.seed_key(seed)
    out = {}
    for name, spec in weights._leaves(template, is_spec):
        dt = jax.numpy.dtype(spec.dtype or dtype)
        if tuple(spec.axes[:2]) == ("layers", "experts"):
            layer_key = lambda l: jax.random.fold_in(weights.leaf_key(key, name), l)
            out[name] = np.stack([np.stack([
                np.asarray(weights.draw(jax.random.fold_in(layer_key(l), e), spec.shape[2:],
                                        spec.init).astype(dt))
                for e in range(spec.shape[1])]) for l in range(spec.shape[0])])
        elif spec.axes and spec.axes[0] == "layers":
            out[name] = jax.lax.map(
                lambda l: weights.draw_layer(key, name, l, spec.shape[1:], spec.init).astype(dt),
                jax.numpy.arange(spec.shape[0]))
        else:
            out[name] = weights.draw_whole(key, name, spec.shape, spec.init).astype(dt)
    return out


def _moe(num_experts):
    return dict(name="moe-draw", family="moe", num_layers=2, d_model=32, num_heads=4,
                num_kv_heads=2, d_ff=24, vocab_size=64, num_experts=num_experts,
                experts_per_token=2, dtype="float32")


# every configuration the benchmark lists, and the program's expert family
# at a small size, whose expert leaves take the per-expert path
DRAWN = {**{c: lambda c=c: _model(c, "bfloat16") for c in CONFIGS},
         "moe-draw": lambda: dict(_moe(4), dtype="bfloat16")}


@pytest.mark.parametrize("config", list(DRAWN))
def test_leaves_without_experts_draw_the_same_bits_leaf_by_leaf(config):
    from repro.models.common import is_spec

    m = DRAWN[config]()
    template = _program(m).param_template
    made = weights.make_params(template, m["dtype"], SEED, is_leaf=is_spec)
    got = dict(weights._leaves(made, lambda x: isinstance(x, jax.Array)))
    want = _leaf_by_leaf(template, m["dtype"], SEED)
    assert set(got) == set(want)
    kinds = {tuple(spec.axes[:2]) for _, spec in weights._leaves(template, is_spec)}
    assert ("layers", "embed") in kinds  # stacked leaves without experts are compared
    if config == "moe-draw":
        assert ("layers", "experts") in kinds
    for name, arr in want.items():
        assert got[name].dtype == arr.dtype
        assert np.array_equal(np.asarray(got[name]).view(np.uint8),
                              np.asarray(arr).view(np.uint8)), name


def test_a_held_share_of_experts_draws_the_first_experts_of_the_whole_layer():
    from repro.models.common import is_spec

    params = {e: weights.make_params(_program(_moe(e)).param_template, "float32", SEED,
                                     is_leaf=is_spec)["blocks"]["moe"] for e in (4, 8)}
    key = weights.seed_key(SEED)
    for leaf, shape in (("w_gate", (32, 24)), ("w_down", (24, 32))):
        few, many = np.asarray(params[4][leaf]), np.asarray(params[8][leaf])
        assert few.shape[:2] == (2, 4) and many.shape[:2] == (2, 8)
        assert np.array_equal(few, many[:, :4]), leaf
        one = weights.draw_expert(key, f"blocks/moe/{leaf}", 1, 6, shape, "normal")
        assert np.array_equal(many[1, 6], np.asarray(one)), leaf
        assert not np.array_equal(many[1, 6], many[1, 5]) and not np.array_equal(many[0, 6], many[1, 6])
    # the router routes over every expert: its width is the count, not a share
    assert params[8]["router"].shape == (2, 32, 8)


def test_one_expert_is_scaled_by_its_own_fan_in():
    d, f = 512, 256
    x = np.asarray(weights.draw_expert(weights.seed_key(SEED), "blocks/moe/w_up", 0, 3,
                                       (d, f), "normal"))
    # a normal truncated at two standard deviations keeps 0.88 of its spread
    assert 0.86 < x.std() * np.sqrt(d) < 0.90
