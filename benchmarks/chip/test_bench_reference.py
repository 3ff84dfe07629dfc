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

import check  # noqa: E402
import smoke_cells  # noqa: E402
import weights  # noqa: E402
from reference import model as reference  # noqa: E402

BASE = {
    "hymba-1.5b": dict(name="hymba-smoke", family="hybrid", ssm_expand=2, ssm_conv_dim=4,
                       tie_embeddings=True, rope_theta=10000.0, norm_eps=1e-5),
    "granite-8b-18l": dict(name="granite-smoke", family="dense", tie_embeddings=True,
                           rope_theta=10000.0, norm_eps=1e-5),
}
SEED = 2**32 + 123


def _model(config, dtype):
    return dict(BASE[config], **smoke_cells.SMALL[config], dtype=dtype)


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


def _reference(m, prompts, served, numerics="float32"):
    seqs = [np.concatenate([p, t[:-1]]).astype(np.int32) for p, t in zip(prompts, served)]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(t)) for p, t in zip(prompts, served)]
    return reference.logits_at(m, SEED, seqs, rows, numerics)


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


@pytest.mark.parametrize("config", ["hymba-1.5b", "granite-8b-18l"])
def test_float32_model_matches_the_reference(config):
    m = _model(config, "float32")
    served = _serve(m, PROMPTS, 12)
    for p, (logits, toks) in zip(PROMPTS, served):
        ref = _reference(m, [p], [toks])
        scale = np.abs(ref).max()
        assert np.abs(logits[0] - ref[0]).max() <= 2e-5 * scale       # prefill
        assert _rel(logits[1:], ref[1:]) < 2e-2                        # decode via the cache


@pytest.mark.parametrize("config", ["hymba-1.5b", "granite-8b-18l"])
def test_served_bfloat16_model_matches_the_reference(config):
    m = _model(config, "bfloat16")
    served = _serve(m, PROMPTS, 12)
    for p, (logits, toks) in zip(PROMPTS, served):
        ref = _reference(m, [p], [toks])
        assert _rel(logits, ref) < 5e-2
        assert check.gaps(ref, toks).max() < 0.05


@pytest.mark.parametrize("config", ["hymba-1.5b", "granite-8b-18l"])
def test_the_control_reads_far_worse_than_the_program(config):
    m = _model(config, "bfloat16")
    served = _serve(m, PROMPTS, 24)
    toks = [t for _, t in served]
    ref = _reference(m, PROMPTS, toks)
    low = _reference(m, PROMPTS, toks, "float8_e4m3fn")
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
