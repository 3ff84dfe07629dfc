"""Serving: prefill-vs-decode consistency, continuous batching, the
platform entry point (``repro.launch.serve``), and the trace-capture shim
that calibrates the platform's batch-step model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke
from repro.launch import serve as serve_lm
from repro.models.model import build
from repro.serving.batching import ContinuousBatcher, Request
from repro.serving.engine import generate
from repro.serving.trace_capture import (
    calibrated_batch_model,
    calibration_residuals,
    capture_step_timings,
    fit_affine,
)

RNG = jax.random.PRNGKey(0)


@pytest.mark.parametrize(
    "arch", ["granite-8b", "qwen2.5-32b", "mamba2-130m", "olmoe-1b-7b", "hymba-1.5b"])
def test_prefill_decode_consistency(arch):
    """Logits from decode steps must match teacher-forced prefill logits.

    Prefill(t[0:n]) gives cache+logits for position n-1; decode_step with
    token t[n] must produce (approximately) the logits a fresh prefill of
    t[0:n+1] would give at its last position.
    """
    cfg = get_smoke(arch)
    api = build(cfg)
    params = api.init_params(RNG)
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S + 1), 0, cfg.vocab_size)

    # reference: prefill the full S+1 prompt
    full_logits, _ = jax.jit(api.prefill)(
        params, tokens, jnp.full((B,), S + 1, jnp.int32)
    )
    # candidate: prefill S (padded to S+1 width), then decode token S
    plens = jnp.full((B,), S, jnp.int32)
    _, cache = jax.jit(api.prefill)(params, tokens, plens)  # pads ignored via plens
    step_logits, _ = jax.jit(api.decode_step)(params, cache, tokens[:, S])

    a = np.asarray(full_logits, np.float32)
    b = np.asarray(step_logits, np.float32)
    # compare top-1 and logit values (bf16 accumulation tolerance)
    np.testing.assert_allclose(a, b, rtol=5e-2, atol=5e-1)
    assert (np.argmax(a, -1) == np.argmax(b, -1)).mean() >= 0.5


@pytest.mark.parametrize("s", [10, 40])
def test_prefill_padded_past_sliding_window(s):
    """Prompts padded to a cache longer than the sliding window keep the
    window that ends at their last real token, not at the padded width."""
    cfg = get_smoke("hymba-1.5b")
    api = build(cfg)
    params = api.init_params(RNG)
    width = 2 * cfg.sliding_window
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, width), 0, cfg.vocab_size)
    prefill = jax.jit(api.prefill)
    full_logits, _ = prefill(params, tokens, jnp.full((2,), s + 1, jnp.int32))
    _, cache = prefill(params, tokens, jnp.full((2,), s, jnp.int32))
    step_logits, _ = jax.jit(api.decode_step)(params, cache, tokens[:, s])
    a = np.asarray(full_logits, np.float32)
    b = np.asarray(step_logits, np.float32)
    np.testing.assert_allclose(a, b, rtol=5e-2, atol=5e-2)
    assert (np.argmax(a, -1) == np.argmax(b, -1)).all()


def test_continuous_batcher_matches_sequential_generate():
    cfg = get_smoke("granite-8b")
    api = build(cfg)
    params = api.init_params(RNG)
    cache_len, max_new = 24, 6
    prompts = [
        [5, 9, 2, 7], [1, 2, 3], [11, 4, 8, 15, 16],
    ]
    batcher = ContinuousBatcher(api, params, num_slots=2, cache_len=cache_len)
    for rid, p in enumerate(prompts):
        batcher.submit(Request(rid, p, max_new_tokens=max_new))
    results = batcher.run_to_completion()
    assert sorted(results) == [0, 1, 2]
    assert all(len(v) == max_new for v in results.values())

    # sequential reference per request (greedy): same tokens
    for rid, p in enumerate(prompts):
        toks = jnp.asarray([p + [0] * (cache_len - len(p))], jnp.int32)
        plen = jnp.asarray([len(p)], jnp.int32)
        seq = generate(api, params, toks, plen, max_new)
        want = np.asarray(seq[0]).tolist()
        assert results[rid] == want, f"req {rid}: {results[rid]} != {want}"


def test_trace_capture_calibrates_batch_model():
    """Real jitted step timings fit the platform's BatchStepModel shape:
    the calibrated model reproduces the measured affine decode curve."""
    cfg = get_smoke("mamba2-130m")
    api = build(cfg)
    params = api.init_params(RNG)
    timings = capture_step_timings(
        api, params, batches=(1, 2), cache_len=16, prompt_len=4, samples=2,
    )
    assert [t.batch for t in timings] == [1, 2]
    assert all(t.prefill_s > 0 and t.decode_s > 0 for t in timings)
    fixed, per_seq = fit_affine(timings)
    model = calibrated_batch_model(timings)
    assert model.step_s(1) == pytest.approx(fixed + per_seq)
    assert model.step_s(2) == pytest.approx(fixed + 2 * per_seq)
    # batching a calibrated model never beats per-sequence linearity
    assert model.step_s(4) <= 4 * model.step_s(1) + 1e-12
    # the residual report scores the fit through the vectorized pricing
    # path; a 2-point affine fit of 2 points is (near) exact unless the
    # lstsq clamp to nonnegative coefficients kicked in
    res = calibration_residuals(timings, model)
    assert [b for b, _ in res] == [1, 2]
    if fixed > 0 and per_seq > 0:
        assert all(abs(r) < 1e-6 for _, r in res)


def test_batcher_frees_slots_and_admits_waiting():
    cfg = get_smoke("mamba2-130m")
    api = build(cfg)
    params = api.init_params(RNG)
    batcher = ContinuousBatcher(api, params, num_slots=2, cache_len=16)
    for rid in range(5):  # more requests than slots
        batcher.submit(Request(rid, [1 + rid, 2, 3], max_new_tokens=3))
    results = batcher.run_to_completion()
    assert sorted(results) == [0, 1, 2, 3, 4]
    assert all(len(v) == 3 for v in results.values())


SERVE_SMOKE = ["--smoke", "--arch", "hymba-1.5b", "--requests", "3",
               "--cache-len", "48", "--min-prompt", "4", "--max-prompt", "40",
               "--max-new", "4"]


def test_serve_runs_every_request_through_the_platform():
    cfg = get_smoke("hymba-1.5b")
    api = build(cfg)
    params = api.init_params(RNG)
    batcher = ContinuousBatcher(api, params, num_slots=2, cache_len=48)
    prompts = serve_lm.random_prompts(5, 4, 40, cfg.vocab_size, seed=1)
    assert [len(p) for p in prompts] == [
        len(p) for p in serve_lm.random_prompts(5, 4, 40, cfg.vocab_size, seed=1)]
    served = serve_lm.serve(batcher, prompts, max_new=4)
    assert served.ok and served.n_done == 5 and not served.failures()
    assert set(served.compile_s) == {"prefill", "decode"}
    # the platform's generate vertex returns what the batcher generated
    for p, toks in zip(prompts, served.tokens):
        padded = jnp.asarray([p.tolist() + [0] * (48 - len(p))], jnp.int32)
        want = generate(api, params, padded, jnp.asarray([len(p)], jnp.int32), 4)
        assert toks == np.asarray(want[0]).tolist()


def test_serve_main_exits_nonzero_with_the_payload_exception(
        monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def boom(self, max_steps=10_000):
        raise RuntimeError("decode exploded")

    monkeypatch.setattr(ContinuousBatcher, "run_to_completion", boom)
    assert serve_lm.main(SERVE_SMOKE) == 1
    out, err = capsys.readouterr()
    assert "served" not in out
    assert "0/3 requests finished" in err and "decode exploded" in err


def test_serve_main_serves_smoke_config(monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve_lm.main(SERVE_SMOKE) == 0
    out = capsys.readouterr().out
    assert "served 3/3 requests, 12 tokens" in out
    assert f"cache_dir={tmp_path}" in out


def test_serve_main_refuses_full_width_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert serve_lm.main(["--arch", "hymba-1.5b"]) == 2
    assert "TPU" in capsys.readouterr().err


def test_compile_cache_placement(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve_lm.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = serve_lm.use_compile_cache()
        assert path == str(serve_lm.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (serve_lm.REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
