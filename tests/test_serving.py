"""Serving: prefill-vs-decode consistency, continuous batching and its
spans and counters, and the platform entry point (``repro.launch.serve``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke
from repro.core import tracing
from repro.launch import serve as serve_lm
from repro.models.model import build
from repro.serving.batching import ContinuousBatcher, Request, _batch_axis_tree, insert_slot
from repro.serving.engine import generate

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


def _greedy_alone(api, params, req, num_slots, cache_len):
    """``req`` served alone by a plain synchronous greedy loop over the
    model's prefill and decode step: a batch-1 prefill put into slot 0 of a
    ``num_slots``-slot cache, then one decode step at a time, each token read
    on the host before the next step runs."""
    p = req.prompt
    logits, one = jax.jit(api.prefill)(
        params, jnp.asarray([p + [0] * (cache_len - len(p))], jnp.int32),
        jnp.asarray([len(p)], jnp.int32))
    cache = insert_slot(api.init_cache(num_slots, cache_len), one, 0,
                        _batch_axis_tree(api, num_slots, cache_len))
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < req.max_new_tokens and out[-1] != req.eos_id:
        tokens = np.zeros((num_slots,), np.int32)
        tokens[0] = out[-1]
        logits, cache = jax.jit(api.decode_step)(params, cache, jnp.asarray(tokens))
        out.append(int(jnp.argmax(logits[0])))
    return out


@pytest.mark.parametrize("eos", [False, True], ids=["budget", "eos"])
@pytest.mark.parametrize("arch", ["granite-8b", "hymba-1.5b", "mamba2-130m"])
def test_overlapped_batcher_serves_the_synchronous_greedy_tokens(arch, eos):
    """The batcher runs one step ahead of the host; what it serves is still
    what a synchronous greedy loop serves. Five requests share two slots, so
    slots are re-admitted while the step that freed them is in flight; one
    request asks for a single token. With ``eos``, request 0 stops at the
    first of its third token, and request 2 at its first token, an EOS that
    the batcher sees only after the next step is queued."""
    cfg = get_smoke(arch)
    api = build(cfg)
    params = api.init_params(RNG)
    slots, cache_len = 2, 24
    prompts = [[5, 9, 2, 7], [1, 2, 3], [11, 4, 8, 15, 16], [3, 3], [7, 1, 8, 2, 8, 1]]
    reqs = [Request(rid, p, max_new_tokens=n)
            for rid, (p, n) in enumerate(zip(prompts, [4, 1, 6, 2, 5]))]
    if eos:
        reqs[0].eos_id = _greedy_alone(api, params, reqs[0], slots, cache_len)[2]
        reqs[2].eos_id = _greedy_alone(api, params, reqs[2], slots, cache_len)[0]
    want = {r.rid: _greedy_alone(api, params, r, slots, cache_len) for r in reqs}
    if eos:
        assert len(want[0]) <= 3 and want[2] == [reqs[2].eos_id]
    batcher = ContinuousBatcher(api, params, num_slots=slots, cache_len=cache_len)
    for r in reqs:
        batcher.submit(r)
    assert batcher.run_to_completion() == want
    assert all(r.done for r in reqs)


def _serve_batch(api, params, prompts, max_new, traced, eos=None):
    """Serve ``prompts`` through a 2-slot batcher; with ``traced`` the
    recorder is on, and its records and counters are returned too."""
    batcher = ContinuousBatcher(api, params, num_slots=2, cache_len=16)
    for rid, p in enumerate(prompts):
        batcher.submit(Request(rid, p, max_new_tokens=max_new[rid],
                               eos_id=(eos or {}).get(rid, -1)))
    if traced:
        tracing.enable()
    try:
        results = batcher.run_to_completion()
    finally:
        tracing.disable()
    return results, tracing.take()


def test_batcher_counters_equal_what_it_served():
    cfg = get_smoke("mamba2-130m")
    api = build(cfg)
    params = api.init_params(RNG)
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6], [5, 3], [5, 8, 9, 7, 9, 3, 2]]
    max_new = [4, 2, 5, 3]
    plain, _ = _serve_batch(api, params, prompts, max_new, traced=False)
    results, (records, counters) = _serve_batch(api, params, prompts, max_new, traced=True)
    assert results == plain
    steps = [r for r in records if r.name == "batcher.step"]
    # one copy per prefill and per step (nine); each but the last step's is
    # made behind a later program, the last once nothing is left to queue
    assert counters == {
        "batcher.prefills": 4,
        "batcher.prefill_tokens": sum(map(len, prompts)),
        "batcher.prefill_padded_tokens": 4 * 16,
        "batcher.decode_steps": len(steps),
        "batcher.decode_slot_steps": sum(len(r.attrs["rids"]) for r in steps),
        "batcher.syncs": 4 + 5,
        "batcher.syncs_behind": 8,
    }
    # every request's first token comes from its prefill, each later one
    # from a step that listed it
    for rid, p in enumerate(prompts):
        assert sum(rid in r.attrs["rids"] for r in steps) == max_new[rid] - 1


def test_batcher_spans_nest_as_admit_and_step():
    cfg = get_smoke("mamba2-130m")
    api = build(cfg)
    params = api.init_params(RNG)
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6], [5, 3]]
    _, (records, _) = _serve_batch(api, params, prompts, [3, 2, 2], traced=True)
    children = {}
    for r in records:
        children.setdefault(r.parent, []).append(r.name)
    admits = [i for i, r in enumerate(records) if r.name == "batcher.admit"]
    steps = [i for i, r in enumerate(records) if r.name == "batcher.step"]
    assert [records[i].attrs for i in admits] == [
        {"rid": 0, "slot": 0, "prompt_len": 3}, {"rid": 1, "slot": 1, "prompt_len": 5},
        {"rid": 2, "slot": 1, "prompt_len": 2}]   # rid 1 finished first
    assert all(records[i].parent == -1 for i in admits + steps)
    # the first tokens stay on the device: admission holds no copy
    assert all(children[i] == ["batcher.prefill", "batcher.insert_slot"] for i in admits)
    assert all(children[i] == ["batcher.decode", "batcher.sync", "batcher.sync"]
               for i in steps)
    assert children[-1] == ["batcher.admit", "batcher.admit", "batcher.step",
                            "batcher.admit", "batcher.step", "batcher.sync"]
    # each step copies what was queued before it: the two first tokens, then
    # step 1 and rid 2's first token; step 2's tokens come once nothing is
    # left to queue, so that copy alone is not behind a later program
    syncs = [(r.attrs["rids"], r.attrs["behind"]) for r in records if r.name == "batcher.sync"]
    assert syncs == [([0], True), ([1], True), ([0, 1], True), ([2], True), ([0, 2], False)]


def test_batcher_counts_copies_behind_and_discarded_slot_steps():
    """Two slots; budgets 4, 2, 5, 3; rid 2's first token is its EOS.
    Hand count, a copy per line (B: behind a later program):
      step 1 (rids 0, 1; rid 1 done by budget): copy P0 B, P1 B
      step 2 (rids 0, 2): copy step 1 B, P2 B - rid 2's EOS, its slot is freed
      step 3 (rids 0, 3; rid 0 done): copy step 2 B - rid 2's token discarded; P3 B
      step 4 (rid 3; done): copy step 3 B
      nothing to queue: copy step 4, not behind."""
    cfg = get_smoke("mamba2-130m")
    api = build(cfg)
    params = api.init_params(RNG)
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6], [5, 3], [5, 8, 9, 7, 9, 3, 2]]
    max_new = [4, 2, 5, 3]
    plain, _ = _serve_batch(api, params, prompts, max_new, traced=False)
    eos = {2: plain[2][0]}
    results, (records, counters) = _serve_batch(api, params, prompts, max_new, traced=True,
                                                eos=eos)
    assert results == {**plain, 2: plain[2][:1]}
    assert [r.attrs["rids"] for r in records if r.name == "batcher.step"] == [
        [0, 1], [0, 2], [0, 3], [3]]
    assert counters["batcher.syncs"] == 8
    assert counters["batcher.syncs_behind"] == 7
    assert counters["batcher.discarded_slot_steps"] == 1
    assert counters["batcher.decode_slot_steps"] == 7


def test_batcher_programs_are_named_prefill_and_decode_step():
    cfg = get_smoke("mamba2-130m")
    api = build(cfg)
    batcher = ContinuousBatcher(api, api.init_params(RNG), num_slots=2, cache_len=16)
    batcher.compile()
    names = [step.runtime_executable().hlo_modules()[0].name
             for step in (batcher.prefill_step, batcher.decode_step)]
    assert names == ["jit_prefill", "jit_decode_step"]


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
