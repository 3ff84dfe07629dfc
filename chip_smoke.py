#!/usr/bin/env python3
"""Serve hymba-1.5b at full width through the platform on one TPU, and check it.

    python3 chip_smoke.py [--seed N]

Everything runs in this one process, which holds the chip:

1. stop, before anything compiles, unless JAX's first device is a TPU;
2. place JAX's compile cache (``repro.launch.serve.use_compile_cache``);
3. draw bf16 weights from ``--seed``, compile prefill and decode, and serve
   8 requests (prompts of 64 to 1024 tokens, 16 new tokens each) through
   the ``serve_lm`` composition on the platform, with 4 slots and a
   2048-token cache; every request must finish with all 16 tokens;
4. prefill/decode consistency: the last-position logits of prefill(S+1)
   against prefill(S) followed by one decode step, through the served
   executables, within ``tests/test_serving.py``'s tolerances;
5. float32 reference: one prompt's bf16 prefill logits against a float32
   run (highest matmul precision) of the same weights.

The last line of stdout is ``{"ok": true, "device": {...}}``; it is printed
only when every step passed. Any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "hymba-1.5b"
SHAPE = dict(slots=4, cache_len=2048, requests=8, min_prompt=64,
             max_prompt=1024, max_new=16,
             # one length inside the 1024-token sliding window, one past it
             consistency_lens=(257, 1500), f32_len=1024)
# tests/test_serving.py's prefill/decode tolerances
CONSIST_RTOL, CONSIST_ATOL, CONSIST_TOP1 = 5e-2, 5e-1, 0.5
# relative L2 gap of bf16 prefill logits to float32 ones; the CPU rehearsal
# at full width measured 0.008-0.012 for 2 to 8 layers
F32_MAX_REL_L2 = 5e-2


def consistency(batcher, tokens, lens):
    """prefill(S+1) against prefill(S) + decode_step, one row per S."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.batching import insert_slot

    api, params = batcher.api, batcher.params
    full_rows, step_rows = [], []
    for s in lens:
        full, _ = batcher.prefill_step(params, tokens, jnp.asarray([s + 1], jnp.int32))
        _, one = batcher.prefill_step(params, tokens, jnp.asarray([s], jnp.int32))
        cache = insert_slot(api.init_cache(batcher.num_slots, batcher.cache_len),
                            one, 0, batcher.batch_axes)
        nxt = jnp.zeros((batcher.num_slots,), jnp.int32).at[0].set(tokens[0, s])
        step, _ = batcher.decode_step(params, cache, nxt)
        full_rows.append(np.asarray(full[0], np.float32))
        step_rows.append(np.asarray(step[0], np.float32))
    a, b = np.stack(full_rows), np.stack(step_rows)
    top1 = float((a.argmax(-1) == b.argmax(-1)).mean())
    ok = bool(np.isfinite(a).all() and np.isfinite(b).all()
              and np.allclose(a, b, rtol=CONSIST_RTOL, atol=CONSIST_ATOL)
              and top1 >= CONSIST_TOP1)
    return ok, dict(max_abs=float(np.abs(a - b).max()), top1=top1)


def float32_reference(batcher, tokens, plen):
    """bf16 prefill logits against a float32 run of the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    plens = jnp.asarray([plen], jnp.int32)
    low, _ = batcher.prefill_step(batcher.params, tokens, plens)
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), batcher.params)
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        ref_step = jax.jit(batcher.api.prefill).lower(p32, tokens, plens).compile()
        compile_s = time.perf_counter() - t0
        ref, _ = ref_step(p32, tokens, plens)
    a = np.asarray(low[0], np.float64)
    b = np.asarray(ref[0], np.float64)
    del p32, ref_step
    rel = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    ok = bool(np.isfinite(a).all() and np.isfinite(b).all() and rel <= F32_MAX_REL_L2)
    return ok, dict(rel_l2=rel, max_abs=float(np.abs(a - b).max()),
                    top1_same=bool(a.argmax() == b.argmax()), compile_s=compile_s)


def run(cfg, *, seed, slots, cache_len, requests, min_prompt, max_prompt,
        max_new, consistency_lens, f32_len) -> bool:
    """Serve ``cfg`` through the platform and run both checks; prints one
    line per phase and returns whether all of them passed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import CacheEvents, random_prompts, serve
    from repro.models.model import build
    from repro.serving.batching import ContinuousBatcher

    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(seed))
    print(f"model={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"params={api.param_count()} param_bytes={api.param_bytes()}")
    batcher = ContinuousBatcher(api, params, num_slots=slots, cache_len=cache_len)
    prompts = random_prompts(requests, min_prompt, max_prompt, cfg.vocab_size, seed)
    served = serve(batcher, prompts, max_new=max_new)
    print(f"compile_s prefill={served.compile_s['prefill']} "
          f"decode={served.compile_s['decode']} "
          f"cache_hits={served.cache['hits']} cache_misses={served.cache['misses']}")
    n_tok = sum(len(t) for t in served.tokens if t is not None)
    print(f"served {served.n_done}/{len(prompts)} requests, {n_tok} tokens "
          f"(prompts {min(map(len, prompts))}-{max(map(len, prompts))} tokens), "
          f"wall_s={served.wall_s}")
    if not served.ok:
        for line in served.failures():
            print(f"FAILED {line}", file=sys.stderr)
        if served.error:
            print(served.error, file=sys.stderr)
        return False
    short = [i for i, t in enumerate(served.tokens) if len(t) != max_new]
    if short:
        print(f"FAILED requests {short} returned fewer than {max_new} tokens",
              file=sys.stderr)
        return False

    rng = np.random.default_rng(seed + 1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, cache_len)), jnp.int32)
    with CacheEvents() as events:
        ok_c, c = consistency(batcher, tokens, consistency_lens)
        print(f"consistency {'PASS' if ok_c else 'FAIL'} lens={list(consistency_lens)} "
              f"max_abs={c['max_abs']} top1={c['top1']} "
              f"(rtol={CONSIST_RTOL} atol={CONSIST_ATOL} top1>={CONSIST_TOP1})")
        ok_f, f = float32_reference(batcher, tokens, f32_len)
        print(f"float32_reference {'PASS' if ok_f else 'FAIL'} len={f32_len} "
              f"rel_l2={f['rel_l2']} max_abs={f['max_abs']} "
              f"top1_same={f['top1_same']} (rel_l2<={F32_MAX_REL_L2}) "
              f"compile_s={f['compile_s']}")
    hits = served.cache["hits"] + events.counts["hits"]
    print(f"compile_cache hits={hits} misses="
          f"{served.cache['misses'] + events.counts['misses']} hit={hits > 0}")
    return ok_c and ok_f


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found: JAX's first device is "
              f"{dev.platform!r}; this smoke runs on a TPU only",
              file=sys.stderr)
        return 2
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")

    from repro.configs import get_config
    from repro.launch.serve import use_compile_cache

    print(f"compile_cache dir={use_compile_cache()}")
    if not run(get_config(ARCH), seed=args.seed, **SHAPE):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
