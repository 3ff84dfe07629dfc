# det-lint: file waive[wall-clock] reason=real-exec CLI driver; wall time measures actual serving steps, not a modeled path
"""Serve a language model through the Dandelion platform.

Each client request is one invocation of a ``serve_lm`` composition
whose single compute vertex, ``generate``, hands the prompt to a
``ContinuousBatcher``: a batch-1 prefill into a free slot of the shared
decode cache, then decode steps until the token budget is spent. The
platform owns admission, memory contexts and engine scheduling; the model
steps are real jitted programs, compiled ahead of time before the first
request arrives.

    PYTHONPATH=src python -m repro.launch.serve --arch hymba-1.5b --smoke \\
        --cache-len 64 --min-prompt 4 --max-prompt 24

Without ``--smoke`` the published (full-width) config is served, which
needs a TPU: on any other backend the command stops before compiling.
It exits 0 only when every request finished; otherwise it names the
requests that did not and prints the payload's exception.
"""
from __future__ import annotations

import argparse
import itertools
import os
import sys
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import sdk
from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.core import Item
from repro.models.model import build
from repro.serving.batching import ContinuousBatcher, Request

REPO_ROOT = Path(__file__).resolve().parents[3]
# fixed, so that each run reads what the previous one wrote; listed in
# the repository's .gitignore
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has read it already and
    nothing is changed here; otherwise the cache goes to
    ``DEFAULT_CACHE_DIR`` inside the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


class CacheEvents:
    """Counts persistent-cache hits and misses while it is registered."""

    def __init__(self):
        self.counts = {"hits": 0, "misses": 0}

    def __call__(self, event: str, **_kw) -> None:
        key = _CACHE_EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1

    def __enter__(self) -> "CacheEvents":
        jax.monitoring.register_event_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_listener(self)


def random_prompts(n: int, lo: int, hi: int, vocab: int, seed: int) -> List[np.ndarray]:
    """``n`` prompts of ``lo``..``hi`` tokens (inclusive), drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, n)
    return [rng.integers(0, vocab, int(k), dtype=np.int32) for k in lens]


@dataclass
class Served:
    """What one ``serve`` call produced, request by request."""

    tokens: List[Optional[List[int]]]   # generated ids; None = not finished
    reasons: List[Optional[str]]        # why a request did not finish
    compile_s: Dict[str, float]
    wall_s: float
    error: Optional[str] = None         # payload exception that stopped the run
    cache: Dict[str, int] = field(default_factory=dict)

    @property
    def n_done(self) -> int:
        return sum(t is not None for t in self.tokens)

    @property
    def ok(self) -> bool:
        return self.error is None and self.n_done == len(self.tokens)

    def failures(self) -> List[str]:
        return [f"request {i}: {r}" for i, r in enumerate(self.reasons) if r]


def serve(batcher: ContinuousBatcher, prompts: Sequence[np.ndarray], *,
          max_new: int) -> Served:
    """Compile the batcher's steps, deploy ``serve_lm`` on a platform, and
    send it one invocation per prompt."""
    with CacheEvents() as events:
        compile_s = batcher.compile()
    rids = itertools.count()

    def generate_fn(inputs):
        prompt = np.frombuffer(inputs["prompt"][0].data, np.int32).tolist()
        rid = next(rids)
        batcher.submit(Request(rid, prompt, max_new_tokens=max_new))
        out = batcher.run_to_completion()[rid]
        return {"tokens": [Item(np.asarray(out, np.int32).tobytes())]}

    generate = sdk.declare(
        "generate", generate_fn, inputs=("prompt",), outputs=("tokens",),
        context_bytes=8 << 20, memoize=False,
        # knowingly impure: drives the stateful continuous batcher and a
        # closed-over request counter — real serving, not a modeled payload
        pure_unsafe=True,
    )
    with sdk.composition("serve_lm") as app:
        g = generate(prompt=app.input("prompt"))
        app.output("tokens", g.tokens)

    platform = sdk.Platform(node=sdk.NodeSpec(num_slots=4, comm_slots=1))
    platform.deploy(app)
    handles = [
        platform.invoke(app, {"prompt": [Item(np.asarray(p, np.int32).tobytes())]},
                        at=i * 1e-3)
        for i, p in enumerate(prompts)
    ]
    error = None
    t0 = time.perf_counter()
    try:
        platform.run()
    except Exception:
        error = traceback.format_exc()
    wall = time.perf_counter() - t0

    tokens, reasons = [], []
    for h in handles:
        done = h.done
        tokens.append(
            np.frombuffer(h.outputs["tokens"][0].data, np.int32).tolist()
            if done else None)
        reasons.append(None if done else (h.failed or "did not finish"))
    return Served(tokens, reasons, compile_s, wall, error, events.counts)


def extras_for(cfg):
    """Prefill extras for the stubbed front ends (audio frames, patches)."""
    if cfg.family == "encdec":
        return lambda rid: {"frames": jnp.zeros((1, 16, cfg.d_model), jnp.bfloat16)}
    if cfg.family == "vlm":
        n = cfg.num_patches or 8
        return lambda rid: {"patches": jnp.zeros((1, n, cfg.d_model), jnp.bfloat16)}
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="hymba-1.5b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced config (runs on the CPU)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=2048)
    ap.add_argument("--min-prompt", type=int, default=64)
    ap.add_argument("--max-prompt", type=int, default=1024)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if not args.smoke and dev.platform != "tpu":
        print(f"error: the full-width {args.arch} config is served on a TPU "
              f"only, and JAX found {dev.platform!r}; pass --smoke for the "
              f"reduced config", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(args.seed))
    print(f"device={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")
    print(f"arch={cfg.name} params={api.param_count()} "
          f"param_bytes={api.param_bytes()}")
    batcher = ContinuousBatcher(api, params, num_slots=args.slots,
                                cache_len=args.cache_len,
                                extras_fn=extras_for(cfg))
    prompts = random_prompts(args.requests, args.min_prompt, args.max_prompt,
                             cfg.vocab_size, args.seed)
    served = serve(batcher, prompts, max_new=args.max_new)
    print(f"compile_s prefill={served.compile_s['prefill']} "
          f"decode={served.compile_s['decode']} cache_dir={cache_dir} "
          f"cache_hits={served.cache['hits']}")
    if not served.ok:
        print(f"FAILED: {served.n_done}/{len(prompts)} requests finished",
              file=sys.stderr)
        for line in served.failures():
            print(f"  {line}", file=sys.stderr)
        if served.error:
            print(served.error, file=sys.stderr)
        return 1
    n_tok = sum(len(t) for t in served.tokens)
    print(f"served {served.n_done}/{len(prompts)} requests, {n_tok} tokens, "
          f"wall_s={served.wall_s}")
    for i, t in enumerate(served.tokens[:3]):
        print(f"  request {i}: {t[:10]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
