"""The ``serve_lm`` composition on one platform node, kept deployed.

It is the composition that ``repro.launch.serve.serve`` builds: one
``generate`` vertex that hands the prompt to the continuous batcher and
runs it to completion. ``serve`` there invokes every prompt up front on
the platform's virtual clock; here the platform is deployed once and
each request is one invocation, driven until it returns.
"""
from __future__ import annotations

import itertools
from typing import Callable, List

import numpy as np

from repro import sdk
from repro.core import Item
from repro.serving.batching import Request


def deploy(batcher, span: Callable) -> Callable[[np.ndarray, int], List[int]]:
    """Deploy ``serve_lm`` over ``batcher``; return ``serve(prompt,
    max_new) -> generated ids``. ``span(name)`` is a context manager that
    records the ``generate`` body."""
    rids = itertools.count()

    def generate_fn(inputs):
        with span("generate"):
            prompt = np.frombuffer(inputs["prompt"][0].data, np.int32).tolist()
            max_new = int(np.frombuffer(inputs["max_new"][0].data, np.int32)[0])
            rid = next(rids)
            batcher.submit(Request(rid, prompt, max_new_tokens=max_new))
            out = batcher.run_to_completion()[rid]
            return {"tokens": [Item(np.asarray(out, np.int32).tobytes())]}

    generate = sdk.declare(
        "generate", generate_fn, inputs=("prompt", "max_new"), outputs=("tokens",),
        context_bytes=8 << 20, memoize=False,
        # knowingly impure: drives the stateful batcher, as the program's
        # own serve_lm does
        pure_unsafe=True,
    )
    with sdk.composition("serve_lm") as app:
        g = generate(prompt=app.input("prompt"), max_new=app.input("max_new"))
        app.output("tokens", g.tokens)
    platform = sdk.Platform(node=sdk.NodeSpec(num_slots=4, comm_slots=1))
    platform.deploy(app)

    def serve(prompt: np.ndarray, max_new: int) -> List[int]:
        h = platform.invoke(app, {
            "prompt": [Item(np.asarray(prompt, np.int32).tobytes())],
            "max_new": [Item(np.asarray([max_new], np.int32).tobytes())],
        })
        out = h.result()
        return np.frombuffer(out["tokens"][0].data, np.int32).tolist()

    return serve
