#!/usr/bin/env python3
"""Compile a cell's programs for a described, unattached TPU v5e and print
their memory, with no chip: the batcher's prefill and decode steps, the
one-call weight maker, and one layer (``block``) of the configuration's
plain reference (``arch.py``).

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse_tpu_compile.py granite-8b-18l

Nothing runs; a compile that passes is not a chip run. It shows what the
TPU compiler refuses and whether each program fits the chip's memory.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]


def main(config_name: str) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import arch
    import weights
    from repro.config import ModelConfig
    from repro.models.common import is_spec
    from repro.models.model import build

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((HERE / "configs" / f"{config_name}.json").read_text())
    m, serving = conf["model"], conf["serving"]
    cfg = ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in m.items()})
    api = build(cfg)
    chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    params = on_chip(api.abstract_params())
    cache = on_chip(jax.eval_shape(lambda: api.init_cache(serving["slots"], serving["cache_len"])))
    n = serving["cache_len"]
    key = on_chip(jax.eval_shape(lambda: weights.seed_key(1)))
    s_ref = 1100  # longest sample row of the reference: a prompt and its output
    progs = {
        "prefill": (jax.jit(api.prefill), (params, on_chip(jax.ShapeDtypeStruct((1, n), jnp.int32)),
                                           on_chip(jax.ShapeDtypeStruct((1,), jnp.int32)))),
        "decode": (jax.jit(api.decode_step), (params, cache, on_chip(
            jax.ShapeDtypeStruct((serving["slots"],), jnp.int32)))),
        "weights": (jax.jit(weights.params_fn(api.param_template, cfg.dtype, is_leaf=is_spec)),
                    (key,)),
        "reference_layer": (
            jax.jit(functools.partial(arch.reference(conf).block, m=m, numerics="float32")),
            (on_chip(jax.ShapeDtypeStruct((12, s_ref, m["d_model"]), jnp.float32)),
             key, on_chip(jax.ShapeDtypeStruct((), jnp.int32)),
             on_chip(jax.ShapeDtypeStruct((), jnp.int32)))),
    }
    for name, (fn, args) in progs.items():
        t = time.perf_counter()
        mem = fn.lower(*args).compile().memory_analysis()
        print(f"{config_name} {name}: compile_s={time.perf_counter() - t:.1f} "
              f"argument_GB={mem.argument_size_in_bytes / 1e9:.3f} "
              f"output_GB={mem.output_size_in_bytes / 1e9:.3f} "
              f"temp_GB={mem.temp_size_in_bytes / 1e9:.3f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
