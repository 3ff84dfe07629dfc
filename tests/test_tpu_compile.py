"""Compile the main path for a TPU v5e that is described, not attached.

The Pallas kernels at hymba-1.5b widths (moe_gmm at olmoe-1b-7b's) and
hymba-1.5b's full-width decode step go through the TPU compiler, which
refuses what interpret mode accepts: blocks that break the (8, 128)
tiling, primitives Mosaic cannot lower, programs that do not fit the
chip. Nothing runs; a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so every worker collects
the same tests and only the one that runs this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention as decode_k
from repro.kernels import flash_attention as flash_k
from repro.kernels import moe_gmm as gmm_k
from repro.kernels import rmsnorm as rms_k
from repro.kernels import ssd_scan as ssd_k
from repro.models.model import build

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]


HYMBA = get_config("hymba-1.5b")
DH = HYMBA.resolved_head_dim
HQ, HKV = HYMBA.num_heads, HYMBA.num_kv_heads
SEQ, SLOTS = 2048, 8


def _rmsnorm(one_chip):
    return (lambda x, s: rms_k.rmsnorm(x, s)), _shapes(
        one_chip, ((SEQ, HYMBA.d_model), jnp.bfloat16), ((HYMBA.d_model,), jnp.bfloat16))


def _flash(window):
    def case(one_chip):
        qkv = [((1, SEQ, h, DH), jnp.bfloat16) for h in (HQ, HKV, HKV)]
        return (lambda q, k, v: flash_k.flash_attention(q, k, v, window=window)), \
            _shapes(one_chip, *qkv)
    return case


def _decode(seq, window):
    def case(one_chip):
        return (lambda q, k, v, sp, cp: decode_k.decode_attention(
            q, k, v, sp, cp, window=window)), _shapes(
            one_chip, ((SLOTS, HQ, DH), jnp.bfloat16),
            ((SLOTS, seq, HKV, DH), jnp.bfloat16), ((SLOTS, seq, HKV, DH), jnp.bfloat16),
            ((SLOTS, seq), jnp.int32), ((SLOTS,), jnp.int32))
    return case


def _ssd(one_chip):
    h, p, n = HYMBA.ssm_heads, HYMBA.ssm_head_dim, HYMBA.ssm_state
    return (lambda x, a, b, c: ssd_k.ssd(x, a, b, c)), _shapes(
        one_chip, ((1, SEQ, h, p), jnp.bfloat16), ((1, SEQ, h), jnp.float32),
        ((1, SEQ, n), jnp.bfloat16), ((1, SEQ, n), jnp.bfloat16))


def _moe_gmm(one_chip):
    olmoe = get_config("olmoe-1b-7b")
    e, d, f = olmoe.num_experts, olmoe.d_model, olmoe.d_ff
    capacity = 320  # 2048 tokens x top-8 / 64 experts x 1.25
    return (lambda xe, we: gmm_k.moe_gmm(xe, we)), _shapes(
        one_chip, ((e, capacity, d), jnp.bfloat16), ((e, d, f), jnp.bfloat16))


@pytest.mark.parametrize("case", [
    _rmsnorm, _flash(0), _flash(HYMBA.sliding_window),
    _decode(SEQ, 0), _decode(HYMBA.sliding_window, HYMBA.sliding_window),
    _ssd, _moe_gmm,
], ids=["rmsnorm", "flash", "flash_window", "decode", "decode_window",
        "ssd", "moe_gmm"])
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = case(one_chip)
    assert "tpu_custom_call" in _compile(fn, *shapes).as_text()


def test_hymba_decode_step_fits_one_v5e(one_chip):
    api = build(HYMBA)
    to_shape = lambda tree: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)
    params = to_shape(api.abstract_params())
    cache = to_shape(api.abstract_cache(SLOTS, SEQ))
    tokens = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    mem = _compile(api.decode_step, params, cache, tokens).memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert api.param_bytes() < mem.argument_size_in_bytes
    assert used < V5E_HBM_BYTES, used
