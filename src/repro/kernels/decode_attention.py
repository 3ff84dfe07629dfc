"""Flash-decode Pallas kernel: one query token vs a (ring) KV cache.

At q_len=1 the MXU would idle on a single query row, so the GQA query
group (G = Hq/Hkv rows) is packed into the sublane dimension: each grid
cell computes a (G, dh) x (dh, kv_block) score tile. The kv dimension is
the innermost grid axis, carried across steps by VMEM scratch (m, l, acc)
- the same online softmax as prefill flash, which is exactly the
"partial softmax + combine" structure flash-decode uses on GPUs, expressed
TPU-natively as a sequentially-revisited grid.

Slot-position masking supports ring buffers (sliding-window caches): a
slot is valid iff ``0 <= slot_pos <= cur_pos`` and, with a window,
``cur_pos - slot_pos < window``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    pos_ref,    # [B] int32, scalar-prefetched into SMEM
    q_ref,      # [1, G, dh]
    k_ref,      # [1, kb, dh]
    v_ref,      # [1, kb, dh]
    slot_ref,   # [1, 1, kb] int32
    o_ref,      # [1, G, dh]
    m_ref,      # scratch [G, 1]
    l_ref,      # scratch [G, 1]
    acc_ref,    # scratch [G, dh]
    *,
    scale: float,
    window: int,
    nk: int,
    hkv: int,
):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                       # [G, dh]
    k = k_ref[0].astype(jnp.float32)                       # [kb, dh]
    v = v_ref[0].astype(jnp.float32)
    slot = slot_ref[0]                                     # [1, kb]
    cur = pos_ref[pl.program_id(0) // hkv]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                              # [G, kb]
    valid = (slot >= 0) & (slot <= cur)
    if window:
        valid &= cur - slot < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l)[None].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "kv_block", "interpret")
)
def decode_attention(
    q: jax.Array,          # [B, Hq, dh]
    k_cache: jax.Array,    # [B, S, Hkv, dh]
    v_cache: jax.Array,
    slot_pos: jax.Array,   # [B, S] int32 (-1 = empty slot)
    cur_pos: jax.Array,    # [B] int32
    *,
    window: int = 0,
    scale: Optional[float] = None,
    kv_block: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, hq, dh = q.shape
    _, s, hkv, _ = k_cache.shape
    assert hq % hkv == 0
    g = hq // hkv
    scale = float(scale if scale is not None else dh**-0.5)

    kb = min(kv_block, s)
    pad = (-s) % kb
    kk = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, dh)
    vv = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, dh)
    sp = slot_pos
    if pad:
        kk = jnp.pad(kk, ((0, 0), (0, pad), (0, 0)))
        vv = jnp.pad(vv, ((0, 0), (0, pad), (0, 0)))
        sp = jnp.pad(slot_pos, ((0, 0), (0, pad)), constant_values=-1)
    # [B, 1, S]: the kv block is the lane axis, the unit axis is whole
    sp_ = sp.astype(jnp.int32)[:, None, :]
    nk = (s + pad) // kb
    qg = q.reshape(b * hkv, g, dh)

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, window=window, nk=nk, hkv=hkv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * hkv, nk),
            in_specs=[
                pl.BlockSpec((1, g, dh), lambda bk, ik, pos: (bk, 0, 0)),
                pl.BlockSpec((1, kb, dh), lambda bk, ik, pos: (bk, ik, 0)),
                pl.BlockSpec((1, kb, dh), lambda bk, ik, pos: (bk, ik, 0)),
                pl.BlockSpec(
                    (1, 1, kb), lambda bk, ik, pos: (bk // hkv, 0, ik)),
            ],
            out_specs=pl.BlockSpec((1, g, dh), lambda bk, ik, pos: (bk, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, dh), q.dtype),
        interpret=interpret,
    )(cur_pos.astype(jnp.int32), qg, kk, vv, sp_)
    return out.reshape(b, hq, dh)
