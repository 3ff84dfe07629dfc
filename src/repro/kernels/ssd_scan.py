"""Mamba2 SSD (state-space dual) chunked-scan Pallas kernel.

TPU adaptation of the SSD algorithm (Dao & Gu 2024): within a chunk the
recurrence is evaluated in its quadratic dual form - three MXU matmuls on
(chunk x chunk) / (chunk x P) tiles resident in VMEM - while the
inter-chunk state recurrence rides the innermost (sequential) grid
dimension, carrying the [P, N] state in VMEM scratch. Chunk length is the
natural 128 so every matmul dimension is MXU-aligned.

Grid: (B, H, num_chunks). B/C projections are shared across heads
(ngroups=1), expressed through index maps that ignore the head axis.
Inputs follow ``repro.models.ssm.ssd_chunked``: x is dt-weighted, ``a`` is
the per-step log decay. The wrapper moves the head axis ahead of the
sequence (x -> [B, H, S, P], a -> [B, H, 1, S]) so that every block's last
two dims are (chunk, P) or (1, chunk): whole or (8, 128)-aligned, as the
TPU's tiling requires.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(
    x_ref,     # [1, 1, l, P]
    a_ref,     # [1, 1, 1, l]
    b_ref,     # [1, l, N]
    c_ref,     # [1, l, N]
    y_ref,     # [1, 1, l, P]
    hf_ref,    # [1, 1, P, N] final state (written on the last chunk)
    h_ref,     # scratch [P, N] f32
    *,
    nc: int,
):
    ic = pl.program_id(2)
    l = x_ref.shape[2]

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)                # [l, P]
    a = a_ref[0, 0].astype(jnp.float32)                # [1, l]
    bm = b_ref[0].astype(jnp.float32)                  # [l, N]
    cm = c_ref[0].astype(jnp.float32)                  # [l, N]

    # Mosaic has no cumsum: the running sum is a matmul with the
    # lower-triangular ones, taken once as a column and once as a row
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    )
    ones = tri.astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))
    hi = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general(ones, a, nt, precision=hi,
                              preferred_element_type=jnp.float32)  # [l, 1]
    cum_row = jax.lax.dot_general(a, ones, nt, precision=hi,
                                  preferred_element_type=jnp.float32)  # [1, l]
    total = jnp.sum(a, axis=1, keepdims=True)          # [1, 1]
    # segsum: seg[i, j] = cum[i] - cum[j] for j <= i else -inf
    L = jnp.exp(jnp.where(tri, cum - cum_row, NEG_INF))  # [l, l]

    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                  # [l, l]
    y_diag = jax.lax.dot_general(
        L * scores, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                  # [l, P]

    h = h_ref[...]                                     # [P, N]
    y_off = jax.lax.dot_general(
        cm, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(cum)                                   # [l, P]

    decay_states = jnp.exp(total - cum)                # [l, 1]
    state_new = jax.lax.dot_general(
        x * decay_states, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                  # [P, N]
    h_new = h * jnp.exp(total) + state_new
    h_ref[...] = h_new

    y_ref[...] = (y_diag + y_off)[None, None].astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _final():
        hf_ref[...] = h_new[None, None].astype(hf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(
    x: jax.Array,   # [B, S, H, P]  dt-weighted inputs
    a: jax.Array,   # [B, S, H]     log decay
    b: jax.Array,   # [B, S, N]
    c: jax.Array,   # [B, S, N]
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    l = min(chunk, s)
    assert s % l == 0, (s, l)
    nc = s // l

    xt = x.transpose(0, 2, 1, 3)                       # [B, H, S, P]
    at = a.transpose(0, 2, 1)[:, :, None, :]           # [B, H, 1, S]

    y, hf = pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc),
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, l, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, l), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, l, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, l, n), lambda ib, ih, ic: (ib, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, l, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xt, at, b, c)
    return y.transpose(0, 2, 1, 3), hf
