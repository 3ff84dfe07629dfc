"""A plain forward pass of the dense and the hybrid (Hymba) decoder: the
default reference of ``arch.py``. A reference module offers
``logits_at(m, seed, seqs, rows, numerics, shape, timings)`` and
``block(h, key, l, window, m, numerics)``, one layer, as below.

Written from the published descriptions in straightforward ``jax.numpy``,
one layer at a time, at full length with no cache, no padding tricks and
no kernels; it imports nothing of the program under test. Its weights are
drawn from the seed by ``weights.draw_layer`` under the names the served
model gives them, rounded to the type they are served in, then held in
float32.

Dense block (Llama-style, as granite-8b):
    h += Attn(RMSNorm(h));  h += SwiGLU(RMSNorm(h))
Hybrid block (Hymba, arXiv:2411.13676, as this repository serves it):
    x = RMSNorm(h)
    h += 0.5 * (RMSNorm(Attn(x)) + RMSNorm(SSM(x)))
    h += SwiGLU(RMSNorm(h))
Attention is causal grouped-query attention with split-half rotary
embeddings; in a hybrid model every layer but the global ones sees only
its last ``sliding_window`` positions. The SSM is Mamba-2 with one group:
a causal depthwise convolution, then the recurrence
    s_t = exp(dt_t * A) s_{t-1} + dt_t x_t B_t^T,   y_t = s_t C_t + D x_t
taken one position after another, gated by silu(z) and normalised.
Departures from the published Hymba: no meta tokens and no key-value
sharing across layers, because the served model has neither.

``numerics`` chooses how matrix products are computed: "float32" at the
highest precision (the reference), or "float8_e4m3fn", each operand
scaled to its range (per tensor for weights, per row for activations) and
rounded to 8 bits before an exact product (the control).
"""
from __future__ import annotations

import functools
import math
import time
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights

HIGHEST = jax.lax.Precision.HIGHEST
F32_LEAVES = ("ssm/A_log", "ssm/D", "ssm/dt_bias")  # served in float32


def _quant(x, axis, dtype):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _mm(x, w, numerics):
    """x [..., k] @ w [k, m]."""
    if numerics != "float32":
        dt = jnp.dtype(numerics)
        x = _quant(x, -1, dt)
        w = _quant(w, None, dt)
    return jnp.einsum("...k,km->...m", x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [n, S, heads, dh], rotated by position (split halves)."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv          # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(x, w, m, window, numerics):
    n, s, _ = x.shape
    hq, hk, dh = m["num_heads"], m["num_kv_heads"], _head_dim(m)
    q = _mm(x, w["attn/wq"], numerics)
    k = _mm(x, w["attn/wk"], numerics)
    v = _mm(x, w["attn/wv"], numerics)
    if m.get("qkv_bias"):
        q, k, v = q + w["attn/bq"], k + w["attn/bk"], v + w["attn/bv"]
    q = _rope(q.reshape(n, s, hq, dh), m["rope_theta"])
    k = _rope(k.reshape(n, s, hk, dh), m["rope_theta"])
    v = v.reshape(n, s, hk, dh)
    q = q.reshape(n, s, hk, hq // hk, dh)
    scores = jnp.einsum("nqkgd,nskd->nkgqs", q, k, precision=HIGHEST) / math.sqrt(dh)
    qp, kp = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    allowed = (kp <= qp) & ((window <= 0) | (qp - kp < window))
    scores = jnp.where(allowed, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nkgqs,nskd->nqkgd", p, v, precision=HIGHEST).reshape(n, s, hq * dh)
    return _mm(out, w["attn/wo"], numerics)


def _ssm(x, w, m, numerics):
    n, s, _ = x.shape
    d_in = m["ssm_expand"] * m["d_model"]
    ns, p = m["ssm_state"], m["ssm_head_dim"]
    heads = d_in // p
    wc = m.get("ssm_conv_dim", 4)
    proj = _mm(x, w["ssm/in_proj"], numerics)
    z, xbc, dt = proj[..., :d_in], proj[..., d_in:2 * d_in + 2 * ns], proj[..., 2 * d_in + 2 * ns:]
    # causal depthwise convolution over the last wc positions
    past = jnp.pad(xbc, ((0, 0), (wc - 1, 0), (0, 0)))
    conv = sum(past[:, i:i + s] * w["ssm/conv_w"][i] for i in range(wc)) + w["ssm/conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :d_in].reshape(n, s, heads, p)
    b, c = xbc[..., d_in:d_in + ns], xbc[..., d_in + ns:]
    dt = jax.nn.softplus(dt + w["ssm/dt_bias"])                      # [n, S, H]
    a = -jnp.exp(w["ssm/A_log"])                                    # [H]

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp                                    # [n,H,P] [n,N] [n,N] [n,H]
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        y_t = jnp.einsum("nhpk,nk->nhp", state, c_t, precision=HIGHEST)
        return state, y_t

    seq = (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(b, 1, 0),
           jnp.moveaxis(c, 1, 0), jnp.moveaxis(dt, 1, 0))
    _, ys = jax.lax.scan(step, jnp.zeros((n, heads, p, ns), jnp.float32), seq, unroll=8)
    y = jnp.moveaxis(ys, 0, 1) + w["ssm/D"][:, None] * xs
    y = y.reshape(n, s, d_in) * jax.nn.silu(z)
    y = _rms(y, w["ssm/norm"], m["norm_eps"])
    return _mm(y, w["ssm/out_proj"], numerics)


def _swiglu(x, w, numerics):
    g = _mm(x, w["mlp/w_gate"], numerics)
    u = _mm(x, w["mlp/w_up"], numerics)
    return _mm(jax.nn.silu(g) * u, w["mlp/w_down"], numerics)


def _head_dim(m):
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def layer_leaves(m: Dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Name -> (shape of one layer, kind of draw) for one block."""
    d, f = m["d_model"], m["d_ff"]
    hq, hk, dh = m["num_heads"], m["num_kv_heads"], _head_dim(m)
    t = {
        "norm1": ((d,), "ones"),
        "attn/wq": ((d, hq * dh), "normal"),
        "attn/wk": ((d, hk * dh), "normal"),
        "attn/wv": ((d, hk * dh), "normal"),
        "attn/wo": ((hq * dh, d), "normal"),
        "norm2": ((d,), "ones"),
        "mlp/w_gate": ((d, f), "normal"),
        "mlp/w_up": ((d, f), "normal"),
        "mlp/w_down": ((f, d), "normal"),
    }
    if m.get("qkv_bias"):
        t.update({"attn/bq": ((hq * dh,), "zeros"), "attn/bk": ((hk * dh,), "zeros"),
                  "attn/bv": ((hk * dh,), "zeros")})
    if m["family"] == "hybrid":
        d_in, ns = m["ssm_expand"] * d, m["ssm_state"]
        heads, wc = d_in // m["ssm_head_dim"], m.get("ssm_conv_dim", 4)
        t.update({
            "ssm/in_proj": ((d, 2 * d_in + 2 * ns + heads), "normal"),
            "ssm/conv_w": ((wc, d_in + 2 * ns), "normal"),
            "ssm/conv_b": ((d_in + 2 * ns,), "zeros"),
            "ssm/A_log": ((heads,), "ssm_a"),
            "ssm/D": ((heads,), "ones"),
            "ssm/dt_bias": ((heads,), "zeros"),
            "ssm/norm": ((d_in,), "ones"),
            "ssm/out_proj": ((d_in, d), "normal"),
            "attn_out_norm": ((d,), "ones"),
            "ssm_out_norm": ((d,), "ones"),
        })
    return t


def _served(x, name, m):
    if name in F32_LEAVES:
        return x
    return x.astype(jnp.dtype(m["dtype"])).astype(jnp.float32)


def _layer_weights(key, l, m):
    return {name: _served(weights.draw_layer(key, "blocks/" + name, l, shape, init), name, m)
            for name, (shape, init) in layer_leaves(m).items()}


def block(h, key, l, window, m, numerics):
    """Layer ``l`` over ``h`` [sequences, positions, d_model] in float32;
    ``window`` is the layer's attention window, 0 for none."""
    w = _layer_weights(key, l, m)
    eps = m["norm_eps"]
    x = _rms(h, w["norm1"], eps)
    if m["family"] == "hybrid":
        a = _rms(_attention(x, w, m, window, numerics), w["attn_out_norm"], eps)
        s = _rms(_ssm(x, w, m, numerics), w["ssm_out_norm"], eps)
        h = h + 0.5 * (a + s)
    elif m["family"] == "dense":
        h = h + _attention(x, w, m, 0, numerics)
    else:
        raise ValueError(f"no reference for family {m['family']!r}")
    return h + _swiglu(_rms(h, w["norm2"], eps), w, numerics)


def _window(m, l):
    if m["family"] == "hybrid" and m.get("sliding_window") and l not in m.get("global_attn_layers", ()):
        return m["sliding_window"]
    return 0


def _head(m, key):
    """The output head [d, V] in float32 (the embedding, transposed, when tied)."""
    if m.get("tie_embeddings"):
        e = weights.draw_whole(key, "embed", (m["vocab_size"], m["d_model"]), "embed")
        return _served(e, "embed", m).T
    w = weights.draw_whole(key, "lm_head", (m["d_model"], m["vocab_size"]), "normal")
    return _served(w, "lm_head", m)


def _embed(key, tokens, m):
    e = weights.draw_whole(key, "embed", (m["vocab_size"], m["d_model"]), "embed")
    return jnp.take(_served(e, "embed", m), tokens, axis=0)


def _logits(h, pick, key, m, numerics):
    h = jnp.take(h.reshape(-1, h.shape[-1]), pick, axis=0)
    scale = _served(weights.draw_whole(key, "final_norm", (m["d_model"],), "ones"), "final_norm", m)
    return _mm(_rms(h, scale, m["norm_eps"]), _head(m, key), numerics)


def logits_at(m: Dict, seed: int, seqs: Sequence[np.ndarray], rows: Sequence[np.ndarray],
              numerics: str = "float32", shape: Tuple[int, int, int] = (0, 0, 0),
              timings: Dict[str, float] = None) -> np.ndarray:
    """Logits [sum(len(r)), V] in float32 at positions ``rows[i]`` of
    ``seqs[i]``. The sequences are run together, padded at the end (which a
    causal model never reads) to ``shape`` = (sequences, positions, rows)
    where that is larger, so that one cell's runs compile each program
    once. ``timings``, if given, receives the seconds spent compiling and
    running."""
    t0 = time.perf_counter()
    key = weights.seed_key(seed)
    n, s = max(len(seqs), shape[0]), max(max(len(x) for x in seqs), shape[1])
    tokens = np.zeros((n, s), np.int32)
    for i, x in enumerate(seqs):
        tokens[i, :len(x)] = x
    pick = np.concatenate([i * s + np.asarray(r) for i, r in enumerate(rows)])
    n_rows = len(pick)
    pick = np.pad(pick, (0, max(0, shape[2] - n_rows)))

    h = jax.jit(functools.partial(_embed, m=m))(key, jnp.asarray(tokens))
    l0 = jnp.int32(0)
    layer = jax.jit(functools.partial(block, m=m, numerics=numerics)).lower(h, key, l0, l0).compile()
    t1 = time.perf_counter()
    for l in range(m["num_layers"]):
        h = layer(h, key, jnp.int32(l), jnp.int32(_window(m, l)))
    h.block_until_ready()
    t2 = time.perf_counter()
    out = np.asarray(jax.jit(functools.partial(_logits, m=m, numerics=numerics))(
        h, jnp.asarray(pick), key))[:n_rows]
    if timings is not None:
        timings[f"{numerics}_compile_s"] = t1 - t0
        timings[f"{numerics}_layers_s"] = t2 - t1
        timings[f"{numerics}_head_s"] = time.perf_counter() - t2
    return out
