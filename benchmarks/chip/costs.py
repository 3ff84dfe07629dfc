"""Operations and bytes of the served model's steps, from its sizes alone.

Counted from the configuration file's numbers, not from the program:
  * a multiply-add is 2 operations;
  * matrix products: every weight matrix once per token, the output head
    included; norms, rotations and elementwise work are left out, so
    these are lower bounds;
  * attention: 4 * heads * head_dim operations per (query, key) pair
    that the mask lets through (scores and the weighted sum);
  * SSM: the recurrence's 6 * d_inner * state operations per token;
  * a decode step must read every weight once, and for each active slot
    the cache entries it attends to and its recurrent state.
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _hd(m):
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def _ssm_sizes(m):
    d_in = m["ssm_expand"] * m["d_model"]
    ns = m["ssm_state"]
    heads = d_in // m["ssm_head_dim"]
    return d_in, ns, heads, m.get("ssm_conv_dim", 4)


def layer_matmul_params(m: Dict) -> int:
    """Weights of one block that take part in matrix products."""
    d, f = m["d_model"], m["d_ff"]
    hq, hk, dh = m["num_heads"], m["num_kv_heads"], _hd(m)
    n = d * hq * dh * 2 + d * hk * dh * 2 + 3 * d * f
    if m["family"] == "hybrid":
        d_in, ns, heads, _ = _ssm_sizes(m)
        n += d * (2 * d_in + 2 * ns + heads) + d_in * d
    return n


def param_count(m: Dict) -> int:
    """Every parameter of the model."""
    d, v = m["d_model"], m["vocab_size"]
    hq, hk, dh = m["num_heads"], m["num_kv_heads"], _hd(m)
    layer = layer_matmul_params(m) + 2 * d
    if m.get("qkv_bias"):
        layer += (hq + 2 * hk) * dh
    if m["family"] == "hybrid":
        d_in, ns, heads, wc = _ssm_sizes(m)
        layer += wc * (d_in + 2 * ns) + (d_in + 2 * ns) + 3 * heads + d_in + 2 * d
    head = 0 if m.get("tie_embeddings") else d * v
    return m["num_layers"] * layer + v * d + head + d


def param_bytes(m: Dict) -> int:
    n = param_count(m)
    if m["family"] == "hybrid":  # A_log, D, dt_bias are kept in float32
        heads = _ssm_sizes(m)[2]
        f32 = 3 * heads * m["num_layers"]
        return (n - f32) * DTYPE_BYTES[m["dtype"]] + f32 * 4
    return n * DTYPE_BYTES[m["dtype"]]


def _attn_keys(m: Dict, layer: int, pos: int) -> int:
    """Keys a query at ``pos`` sees in ``layer``."""
    w = m.get("sliding_window") or 0
    if m["family"] == "hybrid" and w and layer not in m.get("global_attn_layers", ()):
        return min(pos + 1, w)
    return pos + 1


def _keys_summed(m: Dict, pos: int) -> int:
    return sum(_attn_keys(m, l, pos) for l in range(m["num_layers"]))


def _matmul_flops_per_token(m: Dict) -> int:
    return 2 * (m["num_layers"] * layer_matmul_params(m) + m["d_model"] * m["vocab_size"])


def _ssm_flops_per_token(m: Dict) -> int:
    if m["family"] != "hybrid":
        return 0
    d_in, ns, _, _ = _ssm_sizes(m)
    return 6 * d_in * ns * m["num_layers"]


def prefill_flops(m: Dict, prompt_len: int) -> float:
    """Useful operations of one prompt of ``prompt_len`` real tokens (the
    output head only at the last position, as prefill computes it)."""
    per_tok = _matmul_flops_per_token(m) - 2 * m["d_model"] * m["vocab_size"]
    attn = 4 * m["num_heads"] * _hd(m) * sum(_keys_summed(m, p) for p in range(prompt_len))
    return float(prompt_len * (per_tok + _ssm_flops_per_token(m))
                 + 2 * m["d_model"] * m["vocab_size"] + attn)


def decode_flops(m: Dict, positions: Iterable[int]) -> float:
    """Useful operations of one decode step for active slots whose new
    tokens sit at ``positions``."""
    total = 0.0
    for pos in positions:
        total += (_matmul_flops_per_token(m) + _ssm_flops_per_token(m)
                  + 4 * m["num_heads"] * _hd(m) * _keys_summed(m, pos))
    return total


def decode_bytes(m: Dict, positions: Iterable[int]) -> float:
    """Bytes a decode step must move: every weight once, and per active
    slot the keys and values it attends to (read) and writes, and its
    recurrent state (read and written)."""
    kv_bytes = 2 * m["num_kv_heads"] * _hd(m) * 2      # k and v, bfloat16
    total = float(param_bytes(m))
    for pos in positions:
        total += kv_bytes * (_keys_summed(m, pos) + m["num_layers"])
        if m["family"] == "hybrid":
            d_in, ns, heads, wc = _ssm_sizes(m)
            state = heads * m["ssm_head_dim"] * ns * 4 + (wc - 1) * (d_in + 2 * ns) * 2
            total += 2 * state * m["num_layers"]
    return total


def roofline_s(flops: float, nbytes: float, peak: Dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
