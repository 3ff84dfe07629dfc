"""Decoder-only LM assembly for the dense / moe / ssm / hybrid / vlm families.

Every family scans over a layer-stacked parameter
tree -- the HLO stays O(1) in depth, which keeps the 95-layer dry-run
compileable -- with optional per-layer remat (ZeRO-3 FSDP all-gathers the
layer slice inside the scan).  Hybrid decode (sliding + global attention
caches of different shapes) unrolls in Python.

Public entry points (all pure, jit-able):
  train_loss(params, batch, cfg, ...)            -> scalar loss
  prefill(params, tokens, prompt_lens, cfg, ...) -> (last_logits, cache)
  decode_step(params, cache, tokens, cfg, ...)   -> (logits, cache)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import (
    cache_write_decode,
    chunked_attention,
    decode_attention,
)
from repro.models.common import ParamSpec
from repro.models.layers import (
    apply_rope,
    chunked_softmax_xent,
    embed_tokens,
    rms_norm,
    swiglu,
)
from repro.sharding.constraints import shard_act

# ---------------------------------------------------------------------------
# Parameter templates
# ---------------------------------------------------------------------------
def attn_template(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, dh = cfg.d_model, cfg.resolved_head_dim
    hq, hk = cfg.num_heads, cfg.num_kv_heads
    t = {
        "wq": ParamSpec((d, hq * dh), ("embed", "heads")),
        "wk": ParamSpec((d, hk * dh), ("embed", "kv_heads")),
        "wv": ParamSpec((d, hk * dh), ("embed", "kv_heads")),
        "wo": ParamSpec((hq * dh, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        t["bq"] = ParamSpec((hq * dh,), ("heads",), init="zeros")
        t["bk"] = ParamSpec((hk * dh,), ("kv_heads",), init="zeros")
        t["bv"] = ParamSpec((hk * dh,), ("kv_heads",), init="zeros")
    return t


def mlp_template(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": ParamSpec((d, f), ("embed", "ffn")),
        "w_up": ParamSpec((d, f), ("embed", "ffn")),
        "w_down": ParamSpec((f, d), ("ffn", "embed")),
    }


def block_template(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.family == "ssm":
        return {
            "norm": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
            "ssm": ssm_lib.param_template(cfg),
        }
    t: Dict[str, Any] = {
        "norm1": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
        "attn": attn_template(cfg),
        "norm2": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
    }
    if cfg.family == "moe":
        t["moe"] = moe_lib.param_template(cfg)
    else:
        t["mlp"] = mlp_template(cfg)
    if cfg.family == "hybrid":
        t["ssm"] = ssm_lib.param_template(cfg)
        t["attn_out_norm"] = ParamSpec((cfg.d_model,), ("embed",), init="ones")
        t["ssm_out_norm"] = ParamSpec((cfg.d_model,), ("embed",), init="ones")
    return t


def layer_slice(blocks, i: int):
    """Layer ``i`` of a stacked block tree."""
    return jax.tree_util.tree_map(lambda x: x[i], blocks)


def param_template(cfg: ModelConfig) -> Dict[str, Any]:
    # Every family here stacks its blocks. The hybrid (Hymba) stack is
    # uniform too - every block has the attention + SSM + MLP branches -
    # only the sliding ``window`` differs per layer, which rides the scan
    # as a per-layer scalar (dynamic mask in chunked_attention).
    blocks = jax.tree_util.tree_map(
        lambda s: s.with_layers(cfg.num_layers),
        block_template(cfg),
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )
    t: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), init="embed"),
        "blocks": blocks,
        "final_norm": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    if cfg.family == "vlm":
        t["patch_proj"] = ParamSpec((cfg.d_model, cfg.d_model), ("embed", None))
    return t


def lm_head_weight(params: Dict[str, Any], cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


# ---------------------------------------------------------------------------
# Attention block bodies
# ---------------------------------------------------------------------------
def _qkv(x, ap, cfg):
    b = x.shape[:-1]
    dh = cfg.resolved_head_dim
    q = jnp.einsum("...d,de->...e", x, ap["wq"])
    k = jnp.einsum("...d,de->...e", x, ap["wk"])
    v = jnp.einsum("...d,de->...e", x, ap["wv"])
    if cfg.qkv_bias:
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    q = q.reshape(*b, cfg.num_heads, dh)
    k = k.reshape(*b, cfg.num_kv_heads, dh)
    v = v.reshape(*b, cfg.num_kv_heads, dh)
    if len(b) == 2:  # [B, S, H, dh] full-sequence path
        q, k, v = (shard_act(t, "bshd") for t in (q, k, v))
    else:            # [B, H, dh] decode path
        q, k, v = (shard_act(t, "bhd") for t in (q, k, v))
    return q, k, v


def attn_full(x, ap, cfg, *, window: int = 0, positions=None):
    """Full-sequence attention. x [B,S,D] -> (out [B,S,D], k, v rotated)."""
    bsz, s, _ = x.shape
    q, k, v = _qkv(x, ap, cfg)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    out = chunked_attention(q, k, v, causal=True, window=window)
    out = jnp.einsum("...e,ed->...d", out.reshape(bsz, s, -1), ap["wo"])
    return out, k, v


def attn_decode(x, ap, cfg, kc, vc, sp, pos, *, window: int = 0, ring: bool = False):
    """One-token attention. x [B,D]; kc/vc [B,S,K,dh]; sp [B,S]; pos [B]."""
    q, k, v = _qkv(x, ap, cfg)  # [B, H, dh] / [B, K, dh]
    q = apply_rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    k = apply_rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    kc, vc, sp = cache_write_decode(kc, vc, sp, k, v, pos, ring)
    out = decode_attention(q, kc, vc, sp, pos, window=window)
    out = jnp.einsum("be,ed->bd", out.reshape(out.shape[0], -1), ap["wo"])
    return out, kc, vc, sp


# ---------------------------------------------------------------------------
# Block bodies (full-sequence)
# ---------------------------------------------------------------------------
def block_full(h, bp, cfg, *, layer_window=0, prompt_lens=None):
    """Returns (h, per-layer cache pieces dict, aux loss)."""
    h = shard_act(h, "bsd")
    aux = jnp.float32(0.0)
    cache: Dict[str, Any] = {}
    if cfg.family == "ssm":
        y, state = ssm_lib.apply_ssm(
            rms_norm(h, bp["norm"], cfg.norm_eps), bp["ssm"], cfg, prompt_lens)
        cache["ssm"] = state
        return h + y, cache, aux

    x = rms_norm(h, bp["norm1"], cfg.norm_eps)
    if cfg.family == "hybrid":
        a_out, k, v = attn_full(x, bp["attn"], cfg, window=layer_window)
        s_out, state = ssm_lib.apply_ssm(x, bp["ssm"], cfg, prompt_lens)
        a_out = rms_norm(a_out, bp["attn_out_norm"], cfg.norm_eps)
        s_out = rms_norm(s_out, bp["ssm_out_norm"], cfg.norm_eps)
        h = h + 0.5 * (a_out + s_out)
        cache["ssm"] = state
    else:
        a_out, k, v = attn_full(x, bp["attn"], cfg)
        h = h + a_out
    cache["k"], cache["v"] = k, v

    x2 = rms_norm(h, bp["norm2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_lib.apply_moe(x2, bp["moe"], cfg)
    else:
        y = swiglu(x2, bp["mlp"]["w_gate"], bp["mlp"]["w_up"], bp["mlp"]["w_down"])
    return h + y, cache, aux


def block_decode(h, bp, cfg, layer_cache, pos, *, layer_window: int = 0, ring: bool = False):
    """h [B,D]; layer_cache dict of single-layer cache arrays."""
    out_cache: Dict[str, Any] = {}
    if cfg.family == "ssm":
        y, state = ssm_lib.apply_ssm_decode(
            rms_norm(h, bp["norm"], cfg.norm_eps), layer_cache["ssm"], bp["ssm"], cfg
        )
        out_cache["ssm"] = state
        return h + y, out_cache

    x = rms_norm(h, bp["norm1"], cfg.norm_eps)
    if cfg.family == "hybrid":
        a_out, kc, vc, sp = attn_decode(
            x, bp["attn"], cfg, layer_cache["k"], layer_cache["v"],
            layer_cache["slot_pos"], pos, window=layer_window, ring=ring,
        )
        s_out, state = ssm_lib.apply_ssm_decode(x, layer_cache["ssm"], bp["ssm"], cfg)
        a_out = rms_norm(a_out, bp["attn_out_norm"], cfg.norm_eps)
        s_out = rms_norm(s_out, bp["ssm_out_norm"], cfg.norm_eps)
        h = h + 0.5 * (a_out + s_out)
        out_cache["ssm"] = state
    else:
        a_out, kc, vc, sp = attn_decode(
            x, bp["attn"], cfg, layer_cache["k"], layer_cache["v"],
            layer_cache["slot_pos"], pos, ring=ring,
        )
        h = h + a_out
    out_cache.update(k=kc, v=vc, slot_pos=sp)

    x2 = rms_norm(h, bp["norm2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, _ = moe_lib.apply_moe(x2[:, None, :], bp["moe"], cfg, group_size=x2.shape[0])
        y = y[:, 0]
    else:
        y = swiglu(x2, bp["mlp"]["w_gate"], bp["mlp"]["w_up"], bp["mlp"]["w_down"])
    return h + y, out_cache


def _layer_window(cfg: ModelConfig, idx: int) -> int:
    if cfg.family == "hybrid" and cfg.sliding_window:
        return 0 if idx in cfg.global_attn_layers else cfg.sliding_window
    return 0


# ---------------------------------------------------------------------------
# Full-model forward (hidden states)
# ---------------------------------------------------------------------------
def forward_hidden(
    params, tokens, cfg: ModelConfig, *, remat: str = "none",
    collect_cache: bool = False, patches=None, prompt_lens=None,
) -> Tuple[jax.Array, Any, jax.Array]:
    """tokens [B,S_text] -> (h [B,S,D], caches, aux). For vlm, ``patches``
    [B,P,D] are projected and prepended (S = P + S_text)."""
    h = embed_tokens(tokens, params["embed"])
    if cfg.family == "vlm":
        assert patches is not None, "vlm needs patch embeddings"
        pe = jnp.einsum("bpd,de->bpe", patches.astype(h.dtype), params["patch_proj"])
        h = jnp.concatenate([pe, h], axis=1)

    # every layer's prefill cache (full-length k/v, SSM state) has one
    # shape, so every family scans; ``prefill`` cuts the hybrid sliding
    # layers' windows out of the stacked result
    windows = None
    if cfg.family == "hybrid":
        windows = jnp.asarray(
            [_layer_window(cfg, i) for i in range(cfg.num_layers)],
            jnp.int32,
        )

    def body(carry, xs):
        hh, aux = carry
        bp, win = xs if windows is not None else (xs, 0)
        hh, cache, a = block_full(
            hh, bp, cfg, layer_window=win, prompt_lens=prompt_lens)
        out = cache if collect_cache else None
        return (hh, aux + a), out

    wrapped = body
    if remat == "full":
        wrapped = jax.checkpoint(body)
    elif remat == "dots":
        wrapped = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    xs = (params["blocks"], windows) if windows is not None else params["blocks"]
    (h, aux), caches = jax.lax.scan(wrapped, (h, jnp.float32(0.0)), xs)
    aux = aux / cfg.num_layers
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return h, caches, aux


# ---------------------------------------------------------------------------
# Train loss
# ---------------------------------------------------------------------------
def train_loss(
    params, batch: Dict[str, jax.Array], cfg: ModelConfig,
    *, remat: str = "full", loss_chunk: int = 0, aux_weight: float = 0.01,
) -> jax.Array:
    """batch: tokens [B,S], targets [B,S], optional mask [B,S], patches."""
    tokens = batch["tokens"]
    patches = batch.get("patches")
    h, _, aux = forward_hidden(params, tokens, cfg, remat=remat, patches=patches)
    targets, mask = batch["targets"], batch.get("mask")
    if cfg.family == "vlm":
        # loss only over the text region; hidden includes patch prefix
        p = patches.shape[1]
        h = h[:, p:] if p else h
        # align: h[:, i] predicts targets[:, i]
    if loss_chunk <= 0:
        loss_chunk = 128 if cfg.vocab_size % 16 else 512
        loss_chunk = min(loss_chunk, h.shape[1])
    loss = chunked_softmax_xent(h, lm_head_weight(params, cfg), targets, mask, loss_chunk)
    return loss + aux_weight * aux


# ---------------------------------------------------------------------------
# Decode cache construction / templates
# ---------------------------------------------------------------------------
def cache_spec(cfg: ModelConfig, batch: int, cache_len: int) -> Dict[str, Any]:
    """ParamSpec tree describing the decode cache (for input_specs/dry-run).

    Logical axes: "batch" (data-sharded), "cache_seq" (model-sharded when
    batch is too small), "kv_heads", "window".
    """
    dh = cfg.resolved_head_dim
    k = cfg.num_kv_heads
    spec: Dict[str, Any] = {
        "pos": ParamSpec((batch,), ("batch",), dtype="int32"),
    }
    kv = lambda s, seq_ax: {
        "k": ParamSpec((cfg.num_layers, batch, s, k, dh), ("layers", "batch", seq_ax, "kv_heads", None)),
        "v": ParamSpec((cfg.num_layers, batch, s, k, dh), ("layers", "batch", seq_ax, "kv_heads", None)),
        "slot_pos": ParamSpec((cfg.num_layers, batch, s), ("layers", "batch", seq_ax), dtype="int32"),
    }
    if cfg.family in ("dense", "moe", "vlm"):
        spec["attn"] = kv(cache_len, "cache_seq")
    elif cfg.family == "ssm":
        spec["ssm"] = {
            "h": ParamSpec(
                (cfg.num_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                ("layers", "batch", None, None, "ssm_state"), dtype="float32",
            ),
            "conv_buf": ParamSpec(
                (cfg.num_layers, batch, cfg.ssm_conv_dim - 1, cfg.d_inner + 2 * cfg.ssm_state),
                ("layers", "batch", None, None),
            ),
        }
    elif cfg.family == "hybrid":
        n_glob = len(cfg.global_attn_layers)
        n_slide = cfg.num_layers - n_glob
        w = min(cfg.sliding_window, cache_len)
        g = kv(cache_len, "cache_seq")
        s = kv(w, "window")
        spec["attn_global"] = jax.tree_util.tree_map(
            lambda ps: ParamSpec((n_glob,) + ps.shape[1:], ps.axes, ps.init, ps.dtype),
            g, is_leaf=lambda x: isinstance(x, ParamSpec))
        spec["attn_sliding"] = jax.tree_util.tree_map(
            lambda ps: ParamSpec((n_slide,) + ps.shape[1:], ps.axes, ps.init, ps.dtype),
            s, is_leaf=lambda x: isinstance(x, ParamSpec))
        spec["ssm"] = {
            "h": ParamSpec(
                (cfg.num_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                ("layers", "batch", None, None, "ssm_state"), dtype="float32",
            ),
            "conv_buf": ParamSpec(
                (cfg.num_layers, batch, cfg.ssm_conv_dim - 1, cfg.d_inner + 2 * cfg.ssm_state),
                ("layers", "batch", None, None),
            ),
        }
    return spec


def empty_cache(cfg: ModelConfig, batch: int, cache_len: int):
    """Materialized zero/empty cache (slot_pos = -1)."""
    from repro.models.common import abstract_params, is_spec

    spec = cache_spec(cfg, batch, cache_len)

    def mk(s: ParamSpec):
        dt = jnp.dtype(s.dtype or "bfloat16")
        if s.dtype == "int32":
            fill = -1 if len(s.shape) >= 3 else 0  # slot_pos=-1, pos=0
            return jnp.full(s.shape, fill, dt)
        return jnp.zeros(s.shape, dt)

    return jax.tree_util.tree_map(mk, spec, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------
def prefill(
    params, tokens, prompt_lens, cfg: ModelConfig, *, patches=None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Forward the prompt, build the decode cache, return last-token logits.

    tokens [B, S] padded to S; prompt_lens [B] actual lengths (<= S).
    Cache length == S (the serving layer chooses padding = cache size).
    """
    bsz, s = tokens.shape
    h, caches, _ = forward_hidden(
        params, tokens, cfg, collect_cache=True, patches=patches,
        prompt_lens=prompt_lens)
    total = s + (patches.shape[1] if (cfg.family == "vlm" and patches is not None) else 0)

    last = jnp.maximum(prompt_lens - 1, 0)
    if cfg.family == "vlm" and patches is not None:
        last = last + patches.shape[1]
    h_last = jnp.take_along_axis(h, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = jnp.einsum("bd,dv->bv", h_last, lm_head_weight(params, cfg)).astype(jnp.float32)

    valid = jnp.arange(total)[None, :] < (
        prompt_lens[:, None]
        + (patches.shape[1] if (cfg.family == "vlm" and patches is not None) else 0)
    )
    slot_pos = jnp.where(valid, jnp.arange(total)[None, :], -1).astype(jnp.int32)

    cache: Dict[str, Any] = {"pos": prompt_lens.astype(jnp.int32)}
    if cfg.family in ("dense", "moe", "vlm"):
        cache["attn"] = {
            "k": caches["k"], "v": caches["v"],
            "slot_pos": jnp.broadcast_to(slot_pos[None], (cfg.num_layers,) + slot_pos.shape),
        }
    elif cfg.family == "ssm":
        cache["ssm"] = {"h": caches["ssm"].h, "conv_buf": caches["ssm"].conv_buf}
    elif cfg.family == "hybrid":
        glob = [i for i in range(cfg.num_layers) if i in cfg.global_attn_layers]
        slide = [i for i in range(cfg.num_layers) if i not in cfg.global_attn_layers]
        if glob:
            gi = jnp.asarray(glob)
            cache["attn_global"] = {
                "k": caches["k"][gi], "v": caches["v"][gi],
                "slot_pos": jnp.broadcast_to(slot_pos[None], (len(glob),) + slot_pos.shape),
            }
        if slide:
            w = min(cfg.sliding_window, s)
            # ring slot j holds the newest prompt position p <= len-1 with
            # p % w == j: the window that ends at each row's last real
            # token, not at the padded width (-1 where the prompt is
            # shorter than w)
            last = (prompt_lens - 1).astype(jnp.int32)[:, None]
            ring_pos = last - (last - jnp.arange(w)[None, :]) % w      # [B, w]
            take = jnp.maximum(ring_pos, 0)[None, :, :, None, None]
            si = jnp.asarray(slide)
            cache["attn_sliding"] = {
                "k": jnp.take_along_axis(caches["k"][si], take, axis=2),
                "v": jnp.take_along_axis(caches["v"][si], take, axis=2),
                "slot_pos": jnp.broadcast_to(
                    jnp.where(ring_pos >= 0, ring_pos, -1).astype(jnp.int32)[None],
                    (len(slide),) + ring_pos.shape),
            }
        cache["ssm"] = {"h": caches["ssm"].h, "conv_buf": caches["ssm"].conv_buf}
    return logits, cache


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------
def decode_step(
    params, cache: Dict[str, Any], tokens: jax.Array, cfg: ModelConfig,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step. tokens [B] -> (logits [B,V], updated cache)."""
    pos = cache["pos"]
    h = embed_tokens(tokens, params["embed"])

    new_cache = dict(cache)
    if cfg.family in ("dense", "moe", "vlm"):
        att = cache["attn"]

        def body(hh, xs):
            bp, kc, vc, sp = xs
            hh, oc = block_decode(hh, bp, cfg, {"k": kc, "v": vc, "slot_pos": sp}, pos)
            return hh, (oc["k"], oc["v"], oc["slot_pos"])

        h, (k2, v2, sp2) = jax.lax.scan(
            body, h, (params["blocks"], att["k"], att["v"], att["slot_pos"])
        )
        new_cache["attn"] = {"k": k2, "v": v2, "slot_pos": sp2}
    elif cfg.family == "ssm":
        st = cache["ssm"]

        def body(hh, xs):
            bp, sh, sc = xs
            hh, oc = block_decode(hh, bp, cfg, {"ssm": ssm_lib.SSMState(sh, sc)}, pos)
            return hh, (oc["ssm"].h, oc["ssm"].conv_buf)

        h, (h2, c2) = jax.lax.scan(body, h, (params["blocks"], st["h"], st["conv_buf"]))
        new_cache["ssm"] = {"h": h2, "conv_buf": c2}
    else:  # hybrid: unrolled over layer slices of the stacked tree
        gi = si = 0
        glob_out, slide_out, ssm_out = [], [], []
        for i in range(cfg.num_layers):
            bp = layer_slice(params["blocks"], i)
            lw = _layer_window(cfg, i)
            lc = {"ssm": ssm_lib.SSMState(cache["ssm"]["h"][i], cache["ssm"]["conv_buf"][i])}
            if lw:
                src, j, ring = cache["attn_sliding"], si, True
            else:
                src, j, ring = cache["attn_global"], gi, False
            lc.update(k=src["k"][j], v=src["v"][j], slot_pos=src["slot_pos"][j])
            h, oc = block_decode(h, bp, cfg, lc, pos, layer_window=lw, ring=ring)
            ssm_out.append(oc["ssm"])
            if lw:
                slide_out.append((oc["k"], oc["v"], oc["slot_pos"])); si += 1
            else:
                glob_out.append((oc["k"], oc["v"], oc["slot_pos"])); gi += 1
        stack = lambda xs: jax.tree_util.tree_map(lambda *a: jnp.stack(a), *xs)
        if glob_out:
            g = stack(glob_out)
            new_cache["attn_global"] = {"k": g[0], "v": g[1], "slot_pos": g[2]}
        if slide_out:
            sl = stack(slide_out)
            new_cache["attn_sliding"] = {"k": sl[0], "v": sl[1], "slot_pos": sl[2]}
        st = stack(ssm_out)
        new_cache["ssm"] = {"h": st.h, "conv_buf": st.conv_buf}

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", h, lm_head_weight(params, cfg)).astype(jnp.float32)
    new_cache["pos"] = pos + 1
    return logits, new_cache
