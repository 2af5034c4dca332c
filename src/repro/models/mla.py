"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434, as
DeepSeek-V3 and Moonlight use it, with no query compression).

With ``H`` heads, ``r`` = ``kv_lora_rank`` and the rotary width ``R``::

    q = x W_q                  (H, nope + R): q_n, and q_r rotated
    [c, k_r] = x W_kva         c = RMSNorm_kv(c) (r wide); k_r rotated,
                               one R-wide key that every head shares
    [k_n, v] = c W_kvb         (H, nope + v_head_dim)
    score_h(s) = (q_n,h . k_n,h(s) + q_r,h . k_r(s)) / sqrt(nope + R)

The cache holds only ``[c, k_r]``: r + R values a position for all heads
together, stored (G, B, r + R, S) with the sequence on the lanes, as the
KV cache is (``layers.to_cache_layout``).  Prefill attends over the
decompressed heads; a decode step reads the cache in the absorbed form,
``q~_h = q_n,h W_UK,h^T`` against ``c`` and ``o_h = (sum_s p c(s))
W_UV,h``, so keys and values are never decompressed for the cache.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.latent_decode import latent_decode
from repro.models.layers import (ParamSpec, _write_kv_rows, apply_rope,
                                 attention, rms_norm)


def mla_specs(cfg):
    a, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    return {
        "wq": ParamSpec((d, h * a.qk_head_dim), ("embed", "q_features")),
        "wkv_a": ParamSpec((d, a.cache_width), ("embed", None)),
        "kv_norm": ParamSpec((a.kv_lora_rank,), (None,), init="zeros"),
        "wkv_b": ParamSpec((a.kv_lora_rank,
                            h * (a.qk_nope_head_dim + a.v_head_dim)),
                           (None, "q_features")),
        "wo": ParamSpec((h * a.v_head_dim, d), ("q_features", "embed")),
    }


def mla_apply(p, cfg, x, positions, *, cache=None, layer=None,
              cache_index=None):
    """Returns (out, new stacked latent cache).

    cache: the stacked (G, B, r + R, S) latent cache of every layer at
    this pattern position, or None; only layer ``layer``'s entries are
    written.  cache_index: 0 (prefill) or the write position of a
    one-token step, a scalar or (B,) per-slot positions."""
    a, h = cfg.mla, cfg.num_heads
    nope, rope, r, dv = (a.qk_nope_head_dim, a.qk_rope_head_dim,
                         a.kv_lora_rank, a.v_head_dim)
    b, s, _ = x.shape
    dt = x.dtype
    with jax.named_scope("mla"):
        q = (x @ p["wq"].astype(dt)).reshape(b, s, h, nope + rope)
        q_n = q[..., :nope]
        q_r = apply_rope(q[..., nope:], positions, cfg.rope_theta)
        kv = x @ p["wkv_a"].astype(dt)                       # (B, S, r+R)
        c = rms_norm(kv[..., :r], p["kv_norm"], cfg.rms_eps)
        k_r = apply_rope(kv[..., None, r:], positions, cfg.rope_theta)
        wkv_b = p["wkv_b"].astype(dt).reshape(r, h, nope + dv)
        rows = jnp.concatenate([c, k_r[:, :, 0]], axis=-1)   # (B, S, r+R)

        if cache is None or s > 1:
            # decompressed heads, full causal attention
            kvb = jnp.einsum("bsc,chd->bshd", c, wkv_b)
            k = jnp.concatenate(
                [kvb[..., :nope], jnp.broadcast_to(k_r, (b, s, h, rope))],
                axis=-1)
            out = attention(jnp.concatenate([q_n, q_r], axis=-1), k,
                            kvb[..., nope:], causal=True)    # (B, S, H, dv)
            if cache is not None:
                rows = jnp.moveaxis(rows, 1, -1).astype(cache.dtype)
                cache = lax.dynamic_update_slice(
                    cache, rows[None], (layer, 0, 0, cache_index))
        else:
            cache = _write_kv_rows(cache, layer,
                                   rows[:, 0].astype(cache.dtype),
                                   cache_index)
            out = _absorbed_decode(q_n[:, 0], q_r[:, 0], wkv_b, cache,
                                   layer, cache_index)[:, None]
        out = out.astype(dt).reshape(b, s, h * dv)
        return out @ p["wo"].astype(dt), cache


def _absorbed_decode(q_n, q_r, wkv_b, cache, layer, pos):
    """One query per row over layer ``layer`` of the stacked (G, B,
    r + R, S) latent cache, absorbed: q_n (B, H, nope), q_r (B, H, R),
    wkv_b (r, H, nope + dv); positions <= ``pos`` (scalar or (B,)) are
    valid.  -> (B, H, dv) float32.  The cache is handed whole to
    ``latent_decode``, which reads only the layer's filled positions."""
    f32 = jnp.float32
    nope, r = q_n.shape[-1], wkv_b.shape[0]
    q_lat = jnp.einsum("bhn,chn->bhc", q_n.astype(f32),
                       wkv_b[..., :nope].astype(f32))         # (B, H, r)
    qc = jnp.concatenate([q_lat, q_r.astype(f32)], axis=-1)
    pos = jnp.broadcast_to(pos, q_n.shape[:1]).astype(jnp.int32)
    o_lat = latent_decode(qc, cache, layer, pos, rank=r,
                          scale=1.0 / math.sqrt(nope + q_r.shape[-1]))
    return jnp.einsum("bhc,chv->bhv", o_lat, wkv_b[..., nope:].astype(f32))
