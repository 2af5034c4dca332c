"""Distributed flash-decode: online-softmax attention over a seq-sharded
KV cache.

Decode caches shard their SEQUENCE dim on "model" (DESIGN.md §4).  GSPMD
would all-gather the cache per layer (GBs per step); instead this shard_map
computes per-shard partial attention and combines with the standard
online-softmax (m, l, num) reduction — only (B, H, head_dim)-sized tensors
cross shards.  This is the TPU-native analogue of FlashDecoding's split-K.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _partial_attend(q, k, v, valid, logit_cap=0.0):
    """q: (B,Hq,D); k/v: (B,Hkv,D,Sl) in the cache layout; valid: (B,Sl)
    -> (num (B,Hq,D), m (B,Hq), l (B,Hq)).

    The scores reduce over D and the output over Sl, both straight from
    the layout the cache is stored in."""
    b, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qf = q.reshape(b, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhds->bhgs", qf, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    if logit_cap:
        # softcap folds into scores (tanh is monotonic, so the online
        # combine stays exact)
        scores = logit_cap * jnp.tanh(scores / logit_cap)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    m = jnp.max(scores, axis=-1)                       # (B,Hkv,g)
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    lsum = jnp.sum(p, axis=-1)
    num = jnp.einsum("bhgs,bhds->bhgd", p, v.astype(jnp.float32))
    return (num.reshape(b, hq, d), m.reshape(b, hq),
            lsum.reshape(b, hq))


def decode_attention(q, ck, cv, pos, mesh, *, window=0, logit_cap=0.0,
                     seq_axis="model", dp_axes=("pod", "data")):
    """q: (B,1,Hq,D); ck/cv: (B,Hkv,D,Smax) in the cache layout
    (``models.layers.to_cache_layout``), seq-sharded on `seq_axis`;
    pos: scalar — current write position (entries <= pos are valid) — or
    a (B,) vector of per-row positions (continuous-batching slots, where
    every batch row decodes at its own sequence offset).

    Note: logit softcap is applied per-score before max/sum, matching the
    jnp oracle (tanh is monotonic so the online combine stays exact).
    """
    b, smax = ck.shape[0], ck.shape[-1]
    n_shards = mesh.shape[seq_axis] if mesh is not None else 1
    dp = tuple(a for a in dp_axes if mesh is not None
               and a in mesh.axis_names)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    bspec = dp if (dp and b % dp_n == 0) else None
    seq_ok = mesh is not None and smax % n_shards == 0 and n_shards > 1

    def fn(qq, k, v, pos):
        # dequantize (e.g. f8 caches) INSIDE the shard so only the local
        # (B, S/shards) slice ever materializes at compute dtype
        k = k.astype(qq.dtype)
        v = v.astype(qq.dtype)
        s_loc = k.shape[-1]
        base = lax.axis_index(seq_axis) * s_loc if seq_ok else 0
        slots = base + jnp.arange(s_loc)
        if jnp.ndim(pos) == 1:          # per-row positions: (B,) x (Sl,)
            valid = slots[None, :] <= pos[:, None]
            if window:
                valid &= slots[None, :] > (pos - window)[:, None]
        else:
            valid = slots <= pos
            if window:
                valid &= slots > pos - window
        valid = jnp.broadcast_to(valid, (k.shape[0], s_loc))
        num, m, lsum = _partial_attend(qq[:, 0], k, v, valid, logit_cap)
        if seq_ok and n_shards > 1:
            m_g = lax.pmax(m, seq_axis)
            scale = jnp.exp(m - m_g)
            num = lax.psum(num * scale[..., None], seq_axis)
            lsum = lax.psum(lsum * scale, seq_axis)
        out = num / jnp.maximum(lsum[..., None], 1e-30)
        return out[:, None].astype(qq.dtype)

    if not seq_ok:
        # single-shard fallback (smoke tests / non-divisible caches)
        return fn(q, ck, cv, pos)

    from repro.distributed.sharding import shard_map_compat
    kv_spec = P(bspec, None, None, seq_axis)
    pos_spec = P(bspec) if jnp.ndim(pos) == 1 else P()   # per-row: batch
    return shard_map_compat(
        fn, mesh=mesh,
        in_specs=(P(bspec), kv_spec, kv_spec, pos_spec),
        out_specs=P(bspec),
    )(q, ck, cv, pos)
