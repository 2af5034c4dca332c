"""Shared model layers: norms, RoPE/M-RoPE, attention, SwiGLU MLP.

Pure-JAX (jnp + lax) implementations designed to lower efficiently under GSPMD:
  * attention is computed in query chunks (bounded score memory at 32k
    prefill),
  * all matmuls keep a head/feature axis that the sharding rules map
    to "model",
  * every function is shape-polymorphic over batch/seq and dtype-polymorphic.

The Pallas kernels in ``repro.kernels`` (flash_attention, ssm_scan) are TPU
drop-in replacements for the hot paths here; these jnp forms are the oracles
and the CPU/dry-run path.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# Param spec machinery (shapes + logical axes declared once, init derived).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple              # logical axis names, len == len(shape)
    init: str = "normal"     # normal | zeros | ones | small_normal
    dtype: str = "float32"

    def initializer(self, key, param_dtype):
        dtype = jnp.dtype(param_dtype)
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        scale = 0.02 if self.init == "normal" else 0.006
        fan_in = self.shape[0] if len(self.shape) > 1 else 1
        scale = min(scale, (1.0 / max(fan_in, 1)) ** 0.5)
        return (jax.random.normal(key, self.shape) * scale).astype(dtype)

    def struct(self, param_dtype):
        return jax.ShapeDtypeStruct(self.shape, jnp.dtype(param_dtype))


def init_params(specs, key, param_dtype="float32"):
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    vals = [s.initializer(k, param_dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def param_structs(specs, param_dtype="float32"):
    return jax.tree.map(lambda s: s.struct(param_dtype), specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_axes(specs):
    return jax.tree.map(lambda s: s.axes, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps=1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def softcap(x, cap):
    """Gemma2-style logit soft-capping: cap * tanh(x / cap)."""
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE (standard + qwen2-vl M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta=10_000.0, mrope_sections=None):
    """Rotate pairs of features.

    x: (..., S, H, D); positions: (B, S) int32 for standard RoPE, or
    (3, B, S) for M-RoPE (temporal, height, width position streams).
    """
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                       # (D/2,)
    if mrope_sections is not None:
        # M-RoPE: head_dim/2 frequency slots are split into (t, h, w)
        # sections; each section takes its angle from a different position
        # stream (arXiv:2409.12191).
        assert positions.ndim == 3, "M-RoPE needs (3, B, S) positions"
        sec = jnp.concatenate([
            jnp.full((n,), i, dtype=jnp.int32)
            for i, n in enumerate(mrope_sections)])   # (D/2,)
        # select position stream per frequency slot: (3,B,S) -> (B,S,D/2)
        pos = positions.astype(jnp.float32)
        pos_sel = jnp.einsum("kbs,fk->bsf", pos,
                             jax.nn.one_hot(sec, 3, dtype=jnp.float32))
        ang = pos_sel * inv[None, None, :]            # (B, S, D/2)
    else:
        if positions.ndim == 3:       # tolerate (3,B,S) given to standard rope
            positions = positions[0]
        ang = positions.astype(jnp.float32)[..., None] * inv  # (B, S, D/2)
    cos = jnp.cos(ang)[..., None, :]                  # (B, S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, causal / sliding-window / cross, chunked queries)
# ---------------------------------------------------------------------------

def _attend(q, k, v, *, causal, q_offset, window=0, logit_cap=0.0,
            kv_len_mask=None):
    """q/k: (B, Sq|Sk, Hq|Hkv, D); v: (B, Sk, Hkv, Dv).  Chunk-free
    core; scores scale by 1/sqrt(D)."""
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    qf = q.reshape(b, sq, hkv, group, d).astype(jnp.float32)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf,
                        k.astype(jnp.float32)) \
        / jnp.sqrt(d).astype(jnp.float32)
    scores = softcap(scores, logit_cap)
    if causal:
        qpos = q_offset + jnp.arange(sq)
        kpos = jnp.arange(sk)
        mask = qpos[:, None] >= kpos[None, :]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    if kv_len_mask is not None:                       # (B, Sk) valid-kv mask
        scores = jnp.where(kv_len_mask[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, v.shape[-1]).astype(q.dtype)


def attention(q, k, v, *, causal=True, q_offset=0, window=0, logit_cap=0.0,
              kv_len_mask=None, q_chunk=1024):
    """Chunked-query attention: bounds score memory to (B,H,q_chunk,Sk)."""
    sq = q.shape[1]
    if sq % q_chunk:          # largest divisor of sq that is <= q_chunk
        q_chunk = next((c for c in range(q_chunk, 0, -1) if sq % c == 0), sq)
    if sq <= q_chunk:
        return _attend(q, k, v, causal=causal, q_offset=q_offset,
                       window=window, logit_cap=logit_cap,
                       kv_len_mask=kv_len_mask)
    n = sq // q_chunk
    qs = q.reshape(q.shape[0], n, q_chunk, *q.shape[2:]).swapaxes(0, 1)

    # remat the chunk body: backward recomputes the (B,H,chunk,Sk) score
    # block instead of stashing all n of them (the whole point of chunking)
    @jax.checkpoint
    def body(carry, args):
        i, qc = args
        out = _attend(qc, k, v, causal=causal,
                      q_offset=q_offset + i * q_chunk, window=window,
                      logit_cap=logit_cap, kv_len_mask=kv_len_mask)
        return carry, out

    _, outs = lax.scan(body, None, (jnp.arange(n), qs))
    return outs.swapaxes(0, 1).reshape(q.shape[:-1] + v.shape[-1:])


def attention_specs(cfg, *, cross=False, prefix=""):
    """ParamSpecs for one attention block."""
    d, h = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    specs = {
        "wq": ParamSpec((d, nq * h), ("embed", "q_features")),
        "wk": ParamSpec((d, nkv * h), ("embed", "kv_features")),
        "wv": ParamSpec((d, nkv * h), ("embed", "kv_features")),
        "wo": ParamSpec((nq * h, d), ("q_features", "embed")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((nq * h,), ("q_features",), init="zeros")
        specs["bk"] = ParamSpec((nkv * h,), ("kv_features",), init="zeros")
        specs["bv"] = ParamSpec((nkv * h,), ("kv_features",), init="zeros")
    return specs


def to_cache_layout(t):
    """(B, S, Hkv, D) keys or values -> the KV-cache layout (B, Hkv, D, S).

    The sequence is the minor (lane) axis: a 64-wide head dim is not
    padded to 128 lanes, and decode attention reduces over D for the
    scores and over S for the output straight from this layout, so the
    compiler inserts no conversion of the cache."""
    return jnp.moveaxis(t, 1, -1)


def _write_kv_rows(stacked, layer, rows, slot):
    """Write one (B, Hkv, D) row per batch entry into column ``slot`` of
    layer ``layer`` of a stacked (G, B, Hkv, D, W) cache, in place (or
    any (G, B, ..., W) cache: a latent cache's rows are (B, C)).
    ``slot``: scalar, or (B,) when every row has its own position.

    Each row goes in through the aligned block of up to 128 positions
    (one lane tile) that holds its column: the block is read, the column
    replaced and the block written back.  A write one position wide on
    the minor axis would make the compiler keep the whole cache in a
    layout with D on the lanes, padded to 128, and convert it at the
    program's boundary."""
    blk = math.gcd(stacked.shape[-1], 128)
    lane = jnp.arange(blk)

    def put(c, i0, r, pos):
        b0 = pos // blk * blk
        start = (layer, i0) + (0,) * (r.ndim - 1) + (b0,)
        old = lax.dynamic_slice(c, start, (1,) + r.shape + (blk,))
        new = jnp.where(lane == pos - b0, r[None, ..., None], old)
        return lax.dynamic_update_slice(c, new, start)

    if jnp.ndim(slot) == 0:
        return put(stacked, 0, rows, slot)
    for i in range(rows.shape[0]):
        stacked = put(stacked, i, rows[i:i + 1], slot[i])
    return stacked


def attention_apply(p, cfg, x, positions, *, layer_window=0, kv_cache=None,
                    layer=None, cache_index=None, cross_kv=None,
                    causal=True, mesh=None):
    """Returns (out, new_kv_cache).

    kv_cache: dict(k=(G, B, Hkv, D, W), v=...), the stacked cache of
    every layer at this pattern position in the cache layout
    (:func:`to_cache_layout`), or None; ``layer`` indexes it, and only
    that layer's new entries are written.  For sliding-window layers
    W = min(max_len, window) and the cache is a RING indexed by
    position % W; otherwise W = max_len with direct indexing.
    cache_index: scalar int32 — write offset (decode) / 0 (prefill) —
    or a (B,) int32 vector of per-row offsets during single-token decode
    (continuous batching: each slot advances at its own position).
    cross_kv: precomputed (k, v) for cross-attention (whisper decoder),
    each (B, Hkv, D, F) in the cache layout.
    """
    from repro.distributed.decode_attention import decode_attention
    b, s, _ = x.shape
    h = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    dt = x.dtype

    def _pin_heads(t, heads_sharded):
        """§Perf knob: pin (B,S,H,D) shardings so SPMD propagation doesn't
        thrash between feature- and head-sharded layouts (uneven head
        counts pad; tiny KV head counts replicate)."""
        import os
        if mesh is None or s <= 1 \
                or os.environ.get("REPRO_ATTN_HEAD_CONSTRAINT") != "1":
            return t
        from jax.sharding import NamedSharding, PartitionSpec as P
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dpn = 1
        for a in dp:
            dpn *= mesh.shape[a]
        bspec = dp if t.shape[0] % dpn == 0 else None
        hspec = "model" if heads_sharded else None
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh, P(bspec, None, hspec, None)))

    q = (x @ p["wq"].astype(dt)).reshape(b, s, nq, h)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt).reshape(nq, h)
    if cross_kv is not None:
        k, v = cross_kv
        if s == 1:          # decode: every frame is valid
            out = decode_attention(q, k, v, k.shape[-1] - 1, mesh)
        else:
            out = attention(q, jnp.moveaxis(k, -1, 1),
                            jnp.moveaxis(v, -1, 1), causal=False)
        out = out.reshape(b, s, nq * h)
        return out @ p["wo"].astype(dt), kv_cache

    k = (x @ p["wk"].astype(dt)).reshape(b, s, nkv, h)
    v = (x @ p["wv"].astype(dt)).reshape(b, s, nkv, h)
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dt).reshape(nkv, h)
        v = v + p["bv"].astype(dt).reshape(nkv, h)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    q = _pin_heads(q, heads_sharded=True)
    k = _pin_heads(k, heads_sharded=False)
    v = _pin_heads(v, heads_sharded=False)

    if kv_cache is None:
        out = attention(q, k, v, causal=causal, window=layer_window,
                        logit_cap=cfg.logit_softcap)
        out = _pin_heads(out, heads_sharded=True)
        out = out.reshape(b, s, nq * h)
        return out @ p["wo"].astype(dt), None

    w_len = kv_cache["k"].shape[-1]
    ring = bool(layer_window) and w_len <= layer_window
    cd = kv_cache["k"].dtype
    if s > 1:
        # prefill: attend over the fresh k/v, then write the layer's cache
        out = attention(q, k, v, causal=True, window=layer_window,
                        logit_cap=cfg.logit_softcap)
        start = cache_index
        if ring:
            start = 0
            if s >= w_len:
                # position p lives at slot p % W -> rolled last-W block
                r = (s - w_len) % w_len
                k = jnp.roll(k[:, s - w_len:], r, axis=1)
                v = jnp.roll(v[:, s - w_len:], r, axis=1)
        new = {n: lax.dynamic_update_slice(
                   kv_cache[n], to_cache_layout(t).astype(cd)[None],
                   (layer, 0, 0, 0, start))
               for n, t in (("k", k), ("v", v))}
        return out.reshape(b, s, nq * h) @ p["wo"].astype(dt), new

    # decode: one row per slot into the ring or direct slot, then
    # distributed flash-decode over the layer, read in the cache layout
    # (caches stay in their storage dtype; dequant happens per shard)
    slot = jnp.mod(cache_index, w_len) if ring else cache_index
    new = {n: _write_kv_rows(kv_cache[n], layer, t[:, 0].astype(cd), slot)
           for n, t in (("k", k), ("v", v))}
    ck, cv = (lax.dynamic_index_in_dim(new[n], layer, keepdims=False)
              for n in ("k", "v"))
    out = decode_attention(
        q, ck, cv, cache_index, mesh,
        window=0 if ring else layer_window,     # ring bounds the window
        logit_cap=cfg.logit_softcap)
    out = out.astype(dt)
    return out.reshape(b, s, nq * h) @ p["wo"].astype(dt), new


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_specs(cfg, d_ff=None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    return {
        "w_gate": ParamSpec((d, f), ("embed", "mlp")),
        "w_up": ParamSpec((d, f), ("embed", "mlp")),
        "w_down": ParamSpec((f, d), ("mlp", "embed")),
    }


def mlp_apply(p, x):
    dt = x.dtype
    g = jax.nn.silu(x @ p["w_gate"].astype(dt))
    u = x @ p["w_up"].astype(dt)
    return (g * u) @ p["w_down"].astype(dt)
