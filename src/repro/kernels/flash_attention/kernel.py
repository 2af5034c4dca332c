"""Causal GQA flash attention (online softmax), VMEM-tiled for TPU.

Grid: (batch*kv_heads*q_groups, q_blocks).  Each program holds a
(block_q, d) query tile and streams (block_k, d) key/value tiles through
VMEM with the standard running (m, l, acc) online-softmax state.  Optional
gemma2-style logit soft-capping (tanh is monotone: the online max stays
exact).  MXU alignment: block_q/block_k multiples of 128, d = head_dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q, block_k, seq_len,
               causal, logit_cap, sm_scale):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * sm_scale        # (block_q, d)

    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    lsum = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)
    if causal:
        # only kv blocks at/below the diagonal of this q block
        n_kv = (qi * block_q + block_q + block_k - 1) // block_k
    else:
        n_kv = seq_len // block_k

    def body(j, carry):
        m_c, l_c, acc_c = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T                                       # (bq, bk)
        if logit_cap:
            s = logit_cap * jnp.tanh(s / logit_cap)
        if causal:
            k_pos = j * block_k + jax.lax.iota(jnp.int32, block_k)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_c, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        scale = jnp.exp(m_c - m_new)
        l_new = l_c * scale + jnp.sum(p, axis=-1)
        acc_new = acc_c * scale[:, None] + p @ v
        return m_new, l_new, acc_new

    m, lsum, acc = jax.lax.fori_loop(0, n_kv, body, (m, lsum, acc))
    o_ref[...] = (acc
                  / jnp.maximum(lsum, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal=True, logit_cap=0.0,
                           block_q=128, block_k=128, interpret=False):
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D) -> (B, Hq, S, D)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0
    sm_scale = 1.0 / (d ** 0.5)

    qr = q.reshape(b * hq, s, d)
    # expand kv heads to query heads (view-level; XLA folds the gather)
    kr = jnp.repeat(k, g, axis=1).reshape(b * hq, s, d)
    vr = jnp.repeat(v, g, axis=1).reshape(b * hq, s, d)

    grid = (b * hq, s // block_q)
    out = pl.pallas_call(
        functools.partial(_fa_kernel, block_q=block_q, block_k=block_k,
                          seq_len=s, causal=causal, logit_cap=logit_cap,
                          sm_scale=sm_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, s, d), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((None, s, d), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, s, d), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, s, d)
