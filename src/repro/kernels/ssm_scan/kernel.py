"""Selective-scan (mamba-1) inner recurrence, VMEM-tiled.

TPU adaptation of the CUDA selective-scan: grid over (batch, channel
blocks); the (L, block_d) dt/x tiles and (L, N) B/C tiles are VMEM-resident
and the recurrence h_{t} = exp(dt_t A) h_{t-1} + dt_t B_t x_t runs as a
``fori_loop`` over time with the (block_d, N) state held in VREGs/VMEM.

Channel blocks are independent (per-channel SSM), matching the model-axis
TP sharding of d_inner.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssm_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, hout_ref,
                *, seq_len):
    a = a_ref[...].astype(jnp.float32)            # (bd, N)
    h = h0_ref[...].astype(jnp.float32)           # (bd, N)

    def body(t, h):
        dt = dt_ref[pl.ds(t, 1), :][0]                             # (bd,)
        x = x_ref[pl.ds(t, 1), :][0]
        bt = b_ref[pl.ds(t, 1), :][0]                              # (N,)
        ct = c_ref[pl.ds(t, 1), :][0]
        dtf = dt.astype(jnp.float32)
        abar = jnp.exp(dtf[:, None] * a)                           # (bd, N)
        bx = (dtf * x.astype(jnp.float32))[:, None] \
            * bt.astype(jnp.float32)[None, :]
        h = abar * h + bx
        y = jnp.sum(h * ct.astype(jnp.float32)[None, :], axis=-1)  # (bd,)
        y_ref[pl.ds(t, 1), :] = y[None].astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, seq_len, body, h)
    hout_ref[...] = h.astype(hout_ref.dtype)


def selective_scan_kernel(dt, x, b_mat, c_mat, a, h0, *, block_d=128,
                          interpret=False):
    """dt/x: (B, L, D); b_mat/c_mat: (B, L, N); a: (D, N); h0: (B, D, N)
    -> (y (B, L, D), h_last (B, D, N))."""
    bsz, seq_len, d = dt.shape
    n = a.shape[1]
    block_d = min(block_d, d)
    assert d % block_d == 0
    grid = (bsz, d // block_d)
    y, h_last = pl.pallas_call(
        functools.partial(_ssm_kernel, seq_len=seq_len),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, seq_len, block_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, seq_len, block_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, seq_len, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, seq_len, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((block_d, n), lambda i, j: (j, 0)),
            pl.BlockSpec((None, block_d, n), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, seq_len, block_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, block_d, n), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, seq_len, d), x.dtype),
            jax.ShapeDtypeStruct((bsz, d, n), jnp.float32),
        ],
        interpret=interpret,
    )(dt, x, b_mat, c_mat, a, h0)
    return y, h_last
