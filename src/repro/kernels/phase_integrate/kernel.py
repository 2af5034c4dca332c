"""Segmented per-phase energy integration kernel.

Given sample-and-hold power streams (t[i] closes the interval
(t[i-1], t[i]] holding watts[i]) and P phase windows [a_j, b_j), compute
E[stream, phase] = Σ_i watts_i · |(t_{i-1}, t_i] ∩ [a_j, b_j)| — the
inner
loop of phase-level attribution at (nodes × devices × phases) scale.

Tiling: grid over (stream rows × phase blocks); the (block_rows, S) power
tile stays in VMEM across the phase block.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pi_kernel(t_ref, p_ref, ab_ref, o_ref):
    t = t_ref[...]                       # (R, S)
    p = p_ref[...]                       # (R, S)
    ab = ab_ref[...]                     # (Pb, 2)
    t_lo = jnp.concatenate([t[:, :1], t[:, :-1]], axis=1)   # left edges
    a = ab[:, 0][:, None, None]          # (Pb, 1, 1)
    b = ab[:, 1][:, None, None]
    lo = jnp.maximum(t_lo[None], a)
    hi = jnp.minimum(t[None], b)
    overlap = jnp.maximum(hi - lo, 0.0)  # (Pb, R, S)
    e = jnp.sum(overlap * p[None], axis=-1)   # (Pb, R)
    o_ref[...] = e.T                     # (R, Pb)


def phase_integrate_kernel(times, watts, phases, *, block_rows=None,
                           block_phases: int = 32, interpret: bool = False):
    """times/watts: (n_streams, S); phases: (P, 2) -> (n_streams, P).

    ``block_rows=None`` auto-sizes via ``kernels.auto_block_rows``.
    """
    from repro.kernels import auto_block_rows, pad_rows
    n, s = times.shape
    p = phases.shape[0]
    block_rows = auto_block_rows(n, block_rows, interpret)
    block_phases = min(block_phases, p)
    assert p % block_phases == 0
    rows = pad_rows(block_rows, times, watts)
    grid = (rows[0].shape[0] // block_rows, p // block_phases)
    return pl.pallas_call(
        _pi_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, s), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, s), lambda i, j: (i, 0)),
            pl.BlockSpec((block_phases, 2), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_phases),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows[0].shape[0], p), watts.dtype),
        interpret=interpret,
    )(*rows, phases)[:n]
