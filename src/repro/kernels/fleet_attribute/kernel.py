"""Fused streaming-attribution kernel: ΔE/Δt + per-phase integration.

One pass over a (streams × samples) chunk of raw cumulative-counter reads
-> (streams × phases) energies.  Fuses the two stages the streaming
attributor otherwise chains (``power_reconstruct`` then
``phase_integrate``) so the instantaneous-power intermediate never leaves
VMEM — the inner loop of online fleet attribution.

Semantics per interval i (1..S-1) of each stream:
  ΔE_i wrap-corrected per row (reassociated, float32-exact — see
  power_reconstruct), held over (t_{i-1}, t_i]; phase j accumulates
  P_i · |(t_{i-1}, t_i] ∩ [a_j, b_j)|.  Duplicate reads republish equal
  (t, E) pairs -> zero-width intervals -> exactly zero energy, so raw
  padded chunks stream through without dedup compaction.

Tiling: grid over (stream rows × phase blocks); the (block_rows, S) chunk
tiles stay in VMEM across the phase block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.power_reconstruct.ref import wrapped_diff


def _fa_kernel(t_ref, e_ref, w_ref, ab_ref, o_ref):
    t = t_ref[...]                       # (R, S)
    e = e_ref[...]                       # (R, S)
    w = w_ref[...]                       # (R, 1) wrap period; 0 = none
    ab = ab_ref[...]                     # (Pb, 2)
    de = wrapped_diff(e, w)
    dt = t[:, 1:] - t[:, :-1]
    p = de / jnp.maximum(dt, 1e-12)      # (R, S-1) holds on (t_lo, t_hi]
    t_lo = t[:, :-1]
    t_hi = t[:, 1:]
    a = ab[:, 0][:, None, None]          # (Pb, 1, 1)
    b = ab[:, 1][:, None, None]
    lo = jnp.maximum(t_lo[None], a)
    hi = jnp.minimum(t_hi[None], b)
    overlap = jnp.maximum(hi - lo, 0.0)  # (Pb, R, S-1)
    o_ref[...] = jnp.sum(overlap * p[None], axis=-1).T   # (R, Pb)


def fleet_attribute_kernel(times, energy, wrap_row, phases, *,
                           block_rows=None, block_phases: int = 32,
                           interpret: bool = False):
    """times/energy: (n_streams, S) raw reads; wrap_row: (n_streams, 1);
    phases: (P, 2) -> (n_streams, P) joules.

    ``block_rows=None`` auto-sizes via ``kernels.auto_block_rows``.
    """
    from repro.kernels import auto_block_rows, pad_rows
    n, s = times.shape
    p = phases.shape[0]
    block_rows = auto_block_rows(n, block_rows, interpret)
    block_phases = min(block_phases, p)
    assert p % block_phases == 0
    rows = pad_rows(block_rows, times, energy, wrap_row)
    grid = (rows[0].shape[0] // block_rows, p // block_phases)
    return pl.pallas_call(
        _fa_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, s), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, s), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_phases, 2), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_phases),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows[0].shape[0], p), energy.dtype),
        interpret=interpret,
    )(*rows, phases)[:n]
