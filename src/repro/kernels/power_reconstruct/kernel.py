"""ΔE/Δt reconstruction kernel over batched traces (fastotf2 analogue).

Input: cumulative energy counters + timestamps for many (node, device)
streams, already resampled to a common length S.  Output: instantaneous
power per interval with counter-wraparound correction — §III-A2 at
(devices × samples) scale.

Tiling: grid over device rows; each (block_rows, S) tile lives in VMEM and
the shifted-difference is computed with in-VMEM slices (no HBM re-reads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import auto_block_rows, pad_rows
from repro.kernels.power_reconstruct.ref import wrapped_diff


def _pr_kernel(e_ref, t_ref, o_ref, *, wrap_period: float):
    e = e_ref[...]
    t = t_ref[...]
    de = e[:, 1:] - e[:, :-1]
    if wrap_period > 0:
        de = jnp.where(de < -0.5 * wrap_period, de + wrap_period, de)
    dt = t[:, 1:] - t[:, :-1]
    p = de / jnp.maximum(dt, 1e-12)
    o_ref[...] = jnp.pad(p, ((0, 0), (1, 0)))


def power_reconstruct_kernel(energy, times, *, wrap_period: float = 0.0,
                             block_rows: int = 8, interpret: bool = False):
    """energy/times: (n_streams, S) -> power (n_streams, S); col 0 is 0."""
    n, s = energy.shape
    block_rows = min(block_rows, n)
    assert n % block_rows == 0
    grid = (n // block_rows,)
    return pl.pallas_call(
        functools.partial(_pr_kernel, wrap_period=wrap_period),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, s), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, s), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, s), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s), energy.dtype),
        interpret=interpret,
    )(energy, times)


def _pr_rows_kernel(e_ref, t_ref, w_ref, o_ref):
    e = e_ref[...]
    t = t_ref[...]
    w = w_ref[...]                       # (R, 1) per-row period; 0 = none
    de = wrapped_diff(e, w)
    dt = t[:, 1:] - t[:, :-1]
    p = de / jnp.maximum(dt, 1e-12)
    o_ref[...] = jnp.pad(p, ((0, 0), (1, 0)))


def _pr_fleet_kernel(e_ref, t_ref, w_ref, n_ref, p_ref, v_ref, r_ref):
    e = e_ref[...]
    t = t_ref[...]
    w = w_ref[...]                       # (R, 1) per-row period; 0 = none
    n = n_ref[...]                       # (R, 1) raw samples per row
    rows, s = e.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (rows, s), 1)
    valid = idx < n
    # dedup + monotonic in one comparison: cached re-reads republish an
    # unchanged (t, E) pair (==) and jitter can reorder timestamps (<) —
    # keep iff t strictly advanced; slot 0 is kept when the row is live
    t_prev = jnp.pad(t[:, :-1], ((0, 0), (1, 0)))
    adv = (idx == 0) | (t > t_prev)
    keep = valid & adv
    de = wrapped_diff(e, w)
    dt = t[:, 1:] - t[:, :-1]
    p = jnp.pad(de / jnp.maximum(dt, 1e-12), ((0, 0), (1, 0)))
    valid_out = keep & (idx >= 1)
    p_ref[...] = jnp.where(valid_out, p, 0.0)
    # masks leave as int32: Mosaic cannot store a boolean vector
    v_ref[...] = valid_out.astype(jnp.int32)
    # raw adjacent diffs only bridge duplicate runs when nothing is
    # reordered — flag rows that need the carry-forward fallback
    back = valid[:, 1:] & valid[:, :-1] & (t[:, 1:] < t[:, :-1])
    r_ref[...] = jnp.max(back.astype(jnp.int32), axis=1, keepdims=True)


def power_reconstruct_fleet_kernel(energy, times, wrap_row, n_row, *,
                                   block_rows=None,
                                   interpret: bool = False):
    """Fused fleet front-end: dedup mask + wrap fix + ΔE/Δt in one pass.

    energy/times: (n_streams, S) raw padded reads; wrap_row/n_row:
    (n_streams, 1) per-row wrap period and raw sample count.  Returns
    (power, valid, reordered): power[i, j] holds on (t[i, j-1], t[i, j]]
    where valid; ``reordered[i]`` flags rows whose timestamps went
    backwards (those need the carry-forward path — raw adjacent diffs
    only bridge duplicate runs, which republish identical pairs).
    """
    n, s = energy.shape
    block_rows = auto_block_rows(n, block_rows, interpret)
    args = pad_rows(block_rows, energy, times, wrap_row, n_row)
    grid = (args[0].shape[0] // block_rows,)
    power, valid, reordered = pl.pallas_call(
        _pr_fleet_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, s), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, s), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, s), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, s), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(args[0].shape, energy.dtype),
                   jax.ShapeDtypeStruct(args[0].shape, jnp.int32),
                   jax.ShapeDtypeStruct((args[0].shape[0], 1), jnp.int32)],
        interpret=interpret,
    )(*args)
    return power[:n], valid[:n] != 0, reordered[:n] != 0


def power_reconstruct_rows_kernel(energy, times, wrap_row, *,
                                  block_rows=None,
                                  interpret: bool = False):
    """Heterogeneous-fleet variant: per-row counter wrap periods.

    energy/times: (n_streams, S); wrap_row: (n_streams, 1) value-unit
    periods (0 disables) -> power (n_streams, S); column 0 is 0.
    ``block_rows=None`` auto-sizes via ``kernels.auto_block_rows``.
    """
    n, s = energy.shape
    block_rows = auto_block_rows(n, block_rows, interpret)
    args = pad_rows(block_rows, energy, times, wrap_row)
    grid = (args[0].shape[0] // block_rows,)
    return pl.pallas_call(
        _pr_rows_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, s), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, s), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, s), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(args[0].shape, energy.dtype),
        interpret=interpret,
    )(*args)[:n]
