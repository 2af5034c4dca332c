"""Fleet-wide masked lower-bound + hold/linear regrid kernel.

One call resamples every stream in the padded (fleet, samples) block onto
a shared uniform grid, with a per-row delay shift applied to the query
points — the alignment subsystem's inner primitive (regrid once to
estimate delays, regrid again delay-corrected to fuse).

Mosaic lowers no gather whose indices differ in shape from its operand,
so the kernel resolves the lower bound by a column sweep instead of the
oracle's halving search: it walks the sample columns in DESCENDING order
and, for every (query, row) pair, overwrites its selection with column
``j`` whenever ``j`` qualifies (``t[j] >= query`` inside the search
bounds, or ``j`` is the row's last sample).  The last write is the
smallest qualifying column — the lower bound on time-sorted rows, the
search's precondition.  The kernel only selects samples, never computes
with them: hold returns the selected value, linear the selected (t, v)
pair and the column before it, and ``linear_interp`` (shared with the
oracle) interpolates outside the kernel.  Both modes are then
bit-identical to ``grid_resample_ref``; inside a kernel the compiler may
contract the interpolation into a fused multiply-add.

Layout: rows on lanes, queries on sublanes.  Column ``j`` of 128 rows is
then one (1, 128) sublane load that broadcasts down the (block_grid,
128) tile, with no lane shuffles.  Grid: (row tiles, query tiles, sample
chunks); the chunk axis is a reduction that runs from the last chunk to
the first while the selections stay resident in their output tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.grid_resample.ref import linear_interp

LANE_ROWS = 128        # rows per tile (the lane width)
BLOCK_S = 512          # sample columns per reduction step


def _gr_kernel(*refs, n_cols: int, n_sel: int, block_s: int,
               n_chunks: int):
    # columns: t, v (hold selects v) or t, v, t', v' (linear selects all)
    t_ref, srcs = refs[0], refs[n_cols - n_sel:n_cols]
    lo_ref, last_ref, g_ref, d_ref, *accs = refs[n_cols:]
    k = pl.program_id(2)
    q = g_ref[...] + d_ref[...]            # (G, R) shifted queries

    @pl.when(k == 0)
    def _init():
        for a in accs:
            a[...] = jnp.zeros(a.shape, a.dtype)

    base = (n_chunks - 1 - k) * block_s
    lo = lo_ref[...]                       # (1, R) first searchable column
    last = last_ref[...]                   # (1, R) last sample column

    def body(c, sel_vals):
        jl = block_s - 1 - c
        jg = base + jl
        t_j = t_ref[pl.ds(jl, 1), :]       # (1, R)
        # one threshold per row: +inf always qualifies (the last sample,
        # where the search clamps), -inf never (outside [lo, last])
        key = jnp.where(jg == last, jnp.inf,
                        jnp.where((jg >= lo) & (jg <= last), t_j, -jnp.inf))
        take = q <= key
        return tuple(jnp.where(take, s[pl.ds(jl, 1), :], a)
                     for s, a in zip(srcs, sel_vals))

    sel = jax.lax.fori_loop(0, block_s, body, tuple(a[...] for a in accs))
    for a, val in zip(accs, sel):
        a[...] = val


def _rows_to_lanes(x, f_pad, s_pad):
    """(F, S) -> zero-padded, transposed (S_pad, F_pad)."""
    f, s = x.shape
    return jnp.pad(x, ((0, f_pad - f), (0, s_pad - s))).T


def _row_vector(x, f_pad):
    """(F, 1) per-row scalar -> zero-padded (1, F_pad) lane vector."""
    return jnp.pad(x.reshape(1, -1), ((0, 0), (0, f_pad - x.shape[0])))


def grid_resample_kernel(times, values, n_row, first_row, grid, delays, *,
                         mode: str = "hold", block_rows=None,
                         block_grid=None, interpret: bool = False):
    """times/values: (F, S) row-sorted; n_row/first_row/delays: (F, 1);
    grid: (G, 1) -> (out, mask) of shape (F, G).

    ``out[i, g]`` is stream i held (or linearly interpolated) at
    ``grid[g] + delays[i]``; ``mask`` marks in-span grid points.  G must
    be a multiple of ``block_grid`` (the public op pads).  Compiled, rows
    tile by ``LANE_ROWS`` and samples by ``BLOCK_S`` (both zero-padded
    here); interpret mode runs the whole block in one step.
    """
    f, s = times.shape
    g = grid.shape[0]
    if interpret:
        block_rows, block_grid, block_s = f, g, s
    else:
        block_rows = block_rows or LANE_ROWS
        # hold keeps 1 selection tile in vregs, linear 4: halve the tile
        block_grid = block_grid or (128 if mode == "hold" else 64)
        block_grid = min(block_grid, g)
        block_s = BLOCK_S
    assert g % block_grid == 0, (g, block_grid)
    f_pad = -(-f // block_rows) * block_rows
    s_pad = -(-s // block_s) * block_s
    n_chunks = s_pad // block_s

    n_i = n_row.astype(jnp.int32)
    first = first_row.astype(jnp.int32)
    last = jnp.maximum(n_i - 1, 0)
    lo = first if mode == "hold" else first + 1
    rows = [_row_vector(x, f_pad) for x in (lo, last)]
    if mode == "hold":
        cols, n_sel = [times, values], 1
    else:
        # column max(j - 1, 0): the lower interpolation end
        t_prev, v_prev = (jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)
                          for x in (times, values))
        cols, n_sel = [times, values, t_prev, v_prev], 4
    cols = [_rows_to_lanes(x, f_pad, s_pad) for x in cols]
    d_row = _row_vector(delays.astype(times.dtype), f_pad)

    col_spec = pl.BlockSpec((block_s, block_rows),
                            lambda i, j, k: (n_chunks - 1 - k, i))
    row_spec = pl.BlockSpec((1, block_rows), lambda i, j, k: (0, i))
    tile_spec = pl.BlockSpec((block_grid, block_rows),
                             lambda i, j, k: (j, i))
    sel = pl.pallas_call(
        functools.partial(_gr_kernel, n_cols=len(cols), n_sel=n_sel,
                          block_s=block_s, n_chunks=n_chunks),
        grid=(f_pad // block_rows, g // block_grid, n_chunks),
        in_specs=[col_spec] * len(cols) + [row_spec] * len(rows) + [
            pl.BlockSpec((block_grid, 1), lambda i, j, k: (j, 0)),
            row_spec],
        out_specs=[tile_spec] * n_sel,
        out_shape=[jax.ShapeDtypeStruct((g, f_pad), values.dtype)] * n_sel,
        interpret=interpret,
        name=f"grid_resample_{mode}",
    )(*cols, *rows, grid.astype(times.dtype), d_row)
    sel = [x[:, :f].T for x in sel]
    # the span mask and the interpolation as the oracle computes them
    ge = grid[:, 0][None, :] + delays
    t_first = jnp.take_along_axis(times, jnp.minimum(first, s - 1), axis=1)
    t_last = jnp.take_along_axis(times, last, axis=1)
    mask = (ge >= t_first) & (ge <= t_last) & (n_i > first)
    if mode == "hold":
        out = sel[0]
    else:
        t_hi, v_hi, t_lo, v_lo = sel
        out = linear_interp(ge, t_lo, t_hi, v_lo, v_hi)
    return jnp.where(mask, out, 0.0), mask
