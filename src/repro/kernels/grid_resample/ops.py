"""Public API: batched delay-shifted regridding onto a shared grid."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.grid_resample.kernel import grid_resample_kernel
from repro.kernels.grid_resample.ref import grid_resample_ref

GRID_ALIGN = 512


@functools.partial(jax.jit,
                   static_argnames=("mode", "interpret", "use_kernel"))
def grid_resample(times, values, n_row, first_row, grid, delays, *,
                  mode: str = "hold", interpret: bool = False,
                  use_kernel=None):
    """Resample a padded fleet onto one uniform grid -> (out, mask).

    times/values: (F, S); n_row/first_row/delays: (F,) or (F, 1);
    grid: (G,) or (G, 1) shared query points (``grid[g] + delays[i]`` is
    looked up in row i).  G is padded to ``GRID_ALIGN`` internally and
    sliced back, so callers pass any grid length.

    ``use_kernel=None`` auto-dispatches: the Pallas kernel when
    compiled, the bit-identical sort-based jnp lower bound under
    interpret (CPU), where emulating the kernel's column sweep costs
    far more than XLA's sort lowering.  ``True`` forces the kernel
    (parity tests), ``False`` the loop-based jnp oracle.
    """
    n_row = jnp.reshape(n_row, (-1, 1)).astype(jnp.int32)
    first_row = jnp.reshape(first_row, (-1, 1)).astype(jnp.int32)
    delays = jnp.reshape(delays, (-1, 1)).astype(times.dtype)
    grid = jnp.reshape(grid, (-1, 1)).astype(times.dtype)
    g = grid.shape[0]
    if use_kernel is None:
        use_kernel = not interpret
        if not use_kernel:
            out, mask = grid_resample_ref(times, values, n_row,
                                          first_row, grid, delays,
                                          mode=mode, sorted_search=True)
            return out, mask
    if not use_kernel:
        out, mask = grid_resample_ref(times, values, n_row, first_row,
                                      grid, delays, mode=mode)
        return out, mask
    pad = (-g) % GRID_ALIGN
    if pad:
        # replicate the last query point; the padded tail is sliced off
        grid = jnp.concatenate([grid, jnp.broadcast_to(grid[-1:],
                                                       (pad, 1))])
    out, mask = grid_resample_kernel(times, values, n_row, first_row,
                                     grid, delays, mode=mode,
                                     interpret=interpret)
    return out[:, :g], mask[:, :g]
