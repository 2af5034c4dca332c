"""Pure-jnp oracle for batched masked regridding (canonical semantics).

The hold convention matches ``PowerSeries.resample``: the value at grid
point g is the sample whose interval contains g — the FIRST sample with
t >= g (lower bound).  On a reconstructed ΔE/Δt row that is exactly the
interval average covering g, so hold-regridding adds NO group delay (the
property the delay estimator relies on).  Duplicate publications form
equal-time runs; a lower bound lands on the first (informative) slot of
the run, so dedup falls out of the search order for free.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _ceil_log2(n: int) -> int:
    k = 0
    while (1 << k) < n:
        k += 1
    return k


def searchsorted_rows(t, target, lo, hi, *, xp=jnp):
    """Vectorized per-row lower bound: first j in [lo, hi) with
    ``t[r, j] >= target[r, g]`` (hi if none).

    t: (R, S) row-sorted times; target: (R, G); lo/hi: (R, 1) int32 search
    bounds (``lo`` skips leading undefined slots, ``hi`` masks padding).
    A fixed ``ceil(log2(S)) + 1`` halving steps — branch-free, identical
    math in the jnp oracle and (xp=numpy) the host mirror.
    """
    s = t.shape[1]
    lo = xp.broadcast_to(lo.astype(xp.int32), target.shape)
    hi = xp.broadcast_to(hi.astype(xp.int32), target.shape)
    for _ in range(_ceil_log2(s) + 1):
        mid = (lo + hi) // 2
        tm = xp.take_along_axis(t, xp.clip(mid, 0, s - 1), axis=1)
        go_right = (tm < target) & (mid < hi)
        lo = xp.where(go_right, mid + 1, lo)
        hi = xp.where(go_right, hi, xp.minimum(mid, hi))
    return lo


def searchsorted_rows_sorted(t, target, lo, hi):
    """``searchsorted_rows`` via vmapped ``jnp.searchsorted``.

    The lower bound is UNIQUE, so this returns bit-identical indices to
    the halving loop; XLA's sort-based lowering is ~2x faster on CPU
    where per-iteration gathers dominate the loop.  Masking: slots
    before ``lo`` clamp to -inf and slots at/after ``hi`` to +inf, which
    keeps each row sorted and pushes them out of every query's range.
    Used by the non-kernel (jnp) path; the Pallas kernel sweeps the
    columns instead (Mosaic lowers neither sort nor this gather).
    """
    s = t.shape[1]
    j = jnp.arange(s)[None, :]
    t_m = jnp.where(j < lo, -jnp.inf, jnp.where(j >= hi, jnp.inf, t))
    idx = jax.vmap(lambda a, v: jnp.searchsorted(a, v,
                                                 side="left"))(t_m, target)
    return jnp.clip(idx.astype(jnp.int32), lo, hi)


def grid_resample_ref(times, values, n_row, first_row, grid, delays,
                      *, mode: str = "hold", xp=jnp,
                      sorted_search: bool = False):
    """Canonical regrid semantics: the kernel's oracle and host mirror.

    times/values: (R, S); n_row/first_row/delays: (R, 1); grid: (G, 1).
    Returns (out, mask): out[r, g] is the stream's value at
    ``grid[g] + delays[r]`` (per-row delay-shifted lookup — shifting the
    QUERY right by d reads the stream where it lags the reference by d);
    mask marks grid points inside the row's defined span
    [t[first], t[n-1]].  ``sorted_search`` (jnp only) swaps the halving
    loop for the bit-identical sort-based lower bound — the fast CPU
    path.
    """
    r, s = times.shape
    ge = grid[:, 0][None, :] + delays            # (R, G) shifted queries
    n_i = n_row.astype(xp.int32)
    first = first_row.astype(xp.int32)
    if sorted_search:
        idx = searchsorted_rows_sorted(times, ge, first, n_i)
    else:
        idx = searchsorted_rows(times, ge, first, n_i, xp=xp)
    last = xp.maximum(n_i - 1, 0)
    t_first = xp.take_along_axis(times, xp.minimum(first, s - 1), axis=1)
    t_last = xp.take_along_axis(times, last, axis=1)
    mask = (ge >= t_first) & (ge <= t_last) & (n_i > first)
    if mode == "hold":
        j = xp.clip(idx, first, last)
        out = xp.take_along_axis(values, xp.clip(j, 0, s - 1), axis=1)
    else:                                        # linear
        j_hi = xp.clip(idx, first + 1, last)
        j_lo = xp.maximum(j_hi - 1, 0)
        t_lo = xp.take_along_axis(times, xp.clip(j_lo, 0, s - 1), axis=1)
        t_hi = xp.take_along_axis(times, xp.clip(j_hi, 0, s - 1), axis=1)
        v_lo = xp.take_along_axis(values, xp.clip(j_lo, 0, s - 1), axis=1)
        v_hi = xp.take_along_axis(values, xp.clip(j_hi, 0, s - 1), axis=1)
        out = linear_interp(ge, t_lo, t_hi, v_lo, v_hi, xp=xp)
    return xp.where(mask, out, 0.0), mask


def linear_interp(q, t_lo, t_hi, v_lo, v_hi, *, xp=jnp):
    """Value at ``q`` on the segment (t_lo, v_lo)-(t_hi, v_hi), clamped
    to its ends; the one expression the oracle and the kernel's wrapper
    both evaluate, so their results agree bit for bit."""
    frac = xp.clip((q - t_lo) / xp.maximum(t_hi - t_lo, 1e-12), 0.0, 1.0)
    return v_lo + frac * (v_hi - v_lo)
