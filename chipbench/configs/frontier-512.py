"""Plain float64 reference for the frontier-512 deployment.

Straight numpy over whole traces, one sensor row or one device group at
a time, written from the semantics the configuration states and
independent of the program:

* a counter row's power is dE/dt between successive reads whose
  timestamp strictly advanced (a republished read adds nothing);
* every stream is read on one shared grid (origin: the fleet's first
  timestamp; step: half the fastest row's median read spacing), shifted
  by the sensor's stated delay, holding the first read at or after each
  grid time, inside the row's span;
* a device's fused power at a grid time is the inverse-variance weighted
  mean of the sensors that cover it, the variance of each sensor being
  its mean squared residual against the plain per-time mean of its
  group, over the whole capture (weight 1 / (variance + var_floor));
* a phase's energy is the fused power held backwards over each grid
  interval, integrated over the phase;
* for the counter cell, a row's phase energy is each read interval's
  dE spread evenly over the interval and cut at the phase edges.

NIC-rail offsets and PM slopes are removed first, as the configuration
states.  ``lower_precision`` rounds every value the way a bfloat16
computation would hold it: the control that a check must reject.
"""
from __future__ import annotations

import numpy as np

VAR_FLOOR = 0.25        # W^2: the configuration's fusion variance floor


def _bf16(x):
    """Round float64 values to the nearest bfloat16 (8 significant bits)."""
    x = np.asarray(x, np.float32)
    b = x.view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32).astype(np.float64)


def corrected(trace, offsets: dict, slopes: dict) -> np.ndarray:
    off = offsets.get(trace.name, 0.0)
    slope = slopes.get(trace.name, 1.0)
    v = np.asarray(trace.value, np.float64)
    if trace.spec["kind"] == "energy_cum":
        t = np.asarray(trace.t_measured, np.float64)
        return (v - off * (t - t[0])) / slope
    return (v - off) / slope


def unwrap(v: np.ndarray, period: float) -> np.ndarray:
    if not period:
        return v
    d = np.diff(v)
    jumps = np.cumsum(np.where(d < -0.5 * period, period, 0.0))
    return np.concatenate([v[:1], v[1:] + jumps])


def kept(t: np.ndarray) -> np.ndarray:
    """Reads whose timestamp strictly exceeds every earlier one."""
    prev = np.maximum.accumulate(np.concatenate([[-np.inf], t[:-1]]))
    return t > prev


def row_series(trace, offsets, slopes, period, low=False):
    """-> (t, value, t_first): power samples of one row (counters
    reconstructed) and the first time the row's power is defined."""
    t = np.asarray(trace.t_measured, np.float64)
    v = corrected(trace, offsets, slopes)
    if trace.spec["kind"] == "energy_cum":
        v = unwrap(v, period)
    k = kept(t)
    t, v = t[k], v[k]
    if low:
        t, v = _bf16(t), _bf16(v)
    if trace.spec["kind"] == "energy_cum":
        p = np.diff(v) / np.maximum(np.diff(t), 1e-12)
        if low:
            p = _bf16(p)
        return t[1:], p, t[1]
    return t, v, t[0]


def grid_of(traces) -> tuple:
    """(origin, step): the fleet's first timestamp and half the fastest
    row's median positive read spacing."""
    origin = min(float(tr.t_measured[0]) for tr in traces)
    best = np.inf
    for tr in traces:
        t = np.asarray(tr.t_measured, np.float64)
        if tr.spec["kind"] != "energy_cum":
            t = np.maximum.accumulate(t)
        d = np.diff(t)
        d = d[d > 0]
        if len(d):
            best = min(best, float(np.median(d)))
    return origin, 0.5 * best


def fused_phase_energies(group, phases, *, origin, step, t_end, offsets,
                         slopes, periods, low=False) -> np.ndarray:
    """(P,) joules of one device group on the shared grid."""
    k = len(group)
    n = int(np.floor((t_end - origin) / step + 1e-9)) + 1
    grid = origin + step * np.arange(n)
    vals = np.zeros((k, n))
    mask = np.zeros((k, n), bool)
    for r, (tr, period) in enumerate(zip(group, periods)):
        t, v, t_first = row_series(tr, offsets, slopes, period, low)
        q = grid + float(tr.spec.get("delay_s", 0.0))
        j = np.searchsorted(t, q, side="left")
        ok = (q >= t_first) & (q <= t[-1])
        vals[r] = np.where(ok, v[np.minimum(j, len(t) - 1)], 0.0)
        mask[r] = ok
    cnt = mask.sum(axis=0)
    mean = (vals * mask).sum(axis=0) / np.maximum(cnt, 1)
    resid = (vals - mean) * mask
    n_k = mask.sum(axis=1)
    var = (resid * resid).sum(axis=1) / np.maximum(n_k, 1)
    w = np.where(n_k > 1, 1.0 / (var + VAR_FLOOR), 0.0)
    wm = w[:, None] * mask
    wsum = wm.sum(axis=0)
    anyv = mask.any(axis=0) & (wsum > 0)
    fused = np.where(anyv, (wm * vals).sum(axis=0) / np.where(
        wsum > 0, wsum, 1.0), 0.0)
    sel = np.nonzero(mask.any(axis=0))[0]
    tv = grid[sel]
    t_lo = np.concatenate([tv[:1], tv[:-1]])
    fv = fused[sel]
    out = np.zeros((len(phases),))
    for p, (_, a, b) in enumerate(phases):
        ov = np.clip(np.minimum(tv, b) - np.maximum(t_lo, a), 0.0, None)
        out[p] = float(ov @ fv)
    return out


def counter_phase_energies(trace, phases, *, offsets, slopes, period,
                           low=False) -> np.ndarray:
    """(P,) joules of one cumulative counter row."""
    t = np.asarray(trace.t_measured, np.float64)
    v = unwrap(corrected(trace, offsets, slopes), period)
    k = kept(t)
    t, v = t[k], v[k]
    if low:
        t, v = _bf16(t), _bf16(v)
    de = np.diff(v)
    t_lo, t_hi = t[:-1], t[1:]
    p = de / np.maximum(t_hi - t_lo, 1e-12)
    out = np.zeros((len(phases),))
    for i, (_, a, b) in enumerate(phases):
        ov = np.clip(np.minimum(t_hi, b) - np.maximum(t_lo, a), 0.0, None)
        out[i] = float(ov @ p)
    return out
