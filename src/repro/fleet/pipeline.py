"""Composable streaming stage pipeline (streaming-first architecture).

Every online path in the repo is a chain of small stages, each consuming
one fixed-width (fleet, chunk) window plus an explicit carry-state
dataclass, so the whole chain stays O(fleet x chunk) memory however long
the run is:

    Ingest -> Reconstruct -> AlignTrack -> Regrid/Fuse -> PhaseAttribute

  Ingest       host-side chunk hygiene: reorder/duplicate repair
               (``sanitize_chunk``) or valid-mask carry-forward, plus the
               one-column carry that closes every hold interval across
               chunk boundaries.  Emits a CLOSED window: (F, C+1) edges
               whose column 0 is the previous window's last sample.
  Reconstruct  per-row wrap-corrected dE/dt through the
               ``power_reconstruct_rows`` Pallas kernel; power-sensor
               rows pass through untouched (mixed fleets supported).
  AlignTrack   ONLINE delay tracking: a per-stream sliding-window ring
               buffer on a uniform grid feeds the ``xcorr_align`` lag
               bank incrementally; per-window lag estimates are folded
               into an exponential moving average so slow sensor clock
               drift (``SensorSpec.drift_ppm``) is followed during the
               run instead of averaged away.
  Regrid/Fuse  carry-aware streaming ``grid_resample`` onto one shared
               output grid (per-row delay-shifted queries, advancing
               frontier) + the inverse-variance fusion statistics
               (per-stream sample counts and squared residuals against
               the cross-sensor mean), accumulated exactly as the batch
               ``align.fusion.fuse_gridded`` defines them.
  PhaseAttr    per-phase energy: the ``phase_integrate`` kernel for
               plain power streams, or the fused accumulator that folds
               each emitted grid window into per-(device, phase,
               coverage-pattern, stream) integrals and finalizes with
               the END-OF-RUN inverse-variance weights — so the
               streamed result equals the batch ``align_and_fuse`` ->
               ``attribute_energy_fused`` path to <=1e-5 without ever
               materializing a full trace.

Carry-state contract
--------------------
A stage owns exactly one carry dataclass; ``update`` consumes a window,
advances the carry, and returns the window for the next stage (or None
when nothing new can be emitted yet — e.g. the regrid frontier did not
advance).  ``flush`` emits whatever the carry still holds at shutdown.
Closed windows make every interval boundary explicit: sample j closes
(t[j-1], t[j]] and column 0 is zero-width on the first window, so no
stage ever needs to look behind the window it was handed.

Batch is the special case: ``attribute_energy_fused_streaming`` replays
packed traces through this chain in fixed-width chunks and matches the
batch path; ``FleetStream`` / ``StreamingPhaseAccumulator``
(fleet/streaming.py) are thin pre-built two-stage pipelines over the
same Ingest/attribute stages.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import pickle
from pathlib import Path

import jax
import numpy as np

from repro.core import tracing
from repro.fleet.config import resolve_config
from repro.fleet.packing import ROW_ALIGN, _round_up, pack_traces
from repro.fleet.reconstruct import auto_interpret

logger = logging.getLogger(__name__)

# phase_integrate/fleet_attribute tile phases in blocks of 32; phase
# tables are always padded UP to the tile (zero-width windows integrate
# to exactly zero energy, so padding is free).
PHASE_ALIGN = 32


def pad_phases(phases, dtype=np.float32):
    """(P, 2) [a, b) windows -> kernel-aligned array (zero-width padding).

    Always rounds the phase count up to the PHASE_ALIGN tile so the
    kernels' compiled block shape is uniform for ANY count — including
    1 < p < 32, which the pre-pipeline code left unpadded (the kernels
    then compiled a ragged (rows, p) lane tile; correct under interpret
    but off the supported tiling on compiled backends).
    """
    ph = np.asarray(phases, dtype).reshape(-1, 2)
    p = len(ph)
    if p == 0:
        raise ValueError("streaming attribution needs at least one phase "
                         "window (got an empty phase list)")
    pad = (-p) % PHASE_ALIGN
    if pad:
        ph = np.concatenate([ph, np.zeros((pad, 2), dtype)])
    return ph


class DataQualityError(ValueError):
    """A per-stage data-quality policy rejected this window."""


@dataclasses.dataclass(frozen=True)
class DataQualityPolicy:
    """Per-stage late/reordered/dropped-sample handling.

    Production sensor streams deliver reordered reads (``late``) and
    masked/dropped slots (``dropped``); the grid emit can leave streams
    with thin coverage (``min_coverage``, the per-row covered-slot
    fraction of an emitted window).  Every policy defaults to the
    pipeline's historical behavior — repair and keep counting — so a
    policy-less pipeline is byte-for-byte unchanged; ``"raise"`` turns
    the corresponding condition into a :class:`DataQualityError` at the
    window that violates it.  The counters and per-window flags this
    accounting produces surface through the ``data_quality``
    ``HealthRegistry`` source whether or not a policy is attached.
    """
    late: str = "repair"           # "repair" | "raise"
    dropped: str = "repair"        # "repair" | "raise"
    min_coverage: float = 0.0      # emitted-window covered-slot floor
    coverage: str = "flag"         # "flag" | "raise"

    def __post_init__(self):
        assert self.late in ("repair", "raise"), self.late
        assert self.dropped in ("repair", "raise"), self.dropped
        assert self.coverage in ("flag", "raise"), self.coverage
        assert 0.0 <= self.min_coverage <= 1.0, self.min_coverage


def sanitize_chunk(times, energy, valid=None, carry_t=None, carry_e=None,
                   return_counts: bool = False):
    """Host-side ingest guard: make each row's hold edges non-decreasing.

    Keeps a sample iff its timestamp strictly exceeds the running max of
    everything (valid) before it, including the previous chunk's carry;
    dropped samples (reordered reads, masked slots) are replaced by the
    last kept (t, E) so they become zero-width and their dE telescopes
    into the next kept interval.  The common all-monotonic case is a
    single vectorized check with no copies.

    ``return_counts=True`` additionally returns per-row data-quality
    tallies ``{"late", "masked"}`` ((F,) int64 each): ``late`` counts
    valid samples repaired because their timestamp had already been
    passed (reordered/late arrivals — equal-timestamp duplicates are a
    normal hold republication and are NOT counted), ``masked`` counts
    invalid slots.  The fast path returns zeros without extra work.
    """
    t = np.asarray(times)
    e = np.asarray(energy)
    f, c = t.shape
    if valid is not None and bool(np.all(valid)):
        valid = None
    # duplicates (==) already replicate the previous publication and need
    # no repair; only strict decreases and masked slots do.  Any reorder
    # episode starts with an adjacent decrease, so this cheap check is
    # sufficient to route to the repair path.
    if valid is None \
            and not (t[:, 1:] < t[:, :-1]).any() \
            and (carry_t is None or not (t[:, :1] < carry_t).any()):
        if return_counts:
            z = np.zeros((f,), np.int64)
            return t, e, {"late": z, "masked": z.copy()}
        return t, e
    lead = np.full((f, 1), -np.inf, t.dtype) if carry_t is None \
        else np.asarray(carry_t, t.dtype)
    tv = t if valid is None else np.where(valid, t, -np.inf)
    run_max = np.maximum.accumulate(
        np.concatenate([lead, tv], axis=1), axis=1)
    keep = tv > run_max[:, :-1]
    counts = None
    if return_counts:
        vm = (np.ones((f, c), bool) if valid is None
              else np.asarray(valid, bool))
        counts = {
            "late": (vm & ~keep
                     & (tv < run_max[:, :-1])).sum(axis=1,
                                                   dtype=np.int64),
            "masked": (~vm).sum(axis=1, dtype=np.int64),
        }
    idx = np.broadcast_to(np.arange(c)[None, :], (f, c))
    last = np.maximum.accumulate(np.where(keep, idx, -1), axis=1)
    src = np.maximum(last, 0)
    t_eff = np.take_along_axis(t, src, axis=1)
    e_eff = np.take_along_axis(e, src, axis=1)
    no_prev = last < 0                   # before the chunk's first kept
    if carry_t is not None:
        t_eff = np.where(no_prev, np.asarray(carry_t, t.dtype), t_eff)
        e_eff = np.where(no_prev, np.asarray(carry_e, e.dtype), e_eff)
    elif no_prev.any():
        # first chunk: collapse the leading dropped run onto the first
        # kept sample (zero width, zero energy)
        first = np.argmax(keep, axis=1)[:, None]
        t_eff = np.where(no_prev, np.take_along_axis(t, first, axis=1),
                         t_eff)
        e_eff = np.where(no_prev, np.take_along_axis(e, first, axis=1),
                         e_eff)
    if return_counts:
        return t_eff, e_eff, counts
    return t_eff, e_eff


def _maskfill_chunk(times, values, valid, carry_t, carry_v):
    """Valid-mask carry-forward (StreamingPhaseAccumulator semantics).

    Every slot takes the last VALID (t, v) at-or-before it; the carry
    column (always valid) seeds rows whose chunk starts invalid.  Unlike
    ``sanitize_chunk`` this keeps equal-timestamp valid samples — power
    chunks arrive on already-monotone grids.  Pure gathers: identical
    results on host and device.
    """
    t = np.asarray(times)
    v = np.asarray(values)
    f, c = t.shape
    ok = np.concatenate([np.ones((f, 1), bool), np.asarray(valid, bool)],
                        axis=1)
    aug_t = np.concatenate([np.asarray(carry_t, t.dtype), t], axis=1)
    aug_v = np.concatenate([np.asarray(carry_v, v.dtype), v], axis=1)
    idx = np.broadcast_to(np.arange(c + 1)[None, :], (f, c + 1))
    last = np.maximum.accumulate(np.where(ok, idx, 0), axis=1)
    return (np.take_along_axis(aug_t, last, axis=1)[:, 1:],
            np.take_along_axis(aug_v, last, axis=1)[:, 1:])


# ---------------------------------------------------------------------------
# Window types passed between stages
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ClosedWindow:
    """One (F, C+1) window of hold-interval EDGES.

    Column 0 is the carry edge (previous window's last sample; a
    zero-width duplicate of the first sample on the first window), so
    sample j>=1 closes the interval (times[:, j-1], times[:, j]].
    ``t_first[i]`` is row i's first DEFINED query time (+inf until
    known): the first sample for raw power rows, the first
    interval-closing edge for reconstructed counters — exactly the
    ``SeriesRows.first`` convention of the batch path.
    """
    times: np.ndarray          # (F, C+1)
    values: np.ndarray         # (F, C+1) cumulative J (counter) or W
    t_first: np.ndarray        # (F,) float64


@dataclasses.dataclass
class GriddedWindow:
    """Emitted slots [lo, lo+G) of the shared uniform output grid."""
    lo: int                    # first slot index
    grid: np.ndarray           # (G,) float64 slot times (pipeline time)
    values: np.ndarray         # (n_streams, G) regridded power
    mask: np.ndarray           # (n_streams, G) defined-span coverage


# ---------------------------------------------------------------------------
# Stage 1: Ingest
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class IngestCarry:
    """Last sanitized hold edge per row (the one-column cross-chunk
    state every streaming path shares)."""
    t: np.ndarray              # (F, 1)
    v: np.ndarray              # (F, 1)


class IngestStage:
    """Raw (times, values[, valid]) chunks -> sanitized closed windows.

    mode="sanitize"  reorder/duplicate repair incl. masked slots
                     (FleetStream / counter semantics);
    mode="maskfill"  valid-mask carry-forward only, equal timestamps
                     kept (StreamingPhaseAccumulator semantics).

    kind_row (sanitize mode): True marks cumulative-counter rows, whose
    defined span opens at the first interval-CLOSING edge (the first
    strict timestamp advance — reconstruction's column 0 carries no
    power); raw power rows open at their FIRST sample, matching the
    batch ``SeriesRows.first`` convention.  None treats every row as a
    counter (the FleetStream case, which never consults t_first).
    """

    def __init__(self, n_streams: int, *, mode: str = "sanitize",
                 kind_row=None, dq_policy: DataQualityPolicy = None):
        assert mode in ("sanitize", "maskfill")
        self.mode = mode
        self.n_streams = n_streams
        self.kind_row = (None if kind_row is None
                         else np.asarray(kind_row, bool).reshape(-1))
        self.dq_policy = dq_policy
        self.carry: IngestCarry = None
        self._t_first = None
        self._unseeded = None      # (F,) bool: rows with no valid sample yet
        self.dq_late = None        # (F,) int64 cumulative repair counts
        self.dq_masked = None
        self.dq_last: dict = {}    # this window's per-row counts

    def reset(self):
        self.carry = None
        self._t_first = None
        self._unseeded = None
        self.dq_late = None
        self.dq_masked = None
        self.dq_last = {}
        return self

    def _dq_account(self, counts: dict):
        """Fold one window's repair tallies; enforce the policy."""
        if self.dq_late is None:
            self.dq_late = np.zeros_like(counts["late"])
            self.dq_masked = np.zeros_like(counts["masked"])
        self.dq_late += counts["late"]
        self.dq_masked += counts["masked"]
        self.dq_last = counts
        p = self.dq_policy
        if p is None:
            return
        n = self.n_streams
        if p.late == "raise" and counts["late"][:n].any():
            i = int(np.argmax(counts["late"][:n] > 0))
            raise DataQualityError(
                f"ingest: row {i} delivered "
                f"{int(counts['late'][i])} late/reordered sample(s) "
                f"this window and the policy says raise")
        if p.dropped == "raise" and counts["masked"][:n].any():
            i = int(np.argmax(counts["masked"][:n] > 0))
            raise DataQualityError(
                f"ingest: row {i} dropped "
                f"{int(counts['masked'][i])} sample slot(s) this "
                f"window and the policy says raise")

    def update(self, times, values, valid=None) -> ClosedWindow:
        t = np.asarray(times)
        v = np.asarray(values)
        first = self.carry is None
        if first:
            # zero-width seed at the first VALID sample — seeding from a
            # masked slot would turn its garbage timestamp into an edge.
            # Rows with NO valid sample yet stay unseeded: their carry
            # holds the placeholder slot (every emitted edge zero-width,
            # zero energy) and the real seed is deferred to the first
            # chunk that delivers a valid sample for the row.
            if valid is None:
                fi = np.zeros((t.shape[0], 1), np.intp)
                self._unseeded = np.zeros((t.shape[0],), bool)
            else:
                vb = np.asarray(valid, bool)
                fi = np.argmax(vb, axis=1)[:, None]
                self._unseeded = ~vb.any(axis=1)
            seed_t = np.take_along_axis(t, fi, axis=1)
            seed_v = np.take_along_axis(v, fi, axis=1)
            self.carry = IngestCarry(t=seed_t, v=seed_v)
            seed64 = np.where(self._unseeded, np.inf,
                              seed_t[:, 0].astype(np.float64))
            if self.mode == "maskfill":
                # power rows: the first valid sample opens the span
                self._t_first = seed64
            elif self.kind_row is None:
                self._t_first = np.full((t.shape[0],), np.inf)
            else:
                # counters wait for the first closing edge; power rows
                # open at the seed (the later minimum() never undercuts)
                self._t_first = np.where(self.kind_row, np.inf, seed64)
        elif self._unseeded is not None and self._unseeded.any():
            # deferred seeding: a row dark through every previous chunk
            # seeds zero-width at its first valid sample NOW, so the
            # interval from the placeholder to the first real sample
            # carries no fabricated counter delta
            vb = None if valid is None else np.asarray(valid, bool)
            has = np.ones((t.shape[0],), bool) if vb is None \
                else vb.any(axis=1)
            reseed = self._unseeded & has
            if reseed.any():
                fi = (np.zeros((t.shape[0], 1), np.intp) if vb is None
                      else np.argmax(vb, axis=1)[:, None])
                st = np.take_along_axis(t, fi, axis=1)
                sv = np.take_along_axis(v, fi, axis=1)
                r = reseed[:, None]
                self.carry = IngestCarry(
                    t=np.where(r, st, self.carry.t),
                    v=np.where(r, sv, self.carry.v))
                st64 = st[:, 0].astype(np.float64)
                if self.mode == "maskfill":
                    self._t_first = np.where(reseed, st64, self._t_first)
                elif self.kind_row is not None:
                    self._t_first = np.where(
                        reseed & ~self.kind_row,
                        np.minimum(self._t_first, st64), self._t_first)
                self._unseeded = self._unseeded & ~reseed
        if self.mode == "sanitize":
            t_eff, v_eff, dq = sanitize_chunk(t, v, valid,
                                              self.carry.t, self.carry.v,
                                              return_counts=True)
            self._dq_account(dq)
        elif valid is None:
            t_eff, v_eff = t, v
            self._dq_account({
                "late": np.zeros((t.shape[0],), np.int64),
                "masked": np.zeros((t.shape[0],), np.int64)})
        else:
            t_eff, v_eff = _maskfill_chunk(t, v, valid,
                                           self.carry.t, self.carry.v)
            self._dq_account({
                "late": np.zeros((t.shape[0],), np.int64),
                "masked": (~np.asarray(valid, bool)).sum(
                    axis=1, dtype=np.int64)})
        t_aug = np.concatenate([self.carry.t, t_eff], axis=1)
        v_aug = np.concatenate([self.carry.v, v_eff], axis=1)
        if self.mode == "sanitize" and np.isinf(self._t_first).any():
            # first strict advance past the seed = first closing edge
            adv = t_aug > t_aug[:, :1]
            j = np.argmax(adv, axis=1)
            tf = np.where(adv.any(axis=1),
                          t_aug[np.arange(len(j)), j].astype(np.float64),
                          np.inf)
            self._t_first = np.minimum(self._t_first, tf)
        self.carry = IngestCarry(t=t_aug[:, -1:], v=v_aug[:, -1:])
        return ClosedWindow(times=t_aug, values=v_aug,
                            t_first=self._t_first)


# ---------------------------------------------------------------------------
# Stage 2: Reconstruct
# ---------------------------------------------------------------------------

class ReconstructStage:
    """Counter rows -> instantaneous power via wrap-corrected dE/dt.

    Stateless given closed windows (the carry edge closes the boundary
    interval, so dE telescopes across chunks with no extra state).
    ``kind_row`` marks counter rows; power rows pass through.  Device
    path runs the ``power_reconstruct_rows`` Pallas kernel.
    """

    def __init__(self, kind_row, wrap_row=None, *, interpret=None,
                 use_kernel: bool = True):
        self.kind_row = np.asarray(kind_row, bool).reshape(-1)
        f = len(self.kind_row)
        self.wrap_row = (np.zeros((f, 1), np.float64) if wrap_row is None
                         else np.asarray(wrap_row,
                                         np.float64).reshape(f, 1))
        self.interpret = auto_interpret(interpret)
        self.use_kernel = use_kernel

    def reset(self):
        return self

    def update(self, chunk: ClosedWindow) -> ClosedWindow:
        t, v = chunk.times, chunk.values
        if not self.kind_row.any():
            return chunk
        power = np.asarray(_reconstruct_window(
            t, v, self.wrap_row.astype(t.dtype),
            interpret=self.interpret, use_kernel=self.use_kernel))
        out_v = np.where(self.kind_row[:, None], power.astype(v.dtype), v)
        return ClosedWindow(times=t, values=out_v, t_first=chunk.t_first)


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def _reconstruct_window(t, v, wrap_row, *, interpret, use_kernel):
    from repro.kernels.power_reconstruct.kernel import (
        power_reconstruct_rows_kernel)
    from repro.kernels.power_reconstruct.ref import (
        reconstruct_power_rows_ref)
    if use_kernel:
        return power_reconstruct_rows_kernel(v, t, wrap_row,
                                             interpret=interpret)
    return reconstruct_power_rows_ref(v, t, wrap_row)


# ---------------------------------------------------------------------------
# Shared carry piece: raw-sample tails for window-crossing grid queries
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TailCarry:
    """Last ``T`` raw samples per row + the newest time that slid out.

    Grid queries shifted by per-row delays can land slightly BEFORE the
    current window (the emit frontier trails the slowest stream); the
    tail keeps enough history to resolve them.  ``dropped_t`` bounds
    what the tail can still answer: a hold lookup needs every sample
    AT/AFTER the query, so queries must stay > dropped_t.
    """
    t: np.ndarray              # (F, T)
    v: np.ndarray              # (F, T)
    dropped_t: np.ndarray      # (F,) float64


class _RowTail:
    def __init__(self, width: int):
        self.width = width
        self.carry: TailCarry = None

    def reset(self):
        self.carry = None
        return self

    def augmented(self, chunk: ClosedWindow):
        """[-inf sentinel | tail | window] rows for ``grid_resample``.

        The sentinel column neutralizes the op's own lower-span mask
        (its t_first would otherwise be the arbitrary tail start); the
        true per-row span mask is re-applied from ``chunk.t_first`` by
        ``_query_grid``.  The sentinel is never selected by a lower
        bound (first sample >= query) for any finite query.
        """
        t, v = chunk.times, chunk.values
        f = t.shape[0]
        sent_t = np.full((f, 1), -np.inf, t.dtype)
        sent_v = np.zeros((f, 1), v.dtype)
        if self.carry is None:
            # zero-width replicas of the first edge: search-invisible
            tail_t = np.repeat(t[:, :1], self.width, axis=1)
            tail_v = np.repeat(v[:, :1], self.width, axis=1)
            self.carry = TailCarry(t=tail_t, v=tail_v,
                                   dropped_t=np.full((f,), -np.inf))
        return (np.concatenate([sent_t, self.carry.t, t], axis=1),
                np.concatenate([sent_v, self.carry.v, v], axis=1))

    def advance(self, chunk: ClosedWindow):
        """Slide the window into the tail (call after querying).

        ``dropped_t`` only records dropped samples STRICTLY older than
        the retained head: equal-time columns are zero-width replicas
        whose original still answers the lower-bound lookup, and slow
        rows are mostly such replicas.
        """
        t = np.concatenate([self.carry.t, chunk.times], axis=1)
        v = np.concatenate([self.carry.v, chunk.values], axis=1)
        gone = t[:, :-self.width].astype(np.float64)
        head = t[:, -self.width].astype(np.float64)[:, None]
        strict = np.where(gone < head, gone, -np.inf).max(axis=1) \
            if gone.shape[1] else np.full((t.shape[0],), -np.inf)
        dropped = np.maximum(self.carry.dropped_t, strict)
        self.carry = TailCarry(t=t[:, -self.width:], v=v[:, -self.width:],
                               dropped_t=dropped)

    def check_reach(self, q_min: np.ndarray, what: str):
        """Raise when a query needs samples older than the tail holds."""
        bad = q_min <= self.carry.dropped_t
        if bad.any():
            i = int(np.argmax(bad))
            raise ValueError(
                f"{what}: row {i} query at t={q_min[i]:.6f} reaches "
                f"behind the {self.width}-sample tail (oldest answerable "
                f"t>{self.carry.dropped_t[i]:.6f}); widen `tail` or "
                f"reduce the delay range")


def _query_grid(rows_t, rows_v, grid64, delays64, t_first, *,
                interpret, use_kernel):
    """Hold-resample all rows at ``grid + delay[row]`` -> (vals, mask).

    Runs the ``grid_resample`` kernel/op with queries formed in the row
    dtype, exactly as the batch ``regrid_rows`` does, so streamed and
    batch lookups compare the SAME float32 values at hold
    discontinuities.
    """
    f, s = rows_t.shape
    dtype = rows_t.dtype
    n_row = np.full((f, 1), s, np.int32)
    first_row = np.zeros((f, 1), np.int32)
    g = np.asarray(grid64, np.float64).astype(dtype)
    d = np.asarray(delays64, np.float64).astype(dtype).reshape(f, 1)
    import jax.numpy as jnp
    from repro.kernels.grid_resample.ops import grid_resample
    # pad the query count to a coarse multiple (replicating the last
    # point) so the per-window jit sees a handful of shapes instead
    # of one per distinct frontier advance
    gq = len(g)
    pad = (-gq) % 256
    g_in = np.concatenate([g, np.full((pad,), g[-1], dtype)]) \
        if pad else g
    out, mask = grid_resample(jnp.asarray(rows_t), jnp.asarray(rows_v),
                              n_row, first_row, jnp.asarray(g_in),
                              jnp.asarray(d[:, 0]), mode="hold",
                              interpret=interpret,
                              use_kernel=use_kernel)
    out = np.asarray(out)[:, :gq]
    mask = np.asarray(mask)[:, :gq]
    ge = g[None, :] + d                      # row-dtype query, as the op
    span = ge >= np.asarray(t_first, np.float64).astype(dtype)[:, None]
    mask = mask & span
    return np.where(mask, out, 0).astype(dtype, copy=False), mask


# ---------------------------------------------------------------------------
# Stage 3: AlignTrack — online per-sensor delay tracking
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AlignCarry:
    """Sliding uniform-grid ring + the tracked per-row delay EMA."""
    ring_v: np.ndarray         # (F, W) regridded power on the track grid
    ring_m: np.ndarray         # (F, W) coverage
    next_slot: int             # global index of the next unfilled slot
    last_est_slot: int
    delay: np.ndarray          # (F,) float64 EMA-tracked lag (seconds)
    seen: np.ndarray           # (F,) bool — row has >=1 accepted estimate


@dataclasses.dataclass
class DelayTrackPoint:
    """One per-window re-estimate (kept for tests/diagnostics)."""
    t_lo: float                # window start (pipeline time)
    t_hi: float
    t_center: float
    raw: np.ndarray            # (n_streams,) this window's lag estimate
    ema: np.ndarray            # (n_streams,) tracked delay after folding
    peak: np.ndarray           # (n_streams,) correlation at the peak


class AlignTrackStage:
    """Re-estimate per-stream delays on sliding windows, online.

    Maintains an (F, window) ring buffer on a uniform ``grid_step`` grid
    (filled incrementally from each closed window through the same hold
    resample the batch path uses), and every ``hop`` new slots feeds the
    FULL ring to the ``xcorr_align`` lag bank against the reference —
    one MXU matmul per re-estimate — then folds the per-window lag into
    an exponential moving average.  Sensor clock drift
    (``SensorSpec.drift_ppm``) moves the true lag during the run; the
    EMA follows it, where a whole-trace batch estimate can only report
    the mid-run average.

    reference: callable(times_f64) -> (W,) watts — e.g. the known phase
    schedule ``lambda t: truth.power_at(t + t0_abs)``.  When None, each
    group's FIRST stream is its own reference (``groups`` required),
    mirroring the batch default.  Estimates with peak correlation below
    ``min_corr`` leave the EMA untouched.

    grid_step MUST be derived from the MEASURED sample cadence (e.g.
    0.5x the median spacing, as batch ``default_grid`` does), not from a
    nominal round number: a step exactly commensurate with the sensor's
    production interval beats against the hold-resampled intervals and
    biases every window's sub-sample peak by up to half a step —
    measured -0.25 ms at step 0.500 ms on a 1 ms sensor vs -0.03 ms at
    the measured-cadence 0.506 ms.

    Multi-host (``collectives`` + ``shard``): the tracker becomes
    shard-aware — the ring ORIGIN and the per-update fill frontier are
    all-reduced (min), so every host fills identical global grid slots
    and hits the hop boundaries in lockstep; each host scores only its
    own rows (the lag bank is row-local once the tiling is pinned — see
    below), folds its rows' lags into the local EMA exactly as the
    single-host tracker would, and hands the per-window (lag, weight)
    pairs to ``RegridFuseStage``, which sums them across hosts inside
    its existing frontier round-trip and folds the fleet-wide vector
    into the shared ``delay_fleet_s`` EMA — every host therefore holds
    (and applies, for its rows) IDENTICAL delay corrections.  Three
    rules make this bit-stable for any host<-group assignment and any
    process count (the determinism contract of
    ``repro.distributed.multihost``):

      1. the xcorr row tiling is pinned to the fleet row tile
         (``ROW_ALIGN``), so a row's score never depends on how many
         other rows the host happens to score with it;
      2. the (lag, weight) sum is a left fold in process-id order, and
         exclusive row ownership makes it EXACT (each element is
         non-zero on one host only);
      3. origin/frontier mins are float64 all-reduces of identical
         per-row inputs — min is exact.
    """

    def __init__(self, n_streams: int, *, grid_step: float,
                 reference=None, groups=None, window: int = 2048,
                 hop: int = 512, max_lag: int = 64, ema: float = 0.5,
                 min_corr: float = 0.2, min_fill: int = None,
                 tail: int = 256, delay0=None, collectives=None,
                 shard=None, interpret=None, use_kernel: bool = True):
        assert reference is not None or groups is not None, \
            "AlignTrack needs a reference schedule or group structure"
        self.n_streams = n_streams
        self.step = float(grid_step)
        self.reference = reference
        self.groups = groups
        self.window = int(window)
        self.hop = int(hop)
        self.max_lag = int(max_lag)
        self.ema = float(ema)
        self.min_corr = float(min_corr)
        self.min_fill = (self.window // 2 if min_fill is None
                         else int(min_fill))
        self.collectives = collectives
        self.shard = shard
        if collectives is not None:
            assert shard is not None, \
                "synchronized tracking needs the HostShard (global " \
                "row ids place this host's lags in the fleet vector)"
            assert use_kernel is not False, \
                "synchronized tracking requires the kernel scorer — " \
                "the jnp reference's full-fleet matmul ignores the " \
                "pinned row tile and is not partition-invariant"
            assert self.min_corr > 0.0, \
                "synchronized tracking needs min_corr > 0 (the zero " \
                "frames of hop-less windows must never pass the gate)"
        self.interpret = auto_interpret(interpret)
        self.use_kernel = use_kernel
        self._tail = _RowTail(tail)
        self._delay0 = (np.zeros((0,)) if delay0 is None
                        else np.asarray(delay0, np.float64))
        self.origin = None
        self.carry: AlignCarry = None
        self.history: list = []
        self._pending = None
        self.delay_fleet = None    # (n_global,) shared EMA (synced mode)
        self._seen_fleet = None

    def reset(self):
        self.origin = None
        self.carry = None
        self.history = []
        self._pending = None
        self.delay_fleet = None
        self._seen_fleet = None
        self._tail.reset()
        return self

    @property
    def delay_s(self) -> np.ndarray:
        """(F,) currently tracked per-row delay (float64 seconds)."""
        if self.carry is None:
            raise RuntimeError("AlignTrack has seen no data yet")
        return self.carry.delay

    @property
    def synced(self) -> bool:
        """True when tracking state is shared over HostCollectives."""
        return self.collectives is not None

    @property
    def fleet_delay_s(self) -> np.ndarray:
        """(n_global,) fleet-wide tracked delays — identical on every
        host (synced mode only)."""
        assert self.synced, "fleet_delay_s needs collectives"
        if self.delay_fleet is None:
            raise RuntimeError("AlignTrack has seen no data yet")
        return self.delay_fleet.copy()

    def _init(self, chunk: ClosedWindow):
        f = chunk.times.shape[0]
        n = self.n_streams
        origin = float(chunk.times[:n, 0].astype(np.float64).min())
        delay = np.zeros((f,), np.float64)
        if len(self._delay0):
            delay[:len(self._delay0)] = self._delay0
        if self.synced:
            # shared ring origin: every host fills the SAME global grid
            # slots, so hop boundaries (and hence every estimate's
            # window) land in lockstep fleet-wide
            n_global = int(self.shard.row_offsets[-1])
            seed = np.zeros((n_global,))
            seed[self.shard.row_ids] = delay[:n]
            origin, seed = self.collectives.allreduce_framed(
                origin, seed, scalar_op="min")
            self.delay_fleet = seed
            self._seen_fleet = np.zeros((n_global,), bool)
        self.origin = origin
        self.carry = AlignCarry(
            ring_v=np.zeros((f, self.window), chunk.values.dtype),
            ring_m=np.zeros((f, self.window), bool),
            next_slot=0, last_est_slot=0, delay=delay,
            seen=np.zeros((f,), bool))

    def update(self, chunk: ClosedWindow) -> ClosedWindow:
        if self.carry is None:
            self._init(chunk)
        c = self.carry
        n = self.n_streams
        rows_t, rows_v = self._tail.augmented(chunk)
        frontier = float(chunk.times[:n, -1].astype(np.float64).min())
        if self.synced:
            # fill to the globally slowest stream: the ring advances —
            # and the hop re-estimates fire — identically on every host
            frontier = self.collectives.allreduce_min(frontier)
        hi = int(np.floor((frontier - self.origin) / self.step - 0.01))
        if hi >= c.next_slot:
            idx = np.arange(c.next_slot, hi + 1)
            grid64 = self.origin + self.step * idx
            q_min = np.full((rows_t.shape[0],), grid64[0])
            self._tail.check_reach(q_min, "AlignTrack")
            vals, mask = _query_grid(rows_t, rows_v, grid64,
                                     np.zeros(rows_t.shape[0]),
                                     chunk.t_first,
                                     interpret=self.interpret,
                                     use_kernel=self.use_kernel)
            k = len(idx)
            if k >= self.window:
                c.ring_v = vals[:, -self.window:]
                c.ring_m = mask[:, -self.window:]
            else:
                c.ring_v = np.concatenate([c.ring_v[:, k:], vals], axis=1)
                c.ring_m = np.concatenate([c.ring_m[:, k:], mask], axis=1)
            c.next_slot = hi + 1
        self._tail.advance(chunk)
        if (c.next_slot - c.last_est_slot >= self.hop
                and c.next_slot >= self.min_fill):
            self._estimate()
            c.last_est_slot = c.next_slot
        return chunk

    def _estimate(self):
        from repro.align.delay import estimate_delays, stream_reference
        c = self.carry
        n = self.n_streams
        w_idx = np.arange(c.next_slot - self.window, c.next_slot)
        times64 = self.origin + self.step * w_idx
        f = c.ring_v.shape[0]
        raw = np.zeros((f,))
        peak = np.zeros((f,))

        uk = True if self.use_kernel is None else self.use_kernel

        def run(vals, mask, ref):
            # the row tile is PINNED (ROW_ALIGN) so each row's score is
            # bit-identical however many rows this host scores with it
            # — the partition-invariance rule the multi-host tracker
            # depends on (harmless single-host)
            return estimate_delays(vals, mask.astype(vals.dtype), ref,
                                   step=self.step, max_lag=self.max_lag,
                                   interpret=self.interpret,
                                   use_kernel=uk,
                                   block_rows=ROW_ALIGN)

        if self.reference is not None:
            ref = np.asarray(self.reference(times64), np.float64)
            est = run(c.ring_v, c.ring_m, ref)
            raw, peak = est.delay_s, est.peak_corr
        else:
            lo = 0
            for g in self.groups:
                hi = lo + g
                ref = stream_reference(c.ring_v[lo], c.ring_m[lo])
                est = run(c.ring_v[lo:hi], c.ring_m[lo:hi], ref)
                raw[lo:hi], peak[lo:hi] = est.delay_s, est.peak_corr
                lo = hi
        good = peak >= self.min_corr
        good[n:] = False                      # padding rows never track
        a = np.where(c.seen, self.ema, 1.0)   # first estimate: direct
        c.delay = np.where(good, (1 - a) * c.delay + a * raw, c.delay)
        c.seen = c.seen | good
        if self.synced:
            # queue this window's (lag, weight) pairs for the framed
            # reduce that rides RegridFuse's next frontier round-trip
            self._pending = (raw[:n].copy(), peak[:n].copy())
        self.history.append(DelayTrackPoint(
            t_lo=float(times64[0]), t_hi=float(times64[-1]),
            t_center=float(0.5 * (times64[0] + times64[-1])),
            raw=raw[:n].copy(), ema=c.delay[:n].copy(),
            peak=peak[:n].copy()))

    def pending_contribution(self) -> np.ndarray:
        """(2, n_global) framed (lag, weight) contribution — this host's
        rows' raw per-window lags and peak correlations since the last
        fold, zeros elsewhere (and all-zero when no hop fired: the
        zero weights fail the ``min_corr`` gate on every host, so a
        hop-less frame folds nothing).  Consumed by ``fold_fleet`` after
        ``RegridFuseStage`` sums it across hosts."""
        assert self.synced
        n_global = len(self.delay_fleet)
        out = np.zeros((2, n_global))
        if self._pending is not None:
            raw, peak = self._pending
            out[0, self.shard.row_ids] = raw
            out[1, self.shard.row_ids] = peak
            self._pending = None
        return out

    def fold_fleet(self, reduced: np.ndarray):
        """Fold the cross-host-summed (lag, weight) vectors into the
        shared fleet EMA — the SAME gate/fold arithmetic as the local
        ``_estimate``, applied to bit-identical inputs (exclusive row
        ownership makes the sums exact), so ``delay_fleet`` stays
        bitwise consistent with every owner's local ``delay`` carry."""
        assert self.synced
        raw, peak = np.asarray(reduced, np.float64).reshape(2, -1)
        good = peak >= self.min_corr
        a = np.where(self._seen_fleet, self.ema, 1.0)
        self.delay_fleet = np.where(
            good, (1 - a) * self.delay_fleet + a * raw, self.delay_fleet)
        self._seen_fleet = self._seen_fleet | good


# ---------------------------------------------------------------------------
# Stage 4: Regrid/Fuse — streaming resample + fusion statistics
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FuseCarry:
    """Emit frontier + the additive inverse-variance sufficient stats.

    ``n_k``/``ssr`` accumulate exactly the quantities batch
    ``fuse_gridded`` reduces over the whole grid (per-stream valid
    counts and squared residuals against the per-slot unweighted
    cross-sensor mean), so the END-OF-RUN weights equal the batch
    weights without holding any grid column beyond the current window.
    """
    next_slot: int
    n_k: np.ndarray            # (n_streams,) float64
    ssr: np.ndarray            # (n_streams,) float64


class RegridFuseStage:
    """Power windows -> delay-corrected shared-grid slots + fusion stats.

    The output grid is fixed (``origin + step * slot``); each update
    emits every slot whose per-row query ``slot_time + delay[row]`` is
    already closed by ALL active rows (the emit frontier — trailing the
    slowest stream keeps hold lookups final).  Queries resolve against
    [tail | window] through ``grid_resample``; delays come live from an
    ``AlignTrackStage`` or stay fixed.  ``flush`` emits the remaining
    slots once the run ends (rows that end early mask off exactly as in
    the batch regrid).

    Multi-host: with ``collectives`` (a ``distributed.multihost``
    HostCollectives), the per-host frontier is all-reduced (min) every
    update, so every host emits exactly the same grid-slot windows in
    lockstep regardless of which rows it owns.  Emission batching fixes
    the floating-point accumulation order of the fusion statistics and
    the downstream phase integrals, so the fleet-wide fused energies are
    bit-stable under ANY host←row assignment — a host must therefore
    drive its stage through the same number of ``update``/``flush``
    calls as every other host (time-aligned replay windows over the
    all-reduced global span do exactly this).  When a SYNCED
    ``AlignTrackStage`` feeds the delays, its per-window (lag, weight)
    contributions ride this same frontier round-trip as one framed
    all-reduce (``allreduce_framed``) — no extra round trip — and the
    fleet-wide fold lands before the emission that uses the frontier.
    ``record=True`` keeps every emitted window in ``self.emitted``
    (test/diagnostic use: memory grows with the run).
    """

    def __init__(self, group_sizes, *, grid_origin: float,
                 grid_step: float, delays=None, align=None,
                 tail: int = 256, var_floor: float = 0.25,
                 collectives=None, record: bool = False,
                 interpret=None, use_kernel=None,
                 dq_policy: DataQualityPolicy = None):
        self.group_sizes = list(group_sizes)
        self.n_streams = int(sum(self.group_sizes))
        self.origin = float(grid_origin)
        self.step = float(grid_step)
        self.align = align
        self._fixed = (np.zeros((self.n_streams,)) if delays is None
                       else np.asarray(delays, np.float64).reshape(-1))
        self.var_floor = float(var_floor)
        self.collectives = collectives
        self.record = record
        self.emitted: list = []
        self.interpret = auto_interpret(interpret)
        self.use_kernel = use_kernel
        self._tail = _RowTail(tail)
        self.carry = FuseCarry(next_slot=0,
                               n_k=np.zeros((self.n_streams,)),
                               ssr=np.zeros((self.n_streams,)))
        self._t_first = None
        self._nan = None
        # optional SensorHealthStage feedback loop: its pending stats
        # ride _sync's frame (or fold locally), and its quarantine mask
        # gates the fusion statistics from the NEXT window on
        self.health = None
        self.last_frontier = None   # telemetry: emit-frontier lag
        self.dq_policy = dq_policy
        # coverage-pattern accounting: per-stream covered-slot tallies
        # plus the latest emitted window's coverage fraction and flag
        self.dq_covered = np.zeros((self.n_streams,), np.int64)
        self.dq_slots = 0
        self.dq_last_coverage = np.ones((self.n_streams,))
        self.dq_low_coverage = np.zeros((self.n_streams,), bool)

    def reset(self):
        self._tail.reset()
        self.carry = FuseCarry(next_slot=0,
                               n_k=np.zeros((self.n_streams,)),
                               ssr=np.zeros((self.n_streams,)))
        self._t_first = None
        self.emitted = []
        self.dq_covered = np.zeros((self.n_streams,), np.int64)
        self.dq_slots = 0
        self.dq_last_coverage = np.ones((self.n_streams,))
        self.dq_low_coverage = np.zeros((self.n_streams,), bool)
        return self

    def _delays(self, f: int) -> np.ndarray:
        d = np.zeros((f,))
        if self.align is not None:
            d[:] = self.align.delay_s[:f]
        else:
            d[:self.n_streams] = self._fixed
        return d

    def _sync(self, value: float, op: str) -> float:
        """Frontier all-reduce; a synced tracker's pending (lag,
        weight) vectors AND the health stage's pending residual stats
        piggyback on the same frame — still ONE round trip — and are
        folded into the shared fleet state before the value is used.
        The concatenated frame length is identical on every host
        (both blocks are global-fleet sized), and each element is
        written by exactly one host, so the left-fold sum stays exact."""
        al = self.align
        hs = self.health
        pend = (al.pending_contribution()
                if al is not None and al.synced else None)
        if pend is None and hs is None:
            return (self.collectives.allreduce_min(value)
                    if op == "min"
                    else self.collectives.allreduce_max(value))
        blocks = []
        if pend is not None:
            blocks.append(pend.ravel())
        if hs is not None:
            blocks.append(hs.take_pending().ravel())
        vec = (np.concatenate(blocks) if len(blocks) > 1
               else blocks[0])
        value, summed = self.collectives.allreduce_framed(
            value, vec, scalar_op=op)
        off = 0
        if pend is not None:
            off = pend.size
            al.fold_fleet(summed[:off].reshape(2, -1))
        if hs is not None:
            # the fleet delay EMA above folded first, so the drift
            # flag sees this window's shared delays on every host
            hs.fold(summed[off:])
        return value

    def _emit(self, rows_t, rows_v, t_first, delays, lo: int, hi: int):
        idx = np.arange(lo, hi + 1)
        grid64 = self.origin + self.step * idx
        self._tail.check_reach(grid64[0] + delays, "Regrid/Fuse")
        vals, mask = _query_grid(rows_t, rows_v, grid64, delays, t_first,
                                 interpret=self.interpret,
                                 use_kernel=self.use_kernel)
        n = self.n_streams
        vals, mask = vals[:n], mask[:n]
        # coverage-pattern accounting: which slots each stream covered
        # in this emitted window (the per-window data-quality surface)
        self.dq_covered += mask.sum(axis=1, dtype=np.int64)
        self.dq_slots += mask.shape[1]
        cov = mask.mean(axis=1)
        self.dq_last_coverage = cov
        p = self.dq_policy
        if p is not None and p.min_coverage > 0.0:
            low = cov < p.min_coverage
            self.dq_low_coverage = low
            if p.coverage == "raise" and low.any():
                i = int(np.argmax(low))
                raise DataQualityError(
                    f"regrid/fuse: row {i} covered only "
                    f"{cov[i]:.3f} of the emitted window "
                    f"(< min_coverage={p.min_coverage}) and the "
                    f"policy says raise")
        # quarantine feedback: QUARANTINED/RECOVERING rows are dropped
        # from the fusion statistics (the emitted window keeps the RAW
        # mask so the health stage can keep scoring them).  All-healthy
        # fleets skip the masking entirely — the arithmetic below is
        # then bit-identical to a pipeline without the health stage.
        hm = None
        if self.health is not None:
            hm = self.health.local_mask()
            if hm.all():
                hm = None
        stat_mask = mask if hm is None else (mask & hm[:, None])
        # fusion statistics: per-slot cross-sensor mean within each group
        flo = 0
        for k in self.group_sizes:
            fhi = flo + k
            v = vals[flo:fhi].astype(np.float64)
            m = stat_mask[flo:fhi]
            cnt = m.sum(axis=0)
            m0 = (v * m).sum(axis=0) / np.maximum(cnt, 1.0)
            resid = (v - m0[None, :]) * m
            self.carry.n_k[flo:fhi] += m.sum(axis=1)
            self.carry.ssr[flo:fhi] += (resid * resid).sum(axis=1)
            flo = fhi
        self.carry.next_slot = hi + 1
        gw = GriddedWindow(lo=lo, grid=grid64, values=vals, mask=mask)
        if self.record:
            self.emitted.append(gw)
        return gw

    def update(self, chunk: ClosedWindow):
        n = self.n_streams
        self._t_first = chunk.t_first
        rows_t, rows_v = self._tail.augmented(chunk)
        delays = self._delays(rows_t.shape[0])
        frontier = float((chunk.times[:n, -1].astype(np.float64)
                          - delays[:n]).min())
        if self.collectives is not None:
            # emit-frontier all-reduce: every host trails the globally
            # slowest stream and emits identical slot windows (see class
            # docstring: this is what makes the fleet-wide accumulation
            # order — and hence the fused energies — assignment-stable);
            # a synced tracker's (lag, weight) pairs ride the same frame
            frontier = self._sync(frontier, "min")
        elif self.health is not None:
            # single host: fold at the same cadence as the synced path
            # (once per update), so window w's stats gate the masks
            # from window w+1 on — exactly as in the multi-host fold
            self.health.fold(self.health.take_pending())
        self.last_frontier = frontier
        # a safety margin of 1% of a step keeps float32-rounded queries
        # strictly inside every row's closed span (re-emitted exactly at
        # flush time where the span bound is final)
        hi = int(np.floor((frontier - self.origin) / self.step - 0.01))
        out = None
        if hi >= self.carry.next_slot:
            out = self._emit(rows_t, rows_v, chunk.t_first, delays,
                             self.carry.next_slot, hi)
        self._tail.advance(chunk)
        return out

    def flush(self, t_end: float = None):
        """Emit the remaining slots with the rows' FINAL spans.

        t_end: last grid time to cover (pipeline seconds) — pass the
        batch grid's endpoint for replay parity; default covers every
        row's last closed sample.
        """
        if self._tail.carry is None:
            return None
        tc = self._tail.carry
        f = tc.t.shape[0]
        n = self.n_streams
        delays = self._delays(f)
        if t_end is None:
            t_end = float((tc.t[:n, -1].astype(np.float64)
                           - delays[:n]).max())
            if self.collectives is not None:
                # cover through the globally LAST row (hosts whose rows
                # end early mask off, exactly as in the batch regrid)
                t_end = self._sync(t_end, "max")
            elif self.health is not None:
                self.health.fold(self.health.take_pending())
        elif (self.collectives is not None
              and (self.health is not None
                   or (self.align is not None and self.align.synced))):
            # explicit t_end (identical on every host): the reduce is a
            # scalar no-op but still flushes any (lag, weight) pairs a
            # final-window hop left pending — and the health stage's
            # last stats block — keeping the shared fleet state
            # current, and identical, on every host
            t_end = self._sync(float(t_end), "max")
        elif self.health is not None:
            self.health.fold(self.health.take_pending())
        hi = int(np.floor((t_end - self.origin) / self.step + 1e-9))
        if hi < self.carry.next_slot:
            return None
        sent_t = np.full((f, 1), -np.inf, tc.t.dtype)
        sent_v = np.zeros((f, 1), tc.v.dtype)
        rows_t = np.concatenate([sent_t, tc.t], axis=1)
        rows_v = np.concatenate([sent_v, tc.v], axis=1)
        return self._emit(rows_t, rows_v, self._t_first, delays,
                          self.carry.next_slot, hi)

    def weights(self) -> np.ndarray:
        """(n_streams,) end-of-run inverse-variance weights — the batch
        ``fuse_gridded`` weights, reduced incrementally."""
        return _ivw_weights(self.carry.n_k, self.carry.ssr,
                            self.var_floor)


def _ivw_weights(n_k, ssr, var_floor: float) -> np.ndarray:
    """The batch ``fuse_gridded`` per-stream weight rule from the
    additive sufficient statistics — ONE definition, shared by the
    local path and the multi-host merge (bit-identity depends on it)."""
    var = ssr / np.maximum(n_k, 1.0)
    return np.where(n_k > 1, 1.0 / (var + var_floor), 0.0)


# ---------------------------------------------------------------------------
# Stage 5: PhaseAttribute
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FusedAttrCarry:
    """Per-device carry for fused streaming attribution.

    ``integrals[d][pattern]`` is a (P, K_d) block: for every grid
    interval whose closing slot had exactly ``pattern`` coverage, the
    per-stream sum of value x phase-overlap.  The fused per-phase
    energy is then  sum_pattern (I @ w) / sum_{k in pattern} w_k  once
    the end-of-run weights are known — the only quantity the batch path
    computes that a causal stream cannot: per-stream variance needs the
    whole run, so the nonlinear (weights) step is deferred to
    ``totals()`` while everything per-sample stays O(window).
    """
    t_prev: np.ndarray         # (D,) float64 last valid slot time
    integrals: list            # [ {pattern:int -> (P, K_d) float64} ]


class FusedPhaseAttributeStage:
    """Gridded windows -> per-(device, phase) fused energies.

    Integration follows the batch convention exactly: the fused series
    is sample-and-hold on the output grid, invalid slots are bridged by
    carrying the previous valid edge forward (their interval folds into
    the next valid slot), and the first valid slot seeds zero-width.

    Multi-host: with ``collectives`` + ``shard``, ``group_sizes`` are
    this host's LOCAL device groups and ``totals()``/``weights()``
    become collective calls — every host posts its per-(device, phase,
    coverage-pattern, stream) integrals plus the fuse stage's per-stream
    (n_k, ssr) sufficient statistics, and all hosts assemble the same
    fleet-wide result.  Because whole device groups live on one host,
    the reduction is pure placement (no floating-point re-association):
    the fleet answer is bit-identical however the groups were assigned.
    """

    def __init__(self, phases, group_sizes, fuse: RegridFuseStage, *,
                 collectives=None, shard=None):
        ph = np.asarray(phases, np.float64).reshape(-1, 2)
        self.phases = ph
        self.n_phases = len(ph)
        self.group_sizes = list(group_sizes)
        self.fuse = fuse
        self.collectives = collectives
        self.shard = shard
        if collectives is not None:
            assert shard is not None, \
                "multi-host totals need the HostShard (global row ids)"
            assert list(shard.local_group_sizes) == self.group_sizes
        sizes = np.asarray(self.group_sizes, np.int64)
        self._row_lo = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        # size classes: (k, device ids, (G_k, k) row ids) — one gather
        # per window takes every k-row group's rows at once
        self._classes = []
        for k in np.unique(sizes):
            dev = np.nonzero(sizes == k)[0]
            self._classes.append(
                (int(k), dev, self._row_lo[dev][:, None] + np.arange(k)))
        self.reset()

    def _fresh(self):
        d = len(self.group_sizes)
        return FusedAttrCarry(t_prev=np.full((d,), np.nan),
                              integrals=[{} for _ in range(d)])

    def reset(self):
        self.carry = self._fresh()
        self.groups_batched = 0    # group-windows folded in a size class
        self.groups_looped = 0     # group-windows folded one at a time
        return self

    def update(self, gw: GriddedWindow):
        """Fold one window.  A group whose every row covers every slot,
        past its seed window, joins its size class's batch: they share
        one valid-slot set, so one stacked product folds them all.
        Every other group folds alone (partial coverage, its seed
        window).  Either way a group's sums depend only on its own rows
        and the window's shape, never on which groups share the batch,
        so the fleet result stays bit-identical under any host<-group
        assignment."""
        row_full = gw.mask.all(axis=1)
        seeded = np.isfinite(self.carry.t_prev)
        batched = np.zeros(seeded.shape, bool)
        batches = []
        for k, dev, rows in self._classes:
            take = row_full[rows].all(axis=1) & seeded[dev]
            batches.append((k, dev[take], rows[take]))
            batched[dev[take]] = True
        looped = np.flatnonzero(~batched)
        n_batched = len(batched) - len(looped)
        with tracing.span("fleet.fold", n=n_batched, looped=len(looped)):
            for k, dev, rows in batches:
                self._fold_class(gw, k, dev, rows)
        for d in looped:
            self._fold_group(gw, int(d))
        self.groups_batched += n_batched
        self.groups_looped += len(looped)
        return None

    def _fold_class(self, gw: GriddedWindow, k: int, dev, rows):
        """Fully covered k-row groups: slots 1.. share one (P, S-1)
        overlap matrix; slot 0 opens at each group's own ``t_prev``, a
        rank-1 term of its own.  ``np.matmul`` takes one (P, S-1) @
        (S-1, k) product per group; nothing sums across groups."""
        a = self.phases[:, :1]
        b = self.phases[:, 1:]
        grid = gw.grid
        vv = gw.values[rows].astype(np.float64)          # (G, k, S)
        ov = np.clip(np.minimum(grid[1:], b) - np.maximum(grid[:-1], a),
                     0.0, None)
        blocks = np.matmul(ov, vv[:, :, 1:].transpose(0, 2, 1))
        tp = self.carry.t_prev[dev][:, None, None]       # (G, 1, 1)
        ov0 = np.clip(np.minimum(grid[0], b) - np.maximum(tp, a), 0.0,
                      None)                              # (G, P, 1)
        blocks += ov0 * vv[:, None, :, 0]
        full = (1 << k) - 1
        for d, blk in zip(dev, blocks):
            acc = self.carry.integrals[d].setdefault(
                full, np.zeros((self.n_phases, k)))
            acc += blk
        self.carry.t_prev[dev] = grid[-1]

    def _fold_group(self, gw: GriddedWindow, d: int):
        """One group, any coverage: bridge invalid slots, one product
        per coverage pattern."""
        a = self.phases[:, :1]
        b = self.phases[:, 1:]
        k = self.group_sizes[d]
        lo = self._row_lo[d]
        m = gw.mask[lo:lo + k]
        anyv = m.any(axis=0)
        if not anyv.any():
            return
        sel = np.nonzero(anyv)[0]
        tv = gw.grid[sel]
        tp = self.carry.t_prev[d]
        if not np.isfinite(tp):
            tp = tv[0]               # zero-width seed
        t_lo = np.concatenate([[tp], tv[:-1]])
        ov = np.clip(np.minimum(tv[None, :], b)
                     - np.maximum(t_lo[None, :], a), 0.0, None)
        mm = m[:, sel]
        vv = gw.values[lo:lo + k][:, sel].astype(np.float64) * mm
        bits = (1 << np.arange(k, dtype=np.int64))[:, None]
        pat = (mm * bits).sum(axis=0)
        for p in np.unique(pat):
            ps = pat == p
            acc = self.carry.integrals[d].setdefault(
                int(p), np.zeros((self.n_phases, k)))
            acc += ov[:, ps] @ vv[:, ps].T
        self.carry.t_prev[d] = tv[-1]

    def _gathered(self):
        """(integrals, group_sizes, w_flat): local, or the fleet-wide
        merge when collectives are attached (a COLLECTIVE call: every
        host must reach it in lockstep)."""
        n = self.fuse.n_streams
        if self.collectives is None:
            return (self.carry.integrals, self.group_sizes,
                    self.fuse.weights())
        sh = self.shard
        payload = pickle.dumps(
            (tuple(sh.group_ids), self.carry.integrals,
             self.fuse.carry.n_k[:n], self.fuse.carry.ssr[:n]))
        parts = self.collectives.allgather_bytes(payload)
        sizes = list(sh.global_group_sizes)
        off = sh.row_offsets
        integrals = [None] * len(sizes)
        n_k = np.zeros((int(off[-1]),))
        ssr = np.zeros((int(off[-1]),))
        for raw in parts:
            gids, ints, nk_l, ssr_l = pickle.loads(raw)
            lo = 0
            for j, g in enumerate(gids):
                assert integrals[g] is None, \
                    f"device group {g} owned by two hosts"
                integrals[g] = ints[j]
                k = sizes[g]
                n_k[off[g]:off[g] + k] = nk_l[lo:lo + k]
                ssr[off[g]:off[g] + k] = ssr_l[lo:lo + k]
                lo += k
        assert all(i is not None for i in integrals), \
            "multi-host merge is missing device groups (unassigned?)"
        return integrals, sizes, _ivw_weights(n_k, ssr,
                                              self.fuse.var_floor)

    def totals(self) -> np.ndarray:
        """(n_devices, n_phases) fused joules, finalized with the
        end-of-run inverse-variance weights.  Fleet-wide (and identical
        on every host) in multi-host mode."""
        integrals, sizes, w_flat = self._gathered()
        out = np.zeros((len(sizes), self.n_phases))
        lo = 0
        for d, k in enumerate(sizes):
            w = w_flat[lo:lo + k]
            for p, acc in integrals[d].items():
                member = (p >> np.arange(k)) & 1
                w_tot = float((w * member).sum())
                if w_tot > 0:
                    out[d] += acc @ w / w_tot
            lo += k
        return out

    def weights(self) -> list:
        """Per-device normalized stream weights (diagnostics);
        fleet-wide in multi-host mode."""
        _, sizes, w_flat = self._gathered()
        out = []
        lo = 0
        for k in sizes:
            w = w_flat[lo:lo + k]
            out.append(w / max(w.sum(), 1e-30))
            lo += k
        return out


# ---------------------------------------------------------------------------
# Stage 5b: per-request metering (token-weighted occupancy split)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SlotSegment:
    """One constant-occupancy interval of a serve engine's timeline.

    ``rids``/``tokens`` list the requests concurrently active in
    ``[t_lo, t_hi)`` and the token weight each contributed (prompt
    length for prefill segments, decoded steps for decode segments).
    Segment boundaries fall on every admission/eviction, so occupancy
    is constant inside a segment and the union of segments tiles the
    engine's depth-0 phases exactly — which is what makes per-request
    energies conserve against the per-phase totals.
    """
    t_lo: float
    t_hi: float
    rids: tuple
    tokens: tuple
    kind: str = "decode"

    def shifted(self, dt: float) -> "SlotSegment":
        return dataclasses.replace(self, t_lo=self.t_lo + dt,
                                   t_hi=self.t_hi + dt)


class MeteringStage(FusedPhaseAttributeStage):
    """Fused window energies -> per-REQUEST energies.

    A pass-through sibling of ``FusedPhaseAttributeStage``: the phase
    table is the engine's slot-segment schedule (one row per constant-
    occupancy interval), accumulated with the same per-(device,
    segment, coverage-pattern, stream) float64 integrals and finalized
    with the same deferred inverse-variance weights.  Each segment's
    energy is then split across the requests active in it by
    token-weighted occupancy.

    Determinism rule (mirrors the fold-order contract): segments
    integrate in time order, shares within a segment fold in ascending
    request-id order, and every accumulation is an exact float64 left
    fold — per-request energies are bit-identical under any
    slot-assignment permutation (and any multihost layout upstream,
    which never re-associates device-local sums).  Conservation is by
    construction: shares sum to 1 per segment, so per-request energies
    sum to the segment (= phase) totals to float64 round-off, well
    inside the 1e-5 gate.
    """

    def __init__(self, segments, group_sizes, fuse: RegridFuseStage, *,
                 collectives=None, shard=None):
        segs = sorted(segments,
                      key=lambda s: (s.t_lo, s.t_hi, tuple(sorted(s.rids))))
        self.segments = segs
        super().__init__([(s.t_lo, s.t_hi) for s in segs], group_sizes,
                         fuse, collectives=collectives, shard=shard)

    def update(self, gw: GriddedWindow):
        super().update(gw)
        return gw              # pass-through: PhaseAttribute still runs

    def segment_totals(self) -> np.ndarray:
        """(n_devices, n_segments) fused joules per slot segment."""
        return self.totals()

    def request_energies(self) -> dict:
        """{rid: (n_devices,) float64 joules}, token-weighted split."""
        seg_e = self.segment_totals()
        d = seg_e.shape[0]
        out: dict = {}
        for j, s in enumerate(self.segments):
            if not s.rids:
                continue               # idle interval: nobody to bill
            # canonicalize to ascending-rid order FIRST so both the
            # weight-sum fold and the share folds are permutation-proof
            order = np.argsort(np.asarray(s.rids, np.int64),
                               kind="stable")
            w = np.asarray(s.tokens, np.float64)[order]
            tot = float(w.sum())
            if tot <= 0.0:             # degenerate: equal split
                w = np.ones((len(s.rids),), np.float64)
                tot = float(len(s.rids))
            for k, idx in enumerate(order):
                rid = int(s.rids[idx])
                acc = out.setdefault(rid, np.zeros((d,), np.float64))
                acc += (w[k] / tot) * seg_e[:, j]
        return out


class PhaseIntegrateStage:
    """Power windows -> (F, P) energies via the phase_integrate kernel
    (the StreamingPhaseAccumulator core)."""

    def __init__(self, phases, n_streams: int, *, dtype=np.float32,
                 interpret=None, use_kernel: bool = True):
        import jax.numpy as jnp
        self.phases = jnp.asarray(pad_phases(phases, dtype))
        self.n_phases = len(np.asarray(phases,
                                       np.float64).reshape(-1, 2))
        self.interpret = auto_interpret(interpret)
        self.use_kernel = use_kernel
        self._acc = jnp.zeros((n_streams, len(self.phases)), dtype)

    def reset(self):
        import jax.numpy as jnp
        self._acc = jnp.zeros_like(self._acc)
        return self

    def update(self, chunk: ClosedWindow):
        self._acc = _integrate_window(chunk.times, chunk.values,
                                      self.phases, self._acc,
                                      interpret=self.interpret,
                                      use_kernel=self.use_kernel)
        return None

    def totals(self) -> np.ndarray:
        return np.asarray(self._acc)[:, :self.n_phases]


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def _integrate_window(t_aug, w_aug, phases, acc, *, interpret=False,
                      use_kernel=True):
    from repro.kernels.phase_integrate.kernel import phase_integrate_kernel
    from repro.kernels.phase_integrate.ref import phase_energies_ref
    if use_kernel:
        de = phase_integrate_kernel(t_aug, w_aug, phases,
                                    interpret=interpret)
    else:
        de = phase_energies_ref(t_aug, w_aug, phases)
    return acc + de


class CounterAttributeStage:
    """Counter windows -> (F, P) energies through the fused
    ``fleet_attribute`` kernel (dE/dt + integration in one pass, the
    FleetStream core), optionally row-sharded over a fleet mesh."""

    def __init__(self, phases, n_streams: int, wrap_period=None, *,
                 dtype=np.float32, interpret=None,
                 use_kernel: bool = True, mesh="auto"):
        import jax.numpy as jnp
        from repro.distributed.sharding import (fleet_mesh,
                                                fleet_row_padding)
        self.phases = jnp.asarray(pad_phases(phases, dtype))
        self.n_phases = len(np.asarray(phases,
                                       np.float64).reshape(-1, 2))
        self.interpret = auto_interpret(interpret)
        self.use_kernel = use_kernel
        if mesh == "auto":
            mesh = fleet_mesh()
        # a stream count that doesn't divide the mesh pads masked rows
        # up to divisibility (replicated-last-row, zero-width => exactly
        # zero energy) instead of silently dropping to unsharded
        self._row_pad = fleet_row_padding(mesh, n_streams)
        if self._row_pad:
            logger.debug("stream count %d not divisible by fleet mesh "
                         "%d: padding %d masked rows", n_streams,
                         mesh.shape["fleet"], self._row_pad)
        self.mesh = mesh
        self.n_streams = n_streams
        wp = (np.zeros((n_streams,), dtype) if wrap_period is None
              else np.asarray(wrap_period, dtype))
        self._period = jnp.asarray(np.pad(wp, (0, self._row_pad)))
        self._acc = jnp.zeros((n_streams, len(self.phases)), dtype)

    def reset(self):
        import jax.numpy as jnp
        self._acc = jnp.zeros_like(self._acc)
        return self

    def update(self, chunk: ClosedWindow):
        import jax.numpy as jnp
        t_np, e_np = chunk.times, chunk.values
        if self._row_pad:
            # replicate the last row: its duplicate energy is sliced off
            # inside the jitted step before the accumulate
            t_np = np.concatenate(
                [t_np, np.repeat(t_np[-1:], self._row_pad, axis=0)])
            e_np = np.concatenate(
                [e_np, np.repeat(e_np[-1:], self._row_pad, axis=0)])
        t = jnp.asarray(t_np)
        e = jnp.asarray(e_np)
        if self.mesh is not None:
            step = _sharded_attribute_step(self.mesh, self.interpret,
                                           self.use_kernel,
                                           self.n_streams)
            self._acc = step(t, e, self._period, self.phases, self._acc)
        else:
            self._acc = _attribute_window(t, e, self._period, self.phases,
                                          self._acc,
                                          interpret=self.interpret,
                                          use_kernel=self.use_kernel)
        return None

    def totals(self) -> np.ndarray:
        return np.asarray(self._acc)[:, :self.n_phases]


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def _attribute_window(t_aug, e_aug, period, phases, acc, *,
                      interpret=False, use_kernel=True):
    """One streaming step through the fused dE/dt + phase-energy kernel.

    Counter wrap is fixed per interval inside the kernel (no cumulative
    unwrap state — dE telescopes across chunks through the carry edge).
    """
    from repro.kernels.fleet_attribute.kernel import fleet_attribute_kernel
    from repro.kernels.fleet_attribute.ref import fleet_attribute_ref
    wrap_row = period[:, None]
    if use_kernel:
        energy = fleet_attribute_kernel(t_aug, e_aug, wrap_row, phases,
                                        interpret=interpret)
    else:
        energy = fleet_attribute_ref(t_aug, e_aug, wrap_row, phases)
    return acc + energy


_SHARDED_STEP_CACHE: dict = {}


def _sharded_attribute_step(mesh, interpret: bool, use_kernel: bool,
                            n_streams: int):
    """The fused attribution step with the kernel row-sharded over
    ``mesh`` — the kernel is row-independent (each stream's dE/dt and
    phase overlaps touch only its own row; the phase table is
    replicated), so the fleet axis partitions with zero collectives.
    Inputs may carry padding rows past ``n_streams`` (non-divisible
    fleets); their energy is sliced off before the accumulate."""
    from repro.distributed.sharding import fleet_shard_map
    key = (mesh, interpret, use_kernel, n_streams)
    fn = _SHARDED_STEP_CACHE.get(key)
    if fn is not None:
        return fn
    from repro.kernels.fleet_attribute.kernel import fleet_attribute_kernel
    from repro.kernels.fleet_attribute.ref import fleet_attribute_ref

    def block(t_aug, e_aug, wrap_row, phases):
        if use_kernel:
            return fleet_attribute_kernel(t_aug, e_aug, wrap_row, phases,
                                          interpret=interpret)
        return fleet_attribute_ref(t_aug, e_aug, wrap_row, phases)

    inner = fleet_shard_map(block, mesh, n_in=4, n_out=1,
                            replicated_in=(3,))

    @jax.jit
    def step(t_aug, e_aug, period, phases, acc):
        energy = inner(t_aug, e_aug, period[:, None], phases)
        return acc + energy[:n_streams]

    _SHARDED_STEP_CACHE[key] = step
    return step


# ---------------------------------------------------------------------------
# The pipeline driver
# ---------------------------------------------------------------------------

class StreamPipeline:
    """Chain stages; push each (fleet, chunk) window through all of them.

    ``update`` feeds the first stage raw arrays and forwards each
    stage's output window to the next (a stage returning None ends the
    window's journey — e.g. the regrid frontier did not advance).
    ``finalize`` flushes every stage in order, routing whatever it still
    held through the remainder of the chain.

    Self-metrics: per-stage cumulative wall time and the processed
    window count are kept in ``stage_wall_s``/``windows`` (two
    ``perf_counter`` calls per stage per window — noise next to any
    stage's kernel work); ``attach_registry`` exposes them through a
    ``health.HealthRegistry``.  While a profiler session records, each
    stage call is also a ``stage.<StageName>`` program span whose times
    are the same two clock reads.
    """

    def __init__(self, *stages):
        self.stages = list(stages)
        self.stage_wall_s = {type(st).__name__: 0.0 for st in stages}
        self.windows = 0

    def _timed(self, st, fn, *args):
        name = type(st).__name__
        with tracing.span("stage." + name) as sp:
            t0 = tracing.now()
            out = fn(*args)
            t1 = tracing.now()
            sp.clock(t0, t1)
        self.stage_wall_s[name] += t1 - t0
        return out

    def update(self, times, values, valid=None):
        self.windows += 1
        st0 = self.stages[0]
        out = self._timed(st0, st0.update, times, values, valid)
        for st in self.stages[1:]:
            if out is None:
                break
            out = self._timed(st, st.update, out)
        return self

    def finalize(self, t_end: float = None):
        for i, st in enumerate(self.stages):
            flush = getattr(st, "flush", None)
            if flush is None:
                continue
            out = self._timed(st, flush, t_end)
            for st2 in self.stages[i + 1:]:
                if out is None:
                    break
                out = self._timed(st2, st2.update, out)
        return self

    def attach_registry(self, registry) -> None:
        from repro.health.registry import Metric

        def _fn():
            return [
                Metric("stage_wall_seconds", dict(self.stage_wall_s),
                       kind="counter", label="stage"),
                Metric("pipeline_windows_total", float(self.windows),
                       kind="counter"),
            ]
        registry.register_source("pipeline", _fn)

    def reset(self):
        for st in self.stages:
            st.reset()
        self.stage_wall_s = {type(st).__name__: 0.0
                             for st in self.stages}
        self.windows = 0
        return self


# ---------------------------------------------------------------------------
# High level: the streaming fused pipeline and its trace-level entry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StreamRows:
    """Raw packed rows for streaming replay/ingest (mixed sensor kinds).

    Unlike ``align.regrid.SeriesRows`` the values are NOT reconstructed:
    counter rows keep their (float64-unwrapped, rebased) cumulative
    joules so dE/dt happens inside the pipeline's Reconstruct stage.
    The float32 rounding of times matches ``series_rows_from_traces``
    bit-for-bit (same two-step rebase for counter rows), so a streamed
    replay presents the regrid stage with EXACTLY the samples the batch
    path sees.
    """
    times: np.ndarray          # (F, S) seconds since t0
    values: np.ndarray         # (F, S) cumulative J or W
    kind_row: np.ndarray       # (F,) True = cumulative counter
    n_samples: np.ndarray      # (F,)
    names: list
    n_streams: int
    t0: float

    @property
    def shape(self):
        return self.times.shape


def pack_stream_rows(traces, *, corrections=None,
                     use_t_measured: bool = True, t0=None,
                     dtype=np.float32, cum_t0=None) -> StreamRows:
    """SensorTraces (mixed cumulative + power) -> raw streaming rows.

    ``t0``/``cum_t0`` pin the shared origin and the counter sub-pack's
    intermediate origin — a multi-host fleet passes the all-reduced
    global minima so each host's float32 two-step rebase is
    bit-identical to a single-host pack of the same rows.
    """
    from repro.core.calibration import apply_corrections
    with tracing.span("fleet.correct"):
        traces = [apply_corrections(tr, corrections) for tr in traces]
    assert traces, "pack_stream_rows needs at least one trace"
    if t0 is None:
        t0 = min(float((tr.t_measured if use_t_measured
                        else tr.t_read)[0]) for tr in traces)
    cum = [i for i, tr in enumerate(traces) if tr.spec.is_cumulative]
    pwr = [i for i, tr in enumerate(traces) if not tr.spec.is_cumulative]
    f = _round_up(len(traces), ROW_ALIGN)
    s_cum = s_pwr = 2
    packed = None
    if cum:
        packed = pack_traces([traces[i] for i in cum],
                             use_t_measured=use_t_measured, dtype=dtype,
                             t0=cum_t0)
        s_cum = packed.shape[1]
    if pwr:
        s_pwr = max(max(len(traces[i]) for i in pwr), 2)
    s = max(s_cum, s_pwr)
    times = np.zeros((f, s), dtype)
    values = np.zeros((f, s), dtype)
    kind = np.zeros((f,), bool)
    n = np.full((f,), 2, np.int32)
    if cum:
        sel = np.asarray(cum)
        n_cum = len(cum)
        # two-step rebase (pack origin, then the shared origin) exactly
        # as series_rows_from_traces does — identical float32 times
        shift = dtype(packed.t0 - t0)
        times[sel, :s_cum] = packed.times[:n_cum] + shift
        values[sel, :s_cum] = packed.energy[:n_cum]
        if s > s_cum:                        # replicate-last tails
            times[sel, s_cum:] = times[sel, s_cum - 1][:, None]
            values[sel, s_cum:] = values[sel, s_cum - 1][:, None]
        kind[sel] = True
        n[sel] = packed.n_samples[:n_cum]
    for i in pwr:
        tr = traces[i]
        t = (tr.t_measured if use_t_measured else tr.t_read)
        kk = len(tr)
        times[i, :kk] = np.maximum.accumulate(t - t0)
        values[i, :kk] = tr.value
        times[i, kk:] = times[i, kk - 1]
        values[i, kk:] = values[i, kk - 1]
        n[i] = kk
    for i in range(len(traces), f):          # padding rows: zero-width
        times[i] = 0.0
        values[i] = 0.0
    return StreamRows(times, values, kind, n,
                      [tr.name for tr in traces], len(traces), t0)


def default_tail(rows: StreamRows, chunk: int, *, delays=None,
                 max_lag: int = 64, grid_step: float = 1e-3,
                 cadence: float = None) -> int:
    """Tail columns needed so delayed queries never outrun the carry.

    The emit frontier trails the most-delayed stream, so every fast
    row's tail must span the delay SPREAD plus one window of slack
    (the track range bounds the spread when delays are live).
    ``cadence`` overrides the local fastest-row spacing — a multi-host
    run passes the all-reduced fleet-wide value (and fleet-wide delays)
    so every host sizes the same tail against the global frontier.
    """
    min_step = cadence if cadence is not None else _min_cadence(rows)
    if delays is not None:
        d = np.asarray(delays, np.float64)
        spread = float(d.max() - min(d.min(), 0.0))
    else:
        spread = max_lag * grid_step
    tail_s = spread + chunk * min_step
    return max(256, int(np.ceil(tail_s / min_step)) + 64)


def _min_cadence(rows: StreamRows) -> float:
    """Fastest per-row median sample spacing (seconds; 1e-3 fallback)."""
    steps = []
    for i in range(rows.n_streams):
        dt = np.diff(rows.times[i, :rows.n_samples[i]].astype(np.float64))
        dt = dt[dt > 0]
        if len(dt):
            steps.append(float(np.median(dt)))
    return min(steps) if steps else 1e-3


def _replay_window_plan(rows: StreamRows, chunk: int = 1024, *,
                        span=None, cadence: float = None):
    """Shared window-edge math for replay: -> (n_win, idx).

    ONE definition of the time-aligned replay boundaries, shared by
    ``stream_row_windows`` and the replay entry points, so every replay
    of the same rows walks identical window edges.  ``idx[i, w]`` is
    row i's first sample index in window w (idx[:, -1] == S).
    """
    f, s = rows.shape
    n = rows.n_streams
    dt_win = max(chunk, 2) * (cadence if cadence is not None
                              else _min_cadence(rows))
    if span is not None:
        t_lo, t_hi = float(span[0]), float(span[1])
    else:
        t_lo = float(rows.times[:n, 0].astype(np.float64).min())
        t_hi = float(rows.times[:n, -1].astype(np.float64).max())
    n_win = max(int(np.ceil((t_hi - t_lo) / dt_win)), 1)
    edges = (t_lo + dt_win * np.arange(1, n_win)).astype(rows.times.dtype)
    idx = np.zeros((f, n_win + 1), np.int64)
    for i in range(n):                       # padding rows stay empty
        idx[i, 1:-1] = np.searchsorted(rows.times[i], edges,
                                       side="right")
        idx[i, -1] = s
    return n_win, idx


def stream_row_windows(rows: StreamRows, chunk: int = 1024, *,
                       span=None, cadence: float = None):
    """Replay packed rows as TIME-aligned (fleet, C) windows.

    Heterogeneous cadences make equal COLUMN counts span wildly
    different time ranges per row (a 100 ms PM counter covers 100x the
    span of a 1 ms on-chip counter), which would run slow rows
    arbitrarily far ahead of the emit frontier.  Real ingest loops
    (``AsyncFleetIngest``) poll by wall clock, so the replay does the
    same: each window covers one time span for every row, sized so the
    fastest row advances ~``chunk`` samples, and rows short of the
    window width pad by replicating their last sample (zero-width
    intervals — search-invisible, exactly zero energy).  Yields
    (times, values) blocks for ``StreamingFusedPipeline.update``.

    ``span=(t_lo, t_hi)`` / ``cadence`` pin the window edges explicitly
    — a multi-host replay passes the all-reduced FLEET-wide span and
    fastest cadence so every host steps through identical window
    boundaries in lockstep (the frontier all-reduce requires equal
    update counts, and bit-stable emission requires equal edges).
    """
    n_win, idx = _replay_window_plan(rows, chunk, span=span,
                                     cadence=cadence)
    for w in range(n_win):
        yield _replay_window(rows, idx, w)


def _window_width(idx, w: int) -> int:
    """Columns of replay window ``w``: its widest row, rounded up to 64."""
    return max(_round_up(int((idx[:, w + 1] - idx[:, w]).max()), 64), 64)


def _replay_window(rows: StreamRows, idx, w: int):
    """(times, values) block of replay window ``w`` of the plan ``idx``."""
    lo, hi = idx[:, w], idx[:, w + 1]
    cols = lo[:, None] + np.arange(_window_width(idx, w))[None, :]
    # rows short of the window replicate their last in-window
    # sample; rows with no new samples replicate their previous one
    cols = np.minimum(cols, np.maximum(hi - 1, np.maximum(lo - 1,
                                                          0))[:, None])
    return (np.take_along_axis(rows.times, cols, axis=1),
            np.take_along_axis(rows.values, cols, axis=1))


class StreamingFusedPipeline:
    """Ingest -> Reconstruct -> AlignTrack -> Regrid/Fuse -> PhaseAttr.

    The streaming-first counterpart of ``align.align_and_fuse`` +
    ``attribute_energy_fused``: feed raw (fleet, chunk) windows of mixed
    counter/power sensor reads; per-sensor delay is tracked online on
    sliding windows (or fixed via ``delays``), every stream is regridded
    onto one shared grid behind an emit frontier, and fused per-phase
    energies finalize with the end-of-run inverse-variance weights.
    Peak memory is O(fleet x (chunk + tail) + fleet x window) however
    long the run.

    group_sizes: sensors per device, in row order (rows are the
    flattened groups; trailing padding rows up to a ROW_ALIGN multiple
    are ignored).  phases: [(a, b)] in pipeline time (seconds since the
    caller's origin).  reference: callable(times)->watts in pipeline
    time for delay tracking; ``track=False`` freezes ``delays``.
    """

    def __init__(self, group_sizes, phases, *, grid_origin: float,
                 grid_step: float, kind_row=None, wrap_period=None,
                 delays=None, reference=None, track: bool = None,
                 window: int = 2048, hop: int = 512, max_lag: int = 64,
                 ema: float = 0.5, min_corr: float = 0.2, tail: int = 256,
                 var_floor: float = 0.25, collectives=None, shard=None,
                 record: bool = False, dtype=np.float32,
                 interpret=None, use_kernel=None,
                 health=None, registry=None, health_names=None,
                 meter=None, dq_policy: DataQualityPolicy = None):
        self.group_sizes = list(group_sizes)
        self.collectives = collectives
        self.shard = shard
        if collectives is not None:
            assert shard is not None, \
                "multi-host pipelines need the HostShard metadata"
            assert list(shard.local_group_sizes) == self.group_sizes, \
                "group_sizes must be this host's local groups"
        n = int(sum(self.group_sizes))
        self.n_streams = n
        f = _round_up(n, ROW_ALIGN)
        self.n_rows = f
        if kind_row is None:
            kind_row = np.zeros((f,), bool)
        kr = np.zeros((f,), bool)
        kr[:len(np.asarray(kind_row))] = np.asarray(kind_row, bool)
        wp = np.zeros((f,), np.float64)
        if wrap_period is not None:       # pad to the row tile, like kr
            wp_in = np.asarray(wrap_period, np.float64).reshape(-1)
            wp[:len(wp_in)] = wp_in
        interpret = auto_interpret(interpret)
        uk_bool = True if use_kernel is None else use_kernel
        if track is None:
            track = delays is None
        self.ingest = IngestStage(n, mode="sanitize", kind_row=kr,
                                  dq_policy=dq_policy)
        self.reconstruct = ReconstructStage(
            kr, wp, interpret=interpret, use_kernel=uk_bool)
        self.align = None
        if track:
            self.align = AlignTrackStage(
                n, grid_step=grid_step, reference=reference,
                groups=None if reference is not None else self.group_sizes,
                window=window, hop=hop, max_lag=max_lag, ema=ema,
                min_corr=min_corr, tail=tail, delay0=delays,
                collectives=collectives, shard=shard,
                interpret=interpret, use_kernel=use_kernel)
        self.fuse = RegridFuseStage(
            self.group_sizes, grid_origin=grid_origin,
            grid_step=grid_step, delays=delays, align=self.align,
            tail=tail, var_floor=var_floor, collectives=collectives,
            record=record, interpret=interpret,
            use_kernel=use_kernel, dq_policy=dq_policy)
        self.attr = FusedPhaseAttributeStage(phases, self.group_sizes,
                                             self.fuse,
                                             collectives=collectives,
                                             shard=shard)
        self.health_stage = None
        if health is not None and health is not False:
            # lazy import: repro.health depends only on core/, so the
            # fleet <-> health layers never import-cycle
            from repro.health.stage import HealthConfig, \
                SensorHealthStage
            cfg = health if isinstance(health, HealthConfig) else None
            if shard is not None:
                row_ids = np.asarray(shard.row_ids, np.int64)
                n_global = int(sum(shard.global_group_sizes))
            else:
                row_ids, n_global = None, None
            self.health_stage = SensorHealthStage(
                self.group_sizes, cfg, grid_step=grid_step,
                row_ids=row_ids, n_global=n_global,
                names=health_names, align=self.align,
                registry=registry)
            self.fuse.health = self.health_stage
        self.meter_stage = None
        if meter:
            # per-request metering: slot segments as a second phase
            # table, accumulated in the same pass (see MeteringStage)
            self.meter_stage = MeteringStage(
                list(meter), self.group_sizes, self.fuse,
                collectives=collectives, shard=shard)
        stages = [self.ingest, self.reconstruct]
        if self.align is not None:
            stages.append(self.align)
        stages += [self.fuse]
        if self.health_stage is not None:
            stages.append(self.health_stage)
        if self.meter_stage is not None:
            stages.append(self.meter_stage)
        stages += [self.attr]
        self.pipeline = StreamPipeline(*stages)
        if registry is not None:
            self.pipeline.attach_registry(registry)
            self._attach_fuse_metrics(registry)
            self._attach_dq_metrics(registry)
            if collectives is not None:
                registry.track_collectives(collectives)
        self._dtype = dtype
        self._window = int(window)
        self._hop = int(hop)
        self._tail_width = int(tail)
        self._var_floor = float(var_floor)

    def _attach_fuse_metrics(self, registry) -> None:
        from repro.health.registry import Metric
        fuse = self.fuse

        def _fn():
            lag = 0.0
            if fuse.last_frontier is not None:
                lag = (fuse.last_frontier
                       - (fuse.origin + fuse.step
                          * fuse.carry.next_slot))
            return [
                Metric("emit_frontier_lag_s", float(lag),
                       help="closed stream not yet emitted (s)"),
                Metric("emitted_slots_total",
                       float(fuse.carry.next_slot), kind="counter"),
            ]
        registry.register_source("fuse", _fn)

    def _attach_dq_metrics(self, registry) -> None:
        """The ``data_quality`` registry source: ingest repair counters,
        emitted-window coverage, and the per-window flags."""
        from repro.health.registry import Metric
        ing, fuse, n = self.ingest, self.fuse, self.n_streams

        def per(arr):
            return {f"r{i}": float(arr[i]) for i in range(n)}

        def _fn():
            z = np.zeros((n,), np.int64)
            late = ing.dq_late[:n] if ing.dq_late is not None else z
            masked = (ing.dq_masked[:n] if ing.dq_masked is not None
                      else z)
            w_late = ing.dq_last.get("late")
            w_masked = ing.dq_last.get("masked")
            flags = {
                "late": float(bool(w_late is not None
                                   and w_late[:n].any())),
                "dropped": float(bool(w_masked is not None
                                      and w_masked[:n].any())),
                "low_coverage": float(bool(fuse.dq_low_coverage.any())),
            }
            return [
                Metric("ingest_late_samples_total", per(late),
                       kind="counter", label="row",
                       help="reordered/late samples repaired at ingest"),
                Metric("ingest_dropped_samples_total", per(masked),
                       kind="counter", label="row",
                       help="masked/dropped sample slots at ingest"),
                Metric("window_coverage_frac",
                       per(fuse.dq_last_coverage), label="row",
                       help="last emitted window's covered-slot "
                            "fraction per stream"),
                Metric("dq_flag", flags, label="flag",
                       help="per-window data-quality flags (1 = seen "
                            "in the latest window)"),
            ]
        registry.register_source("data_quality", _fn)

    def update(self, times, values, valid=None):
        t = np.asarray(times, self._dtype)
        v = np.asarray(values, self._dtype)
        if t.shape[0] < self.n_rows:         # pad rows to the row tile
            pad = self.n_rows - t.shape[0]
            t = np.concatenate([t, np.repeat(t[-1:], pad, axis=0)])
            v = np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
            if valid is not None:
                valid = np.concatenate(
                    [np.asarray(valid, bool),
                     np.ones((pad, t.shape[1]), bool)])
        self.pipeline.update(t, v, valid)
        return self

    def finalize(self, t_end: float = None):
        self.pipeline.finalize(t_end)
        return self

    def totals(self) -> np.ndarray:
        """(n_devices, n_phases) fused joules accumulated so far.

        Multi-host: FLEET-wide (global device order, identical on every
        host) and a collective call — all hosts must reach it together.
        """
        return self.attr.totals()

    def weights(self) -> list:
        return self.attr.weights()

    def request_energies(self) -> dict:
        """{rid: (n_devices,) float64 joules} from the metering stage
        (needs ``meter=`` slot segments at construction)."""
        assert self.meter_stage is not None, \
            "request_energies() needs meter= slot segments"
        return self.meter_stage.request_energies()

    def fused_series(self):
        """(grid, watts, mask) for this host's LOCAL devices, from the
        recorded emitted windows + end-of-run weights (needs
        ``record=True``): the streaming counterpart of
        ``FusedStream.watts``, used by the sharding-invariance tests.
        Device groups are host-local, so no collectives are involved.
        """
        assert self.fuse.record, "fused_series() needs record=True"
        ems = self.fuse.emitted
        if not ems:
            d = len(self.group_sizes)
            return (np.zeros((0,)), np.zeros((d, 0)),
                    np.zeros((d, 0), bool))
        grid = np.concatenate([gw.grid for gw in ems])
        vals = np.concatenate([gw.values for gw in ems], axis=1)
        mask = np.concatenate([gw.mask for gw in ems], axis=1)
        w_flat = self.fuse.weights()
        d = len(self.group_sizes)
        g = grid.shape[0]
        watts = np.zeros((d, g))
        out_mask = np.zeros((d, g), bool)
        lo = 0
        for di, k in enumerate(self.group_sizes):
            w = w_flat[lo:lo + k][:, None]
            m = mask[lo:lo + k]
            v = vals[lo:lo + k].astype(np.float64)
            w_tot = (w * m).sum(axis=0)
            ok = w_tot > 0
            watts[di] = np.where(ok, (w * v * m).sum(axis=0)
                                 / np.maximum(w_tot, 1e-30), 0.0)
            out_mask[di] = ok
            lo += k
        return grid, watts, out_mask

    def delays(self) -> np.ndarray:
        """(n_streams,) per-stream delay in use (tracked or fixed)."""
        if self.align is not None and self.align.carry is not None:
            return self.align.delay_s[:self.n_streams].copy()
        d = np.zeros((self.n_rows,))
        d[:self.n_streams] = self.fuse._fixed
        return d[:self.n_streams]

    def fleet_delays(self):
        """(n_global,) fleet-wide tracked delays, identical on every
        host (multi-host tracking mode; None otherwise)."""
        if self.align is not None and self.align.synced \
                and self.align.delay_fleet is not None:
            return self.align.fleet_delay_s
        return None

    @property
    def delay_history(self) -> list:
        return [] if self.align is None else self.align.history

    def reset(self):
        self.pipeline.reset()
        return self

    # -- elastic checkpoint/restart --------------------------------------
    #
    # Layout (one directory tree per run, on a filesystem every host can
    # reach):
    #
    #   ckpt_dir/shared/step_W/          process 0 only — state that is
    #                                    IDENTICAL on every host (it is
    #                                    all-reduced: frontier slots,
    #                                    fleet delay EMA, health machine)
    #   ckpt_dir/group_{gid:05d}/step_W/ owning host — per-GLOBAL-group
    #                                    carry slices
    #
    # Keying the per-group trees by global group id (not by host) is
    # what makes restore elastic: any process count and any host<-group
    # assignment can reload the same checkpoint, each host gathering
    # exactly the groups it now owns.  Every saved array is the exact
    # carry (float64 where the pipeline is float64), so a restored run
    # continues the left folds bit-identically — the fold-order
    # determinism rule extends across the kill/restore boundary.

    @property
    def _ckpt_group_ids(self) -> list:
        if self.shard is not None:
            return [int(g) for g in self.shard.group_ids]
        return list(range(len(self.group_sizes)))

    def _ckpt_config(self) -> dict:
        """Pipeline-shape fingerprint: restore refuses a checkpoint
        written by a differently-configured pipeline (values must be
        JSON-round-trip stable: python ints/floats/bools/strs only)."""
        gs = (list(self.shard.global_group_sizes)
              if self.shard is not None else list(self.group_sizes))
        al = self.align
        return {
            "global_group_sizes": [int(s) for s in gs],
            "n_phases": int(self.attr.n_phases),
            "grid_origin": float(self.fuse.origin),
            "grid_step": float(self.fuse.step),
            "track": al is not None,
            "synced": bool(al is not None and al.synced),
            "window": int(self._window),
            "hop": int(self._hop),
            "tail": int(self._tail_width),
            "var_floor": float(self._var_floor),
            "health": self.health_stage is not None,
            "meter": self.meter_stage is not None,
            "dtype": str(np.dtype(self._dtype)),
        }

    def _shared_state(self) -> dict:
        al, hs = self.align, self.health_stage
        fc = self.fuse.carry
        i64 = np.int64
        tree = {
            "windows": np.asarray([self.pipeline.windows], i64),
            "fuse": {
                "next_slot": np.asarray([fc.next_slot], i64),
                "last_frontier": np.asarray(
                    [np.nan if self.fuse.last_frontier is None
                     else self.fuse.last_frontier], np.float64),
                "dq_slots": np.asarray([self.fuse.dq_slots], i64),
            },
        }
        if al is not None:
            a = {"origin": np.asarray(
                     [np.nan if al.origin is None else al.origin],
                     np.float64),
                 "next_slot": np.asarray([al.carry.next_slot], i64),
                 "last_est_slot": np.asarray([al.carry.last_est_slot],
                                             i64)}
            if al.synced:
                a["delay_fleet"] = np.asarray(al.delay_fleet, np.float64)
                a["seen_fleet"] = np.asarray(al._seen_fleet, bool)
            tree["align"] = a
        if hs is not None:
            tree["health"] = {
                "state": np.asarray(hs.state, i64),
                "flag_streak": np.asarray(hs.flag_streak, i64),
                "clean_streak": np.asarray(hs.clean_streak, i64),
                "ema_bias": np.asarray(hs.ema_bias, np.float64),
                "ema_rms": np.asarray(hs.ema_rms, np.float64),
                "ema_refresh": np.asarray(hs.ema_refresh, np.float64),
                "ema_seen": np.asarray(hs._ema_seen, bool),
                "refresh_seen": np.asarray(hs._refresh_seen, bool),
                "bias": np.asarray(hs.bias, np.float64),
                "rms": np.asarray(hs.rms, np.float64),
                "dropout": np.asarray(hs.dropout, np.float64),
                "windows": np.asarray([hs.windows], i64),
            }
        return tree

    def _shared_skeleton(self) -> dict:
        """Zeros tree matching ``_shared_state`` leaf-for-leaf (shape
        AND dtype: restore_checkpoint validates both)."""
        al, hs = self.align, self.health_stage
        i1 = lambda: np.zeros((1,), np.int64)          # noqa: E731
        f1 = lambda: np.zeros((1,), np.float64)        # noqa: E731
        tree = {"windows": i1(),
                "fuse": {"next_slot": i1(), "last_frontier": f1(),
                         "dq_slots": i1()}}
        if al is not None:
            a = {"origin": f1(), "next_slot": i1(),
                 "last_est_slot": i1()}
            if al.synced:
                g = int(self.shard.row_offsets[-1])
                a["delay_fleet"] = np.zeros((g,), np.float64)
                a["seen_fleet"] = np.zeros((g,), bool)
            tree["align"] = a
        if hs is not None:
            g = hs.n_global
            gi = lambda: np.zeros((g,), np.int64)      # noqa: E731
            gf = lambda: np.zeros((g,), np.float64)    # noqa: E731
            gb = lambda: np.zeros((g,), bool)          # noqa: E731
            tree["health"] = {
                "state": gi(), "flag_streak": gi(), "clean_streak": gi(),
                "ema_bias": gf(), "ema_rms": gf(), "ema_refresh": gf(),
                "ema_seen": gb(), "refresh_seen": gb(),
                "bias": gf(), "rms": gf(), "dropout": gf(),
                "windows": i1()}
        return tree

    def _group_skeleton(self, k: int, meta: dict) -> dict:
        """Zeros tree matching one saved group slice (k streams)."""
        dt = np.dtype(self._dtype)
        T = self._tail_width
        tree = {
            "ingest": {"t": np.zeros((k, 1), dt),
                       "v": np.zeros((k, 1), dt),
                       "t_first": np.zeros((k,), np.float64),
                       "dq_late": np.zeros((k,), np.int64),
                       "dq_masked": np.zeros((k,), np.int64)},
            "fuse": {"tail_t": np.zeros((k, T), dt),
                     "tail_v": np.zeros((k, T), dt),
                     "tail_dropped": np.zeros((k,), np.float64),
                     "n_k": np.zeros((k,), np.float64),
                     "ssr": np.zeros((k,), np.float64),
                     "t_first": np.zeros((k,), np.float64),
                     "dq_covered": np.zeros((k,), np.int64)},
            "attr": {"t_prev": np.zeros((1,), np.float64),
                     "integrals": {
                         str(p): np.zeros((self.attr.n_phases, k))
                         for p in meta["attr_patterns"]}},
        }
        if self.align is not None:
            tree["align"] = {
                "ring_v": np.zeros((k, self._window), dt),
                "ring_m": np.zeros((k, self._window), bool),
                "delay": np.zeros((k,), np.float64),
                "seen": np.zeros((k,), bool),
                "tail_t": np.zeros((k, T), dt),
                "tail_v": np.zeros((k, T), dt),
                "tail_dropped": np.zeros((k,), np.float64)}
        if self.meter_stage is not None:
            tree["meter"] = {
                "t_prev": np.zeros((1,), np.float64),
                "integrals": {
                    str(p): np.zeros((self.meter_stage.n_phases, k))
                    for p in meta["meter_patterns"]}}
        if self.health_stage is not None:
            from repro.health.stage import N_STATS
            tree["health"] = {"pending": np.zeros((N_STATS, k))}
        return tree

    def checkpoint(self, ckpt_dir, *, keep: int = 3) -> int:
        """Write one elastic checkpoint at the current window boundary.

        Call between ``update`` calls (every host at the SAME boundary
        in multi-host mode — it is not a collective, but the saved
        shared state must describe one fleet-wide boundary).  Returns
        the step (= windows processed) the checkpoint publishes under.
        """
        from repro.train.checkpoint import save_checkpoint
        assert self.pipeline.windows > 0, \
            "checkpoint() before the first update has nothing to save"
        al = self.align
        assert al is None or al._pending is None, \
            "checkpoint() must run at a window boundary (a pending " \
            "tracker contribution would be lost)"
        step = int(self.pipeline.windows)
        root = Path(ckpt_dir)
        cfg = self._ckpt_config()
        hs = self.health_stage
        pend = None
        if hs is not None:
            from repro.health.stage import N_STATS
            pend = (hs._pending if hs._pending is not None
                    else np.zeros((N_STATS, hs.n_global)))
        lo = 0
        for j, (gid, k) in enumerate(zip(self._ckpt_group_ids,
                                         self.group_sizes)):
            sl = slice(lo, lo + k)
            ic, fz = self.ingest.carry, self.fuse
            tree = {
                "ingest": {
                    "t": np.asarray(ic.t[sl], self._dtype),
                    "v": np.asarray(ic.v[sl], self._dtype),
                    "t_first": np.asarray(self.ingest._t_first[sl],
                                          np.float64),
                    "dq_late": (
                        self.ingest.dq_late[sl].astype(np.int64)
                        if self.ingest.dq_late is not None
                        else np.zeros((k,), np.int64)),
                    "dq_masked": (
                        self.ingest.dq_masked[sl].astype(np.int64)
                        if self.ingest.dq_masked is not None
                        else np.zeros((k,), np.int64)),
                },
                "fuse": {
                    "tail_t": np.asarray(fz._tail.carry.t[sl],
                                         self._dtype),
                    "tail_v": np.asarray(fz._tail.carry.v[sl],
                                         self._dtype),
                    "tail_dropped": np.asarray(
                        fz._tail.carry.dropped_t[sl], np.float64),
                    "n_k": np.asarray(fz.carry.n_k[sl], np.float64),
                    "ssr": np.asarray(fz.carry.ssr[sl], np.float64),
                    "t_first": np.asarray(fz._t_first[sl], np.float64),
                    "dq_covered": np.asarray(fz.dq_covered[sl],
                                             np.int64),
                },
                "attr": {
                    "t_prev": np.asarray([self.attr.carry.t_prev[j]],
                                         np.float64),
                    "integrals": {
                        str(p): np.asarray(acc, np.float64)
                        for p, acc in sorted(
                            self.attr.carry.integrals[j].items())},
                },
            }
            meta = {"config": cfg, "gid": gid,
                    "attr_patterns": sorted(
                        int(p) for p in self.attr.carry.integrals[j])}
            if al is not None:
                ac, tc = al.carry, al._tail.carry
                tree["align"] = {
                    "ring_v": np.asarray(ac.ring_v[sl], self._dtype),
                    "ring_m": np.asarray(ac.ring_m[sl], bool),
                    "delay": np.asarray(ac.delay[sl], np.float64),
                    "seen": np.asarray(ac.seen[sl], bool),
                    "tail_t": np.asarray(tc.t[sl], self._dtype),
                    "tail_v": np.asarray(tc.v[sl], self._dtype),
                    "tail_dropped": np.asarray(tc.dropped_t[sl],
                                               np.float64)}
            if self.meter_stage is not None:
                mc = self.meter_stage.carry
                tree["meter"] = {
                    "t_prev": np.asarray([mc.t_prev[j]], np.float64),
                    "integrals": {
                        str(p): np.asarray(acc, np.float64)
                        for p, acc in sorted(mc.integrals[j].items())}}
                meta["meter_patterns"] = sorted(
                    int(p) for p in mc.integrals[j])
            if hs is not None:
                tree["health"] = {"pending": pend[:, hs.row_ids[sl]]}
            save_checkpoint(root / f"group_{gid:05d}", step, tree,
                            keep=keep, extra_meta=meta)
            lo += k
        if self.collectives is None or self.collectives.process_id == 0:
            save_checkpoint(
                root / "shared", step, self._shared_state(), keep=keep,
                extra_meta={"config": cfg,
                            "suggested": (dict(hs._suggested)
                                          if hs is not None else {})})
        return step

    def _resolve_ckpt_step(self, root, step):
        """Largest step published by shared AND every global group dir
        — the same answer on every host, and immune to a kill that
        landed mid-checkpoint (a group whose save never published drops
        that step for everyone)."""
        n_groups = len(self._ckpt_config()["global_group_sizes"])
        common = _published_steps(root / "shared")
        for gid in range(n_groups):
            common &= _published_steps(root / f"group_{gid:05d}")
        if step is not None:
            if int(step) not in common:
                raise FileNotFoundError(
                    f"checkpoint step {step} is not complete under "
                    f"{root} (published everywhere: {sorted(common)})")
            return int(step)
        if not common:
            raise FileNotFoundError(
                f"no complete checkpoint under {root}")
        return max(common)

    def restore(self, ckpt_dir, *, step: int = None) -> int:
        """Reload carries from :meth:`checkpoint`; returns the window
        count the checkpoint was taken at (the replay skip count).

        Elastic: the CURRENT pipeline's host<-group assignment and
        process count need not match the saving run's — each host
        gathers the global-group slices it now owns.  Trailing padding
        rows replicate the last real row, exactly the state an
        uninterrupted run holds (``update`` pads its inputs the same
        way and every stage treats rows independently), so the resumed
        fold is bit-identical.
        """
        from repro.train.checkpoint import (checkpoint_meta,
                                            restore_checkpoint)
        root = Path(ckpt_dir)
        step = self._resolve_ckpt_step(root, step)
        shared_meta, _ = checkpoint_meta(root / "shared", step=step)
        cfg = self._ckpt_config()
        assert dict(shared_meta["config"]) == cfg, \
            f"checkpoint config mismatch:\n  saved {shared_meta['config']}" \
            f"\n  self  {cfg}"
        shared, _, _ = restore_checkpoint(
            root / "shared", self._shared_skeleton(), step=step)
        n, F = self.n_streams, self.n_rows
        dt = np.dtype(self._dtype)
        T = self._tail_width
        al, hs, ms = self.align, self.health_stage, self.meter_stage
        d = len(self.group_sizes)

        ing_t = np.zeros((F, 1), dt)
        ing_v = np.zeros((F, 1), dt)
        t_first = np.full((F,), np.inf)
        dq_late = np.zeros((F,), np.int64)
        dq_masked = np.zeros((F,), np.int64)
        fu_t = np.zeros((F, T), dt)
        fu_v = np.zeros((F, T), dt)
        fu_drop = np.full((F,), -np.inf)
        n_k = np.zeros((n,))
        ssr = np.zeros((n,))
        fu_first = np.full((F,), np.inf)
        dq_cov = np.zeros((n,), np.int64)
        if al is not None:
            ring_v = np.zeros((F, self._window), dt)
            ring_m = np.zeros((F, self._window), bool)
            delay = np.zeros((F,))
            seen = np.zeros((F,), bool)
            at_t = np.zeros((F, T), dt)
            at_v = np.zeros((F, T), dt)
            at_drop = np.full((F,), -np.inf)
        if hs is not None:
            from repro.health.stage import N_STATS
            pend = np.zeros((N_STATS, hs.n_global))
        attr_tp = np.full((d,), np.nan)
        attr_ints = [{} for _ in range(d)]
        if ms is not None:
            met_tp = np.full((d,), np.nan)
            met_ints = [{} for _ in range(d)]

        lo = 0
        for j, (gid, k) in enumerate(zip(self._ckpt_group_ids,
                                         self.group_sizes)):
            sl = slice(lo, lo + k)
            gdir = root / f"group_{gid:05d}"
            gmeta, _ = checkpoint_meta(gdir, step=step)
            assert dict(gmeta["config"]) == cfg, \
                f"group {gid}: checkpoint config mismatch"
            assert int(gmeta["gid"]) == gid
            g, _, _ = restore_checkpoint(
                gdir, self._group_skeleton(k, gmeta), step=step)
            ing = g["ingest"]
            ing_t[sl] = ing["t"]
            ing_v[sl] = ing["v"]
            t_first[sl] = ing["t_first"]
            dq_late[sl] = ing["dq_late"]
            dq_masked[sl] = ing["dq_masked"]
            fz = g["fuse"]
            fu_t[sl] = fz["tail_t"]
            fu_v[sl] = fz["tail_v"]
            fu_drop[sl] = fz["tail_dropped"]
            n_k[sl] = fz["n_k"]
            ssr[sl] = fz["ssr"]
            fu_first[sl] = fz["t_first"]
            dq_cov[sl] = fz["dq_covered"]
            if al is not None:
                az = g["align"]
                ring_v[sl] = az["ring_v"]
                ring_m[sl] = az["ring_m"]
                delay[sl] = az["delay"]
                seen[sl] = az["seen"]
                at_t[sl] = az["tail_t"]
                at_v[sl] = az["tail_v"]
                at_drop[sl] = az["tail_dropped"]
            if hs is not None:
                pend[:, hs.row_ids[sl]] = g["health"]["pending"]
            attr_tp[j] = float(g["attr"]["t_prev"][0])
            attr_ints[j] = {int(p): np.asarray(a, np.float64)
                            for p, a in g["attr"]["integrals"].items()}
            if ms is not None:
                met_tp[j] = float(g["meter"]["t_prev"][0])
                met_ints[j] = {
                    int(p): np.asarray(a, np.float64)
                    for p, a in g["meter"]["integrals"].items()}
            lo += k
        if F > n:
            # padding rows replicate the LAST real row (see docstring);
            # tracker padding never tracks: delay 0 / seen False, as in
            # the live carry
            r = slice(n - 1, n)
            for arr in (ing_t, ing_v, fu_t, fu_v):
                arr[n:] = arr[r]
            for vec in (t_first, fu_first, fu_drop, dq_late, dq_masked):
                vec[n:] = vec[n - 1]
            if al is not None:
                for arr in (ring_v, ring_m, at_t, at_v):
                    arr[n:] = arr[r]
                at_drop[n:] = at_drop[n - 1]

        self.ingest.carry = IngestCarry(t=ing_t, v=ing_v)
        self.ingest._t_first = t_first
        self.ingest.dq_late = dq_late
        self.ingest.dq_masked = dq_masked
        self.ingest.dq_last = {}
        fuse = self.fuse
        fuse._tail.carry = TailCarry(t=fu_t, v=fu_v, dropped_t=fu_drop)
        fuse.carry = FuseCarry(
            next_slot=int(shared["fuse"]["next_slot"][0]),
            n_k=n_k, ssr=ssr)
        lf = float(shared["fuse"]["last_frontier"][0])
        fuse.last_frontier = None if np.isnan(lf) else lf
        fuse._t_first = fu_first
        fuse.dq_covered = dq_cov
        fuse.dq_slots = int(shared["fuse"]["dq_slots"][0])
        fuse.dq_last_coverage = np.ones((n,))
        fuse.dq_low_coverage = np.zeros((n,), bool)
        if al is not None:
            sa = shared["align"]
            origin = float(sa["origin"][0])
            al.origin = None if np.isnan(origin) else origin
            al.carry = AlignCarry(
                ring_v=ring_v, ring_m=ring_m,
                next_slot=int(sa["next_slot"][0]),
                last_est_slot=int(sa["last_est_slot"][0]),
                delay=delay, seen=seen)
            al._tail.carry = TailCarry(t=at_t, v=at_v,
                                       dropped_t=at_drop)
            al._pending = None
            if al.synced:
                al.delay_fleet = np.asarray(sa["delay_fleet"],
                                            np.float64)
                al._seen_fleet = np.asarray(sa["seen_fleet"], bool)
        if hs is not None:
            sh = shared["health"]
            hs.state = np.asarray(sh["state"], np.int64)
            hs.flag_streak = np.asarray(sh["flag_streak"], np.int64)
            hs.clean_streak = np.asarray(sh["clean_streak"], np.int64)
            hs.ema_bias = np.asarray(sh["ema_bias"], np.float64)
            hs.ema_rms = np.asarray(sh["ema_rms"], np.float64)
            hs.ema_refresh = np.asarray(sh["ema_refresh"], np.float64)
            hs._ema_seen = np.asarray(sh["ema_seen"], bool)
            hs._refresh_seen = np.asarray(sh["refresh_seen"], bool)
            hs.bias = np.asarray(sh["bias"], np.float64)
            hs.rms = np.asarray(sh["rms"], np.float64)
            hs.dropout = np.asarray(sh["dropout"], np.float64)
            hs.windows = int(sh["windows"][0])
            # a saved all-zeros block folds exactly like a fresh None
            # pending (take_pending substitutes zeros), so this is
            # bit-safe whether or not a window was mid-flight
            hs._pending = pend
            hs._suggested = dict(shared_meta.get("suggested", {}))
        self.attr.carry = FusedAttrCarry(t_prev=attr_tp,
                                         integrals=attr_ints)
        if ms is not None:
            ms.carry = FusedAttrCarry(t_prev=met_tp,
                                      integrals=met_ints)
        self.pipeline.windows = int(shared["windows"][0])
        return self.pipeline.windows


def _published_steps(d) -> set:
    """Step numbers atomically published under one checkpoint dir."""
    d = Path(d)
    if not d.exists():
        return set()
    return {int(p.name.split("_")[1]) for p in d.iterdir()
            if p.is_dir() and p.name.startswith("step_")
            and not p.name.endswith(".tmp")}


def attribute_energy_fused_streaming(trace_groups, phases, *,
                                     config=None, reference=None,
                                     corrections=None, registry=None,
                                     meter=None,
                                     return_pipe: bool = False,
                                     on_window=None,
                                     **legacy) -> list:
    """Streaming-first counterpart of ``align.attribute_energy_fused``.

    trace_groups: [[SensorTrace, ...], ...] — all sensors observing one
    device per group.  The traces are packed once (raw, no
    reconstruction) and REPLAYED through the streaming pipeline in
    chunk-column windows: dE/dt, online delay tracking, regrid and
    fusion statistics all run per window, so device memory never holds
    a full trace.  phases: [(name, a, b)] absolute seconds.  Returns
    one ``[PhaseEnergy]`` per group.

    config: a ``fleet.config.PipelineConfig`` (or one of its sections,
    auto-wrapped) holding the chunk/grid/dtype knobs
    (``StreamConfig``), the delay-tracking geometry (``TrackConfig``),
    checkpointing (``CheckpointConfig``), plus ``health`` and ``dq``.
    ``StreamConfig.grid`` (absolute) pins the output grid for
    batch-replay parity; otherwise a default grid at half the fastest
    cadence is derived.  The pre-config flat kwargs (``chunk=``,
    ``window=``, ``checkpoint_dir=``, ...) still resolve — bit-
    identically — through ``fleet.config.resolve_config`` but emit a
    ``DeprecationWarning``.

    health: None/False disables diagnostics (the default — results are
    then byte-for-byte today's pipeline); True or a
    ``health.HealthConfig`` composes a ``SensorHealthStage`` between
    Fuse and PhaseAttribute.  registry: an optional
    ``health.HealthRegistry`` for telemetry export.
    meter: a list of ``SlotSegment`` (absolute seconds, like phases)
    composes a ``MeteringStage`` before PhaseAttribute — per-request
    energies via ``pipe.request_energies()`` with ``return_pipe=True``.
    return_pipe: also return the driven pipeline, for health-event/
    metrics/metering inspection: ``(out, pipe)``.

    Fault tolerance: ``CheckpointConfig(dir=, every=K)`` writes an
    elastic carry checkpoint every K replay windows; ``resume=True``
    reloads the newest complete one and SKIPS the already-processed
    windows — the resumed run's fused energies are bit-identical to
    the uninterrupted run (the carries are exact).
    ``on_window(pipe, w)`` fires after window ``w`` (1-based)
    completes — test hook for kill injection.
    ``PipelineConfig.dq``: a ``DataQualityPolicy`` for the
    ingest/fuse stages.
    """
    from repro.core.attribution import PhaseEnergy
    cfg = resolve_config(config, legacy,
                         "attribute_energy_fused_streaming")
    chunk = cfg.stream.chunk
    grid, grid_step = cfg.stream.grid, cfg.stream.grid_step
    dtype, var_floor = cfg.stream.dtype, cfg.stream.var_floor
    use_t_measured = cfg.stream.use_t_measured
    interpret, use_kernel = cfg.stream.interpret, cfg.stream.use_kernel
    track, delays = cfg.track.track, cfg.track.delays
    window, hop = cfg.track.window, cfg.track.hop
    max_lag, ema = cfg.track.max_lag, cfg.track.ema
    tail = cfg.track.tail
    checkpoint_dir = cfg.checkpoint.dir
    checkpoint_every = cfg.checkpoint.every
    resume = cfg.checkpoint.resume
    health, dq_policy = cfg.health, cfg.dq
    groups = [list(g) for g in trace_groups]
    flat = [tr for g in groups for tr in g]
    n_reads = sum(len(tr) for tr in flat) if tracing.recording() else -1
    with tracing.span("fleet.attribute", n=n_reads):
        with tracing.span("fleet.pack"):
            rows = pack_stream_rows(flat, corrections=corrections,
                                    use_t_measured=use_t_measured,
                                    dtype=dtype)
        with tracing.span("fleet.plan"):
            if grid is not None:
                grid = np.asarray(grid, np.float64)
                grid_step = float(np.median(np.diff(grid)))
                origin = float(grid[0]) - rows.t0
                t_end = float(grid[-1]) - rows.t0
            else:
                if grid_step is None:
                    grid_step = 0.5 * _min_cadence(rows)
                origin = float(rows.times[:rows.n_streams, 0]
                               .astype(np.float64).min())
                t_end = None
            if tail is None:
                tail = default_tail(rows, chunk, delays=delays,
                                    max_lag=max_lag, grid_step=grid_step)
            ref = None
            if reference is not None:
                from repro.core.power_model import PiecewisePower
                if isinstance(reference, PiecewisePower):
                    t0 = rows.t0
                    ref = (lambda t, _r=reference:  # noqa: E731
                           _r.power_at(t + t0))
                else:
                    ref = reference
            if not phases:
                return [[] for _ in groups]
            windows = [(a - rows.t0, b - rows.t0) for _, a, b in phases]
            if meter:
                meter = [s.shifted(-rows.t0) for s in meter]
            pipe = StreamingFusedPipeline(
                [len(g) for g in groups], windows, grid_origin=origin,
                grid_step=grid_step, kind_row=rows.kind_row,
                delays=delays, reference=ref, track=track,
                window=window, hop=hop, max_lag=max_lag, ema=ema,
                tail=tail, var_floor=var_floor, dtype=dtype,
                interpret=interpret, use_kernel=use_kernel,
                health=health, registry=registry,
                health_names=[tr.name for tr in flat], meter=meter,
                dq_policy=dq_policy)
            n_win, idx = _replay_window_plan(rows, chunk)
        start_w = 0
        if resume:
            assert checkpoint_dir is not None, \
                "resume=True needs checkpoint_dir"
            try:
                start_w = pipe.restore(checkpoint_dir)
            except FileNotFoundError:
                start_w = 0      # cold start: nothing published yet
        # windows up to start_w were folded before the checkpoint
        for w in range(start_w + 1, n_win + 1):
            with tracing.span("fleet.window",
                              n=_window_width(idx, w - 1)):
                t_blk, v_blk = _replay_window(rows, idx, w - 1)
                pipe.update(t_blk, v_blk)
                if (checkpoint_dir is not None and checkpoint_every
                        and w % checkpoint_every == 0):
                    pipe.checkpoint(checkpoint_dir)
                if on_window is not None:
                    on_window(pipe, w)
        with tracing.span("fleet.finalize"):
            pipe.finalize(t_end)
        with tracing.span("fleet.totals"):
            totals = pipe.totals()
        with tracing.span("fleet.rows"):
            out = []
            for di in range(len(groups)):
                row = []
                for (name, a, b), e in zip(phases, totals[di]):
                    dur = max(b - a, 1e-12)
                    row.append(PhaseEnergy(name, a, b, float(e),
                                           float(e / dur)))
                out.append(row)
    return (out, pipe) if return_pipe else out
