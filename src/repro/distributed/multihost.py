"""Multi-host fleet layer: host-spanning row sharding for the pipeline.

The paper attributes power across up to 512 GPUs / 480 APUs — a scale
that only exists across many hosts.  This module extends the fleet
subsystem's row partition over ``jax.distributed`` processes:

  * each host packs ONLY its own sensors (``fleet.packing.assign_groups``
    splits the fleet by device group; global row ids ride in the
    ``HostShard`` metadata),
  * the per-host streaming pipeline runs unchanged — every kernel is
    row-local, so the heavy work needs no cross-process XLA at all,
  * the quantities that ARE global go over ``HostCollectives``: the
    emit frontier (all-reduced min every window, so hosts emit
    identical grid slots in lockstep), the ONLINE delay-tracking state
    (ring origin + fill frontier mins, plus each hop window's
    (lag, weight) pairs framed onto the emit-frontier reduce and
    folded into one shared fleet EMA — every host applies identical
    delay corrections), and the end-of-run per-(device, phase,
    coverage-pattern, stream) integrals + fusion sufficient statistics
    (gathered once, assembled identically on every host).

``HostCollectives`` is deliberately NOT an XLA collective: the reduced
quantities are a few hundred bytes of host-side float64 per step, and
the CPU backend (where CI exercises all of this, via the spawn harness
in ``tests/multihost/``) has no cross-process XLA computations at all.
``CoordinatorCollectives`` rides the jax distributed coordination
service's key-value store — the same gRPC service
``jax.distributed.initialize`` already stands up — and
``ThreadCollectives`` simulates N hosts inside one process for
property tests.  On real multi-host GPU/APU nodes the SAME code path
runs; ``global_fleet_mesh`` additionally exposes the
(hosts, local_devices) mesh for placement of fleet-wide arrays there.

Determinism contract: whole device groups live on one host, frontier
all-reduce pins the emission schedule, the end-of-run merge is pure
placement, and the tracking reduce follows the fold-order rule
(``allreduce_framed``: left fold in process-id order; exclusive row
ownership makes the sums exact) with the lag-bank row tiling pinned to
the fleet row tile — fleet-wide fused energies are bit-identical for
ANY host←group assignment and ANY process count (tested at 1/2/4,
fixed-delay AND tracked).
"""
from __future__ import annotations

import logging
import pickle
import threading

import numpy as np

import jax
from jax.sharding import Mesh, PartitionSpec as P

logger = logging.getLogger(__name__)

DEFAULT_TIMEOUT_S = 120.0


# ---------------------------------------------------------------------------
# Host-side collectives
# ---------------------------------------------------------------------------

class HostCollectives:
    """Blocking collectives over tiny host-side arrays (base class).

    Implementations provide ``allgather_bytes`` + ``barrier``; the
    numeric reductions are built on top, always reducing in process-id
    order so every participant computes bit-identical results.  All
    calls are COLLECTIVE: every participant must reach them in lockstep
    or the group deadlocks (until the timeout fires).

    The framed reduces go over the compact lossless wire format
    (``repro.distributed.compression.encode_reduce_frame``): sparse
    delta + bitpacked framing for the per-window (lag, weight) vectors,
    raw float64 for the frontier/origin scalars that ride them —
    ``wire_stats`` counts the posted vs pre-wire-format dense bytes
    (the >=10x payload shrink the bench gate enforces).  Scalar-only
    reduces (``allreduce_min``/``max``/``sum``) stay raw float64: they
    are already minimal and must be uncompressed-exact.
    """

    process_id: int = 0
    num_processes: int = 1

    @property
    def wire_stats(self):
        """Per-participant framed-reduce byte counters (lazy)."""
        from repro.distributed.compression import WireStats
        ws = getattr(self, "_wire_stats", None)
        if ws is None:
            ws = self._wire_stats = WireStats()
        return ws

    def allgather_bytes(self, payload: bytes) -> list:
        raise NotImplementedError

    def barrier(self):
        raise NotImplementedError

    def allreduce(self, x, op: str = "sum") -> np.ndarray:
        arr = np.atleast_1d(np.asarray(x, np.float64))
        if self.num_processes == 1:
            return arr.copy()
        parts = self.allgather_bytes(arr.tobytes())
        stack = np.stack([np.frombuffer(p, np.float64).reshape(arr.shape)
                          for p in parts])
        return {"sum": np.sum, "min": np.min,
                "max": np.max}[op](stack, axis=0)

    def allreduce_min(self, x: float) -> float:
        return float(self.allreduce([float(x)], "min")[0])

    def allreduce_max(self, x: float) -> float:
        return float(self.allreduce([float(x)], "max")[0])

    def allreduce_sum(self, x: float) -> float:
        return float(self.allreduce([float(x)], "sum")[0])

    def allreduce_framed(self, scalar: float, vec, *,
                         scalar_op: str = "min"):
        """One round-trip framed reduce: a scalar plus a float64 vector.

        The frame rides a single ``allgather_bytes`` — this is how the
        per-window (lag, weight) tracking contributions piggyback on the
        emit-frontier reduction instead of costing their own round trip.
        The scalar is min/max-reduced; the vector is summed as a LEFT
        FOLD IN PROCESS-ID ORDER (the fold-order determinism rule:
        every participant accumulates ``v_0 + v_1 + ... + v_{P-1}`` in
        the same sequence, so all hosts compute bit-identical sums; and
        when each element is non-zero on exactly ONE participant — e.g.
        per-row lag contributions under exclusive row ownership — the
        float64 sum is EXACT, hence also invariant to the process
        count).  Returns ``(scalar, vec)``.

        The frame on the wire is the compact lossless encoding from
        ``repro.distributed.compression`` — non-zero values travel as
        raw float64 (bit-exact, so the fold above is unchanged), only
        the zeros and index bookkeeping are compressed away.  Posted
        bytes are tallied in ``wire_stats``.
        """
        assert scalar_op in ("min", "max"), scalar_op
        from repro.distributed.compression import (decode_reduce_frame,
                                                   encode_reduce_frame)
        v = np.asarray(vec, np.float64).reshape(-1)
        if self.num_processes == 1:
            self.wire_stats.record(len(encode_reduce_frame(scalar, v)),
                                   8 * (1 + v.size))
            return float(scalar), v.copy()
        payload = encode_reduce_frame(float(scalar), v)
        self.wire_stats.record(len(payload), 8 * (1 + v.size))
        parts = self.allgather_bytes(payload)
        rows = [decode_reduce_frame(p) for p in parts]
        assert all(r[1].size == v.size for r in rows), \
            "framed reduce: ragged frames (participants disagree on " \
            "the tracked fleet width?)"
        s = rows[0][0]
        acc = rows[0][1].copy()
        red = min if scalar_op == "min" else max
        for rs, rv in rows[1:]:
            s = red(s, float(rs))
            acc += rv
        return float(s), acc


class CoordinatorCollectives(HostCollectives):
    """HostCollectives over the jax distributed coordination service.

    Uses the key-value store + barrier of the gRPC service that
    ``jax.distributed.initialize`` stands up — NOT XLA collectives, so
    it works on any backend including multi-process CPU (where XLA
    cross-process computations don't exist).  Every collective burns
    one generation of namespaced keys; each participant deletes its own
    key after the group passes the generation's barrier, so the store
    stays O(participants) however long the run is.
    """

    def __init__(self, client, process_id: int, num_processes: int, *,
                 namespace: str = "repro_mh",
                 timeout_s: float = DEFAULT_TIMEOUT_S):
        self._client = client
        self.process_id = int(process_id)
        self.num_processes = int(num_processes)
        self._ns = namespace
        self._timeout_ms = int(timeout_s * 1000)
        self._gen = 0

    @classmethod
    def from_jax(cls, **kw) -> "CoordinatorCollectives":
        """Build from the already-initialized jax distributed runtime."""
        from jax._src import distributed
        state = distributed.global_state
        if state.client is None:
            raise RuntimeError(
                "jax.distributed is not initialized — call "
                "repro.distributed.multihost.init_multihost (or "
                "jax.distributed.initialize) first")
        return cls(state.client, state.process_id, state.num_processes,
                   **kw)

    def _next(self) -> str:
        g = self._gen
        self._gen += 1
        return f"{self._ns}/g{g}"

    # 2-byte frame prefix: jaxlib 0.4.x's blocking_key_value_get_bytes
    # SEGFAULTS on 1-byte stored values (observed on 0.4.37), so no
    # value in the store is ever shorter than 2 bytes
    _FRAME = b"MH"

    def allgather_bytes(self, payload: bytes) -> list:
        if self.num_processes == 1:
            return [bytes(payload)]
        base = self._next()
        self._client.key_value_set_bytes(f"{base}/p{self.process_id}",
                                         self._FRAME + bytes(payload))
        out = [self._client.blocking_key_value_get_bytes(
            f"{base}/p{i}", self._timeout_ms)[len(self._FRAME):]
            for i in range(self.num_processes)]
        self._client.wait_at_barrier(f"{base}/done", self._timeout_ms)
        self._client.key_value_delete(f"{base}/p{self.process_id}")
        return out

    def barrier(self):
        if self.num_processes == 1:
            return
        self._client.wait_at_barrier(f"{self._next()}/b",
                                     self._timeout_ms)


class _ThreadParticipant(HostCollectives):
    def __init__(self, group: "ThreadCollectives", i: int):
        self._group = group
        self.process_id = i
        self.num_processes = group.n

    def allgather_bytes(self, payload: bytes) -> list:
        g = self._group
        if g.n == 1:
            return [bytes(payload)]
        g.slots[self.process_id] = bytes(payload)
        g.barrier.wait(g.timeout_s)        # everyone posted
        out = list(g.slots)
        g.barrier.wait(g.timeout_s)        # everyone read (reuse-safe)
        return out

    def barrier(self):
        if self._group.n > 1:
            self._group.barrier.wait(self._group.timeout_s)


class ThreadCollectives:
    """N in-process participants simulating N hosts (property tests).

    ``participant(i)`` hands thread i its HostCollectives view; run one
    simulated host per thread (``threading.Barrier`` underneath, so the
    lockstep contract is enforced exactly as in the distributed case).
    """

    def __init__(self, n: int, *, timeout_s: float = DEFAULT_TIMEOUT_S):
        self.n = int(n)
        self.timeout_s = timeout_s
        self.barrier = threading.Barrier(self.n)
        self.slots = [None] * self.n

    def participant(self, i: int) -> _ThreadParticipant:
        return _ThreadParticipant(self, i)


# ---------------------------------------------------------------------------
# Process bootstrap + the global mesh
# ---------------------------------------------------------------------------

def init_multihost(coordinator_address=None, num_processes=None,
                   process_id=None, **kw) -> CoordinatorCollectives:
    """Idempotent ``jax.distributed.initialize`` + host collectives.

    Call before any backend use (first jax array creation), exactly as
    ``jax.distributed.initialize`` requires; a second call (or a call
    in an already-initialized process, e.g. under SLURM auto-detect)
    just returns a fresh collectives handle over the existing runtime.
    """
    from jax._src import distributed
    if distributed.global_state.client is None:
        jax.distributed.initialize(coordinator_address, num_processes,
                                   process_id, **kw)
        logger.debug("jax.distributed initialized: process %d/%d",
                     jax.process_index(), jax.process_count())
    return CoordinatorCollectives.from_jax()


def global_fleet_mesh(min_devices: int = 2):
    """(hosts, local_devices)-spanning row mesh over EVERY process.

    Built from ``jax.devices()`` after ``jax.distributed.initialize``:
    axis "host" enumerates processes, axis "fleet" their local devices;
    shard fleet-row arrays with ``global_fleet_spec`` (rows split over
    both axes).  Requires a backend with cross-process XLA computations
    (GPU/TPU) to COMPUTE on — on multi-process CPU the mesh is
    placement metadata only, and the fleet pipeline's per-host packing
    + ``HostCollectives`` path carries the actual run (which is why the
    spawn harness can exercise all of this in CI).  Returns None below
    ``min_devices`` total devices — the single-host pipeline then runs
    exactly as before.
    """
    devices = jax.devices()
    if len(devices) < min_devices:
        return None
    n_proc = max(d.process_index for d in devices) + 1
    per, rem = divmod(len(devices), n_proc)
    if rem:
        raise ValueError(
            f"uneven local device counts ({len(devices)} devices over "
            f"{n_proc} processes) — global_fleet_mesh needs a "
            f"rectangular (hosts, local_devices) layout")
    arr = np.empty((n_proc, per), dtype=object)
    fill = [0] * n_proc
    for d in devices:
        arr[d.process_index, fill[d.process_index]] = d
        fill[d.process_index] += 1
    return Mesh(arr, ("host", "fleet"))


def global_fleet_spec(ndim: int) -> P:
    """Row-sharded spec on the global mesh: rows split over BOTH the
    host and local-device axes."""
    return P(("host", "fleet"), *([None] * (ndim - 1)))


# ---------------------------------------------------------------------------
# The multi-host fused-attribution entry point
# ---------------------------------------------------------------------------

def attribute_energy_fused_multihost(local_groups, phases, *, shard,
                                     collectives, config=None,
                                     reference=None, corrections=None,
                                     record: bool = False,
                                     return_pipe: bool = False,
                                     registry=None, on_window=None,
                                     **legacy):
    """Fleet-wide fused per-phase energy, rows sharded across hosts.

    The multi-host counterpart of
    ``fleet.pipeline.attribute_energy_fused_streaming``: every host
    calls it with ONLY the trace groups it owns (``local_groups``, in
    ``shard.group_ids`` order — each inner list is every sensor
    observing one device) plus the shared ``shard``/``collectives``;
    all hosts return the SAME fleet-wide result — one ``[PhaseEnergy]``
    per GLOBAL device group.  ``config`` is the same
    ``fleet.config.PipelineConfig`` bundle the single-host entry point
    takes; the pre-config flat kwargs still resolve bit-identically
    but emit a ``DeprecationWarning``.

    Every origin the float32 packing depends on (shared t0, the counter
    sub-pack origin, the output grid, the replay span and cadence) is
    all-reduced before packing, so each host's rows are bit-identical
    to a single-host pack of the whole fleet — combined with the emit-
    frontier all-reduce this makes the result independent of the
    host←group assignment and of the process count, to the last bit.

    ``delays`` are per-LOCAL-row fixed delays (this host's rows);
    ``grid``/``phases`` are global (identical on every host).
    ``track=True`` re-estimates delays online and SYNCHRONIZES the
    tracking state over the collectives: the tracker's ring origin and
    fill frontier are all-reduced (the hop schedule is global) and each
    window's (lag, weight) pairs ride the emit-frontier frame
    (``allreduce_framed``), folding into one shared fleet EMA — so a
    tracked multi-host run reproduces the single-host tracker's delay
    corrections exactly, and stays bit-identical for any host←group
    assignment and process count just like the fixed-delay mode
    (``pipe.fleet_delays()`` exposes the shared vector).

    ``health`` (True or a ``health.HealthConfig``) composes the
    streaming ``SensorHealthStage``: every window's per-sensor residual
    stats ride the existing framed frontier reduce (one extra
    fleet-sized block, same round trip), so the quarantine decisions —
    and hence the fused results they gate — stay bit-identical across
    process counts and host←group assignments.  Sensor names are
    allgathered once (tiny pickle) so every host labels the same global
    rows identically.  ``registry`` is an optional
    ``health.HealthRegistry`` for telemetry export.

    Elastic fault tolerance: ``checkpoint_dir`` (a path every host can
    reach) + ``checkpoint_every=K`` writes per-GLOBAL-group carry
    checkpoints every K replay windows; ``resume=True`` reloads the
    newest checkpoint complete across ALL groups and skips the
    already-folded windows.  Because the checkpoint is keyed by global
    group id and the replay plan is pinned by all-reduced provenance,
    the resuming fleet may use a DIFFERENT process count or host<-group
    assignment than the killed one — the resumed fused energies are
    bit-identical to the uninterrupted run either way (the skip loop
    performs no collectives and every host skips the same count, so
    lockstep is preserved).  ``on_window(pipe, w)`` fires after replay
    window ``w`` (1-based) completes on this host — the chaos tests'
    kill-injection hook.  ``dq_policy`` is a
    ``fleet.pipeline.DataQualityPolicy`` for ingest/fuse accounting.
    """
    from repro.core.attribution import PhaseEnergy
    from repro.fleet.config import resolve_config
    from repro.fleet.pipeline import (StreamingFusedPipeline,
                                      _min_cadence, default_tail,
                                      pack_stream_rows,
                                      stream_row_windows)
    cfg = resolve_config(config, legacy,
                         "attribute_energy_fused_multihost")
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
    groups = [list(g) for g in local_groups]
    assert len(groups) == len(shard.group_ids), \
        (len(groups), len(shard.group_ids))
    for g, gid in zip(groups, shard.group_ids):
        assert len(g) == shard.global_group_sizes[gid], \
            f"group {gid}: {len(g)} traces != declared " \
            f"{shard.global_group_sizes[gid]}"
    flat = [tr for g in groups for tr in g]

    def _starts(trs):
        return [float((tr.t_measured if use_t_measured
                       else tr.t_read)[0]) for tr in trs]

    t0 = collectives.allreduce_min(min(_starts(flat)))
    cum_starts = _starts([tr for tr in flat if tr.spec.is_cumulative])
    cum_t0 = collectives.allreduce_min(
        min(cum_starts) if cum_starts else np.inf)
    rows = pack_stream_rows(flat, corrections=corrections,
                            use_t_measured=use_t_measured, dtype=dtype,
                            t0=t0, cum_t0=(None if np.isinf(cum_t0)
                                           else cum_t0))
    n = rows.n_streams
    cadence = collectives.allreduce_min(_min_cadence(rows))
    if grid is not None:
        grid = np.asarray(grid, np.float64)
        grid_step = float(np.median(np.diff(grid)))
        origin = float(grid[0]) - rows.t0
        t_end = float(grid[-1]) - rows.t0
    else:
        if grid_step is None:
            grid_step = 0.5 * cadence
        origin = collectives.allreduce_min(
            float(rows.times[:n, 0].astype(np.float64).min()))
        t_end = None
    if tail is None:
        d_ref = None
        if delays is not None:
            d = np.asarray(delays, np.float64)
            # global spread: the frontier trails the fleet-wide
            # most-delayed stream, not just this host's
            d_ref = [collectives.allreduce_min(float(d.min())),
                     collectives.allreduce_max(float(d.max()))]
        tail = default_tail(rows, chunk, delays=d_ref, max_lag=max_lag,
                            grid_step=grid_step, cadence=cadence)
    ref = None
    if reference is not None:
        from repro.core.power_model import PiecewisePower
        if isinstance(reference, PiecewisePower):
            ref = lambda t, _r=reference: _r.power_at(t + t0)  # noqa: E731
        else:
            ref = reference
    n_global = len(shard.global_group_sizes)
    if not phases:
        return ([[] for _ in range(n_global)], None) if return_pipe \
            else [[] for _ in range(n_global)]
    windows = [(a - rows.t0, b - rows.t0) for _, a, b in phases]
    health_names = None
    if health:
        # one tiny pickle allgather so every host labels the same
        # global rows with the same sensor names (events/metrics then
        # compare bitwise across hosts and process counts)
        sizes = [int(s) for s in shard.global_group_sizes]
        g_off = [0]
        for s in sizes:
            g_off.append(g_off[-1] + s)
        health_names = [f"s{i}" for i in range(g_off[-1])]
        blob = pickle.dumps((tuple(int(g) for g in shard.group_ids),
                             [tr.name for tr in flat]))
        for part in collectives.allgather_bytes(blob):
            gids, nms = pickle.loads(part)
            k = 0
            for gid in gids:
                for j in range(sizes[gid]):
                    health_names[g_off[gid] + j] = nms[k]
                    k += 1
    pipe = StreamingFusedPipeline(
        shard.local_group_sizes, windows, grid_origin=origin,
        grid_step=grid_step, kind_row=rows.kind_row, delays=delays,
        reference=ref, track=track, window=window, hop=hop,
        max_lag=max_lag, ema=ema, tail=tail, var_floor=var_floor,
        collectives=collectives, shard=shard, record=record,
        dtype=dtype, interpret=interpret, use_kernel=use_kernel,
        health=health, registry=registry,
        health_names=health_names, dq_policy=dq_policy)
    span = (collectives.allreduce_min(
                float(rows.times[:n, 0].astype(np.float64).min())),
            collectives.allreduce_max(
                float(rows.times[:n, -1].astype(np.float64).max())))
    start_w = 0
    if resume:
        assert checkpoint_dir is not None, \
            "resume=True needs checkpoint_dir"
        try:
            start_w = pipe.restore(checkpoint_dir)
        except FileNotFoundError:
            start_w = 0      # cold start — same outcome on every host:
            #                  _resolve_ckpt_step reads the SHARED dirs
    for w, (t_blk, v_blk) in enumerate(
            stream_row_windows(rows, chunk, span=span, cadence=cadence),
            start=1):
        if w <= start_w:
            continue   # skip replayed windows: NO collectives fire
            #            here and every host skips the same count, so
            #            the fleet stays in reduce lockstep
        pipe.update(t_blk, v_blk)
        if (checkpoint_dir is not None and checkpoint_every
                and w % checkpoint_every == 0):
            pipe.checkpoint(checkpoint_dir)
        if on_window is not None:
            on_window(pipe, w)
    pipe.finalize(t_end)
    totals = pipe.totals()                 # fleet-wide, replicated
    out = []
    for di in range(n_global):
        row = []
        for (name, a, b), e in zip(phases, totals[di]):
            dur = max(b - a, 1e-12)
            row.append(PhaseEnergy(name, a, b, float(e), float(e / dur)))
        out.append(row)
    return (out, pipe) if return_pipe else out
