"""Trace-level fleet entry points mirroring the per-trace host API.

``fleet_power_series`` replaces ``[delta_e_over_delta_t(tr) for tr in ...]``
and ``attribute_energy_fleet`` replaces ``[attribute_energy(tr, phases)
for tr in ...]`` for cumulative-energy traces; the host loops remain the
parity oracles (tests pin fleet == host).  For fused multi-sensor
streaming see ``pipeline.attribute_energy_fused_streaming``.
"""
from __future__ import annotations

import numpy as np

from repro.core import tracing
from repro.core.calibration import apply_corrections
from repro.fleet.packing import pack_traces, unpack_series
from repro.fleet.reconstruct import fleet_reconstruct
from repro.fleet.streaming import FleetStream


def fleet_power_series(traces, *, use_t_measured: bool = True,
                       interpret=None, use_kernel: bool = True,
                       corrections=None, dtype=np.float32):
    """Batched ΔE/Δt for many cumulative-energy traces -> [PowerSeries].

    One pack (memcpy) + one jitted fleet call, any trace count/lengths.
    """
    traces = [apply_corrections(tr, corrections) for tr in traces]
    for tr in traces:
        assert tr.spec.is_cumulative, \
            f"{tr.name} is not an energy counter (fleet ΔE/Δt path)"
    packed = pack_traces(traces, use_t_measured=use_t_measured, dtype=dtype)
    power, times, valid = fleet_reconstruct(packed, interpret=interpret,
                                            use_kernel=use_kernel)
    return unpack_series(packed, power, times, valid)


def attribute_energy_fleet(traces, phases, *, corrections=None,
                           chunk: int = 1024, interpret=None,
                           use_kernel: bool = True, dtype=np.float32):
    """Per-phase energy for many cumulative traces in streamed chunks.

    phases: [(name, t_start, t_end)].  Returns one ``[PhaseEnergy]`` list
    per input trace (same shape as looping ``attribute_energy``), computed
    as reconstruct+integrate over fixed-size windows: device memory stays
    O(fleet × chunk) however long the traces are.
    """
    from repro.core.attribution import PhaseEnergy
    n_reads = sum(len(tr) for tr in traces) if tracing.recording() else -1
    with tracing.span("fleet.attribute", n=n_reads):
        with tracing.span("fleet.pack"):
            with tracing.span("fleet.correct"):
                traces = [apply_corrections(tr, corrections)
                          for tr in traces]
            if not phases:                   # host-path parity: empty rows
                return [[] for _ in traces]
            for tr in traces:
                assert tr.spec.is_cumulative, \
                    f"{tr.name} is not an energy counter (fleet ΔE/Δt path)"
            packed = pack_traces(traces, dtype=dtype)
        with tracing.span("fleet.plan"):
            # packed times are rebased to the fleet origin; shift windows
            windows = [(a - packed.t0, b - packed.t0) for _, a, b in phases]
            stream = FleetStream(windows, packed.shape[0],
                                 wrap_period=packed.wrap_period,
                                 dtype=dtype, interpret=interpret,
                                 use_kernel=use_kernel)
        s = packed.shape[1]
        for lo in range(0, s, chunk):
            hi = min(lo + chunk, s)
            with tracing.span("fleet.window", n=hi - lo):
                stream.update(packed.times[:, lo:hi],
                              packed.energy[:, lo:hi])
        with tracing.span("fleet.totals"):
            totals = stream.totals()
        with tracing.span("fleet.rows"):
            out = []
            for i in range(packed.n_traces):
                row = []
                for (name, a, b), e in zip(phases, totals[i]):
                    dur = max(b - a, 1e-12)
                    row.append(PhaseEnergy(name, a, b, float(e),
                                           float(e / dur)))
                out.append(row)
    return out


def attribute_energy_fused(trace_groups, phases, *, streaming=False,
                           config=None, **kw):
    """Per-phase energy on the FUSED cross-sensor stream of each device.

    trace_groups: [[SensorTrace, ...], ...] — all sensors observing one
    device per group (mixed cumulative + power).  The alignment
    subsystem estimates per-sensor delays, regrids onto one timeline and
    inverse-variance-fuses before integrating, so each number is backed
    by every sensor scope instead of a single counter; see
    ``repro.align`` for the keyword surface (reference, corrections,
    grid_step, ...).  Returns one ``[PhaseEnergy]`` per group.

    ``streaming=True`` routes through the stage pipeline
    (``fleet.pipeline.attribute_energy_fused_streaming``): O(fleet x
    chunk) memory, per-sensor delays re-estimated online; matches the
    batch path to <=1e-5 when given the same grid and fixed delays.
    The streaming path supports the hold-resample convention only and
    its own keyword surface (chunk, window, hop, ema, tail, track, ...)
    — batch-only keywords such as ``mode`` or ``align`` raise TypeError.

    ``shard``+``collectives`` (streaming only) span the fleet across
    ``jax.distributed`` processes: ``trace_groups`` are then this
    host's LOCAL device groups in ``shard.group_ids`` order, and every
    host returns the same fleet-wide result.  Online delay tracking
    (``track=True``, the default when no fixed ``delays`` are given)
    is synchronized over the collectives — shared ring schedule, one
    fleet-wide (lag, weight) EMA — so tracked multi-host runs match
    the single-host tracker and stay bit-identical across process
    counts, exactly like the fixed-delay mode — see
    ``repro.distributed.multihost``.

    ``health``+``registry`` (streaming only) enable fleet-health
    observability: ``health=True`` or a ``health.HealthConfig``
    composes a ``SensorHealthStage`` (rolling per-sensor diagnostics,
    typed quarantine/recovery events, deterministic fusion masking —
    all-healthy fleets stay bit-identical to ``health=None``), and a
    ``health.HealthRegistry`` exports sensor health plus pipeline
    self-metrics as Prometheus text or JSON; see ``repro.health``.
    Pass ``return_pipe=True`` to also get the pipeline for event and
    metrics inspection.
    """
    if kw.get("collectives") is not None:
        assert streaming, \
            "multi-host attribution runs the streaming pipeline " \
            "(pass streaming=True)"
        from repro.distributed.multihost import (
            attribute_energy_fused_multihost)
        return attribute_energy_fused_multihost(trace_groups, phases,
                                                config=config, **kw)
    assert kw.get("shard") is None, \
        "shard without collectives — a multi-host run needs both"
    kw.pop("collectives", None)
    kw.pop("shard", None)
    if streaming:
        from repro.fleet.pipeline import attribute_energy_fused_streaming
        return attribute_energy_fused_streaming(trace_groups, phases,
                                                config=config, **kw)
    if config is not None:
        raise TypeError("config= drives the streaming pipeline — pass "
                        "streaming=True (the batch align path keeps "
                        "its own keyword surface)")
    from repro.align import attribute_energy_fused as _fused
    return _fused(trace_groups, phases, **kw)
