"""Streaming, chunked per-phase energy accumulation (online attribution).

Thin pre-built pipelines over the composable stage layer
(``fleet/pipeline.py``) — the two entry points every pre-pipeline call
site keeps using:

  StreamingPhaseAccumulator — already-reconstructed power chunks
                              -> per-phase energy:
                              Ingest(maskfill) -> PhaseIntegrate
                              (phase_integrate kernel)
  FleetStream               — raw cumulative-counter chunks:
                              Ingest(sanitize) -> CounterAttribute
                              (fused fleet_attribute kernel: carry-aware
                              unwrap + dE/dt + integration in one pass,
                              optionally row-sharded over a fleet mesh)

Arbitrarily long runs never materialize full traces: each ``update``
sees one fixed-size (fleet, chunk) window plus a one-column carry; peak
device memory is O(fleet x chunk + fleet x phases) regardless of run
length — the memory bound the serving/HPL paths rely on.

Dedup falls out of the sample-and-hold algebra instead of compaction: a
repeated publication republishes the previous (t, E) pair, giving a
zero-width interval that holds 0 W over no time — exactly zero energy.
Reordered timestamps (rare tool-jitter artifact) would lose their dE to
the clamped overlap, so the Ingest stage sanitizes chunks on the host
(see ``pipeline.sanitize_chunk``).  For the full streaming-fused chain
(online delay tracking + regrid + inverse-variance fusion) see
``pipeline.StreamingFusedPipeline``.
"""
from __future__ import annotations

import numpy as np

from repro.fleet.pipeline import (PHASE_ALIGN,  # noqa: F401 (re-export)
                                  CounterAttributeStage, IngestStage,
                                  PhaseIntegrateStage, StreamPipeline,
                                  pad_phases,  # noqa: F401 (re-export)
                                  sanitize_chunk)

# backwards-compatible alias (pre-pipeline internal name)
_sanitize_chunk = sanitize_chunk


class StreamingPhaseAccumulator:
    """Online E[stream, phase] from chunked sample-and-hold power streams.

    Feed (times, watts) chunks of any fixed width; the carry column
    closes the hold interval across the chunk boundary.  ``totals()``
    never sees more than one chunk on device.
    """

    def __init__(self, phases, n_streams: int, *, dtype=np.float32,
                 interpret=None, use_kernel: bool = True):
        self._integrate = PhaseIntegrateStage(
            phases, n_streams, dtype=dtype, interpret=interpret,
            use_kernel=use_kernel)
        self._pipe = StreamPipeline(IngestStage(n_streams,
                                                mode="maskfill"),
                                    self._integrate)
        self.phases = self._integrate.phases
        self.n_phases = self._integrate.n_phases
        self.interpret = self._integrate.interpret
        self.use_kernel = use_kernel

    def update(self, times, watts, valid=None):
        self._pipe.update(np.asarray(times), np.asarray(watts), valid)
        return self

    def totals(self):
        """(n_streams, n_phases) accumulated joules (host numpy)."""
        return self._integrate.totals()


class FleetStream:
    """Online fleet attribution straight from cumulative-counter chunks.

    State per stream: the last (t, E) sample — two scalars — plus the
    (F, P) energy accumulator.  Reconstruction and integration both run
    fused through the ``fleet_attribute`` Pallas kernel per chunk.
    """

    def __init__(self, phases, n_streams: int, wrap_period=None, *,
                 dtype=np.float32, interpret=None,
                 use_kernel: bool = True, mesh="auto"):
        self._attr = CounterAttributeStage(
            phases, n_streams, wrap_period, dtype=dtype,
            interpret=interpret, use_kernel=use_kernel, mesh=mesh)
        self._pipe = StreamPipeline(IngestStage(n_streams,
                                                mode="sanitize"),
                                    self._attr)
        self.phases = self._attr.phases
        self.n_phases = self._attr.n_phases
        self.interpret = self._attr.interpret
        self.use_kernel = use_kernel
        self.mesh = self._attr.mesh

    def reset(self):
        """Zero the accumulator/carry for a fresh run (buffers reused)."""
        self._pipe.reset()
        return self

    def update(self, times, energy, valid=None):
        self._pipe.update(np.asarray(times), np.asarray(energy), valid)
        return self

    def totals(self):
        """(n_streams, n_phases) accumulated joules (host numpy)."""
        return self._attr.totals()
