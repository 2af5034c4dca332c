"""Fleet-scale streaming reconstruction + attribution (paper §V at scale).

The paper's headline capability is attribution *at scale* — characterizing
and correcting sensors across 512 GPUs / 480 APUs simultaneously.  This
subsystem is the batched counterpart of ``repro.core.reconstruction`` /
``repro.core.attribution``:

  packing    — ragged (node × device) SensorTraces -> padded (fleet, S)
               arrays with validity masks (pure memcpy, no per-trace math)
  reconstruct— dedup -> unwrap -> ΔE/Δt for the whole fleet in ONE jitted
               call through the ``power_reconstruct`` Pallas kernel
  pipeline   — the composable streaming stage layer: Ingest ->
               Reconstruct -> AlignTrack -> Regrid/Fuse ->
               PhaseAttribute, every stage one (fleet, chunk) window +
               an explicit carry dataclass; online delay tracking and
               streaming fused attribution live here
  streaming  — ``FleetStream`` / ``StreamingPhaseAccumulator``: thin
               pre-built two-stage pipelines (fused ``fleet_attribute``
               / ``phase_integrate`` kernels), O(fleet × chunk) device
               memory regardless of run length
  api        — trace-level entry points mirroring the per-trace host API
               (which remains the parity oracle)

Every future scaling PR (sharding, async ingest, multi-node) composes with
the padded-fleet interface and the stage pipeline here instead of
per-trace Python loops.  Multi-host runs split the fleet by device group
(``assign_groups`` -> ``HostShard``) and attribute through
``repro.distributed.multihost.attribute_energy_fused_multihost``.
"""
from repro.fleet.config import (CheckpointConfig,  # noqa: F401
                                PipelineConfig, StreamConfig,
                                TrackConfig, resolve_config)
from repro.fleet.packing import (HostShard, PackedFleet,  # noqa: F401
                                 assign_groups, pack_traces,
                                 shard_from_assignment, unpack_series)
from repro.fleet.reconstruct import (fleet_reconstruct,  # noqa: F401
                                     fleet_reconstruct_host)
from repro.fleet.streaming import (FleetStream,  # noqa: F401
                                   StreamingPhaseAccumulator)
from repro.fleet.pipeline import (AlignTrackStage,  # noqa: F401
                                  DataQualityError, DataQualityPolicy,
                                  IngestStage, PhaseIntegrateStage,
                                  ReconstructStage, RegridFuseStage,
                                  StreamPipeline, StreamingFusedPipeline,
                                  attribute_energy_fused_streaming,
                                  pack_stream_rows)
from repro.fleet.api import (attribute_energy_fleet,  # noqa: F401
                             attribute_energy_fused, fleet_power_series)
