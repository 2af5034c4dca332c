"""Mixed-precision energy accounting over simulated fleets (§V-B).

Synthesizes the node sensor fabric over a traced HPL/HPG timeline and
attributes per-phase energy — for ONE node (``energize``, the host parity
path the examples started from) or for MANY nodes at once
(``fleet_energize``): every node's chip counters are simulated, packed and
attributed through the fleet subsystem in a single streamed pipeline
instead of a per-node Python loop.
"""
from __future__ import annotations

import numpy as np

from repro.core import (NodeFabric, ToolSpec, attribute_energy,
                        attribute_energy_many, phase_power,
                        split_energy_savings)
from repro.core.measurement_model import CHIP_IDLE_W
from repro.core.power_model import occupancy_power
from repro.core.tracing import RegionTracer

_UNSET = object()      # legacy-kwarg sentinel (see fleet.config)

# phase -> roofline occupancy (compute, memory, collective)
OCC = {
    "hpl_factorize": (1.0, 0.45, 0.1), "mxp_factorize": (1.0, 0.5, 0.1),
    "hpl_solve": (0.3, 1.0, 0.0), "mxp_refine": (0.3, 1.0, 0.0),
    "hpl_verify": (0.5, 1.0, 0.0),
    "hpg_setup": (0.0, 0.5, 0.0), "hpg_krylov": (0.25, 1.0, 0.1),
    "hpg_finalize": (0.1, 0.8, 0.0),
}


def phases_and_truth(tracer: RegionTracer, *, lead: float = 0.05):
    """Traced phases -> (shifted phases, per-chip ground-truth power)."""
    phases = tracer.phases(depth=0)
    shifted = [(n, a + lead, b + lead) for n, a, b in phases]
    watts = {n: {"watts": occupancy_power(*OCC.get(n, (0, 0.1, 0)))}
             for n, _, _ in shifted}
    truth = phase_power([("__lead__", 0.0, lead)] + shifted,
                        {**watts, "__lead__": {"watts": CHIP_IDLE_W}})
    return shifted, truth


def energize(tracer: RegionTracer, n_chips=4, seed=0):
    """One node, host path: synthesize the fabric and attribute chip0."""
    shifted, truth = phases_and_truth(tracer)
    fabric = NodeFabric(chip_truths=[truth] * n_chips)
    traces = fabric.sample_all(ToolSpec(), seed=seed)
    return attribute_energy(traces["chip0_energy"], shifted)


def fleet_energize(tracer: RegionTracer, n_nodes, *, n_chips=4, seed0=0,
                   use_fleet=True, chunk=2048):
    """Per-node phase energies for a whole fleet in one batched pipeline.

    Simulates ``n_nodes`` sensor fabrics over the traced timeline and
    attributes every node's chip0 energy counter together — the batched
    replacement for ``[energize(tracer, seed=k) for k in range(n_nodes)]``
    (which stays the parity oracle).  Returns one [PhaseEnergy] per node.
    """
    shifted, truth = phases_and_truth(tracer)
    traces = []
    for node in range(n_nodes):
        # node_id stays 0 so the per-sensor RNG stream is exactly the
        # oracle's (sample_all seeds with seed*1000003 + node_id)
        fabric = NodeFabric(chip_truths=[truth] * n_chips)
        traces.append(fabric.sample_all(
            ToolSpec(), seed=seed0 + node)["chip0_energy"])
    return attribute_energy_many(traces, shifted, use_fleet=use_fleet,
                                 chunk=chunk)


def fused_fleet_energize(tracer: RegionTracer, n_nodes, *, n_chips=4,
                         seed0=0, sensors_per_chip=3, config=None,
                         interpret=_UNSET, streaming=False,
                         track=_UNSET, chunk=_UNSET, shard=None,
                         collectives=None):
    """Per-node phase energies from FUSED cross-sensor streams.

    Where ``fleet_energize`` trusts chip0's energy counter alone, this
    aligns and inverse-variance-fuses chip0's whole sensor group per
    node (on-chip counter + on-chip filtered power + off-chip PM, NIC
    offsets and upstream slope calibrated out) through ``repro.align``
    in ONE batched call across all nodes, then attributes on the fused
    power — the paper's §V-B time-aligned multi-sensor validation
    applied to the MxP accounting.  Returns one [PhaseEnergy] per node.

    ``streaming=True`` runs the same accounting through the streaming
    stage pipeline (``fleet.pipeline``); ``config`` (a
    ``fleet.config.PipelineConfig`` or section) carries its knobs —
    the flat ``chunk``/``track``/``interpret`` kwargs still
    resolve bit-identically on that path but are deprecated.  The
    replay runs in chunk-sized windows:
    O(fleet x chunk) memory and online per-sensor delay tracking — the
    long-HPL-run mode where sensor clocks drift.

    ``shard``+``collectives`` split the fleet across ``jax.distributed``
    processes: this host simulates (in production: reads) ONLY the
    nodes its ``HostShard`` assigns it — per-node seeds keep each
    node's sensor fabric identical to the single-host run — and the
    fleet-wide result comes back on every host.  Online delay tracking
    is SYNCHRONIZED over the collectives (shared ring schedule + one
    fleet-wide EMA), so the multi-host accounting reproduces the
    single-host streaming tracker instead of drifting ~2% on per-host
    rings (see ``repro.distributed.multihost``).  ``track`` pins the
    tracking mode explicitly (default: track, since no fixed delays
    are passed).
    """
    from repro.core.calibration import nic_rail_corrections
    from repro.fleet.config import resolve_config
    legacy = {k: v for k, v in dict(track=track, chunk=chunk,
                                    interpret=interpret).items()
              if v is not _UNSET}
    shifted, truth = phases_and_truth(tracer)
    # default 3: on-chip counter + on-chip power + off-chip PM — one
    # stream per scope (the two pm_accel0 views of the same tray PM
    # only join at sensors_per_chip >= 4, to avoid double-weighting
    # the off-chip scope)
    wanted = ["chip0_energy", "chip0_power_inst", "pm_accel0_power",
              "pm_accel0_energy", "chip0_power_avg"][:max(sensors_per_chip,
                                                          1)]
    assert (shard is None) == (collectives is None), \
        "shard and collectives come together (a shard without " \
        "collectives would silently attribute this host's nodes only)"
    local_nodes = (range(n_nodes) if shard is None
                   else list(shard.group_ids))
    groups = []
    for node in local_nodes:
        fabric = NodeFabric(chip_truths=[truth] * n_chips)
        traces = fabric.sample_all(ToolSpec(), seed=seed0 + node)
        groups.append([traces[n] for n in wanted])
    if collectives is not None:
        assert shard is not None and len(shard.global_group_sizes) \
            == n_nodes, "HostShard must cover all n_nodes groups"
        from repro.distributed.multihost import (
            attribute_energy_fused_multihost)
        return attribute_energy_fused_multihost(
            groups, shifted, shard=shard, collectives=collectives,
            reference=truth, corrections=nic_rail_corrections(),
            config=resolve_config(config, legacy,
                                  "fused_fleet_energize"))
    if streaming:
        from repro.fleet.pipeline import attribute_energy_fused_streaming
        return attribute_energy_fused_streaming(
            groups, shifted, reference=truth,
            corrections=nic_rail_corrections(),
            config=resolve_config(config, legacy,
                                  "fused_fleet_energize"))
    assert config is None, \
        "config= drives the streaming pipeline — pass streaming=True"
    from repro.align import attribute_energy_fused
    return attribute_energy_fused(groups, shifted, reference=truth,
                                  corrections=nic_rail_corrections(),
                                  interpret=legacy.get("interpret"))


def mxp_energy_report(full_tracer: RegionTracer, mxp_tracer: RegionTracer,
                      n_nodes, *, use_fleet=True, use_fused=False) -> dict:
    """§V-B2 table: fleet-wide full- vs mixed-precision energy accounting.

    Attributes both runs across ``n_nodes`` simulated nodes via the fleet
    path and decomposes the saving into time-to-solution vs power.
    ``use_fused=True`` accounts on cross-sensor fused streams
    (``fused_fleet_energize``) instead of the single chip0 counter.
    """
    if use_fused:
        pe_full = fused_fleet_energize(full_tracer, n_nodes)
        pe_mxp = fused_fleet_energize(mxp_tracer, n_nodes)
    else:
        pe_full = fleet_energize(full_tracer, n_nodes, use_fleet=use_fleet)
        pe_mxp = fleet_energize(mxp_tracer, n_nodes, use_fleet=use_fleet)
    e_full = [sum(p.energy_j for p in row) for row in pe_full]
    e_mxp = [sum(p.energy_j for p in row) for row in pe_mxp]
    dec = split_energy_savings(pe_full[0], pe_mxp[0])
    return {
        "full_j": (float(np.mean(e_full)), float(np.std(e_full))),
        "mxp_j": (float(np.mean(e_mxp)), float(np.std(e_mxp))),
        "saving": 1.0 - float(np.mean(e_mxp)) / float(np.mean(e_full)),
        "decomposition": dec,
        "per_node_full_j": e_full, "per_node_mxp_j": e_mxp,
    }
