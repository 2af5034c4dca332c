"""Continuous-batching serve engine with per-request energy metering.

``ServeEngine`` runs true continuous batching: a slot scheduler admits
queued requests into free batch slots mid-decode and evicts finished
ones (no head-of-line blocking on the longest request in a batch), the
per-slot KV cache is allocated once and reused across requests, a
single jitted masked decode step advances every active slot at its own
position, and generated token ids accumulate in a device-side buffer
drained once per flush interval (no per-token host sync).

Every phase lands on the ``RegionTracer`` twice: engine-global depth-0
regions (admission/prefill/decode — the attribution phases) and
slot-scoped depth-1 regions carrying the slot id and request id.  The
engine also records a ``SlotSegment`` schedule — one entry per
constant-occupancy interval, boundaries on every admission/eviction,
timestamps bit-identical to the depth-0 regions — which is what the
fleet pipeline's ``MeteringStage`` splits fused energies over:
per-request energies conserve against ``attribute_phases`` totals by
construction.

``FixedBatchEngine`` keeps the previous serve-to-completion behaviour
as the benchmark baseline (with its dummy-slot and per-token host-sync
defects fixed).
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.tracing import RegionTracer
from repro.fleet.pipeline import SlotSegment
from repro.kernels.latent_decode import block_positions
from repro.models import Model
from repro.serve.metering import (RequestEnergy, RequestEnergyReport,
                                  RollingPercentiles)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (prompt_len,)
    max_new_tokens: int
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    arrival_s: float = 0.0      # offset from run() start (load gen)
    user: str = ""              # per-user aggregation key
    t_arrival: float = math.nan     # tracer timebase, set by run()
    t_admitted: float = math.nan
    t_first: float = math.nan       # prefill done (first token computed)
    t_done: float = math.nan

    @property
    def ttft_s(self) -> float:
        return self.t_first - self.t_arrival

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_arrival


def _jit(name: str, fn, **kw):
    """``jax.jit(fn)`` under a stable program name: its XLA module, and
    so the trace's ``XLA Modules`` line, reads ``jit_<name>``."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **kw)


def _make_masked_step(model: Model):
    """One jitted decode step over ALL slots: per-slot positions,
    inactive slots pinned to token 0 at position 0 (their cache rows
    are rewritten at the next admission, so the garbage write is never
    read), and the new token scattered into column ``w`` of the
    device-side token buffer.

    A model with MoE layers also takes and returns ``load``, int32
    (2,): the held-expert load counter, to which each step adds the
    active slots' token-to-held-expert assignments and the (layer, held
    expert) pairs that got at least one of them."""

    def step(params, cache, tok, pos, active, buf, w, *load):
        cur = jnp.where(active, pos + w, 0).astype(jnp.int32)
        tok_c = jnp.where(active, tok, 0).astype(jnp.int32)
        logits, cache, lay = model.decode_step_load(
            params, {"tokens": tok_c[:, None], "positions": cur[:, None]},
            cache, cur)
        nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, 0)
        buf = buf.at[:, w].set(nxt)
        if not load:
            return nxt, cache, buf
        lay = jnp.where(active[None, :, None], lay, 0)   # (layers, B, held)
        hit = jnp.sum(jnp.any(lay > 0, axis=1), dtype=jnp.int32)
        return nxt, cache, buf, load[0] + jnp.stack(
            [jnp.sum(lay, dtype=jnp.int32), hit])

    return _jit("serve_decode_step", step, donate_argnums=(1, 5))


def _scatter_slot(big, small, slot):
    """Copy a batch-1 cache (pytree, batch on axis 1) into slot row
    ``slot`` of the persistent slot-batched cache."""
    return jax.tree.map(
        lambda bg, sm: jax.lax.dynamic_update_slice_in_dim(
            bg, sm.astype(bg.dtype), slot, axis=1), big, small)


_UNSET = object()      # legacy-kwarg sentinel (see fleet.config)


def _explicit(**kw) -> dict:
    """The kwargs the caller actually passed (sentinel-filtered)."""
    return {k: v for k, v in kw.items() if v is not _UNSET}


class _AttributionMixin:
    """Shared phase-level energy attribution (both engines record the
    same depth-0 admission/prefill/decode phases)."""

    def attribute_phases(self, traces, *, corrections=None, depth=0,
                         t_shift=0.0, use_fleet=True, config=None,
                         chunk=_UNSET, fuse=False, reference=None,
                         streaming=False, track=_UNSET, delays=_UNSET,
                         shard=None, collectives=None,
                         health=_UNSET, registry=None):
        """Per-phase energy for the engine's recorded serving phases.

        traces: {name: SensorTrace} (e.g. ``NodeFabric.sample_all``) or a
        trace list.  ``t_shift`` maps the tracer timebase into the sensor
        timebase (e.g. a synthesized fabric's lead-in).  All cumulative
        counters batch through the fleet subsystem in one call; returns
        {trace_name: [PhaseEnergy]} for dict input, or a list of
        [PhaseEnergy] rows (input order) for list input — trace names
        need not be unique there.

        ``fuse=True`` (dict input only) instead groups the traces by
        device, time-aligns and inverse-variance-fuses every sensor
        observing each device (``repro.align``), and attributes on the
        fused streams — returns {device: [PhaseEnergy]}.  ``reference``
        optionally passes the known phase schedule (PiecewisePower) for
        delay estimation; default is each device's first counter.
        ``streaming=True`` runs the fused attribution through the
        streaming stage pipeline (``fleet.pipeline``) in chunk-sized
        windows — per-sensor delays tracked online on sliding windows,
        O(fleet x chunk) memory — instead of the batch align-and-fuse.
        ``config`` (a ``fleet.config.PipelineConfig`` or section)
        carries the streaming pipeline's knobs; the flat
        ``chunk``/``track``/``delays``/``health`` kwargs
        still resolve bit-identically but are deprecated on the
        streaming paths.  ``track``/``delays`` pin the tracking mode:
        fixed per-sensor ``delays`` (track=False) or online tracking
        seeded by them.
        ``shard``+``collectives`` (streaming only) extend that pipeline
        across ``jax.distributed`` processes: THIS engine's traces are
        the local device groups described by the HostShard, and the
        returned dict covers the local devices with fleet-consistent
        energies; online tracking state is synchronized over the
        collectives, so tracked multi-host runs apply the same delay
        corrections as the single-host tracker (see
        ``repro.distributed.multihost``).  ``health`` (streaming only)
        composes the ``repro.health.SensorHealthStage`` fleet-health
        diagnostics into the pipeline (``True`` or a ``HealthConfig``);
        ``registry`` (a ``HealthRegistry``, defaulting to the engine's
        own) collects the health + pipeline self-metrics for export.
        """
        reg = registry if registry is not None else self.registry
        phases = [(n, a + t_shift, b + t_shift)
                  for n, a, b in self.tracer.phases(depth=depth)]
        legacy = _explicit(chunk=chunk, track=track, delays=delays,
                           health=health)
        if fuse:
            assert isinstance(traces, dict), \
                "fuse=True groups by sensor name and needs dict input"
            from repro.align import (attribute_energy_fused,
                                     group_traces_by_device)
            from repro.fleet.config import resolve_config
            groups = group_traces_by_device(traces)
            if collectives is not None:
                assert streaming, \
                    "multi-host attribution runs the streaming pipeline"
                from repro.distributed.multihost import (
                    attribute_energy_fused_multihost)
                cfg = resolve_config(config, legacy,
                                     "attribute_phases")
                all_rows = attribute_energy_fused_multihost(
                    list(groups.values()), phases, shard=shard,
                    collectives=collectives, corrections=corrections,
                    reference=reference, config=cfg, registry=reg)
                rows = [all_rows[g] for g in shard.group_ids]
            elif streaming:
                from repro.fleet.pipeline import (
                    attribute_energy_fused_streaming)
                cfg = resolve_config(config, legacy,
                                     "attribute_phases")
                rows = attribute_energy_fused_streaming(
                    list(groups.values()), phases,
                    corrections=corrections, reference=reference,
                    config=cfg, registry=reg)
            else:
                assert config is None, \
                    "config= drives the streaming pipeline — pass " \
                    "streaming=True"
                rows = attribute_energy_fused(
                    list(groups.values()), phases,
                    corrections=corrections, reference=reference,
                    delays=legacy.get("delays"))
            return dict(zip(groups.keys(), rows))
        from repro.core.attribution import attribute_energy_many
        as_dict = isinstance(traces, dict)
        trs = list(traces.values()) if as_dict else list(traces)
        rows = attribute_energy_many(trs, phases,
                                     corrections=corrections,
                                     use_fleet=use_fleet,
                                     chunk=legacy.get("chunk", 1024))
        if as_dict:
            return dict(zip(traces.keys(), rows))
        return rows


class ServeEngine(_AttributionMixin):
    """Continuous-batching engine: slot admission/eviction mid-decode,
    persistent per-slot cache reuse, jitted masked decode, device-side
    token buffers, slot-scoped tracing and a metering schedule.

    flush_interval: decode steps per device->host token drain (ONE
    transfer per segment; also the admission cadence — shorter flushes
    admit faster, longer flushes sync less).
    prefill_bucket: round prompt lengths up to a multiple (left-padded)
    to bound prefill recompiles under mixed-length traffic; 1 keeps
    exact lengths (bit-parity with unpadded prefill).
    """

    def __init__(self, model: Model, params, *, batch_slots=4,
                 max_len=512, tracer: Optional[RegionTracer] = None,
                 greedy=True, registry=None, flush_interval=16,
                 prefill_bucket=1):
        assert greedy, "only greedy decoding is supported"
        self.model = model
        self.params = params
        self.slots = int(batch_slots)
        self.max_len = int(max_len)
        self.tracer = tracer or RegionTracer()
        self.greedy = greedy
        self.registry = registry
        self.flush_interval = max(int(flush_interval), 1)
        self.prefill_bucket = max(int(prefill_bucket), 1)
        # persistent slot-batched cache — allocated ONCE, reused across
        # requests (admission rewrites one slot row)
        self.cache = model.init_cache(self.slots, self.max_len)
        # prefill writes its batch-1 scratch cache in place
        self._prefill = _jit("serve_prefill", model.prefill,
                             donate_argnums=(2,))
        self._step = _make_masked_step(model)
        self._admit_slot = _jit("serve_scatter_slot", _scatter_slot,
                                donate_argnums=(0,))
        self._zeros1 = _jit("serve_zero_cache",
                            lambda: model.init_cache(1, self.max_len))
        self._nxt = jnp.zeros((self.slots,), jnp.int32)
        self._pend = jnp.zeros((self.slots,), jnp.int32)
        self._buf = jnp.zeros((self.slots, self.flush_interval),
                              jnp.int32)
        # held-expert load: assignments and (layer, expert) pairs hit,
        # summed over the decode steps of the engine's life
        self.routed = model.cfg.moe is not None
        self.route_assignments = 0
        self.route_pairs = 0
        # latent attention: positions in each block of the cache that a
        # decode step reads up to every slot's position (0: no latent cache)
        self.latent_block = (block_positions(self.max_len)
                             if model.cfg.mla is not None else 0)
        # gauges / counters (exported via HealthRegistry.track_serve)
        self.host_transfers = 0
        self.requests_served = 0
        self.tokens_emitted = 0
        self.queue_depth = 0
        self.active_slots = 0
        self.segments: list = []        # SlotSegment metering schedule
        self.meter_rolling = RollingPercentiles()
        self._requests: dict = {}
        if registry is not None:
            registry.track_tracer("serve", self.tracer)
            registry.track_serve("serve", self)

    # -- plumbing ---------------------------------------------------------

    def _to_host(self, arr) -> np.ndarray:
        self.host_transfers += 1
        return np.asarray(arr)

    def _idle_until(self, t_target: float) -> None:
        dt = t_target - self.tracer.now()
        if dt > 0:
            time.sleep(dt)

    def slot_schedule(self) -> list:
        """The recorded ``SlotSegment`` schedule (metering input)."""
        return list(self.segments)

    # -- scheduler --------------------------------------------------------

    def _admit(self, slot: int, r: Request) -> int:
        """Prefill ``r`` on a batch-1 scratch cache and scatter it into
        ``slot``; returns the (bucketed) prompt length."""
        t0 = self.tracer.now()
        plen = len(r.prompt)
        lb = -(-plen // self.prefill_bucket) * self.prefill_bucket
        toks = np.zeros((1, lb), np.int32)
        toks[0, lb - plen:] = np.asarray(r.prompt, np.int32)  # left-pad
        t1 = self.tracer.now()
        self.tracer.add_region("admission", t0, t1, depth=0)
        self.tracer.add_region("admission", t0, t1, depth=1,
                               slot=slot, step=r.rid)
        self.segments.append(
            SlotSegment(t0, t1, (r.rid,), (1.0,), "admission"))
        logits, c1 = self._prefill(
            self.params, {"tokens": jnp.asarray(toks)}, self._zeros1())
        nxt0 = jnp.argmax(logits[0, -1]).astype(jnp.int32)
        self.cache = self._admit_slot(self.cache, c1,
                                      jnp.asarray(slot, jnp.int32))
        self._nxt = self._nxt.at[slot].set(nxt0)
        self._pend = self._pend.at[slot].set(nxt0)
        jax.block_until_ready(self._nxt)
        t2 = self.tracer.now()
        self.tracer.add_region("prefill", t1, t2, depth=0)
        self.tracer.add_region("prefill", t1, t2, depth=1,
                               slot=slot, step=r.rid)
        self.segments.append(
            SlotSegment(t1, t2, (r.rid,), (float(lb),), "prefill"))
        r.t_admitted = t0
        r.t_first = t2
        return lb

    def _latent_stats(self, pos, active, k) -> dict:
        """The ``serve.decode`` span's stats for a latent-attention model:
        the latent cache blocks each layer reads over the segment's ``k``
        steps for the active slots (``latent_blocks``; slot b at position
        p reads blocks 0 .. p // block), and those the slots hold
        (``latent_blocks_held``), from the host's positions."""
        if not self.latent_block:
            return {}
        at = pos[active][:, None] + np.arange(k)
        return {"latent_blocks": int(np.sum(at // self.latent_block + 1)),
                "latent_blocks_held":
                    self.slots * (self.max_len // self.latent_block) * k}

    def _decode_segment(self, k, slot_req, pos, remaining, active,
                        pend_fresh, results):
        """Run ``k`` masked decode steps, then drain the device token
        buffer (plus pending prefill tokens) in ONE host transfer;
        evict finished slots."""
        t0 = self.tracer.now()
        act = jnp.asarray(active)
        posd = jnp.asarray(pos, jnp.int32)
        tok, buf = self._nxt, self._buf
        load = (jnp.zeros((2,), jnp.int32),) if self.routed else ()
        for t in range(k):
            tok, self.cache, buf, *load = self._step(
                self.params, self.cache, tok, posd, act, buf,
                jnp.asarray(t, jnp.int32), *load)
        self._nxt, self._buf = tok, buf
        with tracing.span("serve.drain"):
            toks = jnp.concatenate([self._pend[:, None], buf], axis=1)
            if load:
                # the load counter rides on the token drain: one transfer
                self.host_transfers += 1
                toks, load = jax.device_get((toks, load[0]))
            else:
                toks = self._to_host(toks)
        if len(load):
            assign, pairs = (int(v) for v in load)
            with tracing.span("serve.route", n=k, assignments=assign,
                              pairs=pairs):
                self.route_assignments += assign
                self.route_pairs += pairs
        t1 = self.tracer.now()
        if k:
            self.tracer.add_region("decode", t0, t1, depth=0)
            rids, weights = [], []
            for i in np.nonzero(active)[0]:
                r = slot_req[i]
                self.tracer.add_region("decode", t0, t1, depth=1,
                                       slot=int(i), step=r.rid)
                rids.append(r.rid)
                weights.append(float(k))
            self.segments.append(
                SlotSegment(t0, t1, tuple(rids), tuple(weights),
                            "decode"))
        for i in np.nonzero(active)[0]:
            r = slot_req[i]
            start = 0 if pend_fresh[i] else 1
            new = [int(x) for x in toks[i, start:1 + k]]
            pend_fresh[i] = False
            r.generated.extend(new)
            self.tokens_emitted += len(new)
            pos[i] += k
            remaining[i] -= k
            if remaining[i] <= 0:               # evict: slot freed
                r.done = True
                r.t_done = t1
                results[r.rid] = r.generated
                active[i] = False
                slot_req[i] = None
                self.requests_served += 1

    def run(self, requests, *, respect_arrivals=False):
        """Serve ``requests`` with continuous batching; returns
        {rid: generated}.  ``respect_arrivals=True`` holds each request
        back until ``arrival_s`` seconds after this call started (open-
        loop load, e.g. from ``serve.loadgen.poisson_requests``);
        otherwise everything is queued immediately in input order.
        """
        results: dict = {}
        reqs = list(requests)
        with tracing.span("serve.run", n=len(reqs)):
            self._serve(reqs, results, respect_arrivals)
        return results

    def _serve(self, reqs, results, respect_arrivals):
        """``run``'s scheduler loop: admit, decode, evict until done."""
        t_run0 = self.tracer.now()
        for r in reqs:
            r.t_arrival = t_run0 + (r.arrival_s if respect_arrivals
                                    else 0.0)
            self._requests[r.rid] = r
        if respect_arrivals:
            reqs.sort(key=lambda r: (r.arrival_s, r.rid))
        queue = collections.deque(reqs)
        slot_req = [None] * self.slots
        pos = np.zeros((self.slots,), np.int64)
        remaining = np.zeros((self.slots,), np.int64)
        active = np.zeros((self.slots,), bool)
        pend_fresh = np.zeros((self.slots,), bool)
        while queue or active.any():
            free = [i for i in range(self.slots) if not active[i]]
            fi = 0
            while queue and fi < len(free):
                r = queue[0]
                if respect_arrivals and r.t_arrival > self.tracer.now():
                    if active.any():
                        break           # keep decoding while we wait
                    with tracing.span("serve.wait"):
                        self._idle_until(r.t_arrival)
                queue.popleft()
                if r.max_new_tokens <= 0:
                    r.done = True
                    results[r.rid] = r.generated
                    continue
                i = free[fi]
                fi += 1
                with tracing.span("serve.admit", rid=r.rid, slot=i) as adm:
                    lb = self._admit(i, r)
                # the request's wait for a slot, known once it is admitted
                tracing.add_span("serve.queued", adm.t_start
                                 - (r.t_admitted - r.t_arrival),
                                 adm.t_start, rid=r.rid)
                slot_req[i] = r
                pos[i] = lb
                remaining[i] = r.max_new_tokens - 1   # 1 pending token
                active[i] = True
                pend_fresh[i] = True
            self.queue_depth = len(queue)
            self.active_slots = int(active.sum())
            if not active.any():
                continue
            k = int(min(self.flush_interval, remaining[active].min()))
            with tracing.span("serve.decode", n=k, active=self.active_slots,
                              **self._latent_stats(pos, active, k)):
                self._decode_segment(k, slot_req, pos, remaining, active,
                                     pend_fresh, results)
            self.active_slots = int(active.sum())
        self.queue_depth = 0
        self.active_slots = 0

    # -- per-request energy ----------------------------------------------

    def attribute_requests(self, traces, *, corrections=None,
                           t_shift=0.0, config=None, chunk=_UNSET,
                           reference=None, track=_UNSET,
                           delays=_UNSET, health=_UNSET,
                           registry=None) -> RequestEnergyReport:
        """Split fused phase energy across requests -> energy bills.

        Runs the streaming fused pipeline with the slot-segment
        schedule composed as a ``MeteringStage``: each
        segment's energy is divided across its concurrently-active
        requests by token-weighted occupancy.  Returns a
        :class:`RequestEnergyReport` (J/request, J/token, percentiles,
        per-user aggregates); the rolling J/request percentiles update
        the engine's registry gauges, and the report is appended to the
        ``REPRO_METER_LOG_DIR`` JSONL artifact when set.  Per-request
        energies sum to the ``attribute_phases(fuse=True, ...)`` totals
        <= 1e-5 (the segments tile the depth-0 phases exactly).
        """
        assert isinstance(traces, dict), \
            "per-request metering fuses by device and needs dict input"
        reg = registry if registry is not None else self.registry
        phases = [(n, a + t_shift, b + t_shift)
                  for n, a, b in self.tracer.phases(depth=0)]
        segs = [s.shifted(t_shift) for s in self.segments]
        from repro.align import group_traces_by_device
        from repro.fleet.config import resolve_config
        from repro.fleet.pipeline import attribute_energy_fused_streaming
        cfg = resolve_config(config,
                             _explicit(chunk=chunk, track=track,
                                       delays=delays, health=health),
                             "attribute_requests")
        groups = group_traces_by_device(traces)
        _, pipe = attribute_energy_fused_streaming(
            list(groups.values()), phases, corrections=corrections,
            reference=reference, config=cfg, registry=reg, meter=segs,
            return_pipe=True)
        energies = pipe.request_energies()
        entries = []
        for rid in sorted(energies):
            e = energies[rid]
            ej = float(np.sum(e))
            r = self._requests.get(rid)
            tokens = ((len(r.prompt) + len(r.generated))
                      if r is not None else 0)
            entries.append(RequestEnergy(
                rid=rid, energy_j=ej,
                energy_by_device=[float(x) for x in e], tokens=tokens,
                j_per_token=ej / max(tokens, 1),
                user=r.user if r is not None else "",
                ttft_s=r.ttft_s if r is not None else math.nan,
                latency_s=r.latency_s if r is not None else math.nan))
        report = RequestEnergyReport(
            entries, pipe.meter_stage.segment_totals())
        for re_ in report.requests:
            self.meter_rolling.add(re_.energy_j)
        report.maybe_write_jsonl()
        return report


class FixedBatchEngine(_AttributionMixin):
    """The pre-continuous-batching engine: serve fixed batches to
    completion, re-initializing the cache per batch.  Kept as the
    benchmark baseline (``benchmarks/bench_serve.py``) with two defects
    fixed: dummy padding slots are zero-masked instead of cloning
    ``batch[0]`` (no phantom work in the results), and decode drains a
    device-side token buffer once per ``flush_interval`` steps instead
    of a per-token ``int(nxt[i])`` host sync (``host_transfers`` counts
    the drains for the regression test)."""

    def __init__(self, model: Model, params, *, batch_slots=4,
                 max_len=512, tracer: Optional[RegionTracer] = None,
                 greedy=True, registry=None, flush_interval=16):
        assert greedy, "only greedy decoding is supported"
        self.model = model
        self.params = params
        self.slots = int(batch_slots)
        self.max_len = int(max_len)
        self.tracer = tracer or RegionTracer()
        self.greedy = greedy
        self.registry = registry
        self.flush_interval = max(int(flush_interval), 1)
        if registry is not None:
            registry.track_tracer("serve", self.tracer)
        self.cache = model.init_cache(self.slots, self.max_len)
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)
        self.host_transfers = 0
        self.requests_served = 0
        self.tokens_emitted = 0

    def _to_host(self, arr) -> np.ndarray:
        self.host_transfers += 1
        return np.asarray(arr)

    def _pad_prompts(self, reqs):
        """(slots, plen) tokens + (slots,) real-row mask; dummy rows
        are all-zero, NOT clones of ``batch[0]``."""
        plen = max(len(r.prompt) for r in reqs)
        toks = np.zeros((self.slots, plen), np.int32)
        mask = np.zeros((self.slots,), bool)
        for i, r in enumerate(reqs):
            toks[i, plen - len(r.prompt):] = r.prompt   # left-pad
            mask[i] = True
        return jnp.asarray(toks), plen, mask

    def run(self, requests):
        """Serve a list of requests (<= slots at a time), batched."""
        results: dict = {}
        queue = list(requests)
        t_run0 = self.tracer.now()
        for r in queue:
            r.t_arrival = t_run0
        while queue:
            batch = queue[:self.slots]
            queue = queue[self.slots:]
            with self.tracer.region("admission"):
                toks, plen, mask = self._pad_prompts(batch)
                self.cache = self.model.init_cache(self.slots,
                                                   self.max_len)
            with self.tracer.region("prefill"):
                logits, self.cache = self._prefill(
                    self.params, {"tokens": toks}, self.cache)
                jax.block_until_ready(logits)
            t_first = self.tracer.now()
            for r in batch:
                r.t_first = t_first
            act = jnp.asarray(mask)
            pos = plen
            nxt = jnp.where(act, jnp.argmax(logits[:, -1], axis=-1)
                            .astype(jnp.int32), 0)
            max_new = max(r.max_new_tokens for r in batch)
            all_toks: list = []
            with self.tracer.region("decode"):
                dev_buf = [nxt]           # includes the prefill token
                for _t in range(1, max_new):
                    logits, self.cache = self._decode(
                        self.params, {"tokens": nxt[:, None]},
                        self.cache, jnp.asarray(pos, jnp.int32))
                    nxt = jnp.where(act, jnp.argmax(logits[:, 0],
                                                    axis=-1)
                                    .astype(jnp.int32), 0)
                    pos += 1
                    dev_buf.append(nxt)
                    if len(dev_buf) >= self.flush_interval:
                        all_toks.append(
                            self._to_host(jnp.stack(dev_buf, axis=1)))
                        dev_buf = []
                if dev_buf:
                    all_toks.append(
                        self._to_host(jnp.stack(dev_buf, axis=1)))
            flat = (np.concatenate(all_toks, axis=1) if all_toks
                    else np.zeros((self.slots, 0), np.int32))
            t_done = self.tracer.now()
            for i, r in enumerate(batch):
                r.generated.extend(
                    int(x) for x in flat[i, :r.max_new_tokens])
                r.done = True
                r.t_done = t_done
                results[r.rid] = r.generated
                self.tokens_emitted += len(r.generated)
                self.requests_served += 1
        return results
