#!/usr/bin/env python3
"""Bring-up smoke run of the main paths on one TPU.

  python chip_smoke.py              # one chip: fleet attribution + serving
  python chip_smoke.py --chips 4    # fleet-axis sharding, 4 chips vs 1

Default phases, all in this one process, on one device:

* fleet: 512 devices (128 simulated nodes x 4 chips) with the measurement
  model's sensor set per chip plus the node PM rows, following a
  square-wave phase schedule, attributed by the streaming pipeline with
  health diagnostics, then without.  Checks: every per-phase energy is
  within the tests' 6% of the simulator truth.
* kernels: each fleet Pallas kernel lowered at the fleet's width, which
  must hold a Mosaic kernel (compiled, not interpreted); the two kernels
  rewritten for Mosaic also run there, bit-identical to their oracles.
* serve: ``ServeEngine`` on minicpm-2b at its published widths with bf16
  parameters, 8 requests twice (cold, then warm), per-request metering
  on sensor traces synthesized from the engine's timeline, and the first
  decode step's logits against a prefill of prompt plus that token.

``--chips 4`` runs only ``fleet_reconstruct`` and ``FleetStream`` with the
fleet axis sharded over every chip, against the same calls on one device,
including a stream count that does not divide by 4.

Exits non-zero, without a result line, when no TPU is attached or any
check fails.  The last stdout line is one JSON object naming the device;
details go to ``chiprun_out/chip_smoke.json``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

NODES = 128                 # x 4 chips = 512 devices (the paper's fleet)
CHIPS_PER_NODE = 4
# square wave of 8 s halves: the 1.5 s moving-average sensor smears each
# edge, and a phase must be long against that for the fused per-phase
# energy to hold the tests' tolerance (1 s halves miss it by ~25%)
PERIOD_S = 16.0
EDGE_S = 1.0                # idle lead-in and tail
CAPTURE_S = 34.0            # two periods plus the edges, at 1 kHz
TRUTH_REL_TOL = 0.06        # fused vs truth, as tests/test_align.py
METER_REL_TOL = 1e-5        # per-request sums vs fused phase totals
# decode-vs-prefill logits: bf16 keeps 8 significant bits (unit roundoff
# 2^-8 = 3.9e-3) and the two paths round in different orders through 40
# residual layers, so they agree to a few percent, not to f32 levels
LOGIT_REL_TOL = 3e-2
SERVE_ARCH = "minicpm-2b"
SERVE_SLOTS, SERVE_MAX_LEN, SERVE_BUCKET = 4, 1024, 128
SERVE_REQUESTS, SERVE_NEW_TOKENS = 8, 32
SERVE_PROMPT_LENS = (128, 512)

RESULTS: dict = {}


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    """Fail the run (never skipped, unlike ``assert`` under -O)."""
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def require_tpu(n_chips: int):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU attached (JAX platform "
                         f"{platform!r}); this run needs a TPU chip")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: {n_chips} chips requested, "
                         f"{len(devices)} present")
    log(f"device: {devices[0].device_kind} (platform {platform}, "
        f"{len(devices)} present, {n_chips} used)")
    return devices


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (set-up)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0

        def listen(event, duration, **_):
            if event in self.EVENTS:
                self.total += duration
        jax.monitoring.register_event_duration_secs_listener(listen)


def timed(name: str, clock: CompileClock, fn, *args, **kwargs):
    """Run one phase; record its wall and compile seconds."""
    c0, t0 = clock.total, time.perf_counter()
    out = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    comp = clock.total - c0
    RESULTS.setdefault("seconds", {})[name] = {"wall": wall,
                                               "compile": comp}
    log(f"[{name}] {wall:.3f} s wall, {comp:.3f} s of it compiling")
    return out


# ---------------------------------------------------------------------------
# fleet
# ---------------------------------------------------------------------------

def square_wave_phases(seconds: float):
    from repro.core import square_wave
    cycles = int((seconds - 2 * EDGE_S) // PERIOD_S)
    truth = square_wave(PERIOD_S, cycles, lead_s=EDGE_S, tail_s=EDGE_S)
    half = PERIOD_S / 2
    phases = [(f"{'active' if k % 2 == 0 else 'idle'}{k // 2}",
               EDGE_S + k * half, EDGE_S + (k + 1) * half)
              for k in range(2 * cycles)]
    return truth, phases


def simulate_fleet(n_nodes: int, seconds: float, seed: int, sensors=None):
    """-> (truth, phases, groups, group_truths, corrections).

    One group per device: each chip's five sensors (cumulative counters
    first), and each node PM sensor on its own.  Names carry the node.
    ``group_truths`` pairs each group with its (power truth, scale).
    """
    from repro.core import NodeFabric, ToolSpec
    from repro.core.calibration import Corrections, nic_rail_corrections
    truth, phases = square_wave_phases(seconds)
    base = nic_rail_corrections()
    offsets, slopes, groups, node_truths = {}, {}, [], []
    for node in range(n_nodes):
        fabric = NodeFabric(chip_truths=[truth] * CHIPS_PER_NODE,
                            node_id=node)
        raw = fabric.sample_all(ToolSpec(), seed=seed, sensors=sensors)
        pre = f"n{node:03d}_"
        traces = {k: dataclasses.replace(tr, name=pre + k)
                  for k, tr in raw.items()}
        offsets.update({pre + k: v for k, v in base.offsets_w.items()})
        slopes.update({pre + k: v for k, v in base.slopes.items()})
        for c in range(CHIPS_PER_NODE):
            grp = [tr for k, tr in traces.items()
                   if k.startswith((f"chip{c}_", f"pm_accel{c}_"))]
            grp.sort(key=lambda tr: (not tr.spec.is_cumulative, tr.name))
            if grp:
                groups.append(grp)
                node_truths.append((truth, 1.0))
        for k, tr in traces.items():
            if tr.spec.scope == "node":
                groups.append([tr])
                # node PM is uncorrected: it reads upstream of the VRMs
                node_truths.append((fabric.truth_for(tr.spec),
                                    tr.spec.scale))
    return truth, phases, groups, node_truths, Corrections(offsets, slopes)


def worst_rel(a, b) -> float:
    a = np.asarray([[p.energy_j for p in row] for row in a])
    b = np.asarray([[p.energy_j for p in row] for row in b])
    return float((np.abs(a - b) / np.maximum(np.abs(b), 1.0)).max())


def truth_rel(rows, node_truths) -> float:
    worst = 0.0
    for row, (t, scale) in zip(rows, node_truths):
        for p in row:
            e = scale * t.energy_between(p.t_start, p.t_end)
            worst = max(worst, abs(p.energy_j - e) / abs(e))
    return worst


def fleet_phase(clock, *, n_nodes: int = NODES, seconds: float = CAPTURE_S,
                seed: int = 0):
    from repro.fleet import attribute_energy_fused_streaming
    from repro.fleet.config import PipelineConfig
    from repro.health.events import QUARANTINED
    truth, phases, groups, node_truths, corr = timed(
        "fleet_simulate", clock, simulate_fleet, n_nodes, seconds, seed)
    n_rows = sum(len(g) for g in groups)
    n_samples = max(len(tr) for g in groups for tr in g)
    log(f"fleet: {n_nodes} nodes x {CHIPS_PER_NODE} chips = "
        f"{n_nodes * CHIPS_PER_NODE} devices, {len(groups)} attribution "
        f"groups, {n_rows} sensor rows; capture {seconds:g} s at a 1 ms "
        f"tool interval (<= {n_samples} samples per row), "
        f"{len(phases)} phases of {PERIOD_S / 2:g} s")
    kw = dict(reference=truth, corrections=corr)
    health, pipe = timed(
        "fleet_windowed_health", clock, attribute_energy_fused_streaming,
        groups, phases, config=PipelineConfig(health=True),
        return_pipe=True, **kw)
    windowed = timed("fleet_windowed", clock,
                     attribute_energy_fused_streaming, groups, phases, **kw)
    interpreted = [type(st).__name__ for st in pipe.pipeline.stages
                   if getattr(st, "interpret", False)]
    hs = pipe.health_stage
    quarantined = int((hs.state == QUARANTINED).sum())
    health_e = np.asarray([[p.energy_j for p in row] for row in health])
    tru = truth_rel(windowed, node_truths)
    health_shift = worst_rel(health, windowed)
    RESULTS["fleet"] = {
        "nodes": n_nodes, "devices": n_nodes * CHIPS_PER_NODE,
        "groups": len(groups), "rows": n_rows, "capture_s": seconds,
        "max_samples_per_row": n_samples, "phases": len(phases),
        "truth_rel_err": tru,
        "health_windows": hs.windows, "quarantined": quarantined,
        "health_vs_plain_rel": health_shift,
        "stage_wall_s": dict(pipe.pipeline.stage_wall_s)}
    log(f"fleet: windowed vs simulator truth {tru:.4f} "
        f"(limit {TRUTH_REL_TOL})")
    log(f"fleet: health run folded {hs.windows} windows, {quarantined} of "
        f"{n_rows} sensors quarantined at the end; its energies differ "
        f"from the plain run by up to {health_shift:.3e} (relative)")
    log("fleet: health run stage wall s " + ", ".join(
        f"{k}={v:.3f}" for k, v in pipe.pipeline.stage_wall_s.items()))
    check(health_e.shape == (len(groups), len(phases)),
          f"health run energies of shape {health_e.shape}")
    check(np.isfinite(health_e).all() and hs.windows > 0,
          "health run: non-finite energies or no folded window")
    check(tru <= TRUTH_REL_TOL, f"windowed vs truth {tru:.4f}")
    check(not interpreted, f"stages in interpret mode: {interpreted}")
    return n_rows


# ---------------------------------------------------------------------------
# kernels: compiled, not interpreted
# ---------------------------------------------------------------------------

def kernel_phase(n_rows: int, width: int = 1025):
    """Lower every fleet kernel at the fleet's row count and one replay
    window's width; each program must hold a Mosaic kernel."""
    import jax
    import jax.numpy as jnp
    from repro.fleet.reconstruct import auto_interpret
    from repro.kernels.fleet_attribute.kernel import fleet_attribute_kernel
    from repro.kernels.grid_resample.ops import grid_resample
    from repro.kernels.phase_integrate.kernel import phase_integrate_kernel
    from repro.kernels.power_reconstruct.kernel import (
        power_reconstruct_fleet_kernel, power_reconstruct_rows_kernel)
    from repro.kernels.xcorr_align.ops import ROW_ALIGN, xcorr_scores
    interpret = auto_interpret(None)
    check(interpret is False, "Pallas would run in interpret mode")
    f = -(-n_rows // ROW_ALIGN) * ROW_ALIGN
    blk = jax.ShapeDtypeStruct((f, width), jnp.float32)
    col = jax.ShapeDtypeStruct((f, 1), jnp.float32)
    icol = jax.ShapeDtypeStruct((f, 1), jnp.int32)
    ph = jax.ShapeDtypeStruct((32, 2), jnp.float32)
    grid = jax.ShapeDtypeStruct((2 * width,), jnp.float32)
    bank = jax.ShapeDtypeStruct((129, 2 * width), jnp.float32)
    wide = jax.ShapeDtypeStruct((f, 2 * width), jnp.float32)
    cases = {
        "power_reconstruct_fleet": (lambda e, t, w, n:
                                    power_reconstruct_fleet_kernel(
                                        e, t, w, n, interpret=interpret),
                                    (blk, blk, col, icol)),
        "power_reconstruct_rows": (lambda e, t, w:
                                   power_reconstruct_rows_kernel(
                                       e, t, w, interpret=interpret),
                                   (blk, blk, col)),
        "fleet_attribute": (lambda t, e, w, p: fleet_attribute_kernel(
            t, e, w, p, interpret=interpret), (blk, blk, col, ph)),
        "phase_integrate": (lambda t, w, p: phase_integrate_kernel(
            t, w, p, interpret=interpret), (blk, blk, ph)),
        "grid_resample": (lambda t, v, n, fr, g, d: grid_resample(
            t, v, n, fr, g, d, interpret=interpret),
            (blk, blk, icol, icol, grid, col)),
        "xcorr_align": (lambda x, m, b: xcorr_scores(
            x, m, b, interpret=interpret, block_rows=ROW_ALIGN),
            (wide, wide, bank)),
    }
    for name, (fn, shapes) in cases.items():
        text = jax.jit(fn).lower(*shapes).compile().as_text()
        n = text.count("tpu_custom_call")
        check(n > 0, f"{name}: no Mosaic kernel in the compiled program")
        log(f"kernel {name}: compiled for the chip, not interpreted "
            f"({n} tpu_custom_call at {f} rows)")
    RESULTS["kernels_compiled"] = sorted(cases)
    rewritten_kernel_parity(f, width, interpret)


def rewritten_kernel_parity(f: int, width: int, interpret: bool,
                            seed: int = 0):
    """The two kernels rewritten for Mosaic, run on the chip against
    their jnp oracles at fleet rows: bit-identical outputs."""
    import jax.numpy as jnp
    from repro.kernels.grid_resample.ops import grid_resample
    from repro.kernels.power_reconstruct.kernel import \
        power_reconstruct_fleet_kernel
    from repro.kernels.power_reconstruct.ref import \
        reconstruct_power_fleet_ref
    rng = np.random.default_rng(seed)
    dt = rng.uniform(0.5e-3, 1.5e-3, (f, width))
    dt[::7, width // 2] = -2e-3                # reordered timestamps
    dt[::5, 100:104] = 0.0                     # republished samples
    t = np.cumsum(dt, axis=1).astype(np.float32)
    e = np.cumsum(rng.uniform(0.05, 0.3, (f, width)), axis=1)
    n = rng.integers(1, width + 1, (f, 1)).astype(np.int32)
    args = [jnp.asarray(a) for a in (e.astype(np.float32), t,
                                     np.zeros((f, 1), np.float32), n)]
    got = power_reconstruct_fleet_kernel(*args, interpret=interpret)
    want = reconstruct_power_fleet_ref(*args)
    for name, a, b in zip(("power", "valid", "reordered"), got, want):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"power_reconstruct_fleet {name} differs from its oracle")
    t_sorted = np.sort(t, axis=1)
    first = rng.integers(0, 3, (f, 1)).astype(np.int32)
    grid = np.linspace(-0.05, float(t_sorted.max()) + 0.05, 2 * width,
                       dtype=np.float32)
    d = rng.uniform(-0.01, 0.01, (f, 1)).astype(np.float32)
    gargs = [jnp.asarray(a) for a in (t_sorted, e.astype(np.float32), n,
                                      first, grid, d)]
    for mode in ("hold", "linear"):
        got = grid_resample(*gargs, mode=mode, interpret=interpret,
                            use_kernel=True)
        want = grid_resample(*gargs, mode=mode, use_kernel=False)
        for name, a, b in zip(("values", "mask"), got, want):
            check(np.array_equal(np.asarray(a), np.asarray(b)),
                  f"grid_resample {mode} {name} differs from its oracle")
    log(f"kernel parity: power_reconstruct_fleet and grid_resample "
        f"(hold, linear) bit-identical to their oracles at {f} x {width}")
    RESULTS["kernel_parity_bit_identical"] = True


# ---------------------------------------------------------------------------
# metered serving
# ---------------------------------------------------------------------------

def serve_phase(clock, *, seed: int = 0):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.fleet.config import PipelineConfig, TrackConfig
    from repro.launch.serve import LEAD_S, timeline_traces
    from repro.models import Model
    from repro.serve.engine import Request, ServeEngine
    cfg = dataclasses.replace(get_arch(SERVE_ARCH), param_dtype="bfloat16")
    model = Model(cfg)
    params = timed("serve_init_params", clock, lambda: jax.block_until_ready(
        model.init(jax.random.key(seed))))
    leaves = jax.tree.leaves(params)
    p_bytes = sum(x.nbytes for x in leaves)
    dtypes = sorted({str(x.dtype) for x in leaves})
    log(f"serve: {cfg.name} {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}; params {dtypes} {p_bytes / 2**30:.3f} GiB")
    engine = ServeEngine(model, params, batch_slots=SERVE_SLOTS,
                         max_len=SERVE_MAX_LEN,
                         prefill_bucket=SERVE_BUCKET)
    rng = np.random.default_rng(seed)
    lo, hi = SERVE_PROMPT_LENS

    def requests(rid0):
        return [Request(rid=rid0 + i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            int(rng.integers(lo, hi + 1))),
                        max_new_tokens=SERVE_NEW_TOKENS)
                for i in range(SERVE_REQUESTS)]

    served = []
    for tag, rid0 in (("cold", 0), ("warm", SERVE_REQUESTS)):
        reqs = requests(rid0)
        out = timed(f"serve_{tag}", clock, engine.run, reqs)
        check(all(len(out[r.rid]) == SERVE_NEW_TOKENS for r in reqs),
              f"serve_{tag}: a request got the wrong number of tokens")
        served += reqs
        RESULTS.setdefault("serve", {})[f"{tag}_prompt_lens"] = \
            [len(r.prompt) for r in reqs]
    stats = jax.devices()[0].memory_stats() or {}
    held = {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}
    log(f"serve: {len(served)} requests, {engine.tokens_emitted} tokens; "
        f"device memory {held}")

    # per-request metering on traces synthesized from the timeline
    traces, _ = timeline_traces(engine, seed=seed)
    mcfg = PipelineConfig(track=TrackConfig(track=False))
    report = timed("serve_meter", clock, engine.attribute_requests,
                   traces, t_shift=LEAD_S, config=mcfg)
    fused = engine.attribute_phases(traces, t_shift=LEAD_S, fuse=True,
                                    streaming=True, config=mcfg)
    totals = np.asarray([[p.energy_j for p in row]
                         for row in fused.values()])
    meter_err = report.conservation_rel_err(totals)
    check(len(report) == len(served),
          f"{len(report)} requests billed of {len(served)} served")
    log(f"serve: metering conservation rel err {meter_err:.3e} "
        f"(limit {METER_REL_TOL:g}), {len(report)} requests billed, "
        f"{report.total_j:.2f} J")
    check(meter_err <= METER_REL_TOL, f"metering conservation {meter_err:.3e}")

    # first decode step vs a prefill of prompt + that token
    r = served[0]
    plen = len(r.prompt)
    lb = -(-plen // SERVE_BUCKET) * SERVE_BUCKET
    toks = np.zeros((1, lb), np.int32)
    toks[0, lb - plen:] = r.prompt            # left-padded as the engine
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)
    logits, cache = prefill(params, {"tokens": jnp.asarray(toks)},
                            model.init_cache(1, SERVE_MAX_LEN))
    tok = int(jnp.argmax(logits[0, -1]))
    check(tok == r.generated[0],
          f"prefill argmax {tok} != engine's first token {r.generated[0]}")
    pos = jnp.asarray(lb, jnp.int32)
    step, _ = decode(params, {"tokens": jnp.asarray([[tok]], jnp.int32),
                              "positions": jnp.asarray([[lb]], jnp.int32)},
                     cache, pos)
    longer = np.concatenate([toks, [[tok]]], axis=1).astype(np.int32)
    full, _ = prefill(params, {"tokens": jnp.asarray(longer)},
                      model.init_cache(1, SERVE_MAX_LEN))
    a = np.asarray(step[0, -1], np.float32)
    b = np.asarray(full[0, -1], np.float32)
    logit_err = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    top_agree = int(np.argmax(a)) == int(np.argmax(b))
    log(f"serve: decode-step vs prefill logits rel L2 {logit_err:.3e} "
        f"(limit {LOGIT_REL_TOL:g}, bf16), argmax agree {top_agree}")
    check(np.isfinite(a).all() and logit_err <= LOGIT_REL_TOL,
          f"decode vs prefill logits rel L2 {logit_err:.3e}")
    RESULTS["serve"].update({
        "arch": cfg.name, "param_dtypes": dtypes, "param_bytes": p_bytes,
        "device_memory": held, "requests": len(served),
        "tokens": engine.tokens_emitted, "meter_rel_err": meter_err,
        "logit_rel_l2": logit_err, "logit_argmax_agree": top_agree})


# ---------------------------------------------------------------------------
# --chips 4: fleet-axis sharding
# ---------------------------------------------------------------------------

def sharded_phase(clock, *, n_nodes: int = NODES, seconds: float = CAPTURE_S,
                  seed: int = 0, odd_rows: int = 1021):
    from repro.core.measurement_model import (chip_energy_sensor,
                                              pm_energy_sensor)
    from repro.distributed.sharding import fleet_mesh, fleet_row_padding
    from repro.fleet import FleetStream, fleet_reconstruct, pack_traces
    mesh = fleet_mesh()
    check(mesh is not None and mesh.shape["fleet"] == 4,
          f"fleet mesh {mesh and dict(mesh.shape)}, expected 4 chips")
    counters = []
    for c in range(CHIPS_PER_NODE):
        counters += [chip_energy_sensor(c), pm_energy_sensor(c, c in (0, 2))]
    _, phases, groups, _, _ = timed(
        "sharded_simulate", clock, simulate_fleet, n_nodes, seconds, seed,
        sensors=counters)
    packed = pack_traces([tr for g in groups for tr in g])
    log(f"sharded: {packed.n_traces} counter rows (packed {packed.shape})")
    wins = [(a - packed.t0, b - packed.t0) for _, a, b in phases]
    chunk = 1024
    for rows in (packed.shape[0], odd_rows):
        # pack_traces rounds rows up to 8; slice past it so the mesh
        # has to pad the fleet axis
        sub = dataclasses.replace(
            packed, energy=packed.energy[:rows], times=packed.times[:rows],
            n_samples=packed.n_samples[:rows],
            wrap_period=packed.wrap_period[:rows],
            names=packed.names[:rows], n_traces=min(rows, packed.n_traces),
            e0=None if packed.e0 is None else packed.e0[:rows])
        sharded = timed(f"reconstruct_{rows}_4chips", clock,
                        fleet_reconstruct, sub, mesh="auto")
        single = timed(f"reconstruct_{rows}_1chip", clock,
                       fleet_reconstruct, sub, mesh=None)
        spread = len(sharded[0].sharding.device_set)
        check(spread == 4, f"fleet_reconstruct ran on {spread} chips")
        for name, a, b in zip(("power", "times", "valid"), sharded, single):
            check(np.array_equal(np.asarray(a), np.asarray(b)),
                  f"fleet_reconstruct {name} at {rows} rows: 4 chips "
                  f"differ from one")
        log(f"sharded: fleet_reconstruct at {rows} rows "
            f"({fleet_row_padding(mesh, rows)} padding rows on the mesh) is "
            f"bit-identical over 4 chips and one")
        t, e = packed.times[:rows], packed.energy[:rows]
        streams = {}
        for tag, where in (("4chips", "auto"), ("1chip", None)):
            def run(where=where):
                s = FleetStream(wins, rows, packed.wrap_period[:rows],
                                mesh=where)
                for lo in range(0, t.shape[1], chunk):
                    s.update(t[:, lo:lo + chunk], e[:, lo:lo + chunk])
                s.totals()                     # wait for the last step
                return s
            streams[tag] = timed(f"fleetstream_{rows}_{tag}", clock, run)
        pad = streams["4chips"]._attr._row_pad
        check(streams["4chips"].mesh is not None, "FleetStream did not shard")
        check(np.array_equal(streams["4chips"].totals(),
                             streams["1chip"].totals()),
              f"FleetStream at {rows} rows: 4 chips differ from one")
        log(f"sharded: FleetStream at {rows} rows ({pad} padding rows on "
            f"the mesh) is bit-identical over 4 chips and one")
    RESULTS["sharded"] = {"rows": packed.shape[0], "odd_rows": odd_rows,
                          "bit_identical": True}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the fleet-axis sharding path")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    if args.chips == 4:
        sharded_phase(clock)
    else:
        n_rows = fleet_phase(clock)
        kernel_phase(n_rows)
        serve_phase(clock)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    RESULTS["device"] = device
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    name = "chip_smoke.json" if args.chips == 1 else "chip_smoke_4.json"
    (out / name).write_text(json.dumps(RESULTS, indent=1, default=str))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
