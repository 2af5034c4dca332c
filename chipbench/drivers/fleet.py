"""Fleet attribution cells: closed-loop jobs, one capture each.

The traffic file names the program entry (``entry``) and the sensors it
reads (``sensors``; absent = the whole inventory):

* ``fused_streaming``: ``fleet.attribute_energy_fused_streaming`` over
  every device group (windowed engine, online delay tracking);
* ``counters``: ``fleet.attribute_energy_fleet`` over the cumulative
  counter rows only.

Set-up simulates the fleet's capture from the seed and runs one whole
job, which compiles every program the job's windows use.  The window then
runs whole jobs back to back until ``--seconds`` have passed and closes at
the end of the job that crosses it: ``attr_samples_per_s`` is every raw
sensor read of those jobs over that time.  Every job's per-phase
energies, for every group, are compared with the configuration's float64
reference.
"""
from __future__ import annotations

import contextlib
import shutil
import time

import numpy as np

import bench
import fleetgen
import trace_reduce


def _program_inputs(cell, traces):
    """The program's own trace, spec and correction objects."""
    from repro.core.calibration import Corrections
    from repro.core.measurement_model import SensorSpec
    from repro.core.sensors import SensorTrace
    specs = {}
    out = []
    for tr in traces:
        spec = specs.get(tr.name)
        if spec is None:
            spec = specs[tr.name] = SensorSpec(**tr.spec)
        out.append(SensorTrace(f"n{tr.node:03d}_{tr.name}", spec,
                               tr.t_read, tr.t_measured, tr.value))
    offsets, slopes = node_corrections(cell.config, traces)
    return out, Corrections(offsets, slopes)


def node_corrections(cfg, traces):
    off, slo = fleetgen.corrections(cfg)
    offsets, slopes = {}, {}
    for tr in traces:
        full = f"n{tr.node:03d}_{tr.name}"
        if tr.name in off:
            offsets[full] = off[tr.name]
        if tr.name in slo:
            slopes[full] = slo[tr.name]
    return offsets, slopes


@contextlib.contextmanager
def timed_stream(walls: dict):
    """Host seconds of every ``FleetStream.update`` call and of every read
    of its totals, appended to ``walls["update"]`` / ``walls["totals"]``."""
    from repro.fleet.streaming import FleetStream
    orig = {k: getattr(FleetStream, k) for k in ("update", "totals")}

    def timed(kind):
        def call(self, *a, **kw):
            t = time.perf_counter()
            try:
                return orig[kind](self, *a, **kw)
            finally:
                walls[kind].append(time.perf_counter() - t)
        return call

    for k in orig:
        setattr(FleetStream, k, timed(k))
    try:
        yield walls
    finally:
        for k, f in orig.items():
            setattr(FleetStream, k, f)


def run(cell, devices, t_process: float) -> dict:
    import jax
    cfg, traffic = cell.config, cell.traffic
    entry = traffic["entry"]
    fused = entry == "fused_streaming"
    clock = bench.CompileClock()
    spans = bench.Spans(profiling=cell.trace)
    traces = fleetgen.sample_nodes(cfg, cell.seed, range(cfg["nodes"]),
                                   traffic.get("sensors"))
    groups = fleetgen.group_rows(traces, cfg, fused)
    phases = fleetgen.phases(cfg)
    prog, corr = _program_inputs(cell, traces)
    prog_groups = [[prog[i] for i in g] for g in groups]
    n_reads = int(sum(len(tr.t_measured) for tr in traces))
    chunk = int(traffic["chunk"])
    bench.log(f"{cell.name}: {cfg['nodes']} nodes, {len(traces)} sensor "
              f"rows, {len(groups)} groups, {n_reads} reads per job")

    if fused:
        from repro.core.power_model import PiecewisePower
        from repro.fleet import attribute_energy_fused_streaming
        from repro.fleet.config import PipelineConfig, StreamConfig
        sq = fleetgen.square_wave(cfg)
        ref_power = PiecewisePower(sq.times, sq.watts)
        pcfg = PipelineConfig(stream=StreamConfig(chunk=chunk))

        def job():
            rows, pipe = attribute_energy_fused_streaming(
                prog_groups, phases, config=pcfg, reference=ref_power,
                corrections=corr, return_pipe=True)
            e = np.asarray([[p.energy_j for p in r] for r in rows])
            return e, pipe
    else:
        from repro.fleet import attribute_energy_fleet

        def job():
            rows = attribute_energy_fleet(prog, phases, corrections=corr,
                                          chunk=chunk)
            return np.asarray([[p.energy_j for p in r] for r in rows]), None

    job()                                   # warm: compiles every shape
    compiles0, compile_s0 = clock.compiles, clock.total

    trace_dir = bench.ROOT / ".bench_trace" / cell.name
    if cell.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        bench.start_trace(trace_dir)
    t_start = time.perf_counter()
    setup_s = t_start - t_process
    outputs, stage_walls = [], []
    stream_walls = {"update": [], "totals": []}
    with spans.span("window"), (timed_stream(stream_walls) if cell.trace
                                else contextlib.nullcontext()):
        while True:
            with spans.span("job"):
                e, pipe = job()
            outputs.append(e)
            if pipe is not None:
                stage_walls.append((dict(pipe.pipeline.stage_wall_s),
                                    pipe.pipeline.windows))
            if time.perf_counter() - t_start >= cell.seconds:
                break
    t_end = time.perf_counter()
    window_s = t_end - t_start
    summary = None
    if cell.trace:
        jax.profiler.stop_trace()
    compiles_in_window = clock.compiles - compiles0
    mem_peak = bench.memory_peak_bytes(devices)
    if cell.trace:
        events = trace_reduce.load_events(
            trace_reduce.find_xplane(trace_dir))
        summary = trace_reduce.reduce(events, n_devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
    jobs = len(outputs)
    rate = jobs * n_reads / window_s

    # -- correctness: every job's energies in every group -----------------
    ref = cell.reference()
    offsets, slopes = node_corrections(cfg, traces)
    if fused:
        origin, step = ref.grid_of(traces)
        t_endg = max(float(tr.t_measured[-1]) for tr in traces)

    def reference(low=False):
        """(groups, phases) joules of the reference (``low``: in bfloat16)."""
        out = np.zeros((len(groups), len(phases)))
        for j, g in enumerate(groups):
            members = [_Named(traces[i]) for i in g]
            periods = [fleetgen.wrap_period(traces[i].spec) for i in g]
            if fused:
                out[j] = ref.fused_phase_energies(
                    members, phases, origin=origin, step=step, t_end=t_endg,
                    offsets=offsets, slopes=slopes, periods=periods, low=low)
            else:
                out[j] = ref.counter_phase_energies(
                    members[0], phases, offsets=offsets, slopes=slopes,
                    period=periods[0], low=low)
        return out

    t_ref = time.perf_counter()
    ref_e = reference()
    ref_s = time.perf_counter() - t_ref
    denom = np.maximum(np.abs(ref_e), 1.0)
    control = None
    if cell.control:
        # the reference in bfloat16, in the program's place
        control = float(np.max(np.abs(reference(low=True) - ref_e) / denom))
    errs = [float(np.max(np.abs(out - ref_e) / denom)) for out in outputs]
    worst = max(errs)
    failed = sum(int(not e <= traffic["limit_rel"]) for e in errs)
    finite = all(np.isfinite(o).all() for o in outputs)
    checks = [
        {"name": "phase_energy_rel_err", "value": worst, "op": "<=",
         "limit": traffic["limit_rel"],
         "ok": worst <= traffic["limit_rel"]},
        {"name": "compiles_in_window", "value": compiles_in_window,
         "op": "==", "limit": 0, "ok": compiles_in_window == 0},
    ]
    correct = finite and failed == 0 and compiles_in_window == 0
    bench.log(f"{cell.name}: set-up {setup_s:.3f} s ({compiles0} "
              f"compilations, {compile_s0:.3f} s), {jobs} jobs in "
              f"{window_s:.3f} s, {rate:.1f} samples/s; reference over "
              f"{len(groups)} groups took {ref_s:.3f} s")
    job_s = np.asarray([t1 - t0 for name, t0, t1 in spans.events
                        if name == "job"])
    bench.log(f"{cell.name}: job seconds min/q1/median/q3/max "
              + " ".join(f"{q:.4f}" for q in np.percentile(
                  job_s, [0, 25, 50, 75, 100]))
              + "; slowest " + " ".join(f"{q:.4f}" for q in
                                        np.sort(job_s)[-5:]))

    ctx = {"trace": summary, "stage_walls": stage_walls,
           "stream_walls": stream_walls}
    return {"correct": correct, "attempted": jobs * len(groups),
            "failed": failed, "checks": checks, "memory_peak_bytes": mem_peak,
            "control": control,
            "end_to_end": {"attr_samples_per_s": rate, "setup_s": setup_s},
            "ctx": ctx}


class _Named:
    """A generated trace under the full (node-prefixed) sensor name."""

    def __init__(self, tr):
        self.name = f"n{tr.node:03d}_{tr.name}"
        self.spec = tr.spec
        self.t_measured = tr.t_measured
        self.value = tr.value
