"""Program spans (``repro.core.tracing``): recorded only under a profiler
session, nested per thread, tiling the fleet and serve hot paths, and
paired one to one with their ``repro.`` events in the profiler trace."""
import math
import threading
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import ToolSpec, simulate_sensor, square_wave, tracing
from repro.core.measurement_model import chip_energy_sensor, pm_energy_sensor

CHUNK = 1024


@pytest.fixture
def program():
    """The program tracer, empty before and after the test."""
    tracing.PROGRAM.flush()
    tracing.PROGRAM.dropped = 0
    yield tracing.PROGRAM
    tracing.PROGRAM.flush()
    tracing.PROGRAM.dropped = 0


def _counter_traces(n=8):
    truth = square_wave(1.0, 2, lead_s=0.5, tail_s=0.5)
    out = []
    for i in range(n):
        spec = (chip_energy_sensor(i) if i % 3 != 2
                else pm_energy_sensor(i, i % 2 == 0))
        out.append(simulate_sensor(spec, ToolSpec(1e-3), truth, seed=i))
    return out


_PHASES = [("a", 0.6, 1.6), ("b", 1.6, 2.8), ("c", 2.8, 3.4)]


def _fused_groups(n_devices=2):
    from repro.core.measurement_model import SensorSpec
    truth = square_wave(1.0, 2, lead_s=0.5, tail_s=0.5)
    groups = []
    for d in range(n_devices):
        specs = [SensorSpec(name=f"d{d}_energy", scope="chip",
                            kind="energy_cum", quantum=1e-6, wrap_bits=26),
                 SensorSpec(name=f"d{d}_power", scope="chip",
                            kind="power_inst", noise_w=3.0, quantum=1e-6)]
        groups.append([simulate_sensor(sp, ToolSpec(1e-3), truth,
                                       seed=7 * d + i)
                       for i, sp in enumerate(specs)])
    return groups


def _run_counters(monkeypatch):
    """attribute_energy_fleet over 8 counter rows -> the stage_wall_s of
    its stream."""
    import repro.fleet.api as api
    streams = []

    class Recorded(api.FleetStream):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            streams.append(self)

    monkeypatch.setattr(api, "FleetStream", Recorded)
    api.attribute_energy_fleet(_counter_traces(), _PHASES, chunk=CHUNK)
    return streams[0]._pipe.stage_wall_s


def _run_fused(monkeypatch):
    """attribute_energy_fused_streaming over 2 devices -> the
    stage_wall_s of its pipeline."""
    from repro.fleet import attribute_energy_fused_streaming
    from repro.fleet.config import PipelineConfig, StreamConfig
    _, pipe = attribute_energy_fused_streaming(
        _fused_groups(), _PHASES, config=PipelineConfig(
            stream=StreamConfig(chunk=CHUNK)), return_pipe=True)
    return pipe.pipeline.stage_wall_s


def _windows_counters():
    """(samples, windows) of the counter run."""
    from repro.fleet.packing import pack_traces
    samples = pack_traces(_counter_traces()).shape[1]
    return samples, math.ceil(samples / CHUNK)


def _windows_fused():
    from repro.fleet.pipeline import _replay_window_plan, pack_stream_rows
    rows = pack_stream_rows([tr for g in _fused_groups() for tr in g])
    return rows.shape[1], _replay_window_plan(rows, CHUNK)[0]


RUNS = {"counters": (_run_counters, _windows_counters),
        "fused": (_run_fused, _windows_fused)}


def _xplane_spans(trace_dir):
    """{span_id: (name, start_ns, duration_ns, parent)} of the trace's
    ``repro.`` events, and the names of its jitted-function events."""
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    spans, programs = {}, set()
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    st = dict(ev.stats)
                    spans[st["span_id"]] = (ev.name[len("repro."):],
                                            ev.start_ns, ev.duration_ns,
                                            st["parent"])
                elif ev.name.startswith("PjitFunction("):
                    programs.add(ev.name[len("PjitFunction("):-1])
    return spans, programs


@pytest.fixture(params=sorted(RUNS))
def fleet_run(request, program, monkeypatch, tmp_path):
    """One fleet entry point, warmed, then run under the profiler."""
    run, windows = RUNS[request.param]
    run(monkeypatch)
    samples, n_win = windows()
    with jax.profiler.trace(str(tmp_path)):
        walls = run(monkeypatch)
    events = list(program.events)
    return request.param, samples, n_win, walls, events, tmp_path


def test_no_profiler_session_records_nothing(program, monkeypatch):
    assert not tracing.recording()
    assert tracing.span("a") is tracing.span("b", n=3)
    for run, _ in RUNS.values():
        run(monkeypatch)
    tracing.add_span("wait", 0.0, 1.0)
    assert not program.events and program.dropped == 0


def _children(events, parent):
    return [e for e in events if e.parent == parent.span_id]


def _named(events, name):
    return [e for e in events if e.name == name]


def test_fleet_spans_tile_each_job(fleet_run):
    kind, samples, n_win, walls, events, _ = fleet_run
    (root,) = _named(events, "fleet.attribute")
    (pack,) = _named(events, "fleet.pack")
    (correct,) = _named(events, "fleet.correct")
    assert (root.parent, pack.parent, correct.parent) == \
        (-1, root.span_id, pack.span_id)
    assert root.n == sum(len(tr) for tr in
                         (_counter_traces() if kind == "counters"
                          else [t for g in _fused_groups() for t in g]))
    windows = _named(events, "fleet.window")
    assert len(windows) == n_win
    if kind == "counters":
        assert n_win == math.ceil(samples / CHUNK)
        assert sum(w.n for w in windows) == samples
    # the children tile the root: what no child covers is a few gaps
    kids = _children(events, root)
    assert [k.name for k in kids][:2] == ["fleet.pack", "fleet.plan"]
    assert [k.name for k in kids][-2:] == ["fleet.totals", "fleet.rows"]
    covered = sum(k.t_end - k.t_start for k in kids)
    assert covered >= 0.9 * (root.t_end - root.t_start)
    # stage spans are stage_wall_s, by the same clock reads
    stages = [e for e in events if e.name.startswith("stage.")]
    assert stages
    for name, wall in walls.items():
        spans = [e.t_end - e.t_start for e in stages
                 if e.name == "stage." + name]
        assert math.fsum(spans) == pytest.approx(wall, rel=1e-12, abs=0)
    assert {e.parent for e in stages} <= \
        {e.span_id for e in windows + _named(events, "fleet.finalize")}


def test_program_spans_pair_with_trace_events(fleet_run):
    *_, events, trace_dir = fleet_run
    spans, _ = _xplane_spans(trace_dir)
    assert sorted(spans) == sorted(e.span_id for e in events)
    offsets = []
    for e in events:
        name, start_ns, dur_ns, parent = spans[e.span_id]
        assert (name, parent) == (e.name, e.parent)
        offsets.append(start_ns * 1e-9 - e.t_start)
    assert max(offsets) - min(offsets) <= 1e-4


def test_serve_spans(program, tmp_path):
    from repro.configs import get_arch, reduced
    from repro.models import Model
    from repro.serve import ServeEngine, poisson_requests
    cfg = reduced(get_arch("llama3.2-3b"))
    model = Model(cfg)
    engine = ServeEngine(model, model.init(jax.random.key(0)),
                         batch_slots=2, max_len=32, flush_interval=2)

    def requests(seed):
        return poisson_requests(5, rate_rps=2000.0, seed=seed,
                                prompt_lens=(4, 6), new_tokens=(2, 6),
                                vocab_size=cfg.vocab_size)

    engine.run(requests(1))                     # compiles every program
    engine.segments = []
    reqs = requests(3)
    with jax.profiler.trace(str(tmp_path)):
        engine.run(reqs, respect_arrivals=True)
    events = list(program.events)
    (root,) = [e for e in events if e.name == "serve.run"]
    assert root.n == len(reqs)
    queued = {e.step: e for e in events if e.name == "serve.queued"}
    assert sorted(queued) == sorted(r.rid for r in reqs)
    for r in reqs:
        q = queued[r.rid]
        assert q.parent == root.span_id
        assert abs((q.t_end - q.t_start) - (r.t_admitted - r.t_arrival)) \
            <= 1e-6
    admits = [e for e in events if e.name == "serve.admit"]
    assert sorted(e.step for e in admits) == sorted(r.rid for r in reqs)
    decodes = [e for e in events if e.name == "serve.decode"]
    steps = [int(s.tokens[0]) for s in engine.segments
             if s.kind == "decode"]
    assert [e.n for e in decodes] == steps
    drains = [e for e in events if e.name == "serve.drain"]
    assert [e.parent for e in drains] == [e.span_id for e in decodes]
    # spans opened (not recorded after the fact) pair with the trace
    spans, programs = _xplane_spans(tmp_path)
    assert sorted(spans) == sorted(e.span_id for e in events
                                   if e.name != "serve.queued")
    assert {"serve_prefill", "serve_decode_step", "serve_scatter_slot",
            "serve_zero_cache"} <= programs


def test_spans_nest_per_thread(program, tmp_path):
    opened, other_done = threading.Event(), threading.Event()
    seen = {}

    def other():
        assert opened.wait(10)
        with tracing.span("other") as sp:
            seen["other"] = sp.parent
        other_done.set()

    t = threading.Thread(target=other)
    with jax.profiler.trace(str(tmp_path)):
        t.start()
        with tracing.span("outer") as outer:
            opened.set()
            assert other_done.wait(10)
            with tracing.span("inner") as inner:
                pass
        t.join(10)
    assert not t.is_alive()
    assert seen["other"] == -1
    assert (outer.parent, inner.parent) == (-1, outer.span_id)
    by = {e.name: e for e in program.events}
    assert by["other"].depth == 0 and by["inner"].depth == 1


def test_full_ring_counts_its_drops(program, monkeypatch, tmp_path):
    monkeypatch.setattr(program, "max_events", 4)
    with jax.profiler.trace(str(tmp_path)):
        for i in range(6):
            with tracing.span("s", n=i):
                pass
    assert program.dropped == 2
    assert [e.n for e in program.events] == [2, 3, 4, 5]
    ids = [e.span_id for e in program.events]
    assert ids == sorted(ids)
