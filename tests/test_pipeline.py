"""Streaming stage pipeline: batch-replay parity, online drift tracking,
chunk sanitization properties, and phase-table padding regression."""
import dataclasses

import numpy as np
import pytest

from repro.core import ToolSpec, simulate_sensor, square_wave
from repro.core.measurement_model import SensorSpec, chip_energy_sensor
from repro.core.sensors import SensorTrace
from repro.fleet import FleetStream, attribute_energy_fused_streaming
from repro.fleet.pipeline import (PHASE_ALIGN, AlignTrackStage,
                                  IngestStage, ReconstructStage,
                                  StreamPipeline, _min_cadence,
                                  pack_stream_rows, pad_phases,
                                  stream_row_windows)


# ------------------------------------------------ batch-replay parity

def _sim_groups(n_devices, seed=0, span_s=4.5, noise=3.0):
    """Per device: a wrapping energy counter + a noisy power sensor,
    distinct configured delays per device (fast 1 ms cadence so replay
    windows stay small)."""
    truth = square_wave(span_s / 4.0, 3, lead_s=span_s / 8,
                        tail_s=span_s / 8)
    tool = ToolSpec(0.9e-3)
    groups = []
    for d in range(n_devices):
        specs = [
            SensorSpec(name=f"d{d}_energy", scope="chip",
                       kind="energy_cum", quantum=1e-6, wrap_bits=26,
                       delay_s=0.004 * (d % 5)),
            SensorSpec(name=f"d{d}_power", scope="chip",
                       kind="power_inst", noise_w=noise, quantum=1e-6,
                       delay_s=0.011 + 0.003 * (d % 3)),
        ]
        groups.append([simulate_sensor(sp, tool, truth,
                                       seed=seed + 31 * d + i)
                       for i, sp in enumerate(specs)])
    return truth, groups


def _sim_unequal_groups(sizes, span_s=2.5):
    """Device groups of unequal size (alternating wrapping energy
    counters and noisy power sensors) — the row padding of the smaller
    groups must not leak into the fusion statistics."""
    truth = square_wave(span_s / 4.0, 3, lead_s=span_s / 8,
                        tail_s=span_s / 8)
    tool = ToolSpec(0.9e-3)
    groups, i = [], 0
    for d, sz in enumerate(sizes):
        grp = []
        for j in range(sz):
            kind = "energy_cum" if j % 2 == 0 else "power_inst"
            sp = SensorSpec(name=f"d{d}_{j}", scope="chip", kind=kind,
                            quantum=1e-6,
                            wrap_bits=26 if kind == "energy_cum" else 0,
                            noise_w=0.0 if kind == "energy_cum" else 3.0,
                            delay_s=0.002 * (i % 7))
            grp.append(simulate_sensor(sp, tool, truth, seed=100 + 17 * i))
            i += 1
        groups.append(grp)
    return truth, groups


def _parity_phases(grid, n=6):
    edges = np.linspace(float(grid[0]), float(grid[-1]), n + 1)
    return [(f"p{k}", float(a), float(b))
            for k, (a, b) in enumerate(zip(edges[:-1], edges[1:]))]


def _run_parity(n_devices, chunk, span_s=4.5, min_chunks=None,
                sizes=None):
    from repro.align import align_and_fuse, attribute_energy_fused
    if sizes is None:
        truth, groups = _sim_groups(n_devices, span_s=span_s)
    else:
        truth, groups = _sim_unequal_groups(sizes)
    fused = align_and_fuse(groups, reference=truth)
    grid = fused[0].grid
    d_all = np.concatenate([fs.delays for fs in fused])
    phases = _parity_phases(grid)
    batch = attribute_energy_fused(groups, phases, grid=grid,
                                   delays=d_all)
    if min_chunks is not None:      # the pipeline must really chunk
        flat = [tr for g in groups for tr in g]
        rows = pack_stream_rows(flat)
        n_win = sum(1 for _ in stream_row_windows(rows, chunk))
        assert n_win >= min_chunks, (n_win, min_chunks)
    stream = attribute_energy_fused_streaming(
        groups, phases, grid=grid, delays=d_all, chunk=chunk)
    worst = 0.0
    for rb, rs in zip(batch, stream):
        for pb, ps in zip(rb, rs):
            worst = max(worst, abs(ps.energy_j - pb.energy_j)
                        / max(abs(pb.energy_j), 1.0))
    return worst


@pytest.mark.parametrize("chunk,sizes", [
    pytest.param(193, None, id="193"),
    pytest.param(257, None, id="257"),
    pytest.param(512, None, id="512"),
    pytest.param(200, [1, 3, 2], id="groups-1-3-2"),
])
def test_streaming_fused_matches_batch_small(chunk, sizes):
    """Chunked streaming pipeline == batch align_and_fuse ->
    attribute_energy_fused at <=1e-5 (fixed delays, same grid), for
    several chunk widths and for unequal group sizes."""
    worst = _run_parity(2, chunk=chunk, sizes=sizes)
    assert worst <= 1e-5, worst


def test_streaming_fused_long_run_parity():
    """The acceptance-scale run: >=64 devices x >=64 chunks, <=1e-5."""
    worst = _run_parity(64, chunk=64, span_s=4.5, min_chunks=64)
    assert worst <= 1e-5, worst


@pytest.mark.parametrize("chunk,window,hop,with_ref", [
    pytest.param(512, 1024, 256, True, id="reference"),
    pytest.param(256, 512, 128, True, id="reference-short-window"),
    pytest.param(256, 512, 128, False, id="self-reference"),
])
def test_streaming_fused_online_tracking_close_to_batch(chunk, window,
                                                        hop, with_ref):
    """With delays estimated ONLINE (sliding windows) instead of fixed,
    the streamed energies stay within ~2% of the batch path — against
    a known reference, or (no reference, derived default grid) against
    each group's own first stream."""
    from repro.align import attribute_energy_fused
    truth, groups = _sim_groups(2)
    phases = [("a", 0.8, 1.8), ("b", 2.0, 3.6)]
    ref = truth if with_ref else None
    batch = attribute_energy_fused(groups, phases, reference=ref)
    stream = attribute_energy_fused_streaming(
        groups, phases, reference=ref, chunk=chunk, window=window,
        hop=hop, max_lag=64)
    for rb, rs in zip(batch, stream):
        for pb, ps in zip(rb, rs):
            assert abs(ps.energy_j - pb.energy_j) \
                <= 0.02 * max(abs(pb.energy_j), 1.0), pb.phase


def test_streaming_fused_tracks_configured_delays():
    """``return_pipe=True`` hands back the driven pipeline: tracking
    against the truth recovers each energy counter's configured delay
    within a few grid steps, and every end-of-run IVW weight is
    positive."""
    from repro.fleet import PipelineConfig, StreamConfig, TrackConfig
    truth, groups = _sim_groups(2, seed=9, span_s=3.0)
    phases = [("a", 0.6, 1.4), ("b", 1.6, 2.6)]
    cfg = PipelineConfig(stream=StreamConfig(chunk=256),
                         track=TrackConfig(track=True, window=512,
                                           hop=128))
    out, pipe = attribute_energy_fused_streaming(
        groups, phases, config=cfg, reference=truth, return_pipe=True)
    assert len(out) == 2 and all(len(r) == 2 for r in out)
    assert len(pipe.delay_history) > 0          # the tracker fired
    got = pipe.delays()[::2]                     # the energy rows
    want = np.asarray([0.004 * (d % 5) for d in range(2)])
    assert np.all(np.abs(got - want) <= 2e-3), (got, want)
    for w in pipe.weights():
        assert w.shape == (2,) and (w > 0).all(), w


def test_streaming_fused_row_count_off_tile():
    """Stream counts that are NOT a multiple of the row tile must pad
    every per-row input (kind_row AND wrap_period) consistently."""
    from repro.align import align_and_fuse, attribute_energy_fused
    truth, groups = _sim_groups(3)        # 6 rows < ROW_ALIGN
    fused = align_and_fuse(groups, reference=truth)
    grid = fused[0].grid
    d_all = np.concatenate([fs.delays for fs in fused])
    phases = _parity_phases(grid, n=3)
    batch = attribute_energy_fused(groups, phases, grid=grid,
                                   delays=d_all)
    stream = attribute_energy_fused_streaming(
        groups, phases, grid=grid, delays=d_all, chunk=512)
    for rb, rs in zip(batch, stream):
        for pb, ps in zip(rb, rs):
            assert abs(ps.energy_j - pb.energy_j) \
                <= 1e-5 * max(abs(pb.energy_j), 1.0), pb.phase
    # direct construction with wrapping counters (serve_demo's shape
    # generalized off the 8-row tile)
    from repro.fleet import StreamingFusedPipeline
    pipe = StreamingFusedPipeline(
        [2] * 3, [(0.0, 1.0)], grid_origin=0.0, grid_step=1e-3,
        kind_row=[True, False] * 3, wrap_period=[67.0, 0.0] * 3,
        delays=np.zeros(6), track=False)
    assert pipe.totals().shape == (3, 1)


def test_power_row_span_opens_at_first_sample():
    """A raw power row's coverage starts at its FIRST sample (batch
    SeriesRows convention); the first inter-sample gap must not be
    masked off in the streamed path."""
    from repro.align import attribute_energy_fused
    truth = square_wave(1.0, 2, lead_s=0.4, tail_s=0.4)
    spec = SensorSpec(name="p0", scope="chip", kind="power_inst",
                      quantum=1e-6)       # delay_s = 0: queries land in
    tr = simulate_sensor(spec, ToolSpec(1e-3), truth, seed=13)
    groups = [[tr]]                       # the opening gap
    t0 = float(tr.t_measured[0])
    phases = [("head", t0, t0 + 0.05), ("rest", t0 + 0.05, t0 + 2.0)]
    grid = np.arange(t0, float(tr.t_measured[-1]), 0.51e-3)
    batch = attribute_energy_fused(groups, phases, grid=grid,
                                   delays=np.zeros(1))
    stream = attribute_energy_fused_streaming(
        groups, phases, grid=grid, delays=np.zeros(1), chunk=256)
    for pb, ps in zip(batch[0], stream[0]):
        assert abs(ps.energy_j - pb.energy_j) \
            <= 1e-5 * max(abs(pb.energy_j), 1.0), pb.phase


# ------------------------------------------------ online drift tracking

def _track_drift(drift_ppm, span=16.0, seed=3):
    truth = square_wave(0.25, int((span - 1.0) / 0.25), lead_s=0.5,
                        tail_s=0.5)
    spec = dataclasses.replace(chip_energy_sensor(0), delay_s=0.005,
                               drift_ppm=drift_ppm)
    tr = simulate_sensor(spec, ToolSpec(1e-3), truth, seed=seed)
    rows = pack_stream_rows([tr])
    step = 0.5 * _min_cadence(rows)     # measured cadence, NOT nominal
    t0 = rows.t0
    align = AlignTrackStage(
        1, grid_step=step,
        reference=lambda t: truth.power_at(t + t0),
        window=4096, hop=1024, max_lag=40, ema=0.5)
    pipe = StreamPipeline(IngestStage(rows.shape[0], mode="sanitize"),
                          ReconstructStage(rows.kind_row), align)
    for t_blk, v_blk in stream_row_windows(rows, 1024):
        pipe.update(t_blk, v_blk)
    return truth, spec, tr, rows, align


def test_aligntrack_follows_200ppm_drift():
    """The tracked delay stays within 0.5x the sensor update interval of
    the drifting ground truth AT EVERY WINDOW (acceptance criterion),
    while a whole-trace batch estimate can only see the mid-run
    average."""
    drift = 200.0
    truth, spec, tr, rows, align = _track_drift(drift)
    interval = spec.production_interval_s
    assert len(align.history) >= 8
    for p in align.history:
        true_d = spec.delay_s \
            + (p.t_center + rows.t0 - truth.t0) * drift * 1e-6
        assert abs(p.ema[0] - true_d) <= 0.5 * interval, \
            (p.t_center, p.ema[0], true_d)
    # total drift over the run is several intervals — tracking matters
    total_drift = (truth.t1 - truth.t0) * drift * 1e-6
    assert total_drift > 2.5 * interval
    # batch xcorr over the whole trace: pinned to the mid-run AVERAGE
    from repro.align import (estimate_delays, regrid_rows,
                             schedule_reference, series_rows_from_traces)
    from repro.align.fusion import default_grid
    sr = series_rows_from_traces([tr])
    grid, gstep = default_grid(sr)
    vals, mask = regrid_rows(sr, grid)
    est = estimate_delays(vals, mask, schedule_reference(truth, grid),
                          step=gstep, max_lag=64)
    mid = spec.delay_s + 0.5 * (truth.t1 - truth.t0) * drift * 1e-6
    end = spec.delay_s + (truth.t1 - truth.t0) * drift * 1e-6
    assert abs(est.delay_s[0] - mid) <= 0.5 * interval
    assert end - est.delay_s[0] > 0.4 * total_drift   # misses the end lag
    # ... while the online tracker's LAST window sits near the end truth
    last = align.history[-1]
    last_truth = spec.delay_s \
        + (last.t_center + rows.t0 - truth.t0) * drift * 1e-6
    assert abs(last.ema[0] - last_truth) <= 0.5 * interval
    assert last.ema[0] - est.delay_s[0] > 0.25 * total_drift


def test_drift_zero_is_bit_identical():
    """drift_ppm defaults to 0 and leaves the simulator untouched."""
    truth = square_wave(1.0, 2, lead_s=0.3, tail_s=0.3)
    a = simulate_sensor(chip_energy_sensor(0), ToolSpec(1e-3), truth,
                        seed=3)
    b = simulate_sensor(dataclasses.replace(chip_energy_sensor(0),
                                            drift_ppm=0.0),
                        ToolSpec(1e-3), truth, seed=3)
    np.testing.assert_array_equal(a.value, b.value)
    np.testing.assert_array_equal(a.t_measured, b.t_measured)


def test_drift_shifts_only_timestamps():
    """At the production stage, drift stretches the reported clock
    linearly and leaves the measured values bit-identical."""
    from repro.core.sensors import produce
    truth = square_wave(1.0, 2, lead_s=0.3, tail_s=0.3)
    spec0 = chip_energy_sensor(0)
    spec1 = dataclasses.replace(spec0, drift_ppm=500.0)
    tm0, v0 = produce(spec0, truth, np.random.default_rng(9))
    tm1, v1 = produce(spec1, truth, np.random.default_rng(9))
    np.testing.assert_array_equal(v0, v1)
    # reported clock: tm + (tm_true - t0) * ppm; the tiny timestamp
    # jitter enters both paths identically, so the difference IS the
    # drift term (up to jitter * ppm ~ 1e-8)
    drift_term = tm1 - tm0
    assert np.all(drift_term >= 0)
    np.testing.assert_allclose(drift_term,
                               (tm0 - truth.t0) * 500e-6, atol=1e-6)


# ------------------------------------------------ sanitize property

def test_sanitize_chunk_conserves_energy_property():
    """Arbitrary reordered/duplicated timestamp permutations: the
    streamed total over the full span equals the clean trace's dE, for
    ANY chunking (the carry bridges chunk boundaries)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @st.composite
    def perturbed(draw):
        n = draw(st.integers(12, 80))
        rng = np.random.default_rng(draw(st.integers(0, 2 ** 31)))
        dt = rng.uniform(0.5e-3, 2e-3, n)
        t = np.cumsum(dt)
        p = rng.uniform(40.0, 260.0, n)
        e = np.cumsum(p * dt)
        # duplicated reads: repeat random positions
        reps = draw(st.integers(0, 3))
        idx = np.sort(np.concatenate(
            [np.arange(n), rng.integers(0, n, reps)]))
        # reorder episodes: swap a few adjacent index pairs
        swaps = draw(st.integers(0, 3))
        for _ in range(swaps):
            j = int(rng.integers(1, len(idx) - 1))
            idx[j - 1], idx[j] = idx[j], idx[j - 1]
        split = draw(st.integers(1, len(idx) - 1))
        return t, e, idx, split

    @given(perturbed())
    @settings(max_examples=30, deadline=None)
    def inner(case):
        t, e, idx, split = case
        tt, ee = t[idx], e[idx]
        span = [(0.0, float(t[-1]) + 1e-3)]
        one = FleetStream(span, 1)
        one.update(tt[None, :], ee[None, :])
        two = FleetStream(span, 1)
        two.update(tt[None, :split], ee[None, :split])
        two.update(tt[None, split:], ee[None, split:])
        # the running-max keep-set is chunking-invariant, so totals
        # must agree exactly up to float accumulation order
        np.testing.assert_allclose(one.totals(), two.totals(),
                                   rtol=1e-5, atol=1e-4)
        # and conserve the kept subsequence's dE exactly
        keep_e = ee[tt >= np.maximum.accumulate(
            np.concatenate([[-np.inf], tt[:-1]]))]
        expect = float(keep_e[-1] - keep_e[0])
        total = float(one.totals()[0, 0])
        assert abs(total - expect) <= 1e-3 * max(abs(expect), 1.0) + 1e-2

    inner()


# ------------------------------------------------ pad_phases regression

def test_pad_phases_always_rounds_up_to_tile():
    for p in (1, 2, 5, 31, 32, 33, 48, 64):
        ph = pad_phases([(0.0, float(i + 1)) for i in range(p)])
        assert len(ph) % PHASE_ALIGN == 0 and len(ph) >= p, (p, len(ph))
        # padding windows are zero-width -> integrate to exactly zero
        assert (ph[p:, 0] == ph[p:, 1]).all()


@pytest.mark.parametrize("n_phases", [2, 5, 31])
def test_small_phase_counts_through_kernel(n_phases):
    """1 < p < 32 phase tables stream through the fused kernel padded to
    the full tile and match the per-trace host attribution (the
    pre-pipeline pad_phases only padded p > 32)."""
    from repro.core import attribute_energy
    rng = np.random.default_rng(7)
    k = 400
    dt = rng.uniform(0.5e-3, 2e-3, k)
    t = np.cumsum(dt)
    p = rng.uniform(40.0, 260.0, k)
    e = np.cumsum(p * dt)
    spec = SensorSpec(name="s", scope="chip", kind="energy_cum",
                      quantum=1e-6)
    tr = SensorTrace("s", spec, t + 1e-4, t, e)
    edges = np.linspace(float(t[0]), float(t[-1]), n_phases + 1)
    phases = [(f"p{j}", float(a), float(b))
              for j, (a, b) in enumerate(zip(edges[:-1], edges[1:]))]
    stream = FleetStream([(a, b) for _, a, b in phases], 1)
    assert stream.phases.shape[0] % PHASE_ALIGN == 0
    for lo in range(0, k, 128):
        stream.update(t[None, lo:lo + 128], e[None, lo:lo + 128])
    host = attribute_energy(tr, phases)
    got = stream.totals()[0]
    assert got.shape == (n_phases,)
    for h, g in zip(host, got):
        assert abs(g - h.energy_j) <= 1e-3 * max(abs(h.energy_j), 1.0), \
            h.phase


# ------------------------------------------------ hpl / consumers

def test_fused_streaming_hpl_energize_close_to_batch():
    import time
    from repro.core.tracing import RegionTracer
    from repro.hpl.energy import fused_fleet_energize
    tracer = RegionTracer()
    with tracer.region("hpl_factorize"):
        time.sleep(0.6)
    with tracer.region("hpl_solve"):
        time.sleep(0.5)
    batch = fused_fleet_energize(tracer, 1)
    stream = fused_fleet_energize(tracer, 1, streaming=True, chunk=512)
    for rb, rs in zip(batch, stream):
        for pb, ps in zip(rb, rs):
            assert pb.phase == ps.phase
            assert abs(ps.energy_j - pb.energy_j) \
                <= 0.05 * max(abs(pb.energy_j), 1.0), pb.phase


def test_ingest_maskfill_matches_accumulator_semantics():
    """The pipeline Ingest(maskfill) must keep the accumulator's
    invalid-first-slot behavior (zero-width seed at the first VALID
    sample)."""
    from repro.fleet import StreamingPhaseAccumulator
    t = np.array([[0.0, 100.0, 100.1, 100.2, 100.3]], np.float32)
    w = np.array([[999.0, 50.0, 50.0, 50.0, 50.0]], np.float32)
    valid = np.array([[False, True, True, True, True]])
    acc = StreamingPhaseAccumulator([(0.0, 200.0)], 1)
    acc.update(t, w, valid=valid)
    e = float(acc.totals()[0, 0])
    assert abs(e - 50.0 * 0.3) < 1e-3, e
