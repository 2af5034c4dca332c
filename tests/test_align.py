"""Cross-sensor alignment & fusion: kernel-vs-oracle parity, blind delay
recovery against simulator ground truth, fusion energy conservation, and
regridding properties."""
import dataclasses

import numpy as np
import pytest

from repro.align import (align_and_fuse, align_fuse_host,
                         attribute_energy_fused, estimate_delays,
                         estimate_delays_host, fuse_gridded,
                         fuse_gridded_host, group_traces_by_device,
                         make_grid, regrid_rows, regrid_rows_host,
                         schedule_reference, series_rows_from_traces,
                         validate_streams)
from repro.align.fusion import default_grid
from repro.align.regrid import SeriesRows
from repro.core import (NodeFabric, ToolSpec, delta_e_over_delta_t,
                        simulate_sensor, square_wave)
from repro.core.measurement_model import (chip_energy_sensor,
                                          chip_power_inst_sensor,
                                          pm_energy_sensor)
from repro.core.reconstruction import PowerSeries


def _synthetic_rows(k=8, s=200, seed=0):
    rng = np.random.default_rng(seed)
    times = np.zeros((k, s), np.float32)
    values = np.zeros((k, s), np.float32)
    n = np.zeros((k,), np.int32)
    first = np.zeros((k,), np.int32)
    for i in range(k):
        kk = s - int(rng.integers(0, s // 5))
        t = np.cumsum(rng.uniform(0.5e-3, 2e-3, kk))
        v = rng.uniform(50, 250, kk)
        times[i, :kk] = t
        values[i, :kk] = v
        times[i, kk:] = t[-1]
        values[i, kk:] = v[-1]
        n[i] = kk
        first[i] = 1 if i % 2 == 0 else 0
    return SeriesRows(times, values, n, first,
                      [f"s{i}" for i in range(k)], k, t0=0.0)


# ------------------------------------------------------ regrid parity

@pytest.mark.parametrize("mode", ["hold", "linear"])
def test_regrid_kernel_matches_float64_host(mode):
    """Kernel vs jnp oracle vs the float64 numpy mirror: ≤1e-5."""
    rows = _synthetic_rows()
    grid = make_grid(0.0, 0.35, 1e-3)
    delays = np.random.default_rng(1).uniform(-0.01, 0.01, rows.shape[0])
    vk, mk = regrid_rows(rows, grid, delays=delays, mode=mode)
    vr, mr = regrid_rows(rows, grid, delays=delays, mode=mode,
                         use_kernel=False)
    vh, mh = regrid_rows_host(rows, grid, delays=delays, mode=mode)
    assert (np.asarray(mk) == np.asarray(mr)).all()
    assert (np.asarray(mk) == mh).all()
    rel = np.abs(np.asarray(vk, np.float64) - vh) \
        / np.maximum(np.abs(vh), 1.0)
    assert rel.max() <= 1e-5, (mode, rel.max())


@pytest.mark.parametrize("mode", ["hold", "linear"])
def test_grid_resample_kernel_matches_oracle(mode):
    """The Pallas kernel's column sweep (interpret mode) against the
    halving-search oracle: identical masks and lower bounds on sorted
    rows with equal-time runs, a -inf sentinel column, an empty span and
    a single-sample row.  The kernel only selects samples, so values
    are bit-identical in both modes."""
    import jax.numpy as jnp
    from repro.kernels.grid_resample.ops import GRID_ALIGN, grid_resample
    rows = _synthetic_rows(k=13, s=300, seed=4)
    t, v = rows.times.copy(), rows.values.copy()
    t[0, 0] = -np.inf                              # streaming sentinel
    t[3, 40:46] = t[3, 40]                         # duplicate publications
    n = rows.n.reshape(-1, 1).copy()
    first = rows.first.reshape(-1, 1).copy()
    n[-1] = 1                                      # one sample
    first[-2] = n[-2]                              # empty span
    grid = np.arange(-0.01, 0.4, 7e-4, dtype=np.float32)
    pad = (-len(grid)) % GRID_ALIGN
    grid = np.concatenate([grid, np.full(pad, grid[-1], np.float32)])
    d = np.random.default_rng(6).uniform(
        -0.01, 0.01, (13, 1)).astype(np.float32)
    args = [jnp.asarray(a) for a in (t, v, n, first, grid[:, None], d)]
    vk, mk = grid_resample(*args, mode=mode, interpret=True,
                           use_kernel=True)
    # the oracle jitted as the kernel is: XLA fuses both the same way
    vr, mr = grid_resample(*args, mode=mode, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))


def test_regrid_hold_matches_powerseries_resample():
    """The hold convention is PowerSeries.resample, row-batched."""
    rows = _synthetic_rows(k=4, s=150, seed=3)
    grid = make_grid(0.0, 0.25, 7e-4)
    vk, mk = regrid_rows(rows, grid)
    vk, mk = np.asarray(vk), np.asarray(mk)
    for i in range(4):
        f, n = rows.first[i], rows.n[i]
        t = rows.times[i, f:n].astype(np.float64)
        v = rows.values[i, f:n].astype(np.float64)
        w = PowerSeries(t, v).resample(grid).watts
        m = (grid >= t[0]) & (grid <= t[-1])
        assert (mk[i] == m).all()
        np.testing.assert_allclose(vk[i][m], w[m], rtol=1e-6)


def test_regrid_delay_shift_equivariance():
    """regrid(grid, delay=d) == regrid(grid + d, delay=0) per row."""
    rows = _synthetic_rows(k=8, s=120, seed=5)
    d = 0.0125
    grid = make_grid(0.05, 0.15, 1e-3)
    va, ma = regrid_rows(rows, grid, delays=np.full(8, d))
    vb, mb = regrid_rows(rows, grid + d)
    np.testing.assert_allclose(np.asarray(va), np.asarray(vb),
                               rtol=1e-6, atol=1e-5)
    assert (np.asarray(ma) == np.asarray(mb)).all()


# ------------------------------------------------------- xcorr parity

def test_xcorr_kernel_matches_float64_host():
    rng = np.random.default_rng(2)
    g, k, max_lag = 1024, 8, 64
    ref = np.where((np.arange(g) // 100) % 2 == 0, 55.0, 215.0)
    x = np.zeros((k, g), np.float32)
    m = np.ones((k, g), bool)
    for i in range(k):
        shift = int(rng.integers(-40, 40))
        x[i] = np.roll(ref, shift) + rng.normal(0, 2.0, g)
        m[i, : int(rng.integers(0, 30))] = False
    import jax.numpy as jnp
    est = estimate_delays(jnp.asarray(x), jnp.asarray(m), ref,
                          step=1.0, max_lag=max_lag)
    est_h = estimate_delays_host(x, m, ref, step=1.0, max_lag=max_lag)
    np.testing.assert_allclose(est.peak_corr, est_h.peak_corr,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(est.delay_s, est_h.delay_s, atol=1e-3)


# ------------------------------------------- delay recovery (ground truth)

def test_delay_recovery_within_half_update_interval():
    """Blind xcorr estimates recover SensorSpec.delay_s within 0.5x the
    sensor update interval, across a 1 ms on-chip counter and a 100 ms
    PM counter (the paper's §V-A square-wave procedure)."""
    truth = square_wave(1.0, 3, lead_s=0.5, tail_s=0.5)
    tool = ToolSpec(1e-3)
    specs = [
        dataclasses.replace(chip_energy_sensor(0), delay_s=0.0374),
        dataclasses.replace(pm_energy_sensor(0, False), delay_s=0.0612),
    ]
    traces = [simulate_sensor(sp, tool, truth, seed=7 + i)
              for i, sp in enumerate(specs)]
    rows = series_rows_from_traces(traces)
    grid, step = default_grid(rows)
    vals, mask = regrid_rows(rows, grid)
    ref = schedule_reference(truth, grid)
    est = estimate_delays(vals, mask, ref, step=step,
                          max_lag=min(512, int(0.2 / step)))
    for i, sp in enumerate(specs):
        tol = 0.5 * max(sp.production_interval_s, sp.driver_refresh_s)
        err = abs(est.delay_s[i] - sp.delay_s)
        assert err <= tol, (sp.name, est.delay_s[i], sp.delay_s, tol)
        assert est.peak_corr[i] > 0.8, sp.name


def test_filtered_sensor_detects_total_lag():
    """An IIR-filtered power sensor's detected lag includes its filter
    group delay on TOP of delay_s — the total shift alignment must
    correct by (never less than the configured latency)."""
    truth = square_wave(1.0, 3, lead_s=0.5, tail_s=0.5)
    spec = dataclasses.replace(chip_power_inst_sensor(0), delay_s=0.0212)
    tr = simulate_sensor(spec, ToolSpec(1e-3), truth, seed=11)
    rows = series_rows_from_traces([tr])
    grid, step = default_grid(rows)
    vals, mask = regrid_rows(rows, grid)
    est = estimate_delays(vals, mask, schedule_reference(truth, grid),
                          step=step, max_lag=min(512, int(0.3 / step)))
    tau = spec.filter_window_s
    assert spec.delay_s < est.delay_s[0] < spec.delay_s + 3.0 * tau


def test_zero_delay_spec_is_default():
    """delay_s defaults to 0 and the simulator path is unchanged."""
    truth = square_wave(1.0, 2, lead_s=0.3, tail_s=0.3)
    a = simulate_sensor(chip_energy_sensor(0), ToolSpec(1e-3), truth,
                        seed=3)
    b = simulate_sensor(dataclasses.replace(chip_energy_sensor(0),
                                            delay_s=0.0),
                        ToolSpec(1e-3), truth, seed=3)
    np.testing.assert_array_equal(a.value, b.value)
    np.testing.assert_array_equal(a.t_measured, b.t_measured)


# ------------------------------------------------------- fusion

def _node_groups(n_groups=2, seed=0, cycles=3):
    """Simulated node fabric + the paper's App-B calibration set (PM
    upstream slope and NIC-rail offsets must come out BEFORE fusing, or
    the off-chip streams pull the fused estimate ~7-10% high)."""
    from repro.core.calibration import nic_rail_corrections
    truth = square_wave(1.0, cycles, lead_s=0.5, tail_s=0.5)
    fabric = NodeFabric(chip_truths=[truth] * 4)
    traces = fabric.sample_all(ToolSpec(), seed=seed)
    groups = list(group_traces_by_device(traces).values())[:n_groups]
    return truth, traces, groups, nic_rail_corrections()


def test_fuse_kernel_path_matches_float64_mirror():
    """Given identical delays, the whole batched regrid+fuse path stays
    ≤1e-5 of the float64 padded-semantics mirror."""
    import jax.numpy as jnp
    truth, traces, groups, corr = _node_groups()
    fused = align_and_fuse(groups, reference=truth, corrections=corr)
    grid = fused[0].grid
    flat = [tr for g in groups for tr in g]
    rows = series_rows_from_traces(flat, corrections=corr)
    d_all = np.concatenate([fs.delays for fs in fused])
    vk, mk = regrid_rows(rows, grid, delays=d_all)
    vh, mh = regrid_rows_host(rows, grid, delays=d_all)
    assert (np.asarray(mk) == mh).all()
    rel = np.abs(np.asarray(vk, np.float64) - vh) \
        / np.maximum(np.abs(vh), 1.0)
    assert rel.max() <= 1e-5, rel.max()
    k = len(groups[0])
    sv = np.stack([np.asarray(vk)[i * k:(i + 1) * k]
                   for i in range(len(groups))])
    sm = np.stack([np.asarray(mk)[i * k:(i + 1) * k]
                   for i in range(len(groups))])
    fd = np.asarray(fuse_gridded(jnp.asarray(sv), jnp.asarray(sm))[0])
    fh = fuse_gridded_host(vh.reshape(sv.shape), sm)[0]
    rel_f = np.abs(fd - fh) / np.maximum(np.abs(fh), 1.0)
    assert rel_f.max() <= 1e-5, rel_f.max()


def test_fused_matches_per_trace_host_loop():
    """Independent per-trace numpy pipeline (np.correlate + resample
    loops) agrees with the batched kernels: same delays to sub-ms, same
    integrated energy to 1e-3."""
    truth, traces, groups, corr = _node_groups()
    fused = align_and_fuse(groups, reference=truth, corrections=corr)
    grid = fused[0].grid
    f_host, d_host, m_host = align_fuse_host(groups, grid,
                                             reference=truth, max_lag=512,
                                             corrections=corr)
    for di, fs in enumerate(fused):
        assert np.abs(fs.delays
                      - d_host[di, :len(fs.delays)]).max() < 1e-3
        m = fs.mask & m_host[di]
        dt = np.diff(grid).mean()
        e_dev = float((fs.watts[m] * dt).sum())
        e_h = float((f_host[di][m] * dt).sum())
        assert abs(e_dev - e_h) <= 1e-3 * max(abs(e_h), 1.0)


def test_fusion_energy_conservation():
    """Fused phase energies telescope (partition sums == full span) and
    the full-span fused energy matches the counter's ΔE."""
    truth, traces, groups, corr = _node_groups(n_groups=1)
    fs = align_and_fuse(groups, reference=truth, corrections=corr)[0]
    t0, t1 = float(fs.grid[0]), float(fs.grid[-1])
    edges = np.linspace(t0, t1, 6)
    phases = [(f"p{k}", float(a), float(b))
              for k, (a, b) in enumerate(zip(edges[:-1], edges[1:]))]
    rows = attribute_energy_fused(groups, phases, reference=truth,
                                  corrections=corr)
    total_parts = sum(p.energy_j for p in rows[0])
    e_series = fs.series.energy_between(t0, t1)
    assert abs(total_parts - e_series) <= 2e-3 * abs(e_series)
    sh = delta_e_over_delta_t(traces["chip0_energy"])
    e_counter = sh.energy_between(t0, t1)
    assert abs(e_series - e_counter) <= 0.02 * abs(e_counter)


def test_validate_streams_report():
    truth, traces, groups, corr = _node_groups(n_groups=1)
    rep = validate_streams(groups, reference=truth, corrections=corr)
    dev = rep["devices"][0]
    assert set(dev["streams"]) == {tr.name for tr in groups[0]}
    for name, st in dev["streams"].items():
        assert {"bias_w", "rms_w", "delay_s", "peak_corr",
                "weight"} <= set(st)
        assert st["peak_corr"] > 0.3, name
    w = sum(st["weight"] for st in dev["streams"].values())
    assert abs(w - 1.0) < 1e-3
    assert np.isfinite(dev["mean_disagreement_w"])
    # the unfiltered on-chip counter must be among the least-biased
    assert abs(dev["streams"]["chip0_energy"]["bias_w"]) < 2.0


def test_group_traces_by_device():
    _, traces, _, _ = _node_groups()
    groups = group_traces_by_device(traces)
    assert set(groups) == {f"device{i}" for i in range(4)}
    for trs in groups.values():
        assert trs[0].spec.is_cumulative          # counter leads (ref)
        assert len(trs) == 5
    with_node = group_traces_by_device(traces, include_node=True)
    assert "node" in with_node


def test_attribute_energy_fused_vs_truth():
    truth, traces, groups, corr = _node_groups(n_groups=2)
    phases = [("a", 0.6, 1.1), ("b", 1.3, 2.4)]
    rows = attribute_energy_fused(groups, phases, reference=truth,
                                  corrections=corr)
    assert len(rows) == 2 and len(rows[0]) == 2
    for p in rows[0]:
        et = truth.energy_between(p.t_start, p.t_end)
        assert abs(p.energy_j - et) <= 0.06 * abs(et), (p.phase, et)


def test_fleet_api_reexport():
    from repro.fleet import attribute_energy_fused as via_fleet
    truth, traces, groups, corr = _node_groups(n_groups=1, cycles=2)
    phases = [("a", 0.6, 1.2)]
    a = via_fleet(groups, phases, reference=truth, corrections=corr)
    b = attribute_energy_fused(groups, phases, reference=truth,
                               corrections=corr)
    assert abs(a[0][0].energy_j - b[0][0].energy_j) < 1e-9


def test_fused_hpl_energize_close_to_counter_path():
    """Phases must outlast the on-chip IIR sensor's settling (~3 tau =
    0.5 s) for the fused mix to track the counter; shorter phases
    distort through the filter — the paper's short-phase point."""
    import time
    from repro.core.tracing import RegionTracer
    from repro.hpl.energy import fleet_energize, fused_fleet_energize
    tracer = RegionTracer()
    with tracer.region("hpl_factorize"):
        time.sleep(0.55)
    with tracer.region("hpl_solve"):
        time.sleep(0.5)
    fused = fused_fleet_energize(tracer, 2)
    counter = fleet_energize(tracer, 2)
    for rf, rc in zip(fused, counter):
        for pf, pc in zip(rf, rc):
            assert pf.phase == pc.phase
            assert abs(pf.energy_j - pc.energy_j) \
                <= 0.10 * max(abs(pc.energy_j), 1.0), pf.phase


# ------------------------------------------------- hypothesis property

def test_regrid_monotonic_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @st.composite
    def row(draw):
        n = draw(st.integers(3, 40))
        steps = draw(st.lists(st.floats(1e-4, 0.1), min_size=n,
                              max_size=n))
        vals = draw(st.lists(st.floats(0.0, 500.0), min_size=n,
                             max_size=n))
        return np.cumsum(steps), np.asarray(vals)

    @given(row(), st.integers(5, 60), st.floats(-0.05, 0.05))
    @settings(max_examples=25, deadline=None)
    def inner(tv, g_n, delay):
        t, v = tv
        s = len(t)
        rows = SeriesRows(t[None].astype(np.float32),
                          v[None].astype(np.float32),
                          np.asarray([s], np.int32),
                          np.asarray([0], np.int32), ["r"], 1, t0=0.0)
        grid = np.linspace(t[0] - 0.1, t[-1] + 0.1, g_n)
        vk, mk = regrid_rows(rows, grid, delays=np.asarray([delay]))
        vk, mk = np.asarray(vk)[0], np.asarray(mk)[0]
        ge = grid.astype(np.float32) + np.float32(delay)
        # mask is exactly the in-span predicate on the shifted query
        t32 = t.astype(np.float32)
        expect_m = (ge >= t32[0]) & (ge <= t32[-1])
        assert (mk == expect_m).all()
        # hold output only ever takes values from the input row
        assert np.isin(vk[mk], v.astype(np.float32)).all()
        # ... and agrees with the float64 mirror everywhere
        vh, mh = regrid_rows_host(rows, grid,
                                  delays=np.asarray([delay]))
        assert (mh[0] == mk).all()
        np.testing.assert_allclose(vk[mk], vh[0][mk], rtol=1e-6)

    inner()
