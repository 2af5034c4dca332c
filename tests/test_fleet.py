"""Fleet pipeline: packing round-trips, masked-sample correctness, and
fleet-vs-host parity on 1, 3 and 17 heterogeneous traces."""
import numpy as np
import pytest

from repro.core import (ToolSpec, attribute_energy, attribute_energy_many,
                        delta_e_over_delta_t, simulate_sensor, square_wave)
from repro.core.measurement_model import (SensorSpec, chip_energy_sensor,
                                          pm_energy_sensor)
from repro.core.sensors import SensorTrace
from repro.fleet import (FleetStream, attribute_energy_fleet,
                         fleet_power_series, fleet_reconstruct,
                         fleet_reconstruct_host, pack_traces, unpack_series)
from repro.fleet.packing import ROW_ALIGN


def _sim_traces(n, seed=0):
    """n heterogeneous cumulative traces (mixed cadence, wrap, length)."""
    truth = square_wave(1.0, 2, lead_s=0.5, tail_s=0.5)
    tool = ToolSpec(1e-3)
    out = []
    for i in range(n):
        spec = (chip_energy_sensor(i) if i % 3 != 2
                else pm_energy_sensor(i, i % 2 == 0))
        out.append(simulate_sensor(spec, tool, truth, seed=seed + i))
    return out


def _synthetic_trace(name="t0", k=257, seed=0, wrap_bits=0, reorder_at=None):
    rng = np.random.default_rng(seed)
    dt = rng.uniform(0.5e-3, 2e-3, k)
    t = np.cumsum(dt)
    p = rng.uniform(40.0, 260.0, k)
    e = np.cumsum(p * dt)
    spec = SensorSpec(name=name, scope="chip", kind="energy_cum",
                      quantum=1e-6, wrap_bits=wrap_bits)
    if wrap_bits:
        e = np.mod(e, (2.0 ** wrap_bits) * spec.quantum)
    if reorder_at is not None:
        t[reorder_at] = t[reorder_at - 2]          # jitter reordering
    return SensorTrace(name, spec, t + 1e-4, t, e)


# ------------------------------------------------------------------ packing

@pytest.mark.parametrize("n", [1, 3, 17])
def test_pack_shapes_and_alignment(n):
    packed = pack_traces(_sim_traces(n))
    f, s = packed.shape
    assert f % ROW_ALIGN == 0 and f >= n
    assert packed.n_traces == n
    assert len(packed.names) == n
    # validity is a per-row prefix matching the raw lengths
    for i in range(n):
        k = packed.n_samples[i]
        assert packed.valid[i, :k].all() and not packed.valid[i, k:].any()
    # padding rows are fully masked
    assert not packed.valid[n:].any()


def test_pack_tail_replicates_last_sample():
    traces = _sim_traces(3)
    packed = pack_traces(traces)
    i = int(np.argmin(packed.n_samples[:3]))
    k = packed.n_samples[i]
    if k < packed.shape[1]:
        assert (packed.times[i, k:] == packed.times[i, k - 1]).all()
        assert (packed.energy[i, k:] == packed.energy[i, k - 1]).all()


def test_pack_buffer_reuse():
    traces = _sim_traces(4)
    a = pack_traces(traces)
    b = pack_traces(traces, out=a)
    assert b.energy is a.energy and b.times is a.times
    c = pack_traces(traces)
    np.testing.assert_array_equal(b.energy, c.energy)
    np.testing.assert_array_equal(b.times, c.times)


# -------------------------------------------------- reconstruction parity

@pytest.mark.parametrize("n", [1, 3, 17])
def test_fleet_matches_per_trace_host(n):
    """Batched fleet reconstruction == per-trace numpy loop (the oracle)."""
    traces = _sim_traces(n)
    series = fleet_power_series(traces)
    assert len(series) == n
    for tr, sf in zip(traces, series):
        sh = delta_e_over_delta_t(tr)
        assert len(sf.t) == len(sh.t)
        np.testing.assert_allclose(sf.t, sh.t, atol=2e-6)
        # float32 packing quantizes timestamps -> bounded dt error
        np.testing.assert_allclose(sf.watts, sh.watts, rtol=2e-2)


@pytest.mark.parametrize("wrap_bits", [0, 24])
def test_fleet_matches_float64_fleet_oracle(wrap_bits):
    """Device pipeline vs the float64 host mirror on identical inputs:
    the reassociated wrap fix keeps float32 ΔE exact (≤1e-5 criterion)."""
    traces = [_synthetic_trace(f"s{i}", k=200 + 17 * i, seed=i,
                               wrap_bits=wrap_bits) for i in range(5)]
    packed = pack_traces(traces)
    power, times, valid = fleet_reconstruct(packed)
    ph, th, vh = fleet_reconstruct_host(packed)
    pj, vj = np.asarray(power), np.asarray(valid)
    assert (vj == vh).all()
    rel = np.abs(pj[vj] - ph[vh]) / np.maximum(np.abs(ph[vh]), 1.0)
    assert rel.max() <= 1e-5
    if wrap_bits:
        # the raw counters wrapped; pack unwrapped them in float64
        assert any((np.diff(tr.value) < 0).any() for tr in traces)
        assert (np.diff(packed.energy[0][packed.valid[0]]) >= 0).all()


def test_long_running_counter_keeps_precision():
    """A counter with a large absolute baseline and late timestamps (a
    sensor that has been up for hours) must survive float32 packing:
    ingest unwraps + rebases in float64 so only ΔE/Δt reach float32."""
    rng = np.random.default_rng(42)
    k = 400
    dt = rng.uniform(0.8e-3, 1.6e-3, k)
    t = 2.0e4 + np.cumsum(dt)                   # ~5.5 h uptime
    p = rng.uniform(400.0, 600.0, k)
    spec = SensorSpec(name="old", scope="chip", kind="energy_cum",
                      quantum=1e-6, wrap_bits=44)   # period ~1.76e7 J
    period = (2.0 ** 44) * spec.quantum
    e = np.mod(1.0e7 + np.cumsum(p * dt), period)   # huge baseline
    tr = SensorTrace("old", spec, t + 1e-4, t, e)
    sf = fleet_power_series([tr])[0]
    sh = delta_e_over_delta_t(tr)
    assert len(sf.t) == len(sh.t), "float32 time rounding dropped samples"
    np.testing.assert_allclose(sf.watts, sh.watts, rtol=2e-3)
    np.testing.assert_allclose(sf.t, sh.t, atol=5e-6)
    # attribution parity at the same scale
    phases = [("w", float(t[0]), float(t[-1]))]
    f = attribute_energy_fleet([tr], phases)[0][0].energy_j
    h = attribute_energy(tr, phases)[0].energy_j
    assert abs(f - h) / abs(h) < 1e-3


def test_fleet_kernel_matches_ref():
    traces = _sim_traces(6)
    packed = pack_traces(traces)
    pk, tk, vk = fleet_reconstruct(packed, use_kernel=True)
    pr, tr_, vr = fleet_reconstruct(packed, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pr),
                               rtol=1e-5, atol=1e-4)


def test_fleet_kernel_flags_reordered_rows():
    """The fused front-end kernel (interpret mode) against its oracle on
    rows whose timestamps go backwards: identical valid masks, identical
    ``reordered`` flags (only the reordered rows), identical power."""
    import jax.numpy as jnp
    from repro.kernels.power_reconstruct.kernel import \
        power_reconstruct_fleet_kernel
    from repro.kernels.power_reconstruct.ref import \
        reconstruct_power_fleet_ref
    traces = [_synthetic_trace(f"t{i}", k=150 + 7 * i, seed=20 + i,
                               wrap_bits=24 if i % 2 else 0,
                               reorder_at=60 + i if i in (1, 4) else None)
              for i in range(6)]
    packed = pack_traces(traces)
    args = (jnp.asarray(packed.energy), jnp.asarray(packed.times),
            jnp.asarray(packed.wrap_period)[:, None],
            jnp.asarray(packed.n_samples)[:, None])
    pk, vk, rk = power_reconstruct_fleet_kernel(*args, interpret=True)
    pr, vr, rr = reconstruct_power_fleet_ref(*args)
    assert vk.dtype == rk.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))
    assert np.flatnonzero(np.asarray(rk)[:, 0]).tolist() == [1, 4]
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))


def test_duplicate_reads_are_masked_not_zero_power():
    """Cached publications must be dropped (masked), not read as 0 W."""
    tr = _synthetic_trace(k=100, seed=3)
    dup = np.repeat(np.arange(100), 2)[:150]        # every read twice
    tr = SensorTrace(tr.name, tr.spec, tr.t_read[dup], tr.t_measured[dup],
                     tr.value[dup])
    packed = pack_traces([tr])
    power, times, valid = fleet_reconstruct(packed)
    sh = delta_e_over_delta_t(tr)
    sf = unpack_series(packed, power, times, valid)[0]
    assert len(sf.t) == len(sh.t)
    assert (sf.watts > 0).all()                     # no spurious zeros
    np.testing.assert_allclose(sf.watts, sh.watts, rtol=2e-2)


def test_reordered_timestamps_fallback():
    """A backwards t_measured routes through the carry-forward path and
    still matches the per-trace host semantics."""
    tr = _synthetic_trace(k=120, seed=5, reorder_at=60)
    assert (np.diff(tr.t_measured) < 0).any()
    packed = pack_traces([tr])
    sf = unpack_series(packed, *fleet_reconstruct(packed))[0]
    sh = delta_e_over_delta_t(tr)
    assert len(sf.t) == len(sh.t)
    np.testing.assert_allclose(sf.watts, sh.watts, rtol=2e-2)


# ------------------------------------------------------ streaming/attr

def test_streaming_chunks_match_one_shot_and_host():
    traces = _sim_traces(3)
    phases = [("a", 0.6, 1.2), ("b", 1.2, 2.1), ("c", 2.3, 3.4)]
    one = attribute_energy_fleet(traces, phases, chunk=10 ** 9)
    small = attribute_energy_fleet(traces, phases, chunk=137)
    for tr, row1, row2 in zip(traces, one, small):
        host = attribute_energy(tr, phases)
        for h, f1, f2 in zip(host, row1, row2):
            assert abs(f1.energy_j - f2.energy_j) \
                <= 1e-3 * max(abs(h.energy_j), 1.0), "chunking changed sums"
            assert abs(f1.energy_j - h.energy_j) \
                <= 1e-3 * max(abs(h.energy_j), 1.0), "fleet != host"


def test_streaming_energy_conservation():
    """Σ phase energies over a partition == total ΔE (telescoping)."""
    tr = _synthetic_trace(k=500, seed=9, wrap_bits=24)
    packed = pack_traces([tr])
    t0, t1 = float(tr.t_measured[0]), float(tr.t_measured[-1])
    edges = np.linspace(t0, t1, 7) - packed.t0   # stream uses rebased time
    stream = FleetStream(list(zip(edges[:-1], edges[1:])), packed.shape[0],
                         wrap_period=packed.wrap_period)
    for lo in range(0, packed.shape[1], 100):
        stream.update(packed.times[:, lo:lo + 100],
                      packed.energy[:, lo:lo + 100])
    total = stream.totals()[0].sum()
    sh = delta_e_over_delta_t(tr)
    expect = sh.energy_between(t0, t1)
    assert abs(total - expect) <= 2e-3 * abs(expect)


def test_streaming_valid_mask_zeroes_energy():
    """Samples masked invalid must contribute no energy."""
    tr = _synthetic_trace(k=300, seed=11)
    packed = pack_traces([tr])
    phases = [(float(tr.t_measured[0]) - packed.t0,
               float(tr.t_measured[-1]) - packed.t0)]
    full = FleetStream(phases, packed.shape[0],
                       wrap_period=packed.wrap_period)
    full.update(packed.times, packed.energy)
    masked = FleetStream(phases, packed.shape[0],
                         wrap_period=packed.wrap_period)
    valid = packed.valid.copy()
    valid[:, 150:] = False                          # drop the second half
    masked.update(packed.times, packed.energy, valid=valid)
    e_full = full.totals()[0, 0]
    e_masked = masked.totals()[0, 0]
    sh = delta_e_over_delta_t(tr)
    e_head = sh.energy_between(float(tr.t_measured[0]),
                               float(tr.t_measured[149]))
    assert e_masked < e_full
    assert abs(e_masked - e_head) <= 2e-3 * abs(e_head) + 0.5


def test_streaming_reordered_timestamps_conserve_energy():
    """A jitter-reordered read must not lose its ΔE in the streamed path
    (chunk sanitization bridges it with a zero-width carry-forward)."""
    tr = _synthetic_trace(k=120, seed=5, reorder_at=60)
    assert (np.diff(tr.t_measured) < 0).any()
    phases = [("w", float(tr.t_measured[0]), float(np.max(tr.t_measured)))]
    for chunk in (10 ** 9, 59):          # one-shot and boundary-straddling
        fleet = attribute_energy_fleet([tr], phases, chunk=chunk)
        host = attribute_energy(tr, phases)
        rel = abs(fleet[0][0].energy_j - host[0].energy_j) \
            / max(abs(host[0].energy_j), 1e-9)
        assert rel < 1e-3, (chunk, rel)


def test_power_accumulator_invalid_first_slot():
    """An invalid first sample must not seed the hold-interval carry
    (its garbage timestamp would inflate the first valid interval)."""
    from repro.fleet import StreamingPhaseAccumulator
    t = np.array([[0.0, 100.0, 100.1, 100.2, 100.3]], np.float32)
    w = np.array([[999.0, 50.0, 50.0, 50.0, 50.0]], np.float32)
    valid = np.array([[False, True, True, True, True]])
    acc = StreamingPhaseAccumulator([(0.0, 200.0)], 1)
    acc.update(t, w, valid=valid)
    e = float(acc.totals()[0, 0])
    assert abs(e - 50.0 * 0.3) < 1e-3, e   # not 50 W held over (0, 100]


def test_fleet_energize_matches_oracle_loop():
    """fleet_energize must reproduce [energize(seed=k) for k] exactly."""
    import time
    from repro.core.tracing import RegionTracer
    from repro.hpl.energy import energize, fleet_energize
    tracer = RegionTracer()
    with tracer.region("hpl_factorize"):
        time.sleep(0.05)
    rows = fleet_energize(tracer, 3)
    for k, row in enumerate(rows):
        host = energize(tracer, seed=k)
        for h, f in zip(host, row):
            assert abs(f.energy_j - h.energy_j) \
                <= 1e-3 * max(abs(h.energy_j), 1.0), (k, h.phase)


def test_attribute_energy_many_fleet_vs_host():
    traces = _sim_traces(5)
    phases = [("x", 0.7, 1.9), ("y", 2.0, 3.1)]
    fleet = attribute_energy_many(traces, phases, use_fleet=True)
    host = attribute_energy_many(traces, phases, use_fleet=False)
    for rf, rh in zip(fleet, host):
        for f, h in zip(rf, rh):
            assert f.phase == h.phase
            assert abs(f.energy_j - h.energy_j) \
                <= 1e-3 * max(abs(h.energy_j), 1.0)
