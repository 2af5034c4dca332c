"""Typed-config streaming API: legacy flat kwargs resolve to the same
PipelineConfig as ``config=`` (bit-identical results) while warning,
unknown/mixed keywords fail fast, and every entry point forwards."""
import warnings

import numpy as np
import pytest

from repro.core import ToolSpec, simulate_sensor, square_wave
from repro.core.measurement_model import SensorSpec
from repro.fleet import (CheckpointConfig, PipelineConfig, StreamConfig,
                         TrackConfig, attribute_energy_fused,
                         attribute_energy_fused_streaming,
                         resolve_config)


def _truth(span_s=3.0):
    return square_wave(span_s / 4.0, 3, lead_s=span_s / 8,
                       tail_s=span_s / 8)


def _sim_groups(n_devices=2, seed=0, span_s=3.0):
    truth = _truth(span_s)
    tool = ToolSpec(0.9e-3)
    groups = []
    for d in range(n_devices):
        specs = [
            SensorSpec(name=f"d{d}_energy", scope="chip",
                       kind="energy_cum", quantum=1e-6, wrap_bits=26,
                       delay_s=0.004 * (d % 5)),
            SensorSpec(name=f"d{d}_power", scope="chip",
                       kind="power_inst", noise_w=3.0, quantum=1e-6,
                       delay_s=0.011 + 0.003 * (d % 3)),
        ]
        groups.append([simulate_sensor(sp, tool, truth,
                                       seed=seed + 31 * d + i)
                       for i, sp in enumerate(specs)])
    return groups


@pytest.fixture(scope="module")
def setup():
    from repro.align import align_and_fuse
    groups = _sim_groups()
    fused = align_and_fuse(groups)
    grid = fused[0].grid
    d_all = np.concatenate([fs.delays for fs in fused])
    edges = np.linspace(float(grid[0]), float(grid[-1]), 5)
    phases = [(f"p{k}", float(a), float(b))
              for k, (a, b) in enumerate(zip(edges[:-1], edges[1:]))]
    return groups, grid, d_all, phases


# ------------------------------------------------ resolve_config unit

def test_resolve_config_defaults_and_section_wrap():
    assert resolve_config(None, {}, "f") == PipelineConfig()
    cfg = resolve_config(StreamConfig(chunk=7), {}, "f")
    assert cfg == PipelineConfig(stream=StreamConfig(chunk=7))
    cfg = resolve_config(TrackConfig(window=9), {}, "f")
    assert cfg.track.window == 9
    cfg = resolve_config(CheckpointConfig(every=3), {}, "f")
    assert cfg.checkpoint.every == 3
    with pytest.raises(TypeError):
        resolve_config("not-a-config", {}, "f")


def test_legacy_kwargs_fold_onto_the_right_fields():
    with pytest.warns(DeprecationWarning) as rec:
        cfg = resolve_config(None, {"chunk": 7, "window": 9,
                                    "checkpoint_dir": "/x",
                                    "health": True, "dq_policy": "p"},
                             "f")
    assert cfg.stream.chunk == 7
    assert cfg.track.window == 9
    assert cfg.checkpoint.dir == "/x"
    assert cfg.health is True and cfg.dq == "p"
    msg = str(rec[0].message)
    assert "PipelineConfig.stream.chunk" in msg
    assert "PipelineConfig.checkpoint.dir" in msg


def test_unknown_legacy_kwarg_is_a_typeerror():
    with pytest.raises(TypeError, match="bogus"):
        resolve_config(None, {"bogus": 1}, "f")


@pytest.mark.parametrize("name", ["engine", "host"])
def test_retired_stream_kwargs_are_typeerrors(name):
    """``engine`` and ``host`` are no longer StreamConfig fields: the
    flat kwargs fail like any unknown keyword."""
    with pytest.raises(TypeError, match=name):
        resolve_config(None, {name: True}, "f")
    with pytest.raises(TypeError):
        StreamConfig(**{name: True})


def test_mixing_config_and_legacy_is_a_typeerror():
    with pytest.raises(TypeError, match="both config="):
        resolve_config(PipelineConfig(), {"chunk": 8}, "f")


# ------------------------------------------------ entry-point behaviour

def test_streaming_unknown_kwarg_typeerror(setup):
    groups, grid, d_all, phases = setup
    with pytest.raises(TypeError, match="bogus"):
        attribute_energy_fused_streaming(groups, phases, bogus=1)


def test_streaming_mix_typeerror(setup):
    groups, grid, d_all, phases = setup
    with pytest.raises(TypeError, match="both config="):
        attribute_energy_fused_streaming(
            groups, phases, config=PipelineConfig(), chunk=64)


def test_batch_api_rejects_config(setup):
    groups, grid, d_all, phases = setup
    with pytest.raises(TypeError, match="streaming=True"):
        attribute_energy_fused(groups, phases,
                               config=PipelineConfig())


@pytest.mark.parametrize("tracked", [
    pytest.param(False, id="pinned-delays-untracked"),
    pytest.param(True, id="tracked-reference"),
])
def test_legacy_and_config_calls_bit_identical(setup, tracked):
    """The acceptance bar: a legacy-kwarg call and the equivalent
    ``config=`` call produce bit-identical energies (both resolve to
    the same PipelineConfig), and only the legacy one warns."""
    groups, grid, d_all, phases = setup
    if tracked:
        flat = dict(track=True, window=512, hop=128)
        ref = _truth()
    else:
        flat = dict(delays=d_all)
        ref = None
    with pytest.warns(DeprecationWarning, match="chunk"):
        legacy = attribute_energy_fused_streaming(
            groups, phases, grid=grid, chunk=257, reference=ref, **flat)
    cfg = PipelineConfig(
        stream=StreamConfig(grid=grid, chunk=257),
        track=TrackConfig(**flat))
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        modern = attribute_energy_fused_streaming(
            groups, phases, config=cfg, reference=ref)
    for rl, rm in zip(legacy, modern):
        for pl, pm in zip(rl, rm):
            assert pl.phase == pm.phase
            assert pl.energy_j == pm.energy_j      # bit-identical


def test_api_entry_forwards_config(setup):
    groups, grid, d_all, phases = setup
    cfg = PipelineConfig(
        stream=StreamConfig(grid=grid, chunk=257),
        track=TrackConfig(delays=d_all))
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        via_api = attribute_energy_fused(groups, phases,
                                         streaming=True, config=cfg)
        direct = attribute_energy_fused_streaming(groups, phases,
                                                  config=cfg)
    for ra, rd in zip(via_api, direct):
        for pa, pd in zip(ra, rd):
            assert pa.energy_j == pd.energy_j


def test_hpl_energize_legacy_and_config_identical():
    import time
    from repro.core.tracing import RegionTracer
    from repro.hpl.energy import fused_fleet_energize
    tracer = RegionTracer()
    with tracer.region("hpl_factorize"):
        time.sleep(0.3)
    with tracer.region("hpl_solve"):
        time.sleep(0.25)
    with pytest.warns(DeprecationWarning, match="chunk"):
        legacy = fused_fleet_energize(tracer, 1, streaming=True,
                                      chunk=512)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        modern = fused_fleet_energize(
            tracer, 1, streaming=True,
            config=PipelineConfig(stream=StreamConfig(chunk=512)))
    for rl, rm in zip(legacy, modern):
        for pl, pm in zip(rl, rm):
            assert pl.phase == pm.phase
            assert pl.energy_j == pm.energy_j
