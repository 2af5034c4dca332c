"""Tests of the chip benchmark's own parts, on the CPU at small sizes.

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest chipbench/tests -q

They check the fleet generator against the repository's simulator, the
operation and byte counts, the trace reduction (on a trace recorded on a
TPU v5e), that every cell of BENCHMARK.json resolves to its files, that
the command refuses a machine without a TPU, and that ``correct`` comes
out false when the timed path is broken underneath a run or when the
lower-precision control takes its place.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import bench  # noqa: E402
import fleetgen  # noqa: E402
import flops  # noqa: E402
import trace_reduce  # noqa: E402

FIXTURE = BENCH / "fixtures" / "v5e_trace.xplane.pb"
SEED = 2 ** 31 + 12345          # past 32 signed bits, as the driver's are


# ---------------------------------------------------------------------------
# generator, counts, reduction, layout
# ---------------------------------------------------------------------------

def test_fleet_generator_matches_node_fabric():
    from repro.core import NodeFabric, ToolSpec, square_wave
    cfg = json.loads((BENCH / "configs" / "frontier-512.json").read_text())
    mine = fleetgen.sample_nodes(cfg, SEED, [0, 1])
    w = cfg["workload"]
    truth = square_wave(w["period_s"], 2, lead_s=w["edge_s"],
                        tail_s=w["edge_s"])
    ref = []
    for node in (0, 1):
        ref += list(NodeFabric(chip_truths=[truth] * 4, node_id=node)
                    .sample_all(ToolSpec(), seed=SEED).values())
    assert [t.name for t in mine] == [t.spec.name for t in ref]
    for a, b in zip(mine, ref):
        assert np.array_equal(a.t_read, b.t_read), a.name
        assert np.array_equal(a.t_measured, b.t_measured), a.name
        assert np.array_equal(a.value, b.value), a.name


def test_counts_on_hand_computed_shapes():
    cfg = {"hidden_size": 8, "num_hidden_layers": 2,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "intermediate_size": 16, "vocab_size": 10}
    # per layer: q 64 + k,v 2*32 + o 64 + mlp 3*128 = 576 weights
    per_token = 2 * (2 * 576 + 80) + 4 * 2 * 2 * 4 * 5
    assert flops.decoder_token_flops(cfg, 5) == per_token


def _synthetic_events():
    ms = 1_000_000
    return {
        "devices": {"/device:TPU:0": [("a", 10 * ms, 20 * ms),
                                      ("b", 15 * ms, 30 * ms),
                                      ("a", 60 * ms, 70 * ms)]},
        "spans": [("window", 0, 100 * ms), ("job", 0, 50 * ms),
                  ("job", 50 * ms, 100 * ms)],
    }


def test_reduce_synthetic_trace():
    s = trace_reduce.reduce(_synthetic_events())
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.030)       # union, not the sum
    assert s["op_seconds"]["a"] == pytest.approx(0.020)
    assert s["breakdown"]["device_ops"][0] == ["a", pytest.approx(0.020)]
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps["job"] == pytest.approx(0.070)
    busy, length = trace_reduce.busy_in(_synthetic_events(),
                                        [(0, 25_000_000)])
    assert busy == pytest.approx(0.015) and length == pytest.approx(0.025)


def test_reduce_trace_recorded_on_the_chip():
    if not FIXTURE.exists():
        pytest.skip("no trace has been recorded on a TPU yet "
                    "(chipbench/tools/record_fixture.py)")
    ev = trace_reduce.load_events(FIXTURE)
    assert any(p.startswith("/device:TPU:") for p in ev["devices"])
    names = {n for n, _, _ in ev["spans"]}
    assert {"step0", "step1", "step2"} <= names
    ev["spans"].append(("window", min(s[1] for s in ev["spans"]),
                        max(s[2] for s in ev["spans"])))
    s = trace_reduce.reduce(ev, n_devices=1)
    assert 0 < s["busy_s"] < s["window_s"]


def test_every_cell_resolves_to_its_files():
    spec = bench.benchmark_spec()
    for w in spec["workloads"]:
        cell = bench.find_cell(w["name"], 1, 1.0, False)
        assert (BENCH / "drivers" / f"{cell.traffic['driver']}.py").exists()
        assert cell.reference() is not None
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in spec["per_layer"]:
        mod = bench.load_module(BENCH / "metrics" / f"{m['name']}.py",
                                "m_" + m["name"].replace(".", "_"))
        assert mod.read({}) is None        # nothing to read: left out
    for c in spec["configs"]:
        assert (ROOT / c["file"]).exists()


def test_unlisted_cell_resolves_by_name():
    cell = bench.find_cell("minicpm-2b.chat", 1, 1.0, False)
    assert (cell.config_name, cell.chips) == ("minicpm-2b", 1)
    assert cell.traffic["driver"] == "serve"
    with pytest.raises(SystemExit):
        bench.find_cell("minicpm-2b.nosuchmix", 1, 1.0, False)


def test_run_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"),
                        "--workload", "frontier-512.replay", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# ---------------------------------------------------------------------------
# broken timed paths and the control: correct must come out false
# ---------------------------------------------------------------------------

def _fleet_cell(name: str, nodes: int = 2):
    cell = bench.find_cell(name, SEED, 0.05, False)
    cell.config["nodes"] = nodes
    return cell


def _run_fleet(cell):
    drv = bench.load_module(BENCH / "drivers" / "fleet.py", "drv_fleet")
    import jax
    return drv.run(cell, jax.devices(), time.perf_counter())


FLEET_CELLS = ["frontier-512.replay", "frontier-512.counters"]


def _break(monkeypatch, name: str, alter):
    """Wrap the cell's program entry so its answers pass ``alter``."""
    import repro.fleet as fleet
    fn_name = ("attribute_energy_fused_streaming" if name.endswith("replay")
               else "attribute_energy_fleet")
    orig = getattr(fleet, fn_name)

    def broken(*a, **kw):
        out = orig(*a, **kw)
        rows, pipe = out if isinstance(out, tuple) else (out, None)
        rows = alter(rows)
        return (rows, pipe) if pipe is not None else rows

    monkeypatch.setattr(fleet, fn_name, broken)


def _scaled(rows, which, factor):
    out = []
    for i, row in enumerate(rows):
        if which(i, len(rows)):
            row = [dataclasses.replace(p, energy_j=p.energy_j * factor)
                   for p in row]
        out.append(row)
    return out


@pytest.mark.parametrize("name", FLEET_CELLS)
def test_fleet_cell_is_correct_unbroken(name):
    res = _run_fleet(_fleet_cell(name))
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("name", FLEET_CELLS)
@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out",
                                   "state_unchanged"])
def test_fleet_cell_catches_fault(monkeypatch, name, fault):
    alter = {
        # one device's answer altered where it is produced
        "answer_altered": lambda rows: _scaled(
            rows, lambda i, n: i == n // 2, 1.05),
        # half of the fleet's rows left out of the fold
        "half_left_out": lambda rows: _scaled(
            rows, lambda i, n: i % 2 == 1, 0.0),
        # the accumulators never move from their initial state
        "state_unchanged": lambda rows: _scaled(
            rows, lambda i, n: True, 0.0),
    }[fault]
    _break(monkeypatch, name, alter)
    res = _run_fleet(_fleet_cell(name))
    assert not res["correct"]


def test_fleet_bfloat16_control_fails_the_limit():
    """The reference computed in bfloat16 in the program's place reads far
    past the replay cell's limit (and the counter cell's)."""
    cell = _fleet_cell("frontier-512.replay")
    cfg = cell.config
    ref = cell.reference()
    traces = fleetgen.sample_nodes(cfg, SEED, range(2))
    groups = fleetgen.group_rows(traces, cfg, True)
    phases = fleetgen.phases(cfg)
    drv = bench.load_module(BENCH / "drivers" / "fleet.py", "drv_fleet")
    offsets, slopes = drv.node_corrections(cfg, traces)
    origin, step = ref.grid_of(traces)
    t_end = max(float(t.t_measured[-1]) for t in traces)
    worst = 0.0
    for g in groups:
        kw = dict(origin=origin, step=step, t_end=t_end, offsets=offsets,
                  slopes=slopes,
                  periods=[fleetgen.wrap_period(traces[i].spec) for i in g])
        members = [drv._Named(traces[i]) for i in g]
        hi = ref.fused_phase_energies(members, phases, **kw)
        lo = ref.fused_phase_energies(members, phases, low=True, **kw)
        worst = max(worst, float(np.max(np.abs(lo - hi)
                                        / np.maximum(np.abs(hi), 1.0))))
    assert worst > cell.traffic["limit_rel"]
    c = traces[0]
    assert c.spec["kind"] == "energy_cum"
    kw = dict(offsets=offsets, slopes=slopes,
              period=fleetgen.wrap_period(c.spec))
    hi = ref.counter_phase_energies(drv._Named(c), phases, **kw)
    lo = ref.counter_phase_energies(drv._Named(c), phases, low=True, **kw)
    limit = bench.find_cell("frontier-512.counters", 1, 1, False) \
        .traffic["limit_rel"]
    assert np.max(np.abs(lo - hi) / np.abs(hi)) > limit


# big enough that int8 weights move the argmax of some positions
SMALL = dict(num_hidden_layers=4, hidden_size=512, num_attention_heads=4,
             num_key_value_heads=4, intermediate_size=1024,
             vocab_size=16384)
# at this size and in float32 the program's mean gap reads 0 (its widest
# is rounding only), while the int8 control's mean reads 5.2e-4 (CPU)
SMALL_LIMIT = 1e-4


SERVE_CELLS = ["minicpm-2b.offline", "minicpm-2b.chat"]


def _serve_cell(name="minicpm-2b.offline", seconds=2.0):
    cell = bench.find_cell(name, SEED, seconds, False)
    cell.config.update(SMALL)
    srv = cell.config["serving"]
    srv.update(batch_slots=4, compute_dtype="float32",
               param_dtype="float32")
    if "rate_rps" in cell.traffic:
        cell.traffic["rate_rps"] = 3.0
    else:
        cell.traffic["requests"] = 6
    cell.traffic["limit_mean_logit_gap"] = SMALL_LIMIT
    return cell


def _run_serve(cell):
    drv = bench.load_module(BENCH / "drivers" / "serve.py", "drv_serve")
    import jax
    return drv.run(cell, jax.devices(), time.perf_counter())


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_serve_cell_is_correct_unbroken(name):
    res = _run_serve(_serve_cell(name))
    assert res["correct"], res["checks"]


def test_serve_intervals_fall_inside_the_traced_window(monkeypatch):
    """The engine's request and decode times, mapped onto the profiler's
    clock, lie inside the traced window (the device readers count busy
    time in them)."""
    monkeypatch.setattr(bench, "peaks_for", lambda kind: {})  # a CPU run
    cell = _serve_cell()
    cell.trace = True
    ctx = _run_serve(cell)["ctx"]
    w = [s for s in ctx["events"]["spans"] if s[0] == "window"][0]
    slack = 5e6                                          # 5 ms
    for iv in ("request_intervals_ns", "decode_intervals_ns"):
        assert ctx[iv]
        for a, b in ctx[iv]:
            assert w[1] - slack <= a <= b <= w[2] + slack, (iv, a, b, w)


def test_offline_requests_are_all_due_at_the_start():
    drv = bench.load_module(BENCH / "drivers" / "serve.py", "drv_serve")
    traffic = bench.find_cell("minicpm-2b.offline", 1, 40.0, False).traffic
    reqs = drv.make_requests(traffic, 40.0, 2 ** 31 + 7, 1000)
    again = drv.make_requests(traffic, 40.0, 5, 1000)
    assert len(reqs) == traffic["requests"]
    assert all(a == 0.0 for a, _, _ in reqs)
    # another seed: the same lengths in another order
    assert sorted(len(p) for _, p, _ in reqs) == \
        sorted(len(p) for _, p, _ in again)
    assert sorted(m for _, _, m in reqs) == sorted(m for _, _, m in again)


@pytest.mark.parametrize("name", SERVE_CELLS)
@pytest.mark.parametrize("fault", ["token_altered", "half_left_out"])
def test_serve_cell_catches_fault(monkeypatch, fault, name):
    import jax.numpy as jnp
    import repro.serve.engine as eng
    orig = eng._make_masked_step

    def make(model):
        step = orig(model)

        def broken(params, cache, tok, pos, active, buf, w):
            if fault == "half_left_out":
                # the upper half of the slots is never decoded
                half = jnp.arange(active.shape[0]) < active.shape[0] // 2
                active = active & half
            nxt, cache, buf = step(params, cache, tok, pos, active, buf, w)
            if fault == "token_altered":
                nxt = (nxt + 1) % model.cfg.vocab_size
                buf = buf.at[:, w].set(nxt)
            return nxt, cache, buf
        return broken

    monkeypatch.setattr(eng, "_make_masked_step", make)
    res = _run_serve(_serve_cell(name))
    assert not res["correct"]


def test_serve_int8_control_fails_the_limit():
    """The int8 control, read at every position of the same sequences,
    puts first tokens whose reference logit lies past the limit."""
    cell = _serve_cell()
    cfg = cell.config
    ref = cell.reference()
    params = ref.weights(cfg, SEED)
    rng = np.random.default_rng(0)
    seqs = []
    for n in (200, 256, 300):
        toks = rng.integers(1, cfg["vocab_size"], n).astype(np.int32)
        seqs.append((toks, 0, np.zeros(n, np.int32)))
    ctrl = ref.served_gaps(cfg, params, seqs, lower="int8")
    assert float(np.concatenate(ctrl).mean()) > SMALL_LIMIT
