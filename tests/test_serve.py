"""Continuous-batching serve engine + per-request energy metering."""
import json
import math

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import Model

_CACHE = {}


def _setup(arch="llama3.2-3b"):
    if arch not in _CACHE:
        cfg = reduced(get_arch(arch))
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        _CACHE[arch] = (cfg, model, params)
    return _CACHE[arch]


def _reqs(cfg, lens, max_new, seed=0):
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        size=(ln,)).astype(np.int32),
                    max_new_tokens=mn)
            for i, (ln, mn) in enumerate(zip(lens, max_new))]


# ---------------------------------------------------------------------------
# greedy parity: continuous batching == fixed-batch serve-to-completion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["llama3.2-3b", "gemma2-27b"])
def test_continuous_matches_fixed_batch(arch):
    from repro.serve import FixedBatchEngine, ServeEngine
    cfg, model, params = _setup(arch)
    lens = [6, 6, 6, 6]                 # equal lengths: no padding skew
    max_new = [7, 3, 5, 2]
    fixed = FixedBatchEngine(model, params, batch_slots=2, max_len=32)
    out_f = fixed.run(_reqs(cfg, lens, max_new))
    cont = ServeEngine(model, params, batch_slots=2, max_len=32,
                       flush_interval=2)
    out_c = cont.run(_reqs(cfg, lens, max_new))
    assert set(out_c) == set(out_f) == {0, 1, 2, 3}
    for rid in out_f:
        assert out_c[rid] == out_f[rid], rid
        assert len(out_c[rid]) == max_new[rid]
    # continuous reuses ONE persistent cache; fixed re-inits per batch
    assert cont.requests_served == 4
    assert cont.tokens_emitted == sum(max_new)


def test_masked_slots_do_no_phantom_work():
    """Dummy (inactive) slots must not leak tokens into results."""
    from repro.serve import FixedBatchEngine, ServeEngine
    cfg, model, params = _setup()
    # 3 requests on 2 fixed slots -> second batch has a dummy row
    fixed = FixedBatchEngine(model, params, batch_slots=2, max_len=32)
    out = fixed.run(_reqs(cfg, [4, 4, 4], [3, 3, 3]))
    assert set(out) == {0, 1, 2}
    assert fixed.requests_served == 3
    assert fixed.tokens_emitted == 9
    # continuous: a single request on 4 slots (3 masked the whole run)
    cont = ServeEngine(model, params, batch_slots=4, max_len=32)
    out_c = cont.run(_reqs(cfg, [4], [3]))
    assert set(out_c) == {0} and len(out_c[0]) == 3


# ---------------------------------------------------------------------------
# scheduler: admission/eviction ordering + slot-scoped tracing
# ---------------------------------------------------------------------------

def test_admission_eviction_ordering():
    from repro.serve import ServeEngine
    cfg, model, params = _setup()
    engine = ServeEngine(model, params, batch_slots=2, max_len=64,
                         flush_interval=2)
    # r0 is long; r1..r3 are short and must rotate through slot 1 while
    # r0 keeps decoding (no head-of-line blocking)
    reqs = _reqs(cfg, [4, 4, 4, 4], [20, 2, 2, 2])
    out = engine.run(reqs)
    assert sorted(out) == [0, 1, 2, 3]
    # FIFO admission order
    adm = [s for s in engine.segments if s.kind == "prefill"]
    assert [s.rids[0] for s in adm] == [0, 1, 2, 3]
    # mid-decode admission: some decode segment pairs r0 with a request
    # admitted AFTER an earlier one was evicted
    joint = [set(s.rids) for s in engine.segments if s.kind == "decode"
             and len(s.rids) > 1]
    assert any({0, 2} <= j or {0, 3} <= j for j in joint), joint
    # eviction frees the slot before the next admission reuses it
    by_rid = {r.rid: r for r in reqs}
    assert by_rid[1].t_done <= by_rid[2].t_admitted
    assert by_rid[2].t_done <= by_rid[3].t_admitted
    # slot-scoped depth-1 regions: slot 0 only ever runs r0's decode
    slot0 = engine.tracer.phases(depth=1, name="decode", slot=0)
    slot1 = engine.tracer.phases(depth=1, name="decode", slot=1)
    assert slot0 and slot1
    ev_steps = {e.step for e in engine.tracer.events
                if e.depth == 1 and e.slot == 1}
    assert ev_steps >= {1, 2, 3}
    # the slot-segment schedule tiles the depth-0 phases EXACTLY
    # (bit-identical boundaries -> conservation by construction)
    ph = sorted((a, b) for _, a, b in engine.tracer.phases(depth=0))
    sg = sorted((s.t_lo, s.t_hi) for s in engine.segments)
    assert ph == sg
    # trace array export carries the slot column
    arrs = engine.tracer.to_arrays()
    assert "slot" in arrs and set(np.unique(arrs["slot"])) <= {-1, 0, 1}


def test_arrival_respecting_run_completes():
    from repro.serve import ServeEngine, poisson_requests
    cfg, model, params = _setup()
    reqs = poisson_requests(5, rate_rps=2000.0, seed=3,
                            prompt_lens=(4, 6), new_tokens=(1, 4),
                            vocab_size=cfg.vocab_size)
    engine = ServeEngine(model, params, batch_slots=2, max_len=32,
                         flush_interval=2)
    out = engine.run(reqs, respect_arrivals=True)
    assert sorted(out) == list(range(5))
    for r in reqs:
        assert len(r.generated) == r.max_new_tokens
        assert r.t_first >= r.t_arrival or math.isnan(r.t_first)
        assert r.ttft_s >= 0.0


def test_zero_budget_request_completes_empty():
    from repro.serve import ServeEngine
    cfg, model, params = _setup()
    engine = ServeEngine(model, params, batch_slots=2, max_len=32)
    reqs = _reqs(cfg, [4, 4], [0, 2])
    out = engine.run(reqs)
    assert out[0] == [] and len(out[1]) == 2


# ---------------------------------------------------------------------------
# host-sync regression: device-side token buffers, counted drains
# ---------------------------------------------------------------------------

def test_host_transfer_counts():
    from repro.serve import FixedBatchEngine, ServeEngine
    cfg, model, params = _setup()
    # fixed engine: 20 decode tokens at flush=8 -> ceil(20/8)=3 drains
    fixed = FixedBatchEngine(model, params, batch_slots=2, max_len=64,
                             flush_interval=8)
    fixed.run(_reqs(cfg, [4, 4], [20, 20]))
    assert fixed.host_transfers == 3
    # continuous: 1 pending prefill token + 32 decode steps at flush=16
    # -> exactly 2 segment drains, NOT one transfer per token
    cont = ServeEngine(model, params, batch_slots=2, max_len=64,
                       flush_interval=16)
    cont.run(_reqs(cfg, [4], [33]))
    assert cont.host_transfers == 2
    assert cont.tokens_emitted == 33
    assert cont.host_transfers < cont.tokens_emitted // 4


# ---------------------------------------------------------------------------
# per-request energy: conservation, registry gauges, JSONL artifact
# ---------------------------------------------------------------------------

def _serve_fabric(engine, lead=0.05, n_chips=2, seed=0):
    """Synthesize a sensor fabric whose truth follows the engine's
    recorded phases (the serve_demo idiom)."""
    from repro.core import NodeFabric, ToolSpec, phase_power
    from repro.core.measurement_model import CHIP_IDLE_W
    from repro.core.power_model import occupancy_power
    occ = {"admission": (0.0, 0.05, 0.0), "prefill": (1.0, 0.5, 0.1),
           "decode": (0.15, 1.0, 0.1)}
    shifted = [(n, a + lead, b + lead)
               for n, a, b in engine.tracer.phases(depth=0)]
    watts = {n: {"watts": occupancy_power(*occ.get(n, (0, 0.1, 0)))}
             for n, _, _ in shifted}
    truth = phase_power([("__lead__", 0.0, lead)] + shifted,
                        {**watts, "__lead__": {"watts": CHIP_IDLE_W}})
    fabric = NodeFabric(chip_truths=[truth] * n_chips)
    return fabric.sample_all(ToolSpec(), seed=seed)


def test_per_request_energy_conserves(tmp_path, monkeypatch):
    from repro.health import HealthRegistry
    from repro.serve import METER_LOG_ENV, ServeEngine
    cfg, model, params = _setup()
    reg = HealthRegistry()
    engine = ServeEngine(model, params, batch_slots=2, max_len=64,
                         flush_interval=4, registry=reg)
    reqs = _reqs(cfg, [4, 8, 6], [10, 3, 6], seed=1)
    for i, r in enumerate(reqs):
        r.user = f"user{i % 2}"
    engine.run(reqs)
    lead = 0.05
    traces = _serve_fabric(engine, lead=lead)
    with monkeypatch.context() as m:
        m.setenv(METER_LOG_ENV, str(tmp_path))
        report = engine.attribute_requests(traces, t_shift=lead,
                                           track=False)
    # every request billed, energies positive, J/token consistent
    assert sorted(r.rid for r in report.requests) == [0, 1, 2]
    for r in report.requests:
        assert r.energy_j > 0.0
        assert r.tokens == len(reqs[r.rid].prompt) + reqs[r.rid].max_new_tokens
        assert r.j_per_token == pytest.approx(r.energy_j / r.tokens)
        assert r.ttft_s >= 0.0 and r.latency_s >= r.ttft_s
    # conservation: per-request energies sum to the fused PHASE totals
    fused = engine.attribute_phases(traces, t_shift=lead, fuse=True,
                                    streaming=True, track=False)
    phase_totals = np.asarray([[p.energy_j for p in row]
                               for row in fused.values()])
    assert report.conservation_rel_err(phase_totals) <= 1e-5
    # ... and to the metering stage's own segment totals exactly-ish
    assert report.conservation_rel_err(report.segment_totals) <= 1e-9
    # per-user aggregation partitions the total
    pu = report.per_user()
    assert set(pu) == {"user0", "user1"}
    assert sum(u["energy_j"] for u in pu.values()) == \
        pytest.approx(report.total_j)
    assert report.percentiles()["j_per_request"]["p50"] > 0.0
    # registry export: scheduler counters + rolling metering gauges
    snap = reg.json_snapshot()
    assert snap["serve_requests_total"] == 3.0
    assert snap["serve_host_transfers_total"] >= 1.0
    assert snap["meter_j_per_request"]["p50"] > 0.0
    assert "repro_meter_j_per_request" in reg.prometheus_text()
    # JSONL artifact trail (the CI per-request metering artifact)
    files = list(tmp_path.glob("request-energies-*.jsonl"))
    assert len(files) == 1
    lines = [json.loads(ln) for ln in
             files[0].read_text().strip().splitlines()]
    assert [ln["rid"] for ln in lines] == [0, 1, 2]
    assert all(ln["energy_j"] > 0.0 for ln in lines)
    # re-attribution is bit-identical (outside the monkeypatch scope,
    # so in CI this run feeds the ambient REPRO_METER_LOG_DIR artifact)
    again = engine.attribute_requests(traces, t_shift=lead, track=False)
    for r1, r2 in zip(report.requests, again.requests):
        assert r1.energy_by_device == r2.energy_by_device, r1.rid


def test_metering_deterministic_under_permutation():
    """Bit-identical per-request energies under slot-assignment
    permutations: segment list order and within-segment rid order."""
    from repro.align import group_traces_by_device
    from repro.core import NodeFabric, ToolSpec, square_wave
    from repro.fleet.pipeline import (SlotSegment,
                                      attribute_energy_fused_streaming)
    truth = square_wave(1.0, 2, lead_s=0.5, tail_s=0.5)
    traces = NodeFabric(chip_truths=[truth] * 2).sample_all(
        ToolSpec(), seed=0)
    groups = list(group_traces_by_device(traces).values())
    phases = [("work", 0.5, 1.2), ("work", 1.2, 2.0)]
    segs_a = [SlotSegment(0.5, 1.2, (0, 1, 2), (3.0, 1.0, 2.0)),
              SlotSegment(1.2, 2.0, (1, 2), (2.0, 5.0))]
    segs_b = [SlotSegment(1.2, 2.0, (2, 1), (5.0, 2.0)),
              SlotSegment(0.5, 1.2, (2, 0, 1), (2.0, 3.0, 1.0))]
    out = {}
    for key, segs in (("a", segs_a), ("b", segs_b)):
        _, pipe = attribute_energy_fused_streaming(
            groups, phases, meter=segs, track=False, return_pipe=True)
        out[key] = pipe.request_energies()
    assert sorted(out["a"]) == sorted(out["b"]) == [0, 1, 2]
    for rid in out["a"]:
        assert np.array_equal(out["a"][rid], out["b"][rid]), rid
    # shares conserve: requests sum to segment totals
    tot = np.sum([out["a"][r] for r in out["a"]], axis=0)
    seg_tot = pipe.meter_stage.segment_totals().sum(axis=1)
    np.testing.assert_allclose(tot, seg_tot, rtol=1e-12)


# ---------------------------------------------------------------------------
# load generator
# ---------------------------------------------------------------------------

def test_poisson_loadgen_seeded_and_shaped():
    from repro.serve import poisson_requests
    a = poisson_requests(40, rate_rps=100.0, seed=7)
    b = poisson_requests(40, rate_rps=100.0, seed=7)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert [r.user for r in a] == [r.user for r in b]
    arr = [r.arrival_s for r in a]
    assert all(t2 > t1 for t1, t2 in zip(arr, arr[1:]))
    assert {len(r.prompt) for r in a} <= {4, 8, 12}
    assert all(1 <= r.max_new_tokens <= 32 for r in a)
    # bimodal budgets: both short and long modes show up
    assert min(r.max_new_tokens for r in a) <= 11
    assert max(r.max_new_tokens for r in a) >= 22
    c = poisson_requests(40, rate_rps=100.0, seed=8)
    assert [r.arrival_s for r in c] != [r.arrival_s for r in a]


def test_serve_cli_reduced_meters_timeline(capsys, monkeypatch, tmp_path):
    """``launch.serve --reduced`` serves the tiny config and attributes
    energy on sensor traces synthesized from the engine's timeline: every
    phase it prints was recorded by the engine, shifted by ``LEAD_S``."""
    from repro.launch import serve as serve_cli
    from repro.serve import ServeEngine
    # a set variable leaves the cache to JAX: main changes no config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve_cli.main(["--reduced", "--requests", "2", "--max-new",
                        "2"]) == 0
    out = capsys.readouterr().out
    assert "served 2 requests, 4 tokens" in out
    assert "energy per generated token" in out
    cfg, model, params = _setup()
    engine = ServeEngine(model, params, batch_slots=2, max_len=32)
    engine.run(_reqs(cfg, [5, 7], [2, 2]))
    traces, shifted = serve_cli.timeline_traces(engine)
    recorded = engine.tracer.phases(depth=0)
    assert [(n, a + serve_cli.LEAD_S, b + serve_cli.LEAD_S)
            for n, a, b in recorded] == shifted
    assert {f"chip{c}_energy" for c in range(4)} <= set(traces)
