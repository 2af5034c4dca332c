"""Meta-tests for the benchmark regression gate (benchmarks/compare.py).

These run the gate's own logic against synthetic results — no benches
execute — and pin the three CI contracts:

  * the baseline-registry sync gate: every bench registered in
    ``run.py``'s BENCHES needs a baseline entry, so a new benchmark
    cannot land ungated;
  * per-bench ``floors``: derived metrics (throughput ratios,
    wire-compression ratio, ...) are hard minimums, and a baseline
    refresh (``--write-baseline``) preserves them verbatim;
  * parity capture: every ``*rel_err`` derived key is recorded as a
    parity metric on refresh.
"""
import importlib.util
import json
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).parent.parent / "benchmarks"


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"_bench_meta_{name}", BENCH_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def compare_mod():
    return _load("compare")


def test_checked_in_baseline_covers_registry(compare_mod):
    """The sync gate on the REAL files: run.py's BENCHES vs the
    checked-in smoke and full baselines."""
    benches = compare_mod.registry_benches(BENCH_DIR / "run.py")
    assert benches, "run.py BENCHES is empty?"
    for fname in ("baseline.json", "baseline-full.json"):
        baseline = json.loads((BENCH_DIR / fname).read_text())
        missing = compare_mod.check_registry(baseline, benches)
        assert not missing, f"{fname}: {missing}"


def test_registry_gate_fails_on_missing_entry(compare_mod):
    baseline = {"bench_a": {"us_per_call": 1.0, "parity": {}}}
    fails = compare_mod.check_registry(baseline, ["bench_a", "bench_b"])
    assert len(fails) == 1 and "bench_b" in fails[0]


def test_gated_metrics_present_in_baselines(compare_mod):
    """The gated metrics are actually wired into the gate: both
    baselines floor the wire compression."""
    for fname in ("baseline.json", "baseline-full.json"):
        base = json.loads((BENCH_DIR / fname).read_text())
        floors = base["bench_stream"].get("floors", {})
        assert "wire_ratio" in floors, fname
        assert "wire_ratio" in base["bench_multihost"].get("floors", {}), \
            fname
    full = json.loads((BENCH_DIR / "baseline-full.json").read_text())
    assert full["bench_stream"]["floors"]["wire_ratio"] >= 10.0, \
        "the >=10x collective-payload shrink must stay enforced"


def test_serve_metrics_gated_in_baselines(compare_mod):
    """Continuous batching stays gated: BOTH baselines must floor the
    serve speedup at >= 1.5x and pin the per-request conservation
    parity (meter_rel_err)."""
    for fname in ("baseline.json", "baseline-full.json"):
        base = json.loads((BENCH_DIR / fname).read_text())
        serve = base["bench_serve"]
        assert serve["floors"]["serve_speedup"] >= 1.5, fname
        assert serve["parity"]["meter_rel_err"] <= 1e-9, \
            f"{fname}: conservation parity must gate at float64 roundoff"


def test_ft_resume_exact_gated_in_baselines(compare_mod):
    """Elastic fault tolerance stays gated: BOTH baselines must floor
    ``resume_exact`` at 1.0 — a killed+resumed streaming run that is
    not bit-identical to the uninterrupted oracle fails the bench job,
    on every machine (the metric is 0/1, not a timing)."""
    for fname in ("baseline.json", "baseline-full.json"):
        base = json.loads((BENCH_DIR / fname).read_text())
        assert base["bench_ft"]["floors"]["resume_exact"] >= 1.0, fname


def test_floor_gate(compare_mod, tmp_path):
    baseline = {"bench_a": {"us_per_call": 100.0, "parity": {},
                            "floors": {"thr_ratio": 1.5,
                                       "wire_ratio": 10.0}}}
    csv = tmp_path / "r.csv"
    csv.write_text("name,us_per_call,derived\n"
                   "bench_a,120,thr_ratio=x1.80,wire_ratio=x9.1\n")
    results = compare_mod.parse_results(csv)
    _, fails = compare_mod.compare(baseline, results, max_slowdown=1.5,
                                   min_us=500.0, parity_floor=1e-9)
    assert len(fails) == 1
    assert "FLOOR wire_ratio" in fails[0]


def test_floor_gate_fails_on_missing_metric(compare_mod, tmp_path):
    baseline = {"bench_a": {"us_per_call": 100.0, "parity": {},
                            "floors": {"thr_ratio": 1.5}}}
    csv = tmp_path / "r.csv"
    csv.write_text("name,us_per_call,derived\nbench_a,120,eff=x1.0\n")
    results = compare_mod.parse_results(csv)
    _, fails = compare_mod.compare(baseline, results, max_slowdown=1.5,
                                   min_us=500.0, parity_floor=1e-9)
    assert any("floor metric thr_ratio missing" in f for f in fails)


def test_parse_results_strips_ratio_prefix(compare_mod, tmp_path):
    csv = tmp_path / "r.csv"
    csv.write_text("name,us_per_call,derived\n"
                   "bench_a,120,thr=x1.25,rel_err=3.0e-07,note=fast\n")
    us, metrics = compare_mod.parse_results(csv)["bench_a"]
    assert us == 120.0
    assert metrics == {"thr": 1.25, "rel_err": 3.0e-07}


def test_write_baseline_preserves_floors_and_rel_err(compare_mod,
                                                     tmp_path):
    old = {"bench_a": {"us_per_call": 100.0, "parity": {},
                       "floors": {"wire_ratio": 10.0}}}
    csv = tmp_path / "r.csv"
    csv.write_text("name,us_per_call,derived\n"
                   "bench_a,80,wire_ratio=x12.6,stream_rel_err=9.2e-07,"
                   "rel_err=1.0e-07\n")
    results = compare_mod.parse_results(csv)
    out = tmp_path / "base.json"
    compare_mod.write_baseline(results, out, old=old)
    base = json.loads(out.read_text())
    assert base["bench_a"]["floors"] == {"wire_ratio": 10.0}
    assert base["bench_a"]["parity"] == {"stream_rel_err": 9.2e-07,
                                         "rel_err": 1.0e-07}
    assert base["bench_a"]["us_per_call"] == 80.0


def test_end_to_end_gate_exit_codes(compare_mod, tmp_path):
    """main() wires it all together: pass -> 0, floor breach -> exit 1."""
    base = tmp_path / "base.json"
    base.write_text(json.dumps(
        {"bench_a": {"us_per_call": 100.0, "parity": {},
                     "floors": {"thr": 1.5}}}))
    reg = tmp_path / "run.py"
    reg.write_text("BENCHES = ['bench_a']\n")
    good = tmp_path / "good.csv"
    good.write_text("name,us_per_call,derived\nbench_a,110,thr=x2.0\n")
    bad = tmp_path / "bad.csv"
    bad.write_text("name,us_per_call,derived\nbench_a,110,thr=x1.0\n")
    compare_mod.main(["--baseline", str(base), "--results", str(good),
                      "--registry", str(reg)])
    with pytest.raises(SystemExit):
        compare_mod.main(["--baseline", str(base), "--results",
                          str(bad), "--registry", str(reg)])
