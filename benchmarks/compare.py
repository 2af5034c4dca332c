"""Benchmark regression gate: compare a bench run against a baseline.

    python benchmarks/compare.py --baseline benchmarks/baseline.json \
        --results bench-results.csv --out bench-compare.md

Reads the ``name,us_per_call,derived`` CSV that ``run.py`` emits and the
checked-in baseline (regenerate with ``--write-baseline`` after an
intentional perf change — per-bench ``floors`` survive the rewrite),
writes a markdown comparison table, and exits non-zero when

  * a bench registered in ``run.py`` has no baseline entry (the
    baseline-registry sync gate: new benchmarks cannot land ungated),
  * a bench FAILED or went missing,
  * throughput regressed by more than ``--max-slowdown`` (default 1.5x;
    ``REPRO_BENCH_MAX_SLOWDOWN`` overrides — benches faster than
    ``--min-us`` are exempt from the ratio gate, their absolute times
    are too noisy to gate on),
  * a parity metric drifted: every numeric key recorded under a
    bench's ``parity`` map in the baseline (every ``*rel_err`` derived
    key) must stay within max(10x its baseline value,
    ``--parity-floor``), or
  * a derived metric dropped below its checked-in floor: each entry in
    a bench's ``floors`` map (e.g. the peak-memory ratio or the
    collective wire-compression ratio) is a hard minimum on the
    current run's derived value.

``benchmarks/baseline.json`` is recorded from a ``run.py --smoke`` run
and gates the per-PR CI; ``benchmarks/baseline-full.json`` is recorded
from a full run and gates the nightly tier.  The slowdown margin
absorbs runner-to-runner speed differences; the parity and floor gates
do not depend on machine speed at all.
"""
import argparse
import json
import os
import re
import sys
from pathlib import Path

_NUM = re.compile(r"^[+-]?(\d+\.?\d*|\.\d+)([eE][+-]?\d+)?$")


def parse_results(path):
    """CSV -> {name: (us_per_call, {derived key: float})}.

    Derived ratio values print as ``x1.23`` — the ``x`` prefix is
    stripped so ratios gate like any other numeric metric.
    """
    out = {}
    for line in Path(path).read_text().splitlines():
        line = line.strip()
        if not line or line.startswith("name,"):
            continue
        name, us, derived = line.split(",", 2)
        metrics = {}
        for tok in derived.split(","):
            if "=" not in tok:
                continue
            k, _, v = tok.partition("=")
            v = v.strip().lstrip("x")
            if _NUM.match(v):
                metrics[k.strip()] = float(v)
        out[name] = (float(us), metrics)
    return out


def registry_benches(registry_path):
    """The bench names ``run.py`` registers (its ``BENCHES`` list)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bench_registry", registry_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return list(mod.BENCHES)


def check_registry(baseline, benches):
    """Baseline-registry sync gate -> [failure strings]."""
    return [f"{name}: registered in run.py but missing from the "
            f"baseline (add an entry — new benchmarks cannot land "
            f"ungated)"
            for name in benches if name not in baseline]


def write_baseline(results, path, *, old=None):
    """Record ``results`` as the new baseline.

    Every ``*rel_err`` derived key is captured as a parity metric;
    per-bench ``floors`` from the previous baseline are preserved
    verbatim (a refresh must never silently drop a gate).
    """
    old = old or {}
    base = {}
    for name, (us, metrics) in results.items():
        parity = {k: v for k, v in metrics.items()
                  if k.endswith("rel_err") or k == "parity"}
        base[name] = {"us_per_call": us, "parity": parity}
        floors = old.get(name, {}).get("floors")
        if floors:
            base[name]["floors"] = floors
    Path(path).write_text(json.dumps(base, indent=2, sort_keys=True)
                          + "\n")
    print(f"baseline written to {path}")


def compare(baseline, results, *, max_slowdown, min_us, parity_floor):
    """-> (rows for the table, [failure strings])."""
    rows, failures = [], []
    for name, base in sorted(baseline.items()):
        if name not in results:
            failures.append(f"{name}: missing from results")
            rows.append((name, base["us_per_call"], None, "-", "MISSING"))
            continue
        us, metrics = results[name]
        b_us = float(base["us_per_call"])
        if us < 0:
            failures.append(f"{name}: bench FAILED")
            rows.append((name, b_us, us, "-", "FAILED"))
            continue
        ratio = us / b_us if b_us > 0 else 1.0
        status = "ok"
        if us > min_us and b_us > min_us and ratio > max_slowdown:
            status = f"SLOW x{ratio:.2f} > x{max_slowdown:.2f}"
            failures.append(f"{name}: {us:.0f}us vs baseline "
                            f"{b_us:.0f}us ({status})")
        parity_bits = []
        for k, b_v in base.get("parity", {}).items():
            v = metrics.get(k)
            if v is None:
                status = f"parity metric {k} missing"
                failures.append(f"{name}: {status}")
                continue
            limit = max(10.0 * float(b_v), parity_floor)
            parity_bits.append(f"{k}={v:.1e} (≤{limit:.1e})")
            if v > limit:
                status = f"PARITY {k}={v:.1e} > {limit:.1e}"
                failures.append(f"{name}: drifted {status}")
        for k, floor in base.get("floors", {}).items():
            v = metrics.get(k)
            if v is None:
                status = f"floor metric {k} missing"
                failures.append(f"{name}: {status}")
                continue
            parity_bits.append(f"{k}={v:.3g} (≥{float(floor):.3g})")
            if v < float(floor):
                status = f"FLOOR {k}={v:.3g} < {float(floor):.3g}"
                failures.append(f"{name}: {status}")
        rows.append((name, b_us, us, f"x{ratio:.2f}",
                     status if status != "ok"
                     else "ok " + " ".join(parity_bits)))
    return rows, failures


def render(rows, failures):
    lines = ["# Benchmark comparison (smoke) vs checked-in baseline",
             "",
             "| bench | baseline µs | current µs | ratio | status |",
             "|---|---:|---:|---:|---|"]
    for name, b_us, us, ratio, status in rows:
        cur = "-" if us is None else f"{us:.0f}"
        lines.append(f"| {name} | {b_us:.0f} | {cur} | {ratio} "
                     f"| {status} |")
    lines.append("")
    lines.append("**GATE: FAIL**" if failures else "**GATE: pass**")
    for f in failures:
        lines.append(f"- {f}")
    return "\n".join(lines) + "\n"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", default="benchmarks/baseline.json")
    ap.add_argument("--results", required=True)
    ap.add_argument("--out", default=None,
                    help="also write the markdown table here (artifact)")
    ap.add_argument("--max-slowdown", type=float,
                    default=float(os.environ.get(
                        "REPRO_BENCH_MAX_SLOWDOWN", "1.5")))
    ap.add_argument("--min-us", type=float, default=500.0,
                    help="exempt sub-noise benches from the ratio gate")
    ap.add_argument("--parity-floor", type=float, default=1e-9)
    ap.add_argument("--registry",
                    default=str(Path(__file__).parent / "run.py"),
                    help="run.py whose BENCHES list the baseline must "
                         "cover (pass an empty string to skip)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline from --results "
                         "instead of gating (per-bench floors are "
                         "preserved)")
    args = ap.parse_args(argv)
    results = parse_results(args.results)
    if args.write_baseline:
        old = json.loads(Path(args.baseline).read_text()) \
            if Path(args.baseline).exists() else {}
        write_baseline(results, args.baseline, old=old)
        return
    baseline = json.loads(Path(args.baseline).read_text())
    rows, failures = compare(baseline, results,
                             max_slowdown=args.max_slowdown,
                             min_us=args.min_us,
                             parity_floor=args.parity_floor)
    if args.registry:
        failures = check_registry(baseline,
                                  registry_benches(args.registry)) \
            + failures
    text = render(rows, failures)
    print(text)
    if args.out:
        Path(args.out).write_text(text)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
