"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV after each bench's own report.

  python benchmarks/run.py [--smoke] [--csv PATH] [--only NAME[,NAME...]]

``--smoke`` caps iteration counts/sizes (via ``common.smoke``) so the CI
bench job finishes in a few minutes; ``--csv`` additionally writes the
summary CSV to a file (uploaded as a CI artifact).
"""
import argparse
import os
import sys
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).parent.parent))

BENCHES = [
    "bench_update_intervals",   # Fig. 4
    "bench_step_response",      # Fig. 5
    "bench_aliasing",           # Fig. 6
    "bench_fft_aliasing",       # Fig. 10
    "bench_reconstruction",     # §III-A2 + fastotf2 throughput
    "bench_fleet",              # fleet batched vs per-trace numpy loop
    "bench_align",              # cross-sensor align+fuse vs host loop
    "bench_stream",             # streaming fused pipeline vs batch replay
    "bench_health",             # health-stage overhead + detect latency
    "bench_ingest",             # prioritized real-sensor ingest reads
    "bench_serve",              # continuous batching + request metering
    "bench_multihost",          # multi-host weak scaling (spawn harness)
    "bench_ft",                 # carry checkpoint/restore + exact resume
    "bench_hpl",                # Fig. 7 + energy table
    "bench_hpg",                # Fig. 8
    "bench_overhead",           # §II-D <1% overhead
    "roofline",                 # §Roofline table from the dry-run
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes/iteration caps for CI (<~3 min)")
    ap.add_argument("--csv", default=None, metavar="PATH",
                    help="also write the summary CSV to PATH")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names to run")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        # set BEFORE bench modules import common-driven size constants
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    benches = BENCHES
    if args.only:
        wanted = set(args.only.split(","))
        unknown = wanted - set(BENCHES)
        if unknown:
            ap.error(f"unknown bench(es) {sorted(unknown)} "
                     f"(known: {', '.join(BENCHES)})")
        benches = [b for b in BENCHES if b in wanted]

    csv = ["name,us_per_call,derived"]
    failures = 0
    for name in benches:
        print(f"\n{'='*72}\n== benchmarks.{name}\n{'='*72}")
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            us, derived = mod.main()
            csv.append(f"{name},{us:.0f},{derived}")
        except Exception:
            traceback.print_exc()
            csv.append(f"{name},-1,FAILED")
            failures += 1
    text = "\n".join(csv)
    print("\n" + text)
    if args.csv:
        Path(args.csv).write_text(text + "\n")
        print(f"(csv written to {args.csv})")
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
