"""Run benchmark cells several times in one process tree, one run at a
time, and keep each run's result line and the end of its log.

  python chipbench/tools/series.py OUT.jsonl CELL:SEED:SECONDS:TRACE ...

Each argument is one run of ``chipbench/run.py``; runs go in the order
given (so a cell's first run compiles and the next ones read the cache).
Every run appends one JSON object to OUT.jsonl: the run's arguments, exit
code, wall seconds, its result line (or null) and the last lines of its
standard error.
"""
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
TIMEOUT_S = 1200        # a cell's first run in a checkout may compile


def _text(b) -> str:
    return b.decode(errors="replace") if isinstance(b, bytes) else (b or "")


def main(out: str, runs: list) -> int:
    dest = Path(out)
    dest.parent.mkdir(parents=True, exist_ok=True)
    for spec in runs:
        cell, seed, seconds, trace = spec.split(":")
        cmd = [sys.executable, str(ROOT / "chipbench" / "run.py"),
               "--workload", cell, "--seed", seed, "--seconds", seconds,
               "--trace", trace]
        t0 = time.perf_counter()
        try:
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                               timeout=TIMEOUT_S)
        except subprocess.TimeoutExpired as exc:
            p = subprocess.CompletedProcess(
                cmd, 124, _text(exc.stdout), _text(exc.stderr))
        wall = time.perf_counter() - t0
        lines = p.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except json.JSONDecodeError:
            result = None
        rec = {"run": spec, "rc": p.returncode, "wall_s": wall,
               "result": result,
               "stderr_tail": p.stderr.strip().splitlines()[-25:]}
        with dest.open("a") as f:
            f.write(json.dumps(rec) + "\n")
        short = {k: v for k, v in (result or {}).items()
                 if k in ("correct", "metrics", "checks")}
        print(f"{spec} rc={p.returncode} wall={wall:.1f}s {json.dumps(short)}",
              flush=True)
        if p.returncode != 0:
            print("\n".join(rec["stderr_tail"]), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1], sys.argv[2:]))
