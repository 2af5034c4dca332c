#!/usr/bin/env python3
"""Run one benchmark cell once, on the TPU chips of this machine.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

Set-up makes the cell's inputs (and weights) from ``--seed``, warms every
shape the cell's traffic uses and counts all that as ``setup_s``; then
the cell's driver measures for ``--seconds``, checks what the timed path
produced against the configuration's plain reference, and this prints one
JSON line: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window), ``device``, optionally
``breakdown``, and ``checks`` last: each number compared with its limit.
The same checks end standard error.

Exits non-zero without a result line when no TPU (or too few chips) is
attached, or when the cell's files are missing.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
# the TPU runtime would otherwise log under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = bench.find_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace))
    devices = bench.require_chips(cell.chips)
    bench.compile_cache_dir()
    driver = bench.load_module(
        BENCH / "drivers" / f"{cell.traffic['driver']}.py",
        f"driver_{cell.traffic['driver']}")
    res = driver.run(cell, devices, T_PROCESS)
    if cell.trace:
        metrics = bench.read_per_layer(cell, res["ctx"])
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in res["end_to_end"].items() if k in units}
    device = bench.device_info(devices)
    device["memory_peak_bytes"] = int(res["memory_peak_bytes"])
    out = {"correct": bool(res["correct"]),
           "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if cell.trace:
        summary = res["ctx"]["trace"]
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = summary["breakdown"]
    checks = {c["name"]: {"value": c["value"], "limit": c["limit"]}
              for c in res["checks"]}
    out["checks"] = checks
    for c in res["checks"]:
        bench.log(f"check {c['name']}: {c['value']!r} "
                  f"({c['op']} limit {c['limit']!r}) "
                  f"{'ok' if c['ok'] else 'FAILED'}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
