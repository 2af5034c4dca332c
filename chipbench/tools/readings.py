"""Read a cell's compared number over many seeds, and its control's, in
one process (set-up compiles once), to set the cell's limit from.

  python chipbench/tools/readings.py CELL SECONDS N_SEEDS N_CONTROL SEED0

Runs the cell's driver once per seed (SEED0, SEED0 + 1, ...) at the
cell's own size and load; for the first N_CONTROL seeds it also reads
the lower-precision control at the same inputs.  Prints one JSON line
per seed: the checks (the program's readings) and the control's.
"""
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import bench  # noqa: E402


def main(name: str, seconds: str, n: str, n_control: str, seed0: str):
    devices = bench.require_chips(1)
    bench.compile_cache_dir()
    for i in range(int(n)):
        cell = bench.find_cell(name, int(seed0) + i, float(seconds), False)
        cell.control = i < int(n_control)
        drv = bench.load_module(
            BENCH / "drivers" / f"{cell.traffic['driver']}.py", "drv")
        res = drv.run(cell, devices, time.perf_counter())
        print(json.dumps({"seed": cell.seed, "correct": res["correct"],
                          "checks": res["checks"],
                          "control": res["control"],
                          "e2e": res["end_to_end"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(*sys.argv[1:]))
