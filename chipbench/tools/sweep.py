"""Find a serving cell's knee: offer its traffic at several fixed rates
in one process and see at which the backlog grows over the window.

  python chipbench/tools/sweep.py CELL SECONDS SEED SLOTS RATE [RATE ...]

It serves with SLOTS cache slots.  For each rate it prints one JSON line:
requests, tokens per second, median and p90 time to first token, the
admission wait (due time to admission) of the first and last fifth of the
requests, and the device's peak memory so far.  A backlog that
grows shows as a last-fifth wait far above the first fifth's; the knee
is the highest rate at which it does not.
"""
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import bench  # noqa: E402


def main(name, seconds, seed, slots, *rates):
    devices = bench.require_chips(1)
    bench.compile_cache_dir()
    from repro.configs import get_arch
    from repro.models import Model
    from repro.serve.engine import Request, ServeEngine
    cell = bench.find_cell(name, int(seed), float(seconds), False)
    cfg, srv = cell.config, cell.config["serving"]
    drv = bench.load_module(BENCH / "drivers" / "serve.py", "drv")
    ref = cell.reference()
    arch = dataclasses.replace(get_arch(cfg["name"]),
                               param_dtype=srv["param_dtype"],
                               compute_dtype=srv["compute_dtype"],
                               rms_eps=float(cfg["rms_norm_eps"]))
    engine = ServeEngine(Model(arch), ref.weights(cfg, cell.seed),
                         batch_slots=int(slots),
                         max_len=srv["max_len"],
                         prefill_bucket=srv["prefill_bucket"],
                         flush_interval=srv["flush_interval"])
    b = srv["prefill_bucket"]
    warm = [Request(rid=-1 - i, prompt=np.ones((b * (1 + i % 6),), np.int32),
                    max_new_tokens=18) for i in range(max(6, engine.slots))]
    engine.run(warm)
    for rate in rates:
        traffic = dict(cell.traffic, rate_rps=float(rate))
        reqs = [Request(rid=i, prompt=p, max_new_tokens=m, arrival_s=a)
                for i, (a, p, m) in enumerate(drv.make_requests(
                    traffic, cell.seconds, cell.seed, cfg["vocab_size"]))]
        t0 = time.perf_counter()
        engine.run(reqs, respect_arrivals=True)
        wall = time.perf_counter() - t0
        wait = np.asarray([r.t_admitted - r.t_arrival for r in reqs])
        ttft = np.asarray([r.t_first - r.t_arrival for r in reqs])
        k = max(len(reqs) // 5, 1)
        tokens = sum(len(r.generated) for r in reqs)
        span = max(r.t_done for r in reqs) - reqs[0].t_arrival
        print(json.dumps({
            "rate_rps": float(rate), "requests": len(reqs),
            "wall_s": wall, "tokens_per_s": tokens / span,
            "ttft_p50_ms": 1e3 * float(np.median(ttft)),
            "ttft_p90_ms": 1e3 * float(np.percentile(ttft, 90)),
            "wait_first_fifth_ms": 1e3 * float(wait[:k].mean()),
            "wait_last_fifth_ms": 1e3 * float(wait[-k:].mean()),
            "slots": int(slots),
            "memory_peak_bytes": bench.memory_peak_bytes(devices)}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(*sys.argv[1:]))
