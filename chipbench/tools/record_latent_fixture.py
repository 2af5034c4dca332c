"""Record the decode-step trace fixture of a latent-attention MoE cell on
the chip, for the tests of its per-layer readers.

  python chipbench/tools/record_latent_fixture.py [cell] [out.json]

Runs the cell's driver traced, at the cell's configuration, on a short
queue (one request per slot, 1,024-token prompts, 6 new tokens), and
writes the device operations that fall in the engine's decode regions,
with the readers' other inputs, as JSON (names stored once).  Prints
each reader's value and the longest decode operations.
"""
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import bench  # noqa: E402

READERS = ("decode_ms.latent_attn", "latent_attn_hbm", "decode_ms.experts",
           "experts_hbm")
KEEP = ("decode_steps", "decode_intervals_ns", "latent_bytes",
        "expert_bytes", "decode_flops", "peaks")


def main(cell_name="moonlight-16b-a3b.docqa",
         out=str(BENCH / "fixtures" / "moonlight-16b-a3b.decode.json")):
    cell = bench.find_cell(cell_name, 2 ** 31 + 99, 1.0, True)
    slots = cell.config["serving"]["batch_slots"]
    cell.traffic.update(requests=slots, check_requests=1,
                        prompt={"median": 1024, "sigma": 0.0, "min": 1024,
                                "max": 1024},
                        output={"median": 6, "sigma": 0.0, "min": 6,
                                "max": 6})
    devices = bench.require_chips(1)
    bench.compile_cache_dir()
    drv = bench.load_module(BENCH / "drivers"
                            / f"{cell.traffic['driver']}.py", "drv")
    ctx = drv.run(cell, devices, time.perf_counter())["ctx"]
    iv = ctx["decode_intervals_ns"]
    lo, hi = min(a for a, _ in iv), max(b for _, b in iv)
    names, ops = {}, []
    for plane, evs in ctx["events"]["devices"].items():
        for name, a, b in evs:
            if b > lo and a < hi:
                ops.append([plane, names.setdefault(name, len(names)),
                            a, b])
    fixture = {k: ctx[k] for k in KEEP}
    fixture.update(names=sorted(names, key=names.get), ops=ops)
    Path(out).write_text(json.dumps(fixture))
    for m in READERS:
        mod = bench.load_module(BENCH / "metrics" / f"{m}.py", "m")
        print(m, mod.read(ctx))
    per = {}
    for _, i, a, b in ops:
        per[i] = per.get(i, 0) + (b - a) * 1e-9
    by_name = sorted(names, key=names.get)
    for i, s in sorted(per.items(), key=lambda kv: -kv[1])[:40]:
        print(f"{s * 1e3:9.3f} ms  {by_name[i][:220]}")
    print("ops", len(ops), "names", len(names), "bytes",
          Path(out).stat().st_size)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(*sys.argv[1:]))
