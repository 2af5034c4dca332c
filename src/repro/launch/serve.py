"""Serving entry point: batched requests against any arch at its published
widths (bf16 parameters), with phase-level power/energy attribution of
the serving timeline.  ``--reduced`` serves a tiny same-family config
instead, for CPU runs.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
      --requests 12 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --reduced   # on a CPU
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_arch, reduced as reduce_cfg
from repro.core import NodeFabric, ToolSpec, attribute_energy, phase_power
from repro.core.measurement_model import CHIP_IDLE_W
from repro.core.power_model import occupancy_power
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serve.engine import Request, ServeEngine

OCC = {"admission": (0.0, 0.05, 0.0), "prefill": (1.0, 0.5, 0.1),
       "decode": (0.15, 1.0, 0.1)}
LEAD_S = 0.05


def timeline_traces(engine, *, seed: int = 0):
    """Sensor traces of one 4-chip node whose power follows the engine's
    recorded phases (occupancy model ``OCC``), after ``LEAD_S`` of idle.
    -> (traces, phases shifted by ``LEAD_S``)."""
    shifted = [(n, a + LEAD_S, b + LEAD_S)
               for n, a, b in engine.tracer.phases(depth=0)]
    watts = {n: {"watts": occupancy_power(*OCC.get(n, (0, 0.1, 0)))}
             for n, _, _ in shifted}
    truth = phase_power([("__lead__", 0.0, LEAD_S)] + shifted,
                        {**watts, "__lead__": {"watts": CHIP_IDLE_W}})
    traces = NodeFabric(chip_truths=[truth] * 4).sample_all(ToolSpec(),
                                                            seed=seed)
    return traces, shifted


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--reduced", action="store_true",
                    help="serve a tiny same-family config (CPU runs)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    else:
        # bf16 halves the parameter bytes: a 3B model then leaves most
        # of one 16 GB chip to the KV cache
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, batch_slots=args.slots,
                         max_len=args.max_len)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               6 + i % 9),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    results = engine.run(reqs)
    n_tokens = sum(len(v) for v in results.values())
    print(f"served {len(results)} requests, {n_tokens} tokens")

    traces, shifted = timeline_traces(engine)
    agg = {}
    for p in attribute_energy(traces["chip0_energy"], shifted):
        a = agg.setdefault(p.phase, [0.0, 0.0])
        a[0] += p.energy_j
        a[1] += p.t_end - p.t_start
    print("\nper-phase serving energy (chip0 ΔE/Δt):")
    total_e = sum(a[0] for a in agg.values())
    for name, (e, t) in sorted(agg.items()):
        print(f"  {name:10s} {e:9.2f} J ({100*e/max(total_e,1e-9):4.1f}%)"
              f"  {t:7.3f} s  {e/max(t,1e-9):7.1f} W")
    if n_tokens:
        print(f"\nenergy per generated token: {total_e/n_tokens:.2f} J")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
