"""Serving cells of a latent-attention MoE decoder (moonlight-16b-a3b):
``serve.engine.ServeEngine`` on one chip's share of the experts, at the
published widths.

The same window, traffic and checks as ``drivers/serve.py``, whose
request generator it reuses: every request queued at the start (or on
its schedule), set-up warming every prefill bucket and every slot, the
requests metered after the window, and a sample of them (the longest
among them) read by the configuration's float32 reference.  What
differs: the model is built from the configuration's published keys
(latent attention, the leading dense layer, the sigmoid router over the
published expert count, this chip's held experts), its FLOPs and bytes
come from ``flops_latent`` with the held experts' share from the
engine's own load counter, the traced run hands the per-layer
readers the counter, the configuration and the latent bytes read, and
the served gaps are held to their 90th percentile besides their mean
(``gap_stats``).
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import time

import numpy as np

import bench
import flops_latent as F
import trace_reduce

make_requests = bench.load_module(bench.BENCH / "drivers" / "serve.py",
                                  "driver_serve").make_requests


def build_arch(cfg: dict):
    """The serving ``ArchConfig``: the published keys, the router over
    the published expert count, this chip's held experts."""
    from repro.configs import arch_from_hf
    srv = cfg["serving"]
    arch = arch_from_hf(
        dict(cfg, n_routed_experts=cfg["published_n_routed_experts"]),
        param_dtype=srv["param_dtype"], compute_dtype=srv["compute_dtype"])
    return dataclasses.replace(arch, moe=dataclasses.replace(
        arch.moe, held_experts=cfg["n_routed_experts"]))


def gap_stats(gaps) -> dict:
    """The compared numbers of the served gaps of every checked token:
    their mean, and the gap that nine in ten tokens stay within.

    With random weights the router's selections (sigmoid scores plus
    the correction bias) sit within rounding of each other at many
    positions, so bfloat16 and the float32 reference choose different
    held experts at some of them; a different expert moves the hidden
    state, and the served token there scores up to ~1 below the
    reference's best (PERF.md).  Those positions set the mean, and at a lower
    precision they grow only about threefold.  The 90th percentile
    reads the rounding of the other nine in ten instead, which a lower
    precision moves by more than fivefold on the chip."""
    g = np.concatenate(gaps)
    return {"mean": float(g.mean()), "p90": float(np.percentile(g, 90))}


def run(cell, devices, t_process: float) -> dict:
    import jax
    from repro.core.tracing import RegionTracer
    from repro.fleet.config import PipelineConfig, TrackConfig
    from repro.launch.serve import LEAD_S, timeline_traces
    from repro.models import Model
    from repro.serve.engine import Request, ServeEngine
    cfg, traffic = cell.config, cell.traffic
    srv = cfg["serving"]
    clock = bench.CompileClock()
    spans = bench.Spans(profiling=cell.trace)
    ref = cell.reference()

    model = Model(build_arch(cfg))
    want = {k: tuple(s) for k, s in ref.leaf_shapes(cfg).items()}
    got = {"/".join(str(getattr(k, "key", k)) for k in path): tuple(s.shape)
           for path, s in jax.tree_util.tree_leaves_with_path(
               model.param_structs())}
    if want != got:
        diff = sorted(set(want.items()) ^ set(got.items()))[:4]
        raise SystemExit(f"chipbench: {cfg['name']} widths differ from the "
                         f"serving model's: {diff}")
    params = jax.block_until_ready(ref.weights(cfg, cell.seed))
    engine = ServeEngine(model, params, batch_slots=srv["batch_slots"],
                         max_len=srv["max_len"],
                         prefill_bucket=srv["prefill_bucket"],
                         flush_interval=srv["flush_interval"])
    bucket = srv["prefill_bucket"]
    reqs = make_requests(traffic, cell.seconds, cell.seed,
                         cfg["vocab_size"])
    buckets = sorted({-(-len(p) // bucket) * bucket for _, p, _ in reqs})
    # one request per bucket, and at least one per slot, so that every
    # prefill shape and every slot's admission runs before the window
    n_warm = max(len(buckets), srv["batch_slots"])
    warm = [Request(rid=-1 - i,
                    prompt=np.ones((buckets[i % len(buckets)],), np.int32),
                    max_new_tokens=srv["flush_interval"] + 2)
            for i in range(n_warm)]
    engine.run(warm)
    # the window's schedule and counters start clean
    engine.tracer = RegionTracer()
    engine.segments = []
    engine._requests = {}
    engine.tokens_emitted = engine.requests_served = 0
    engine.route_assignments = engine.route_pairs = 0
    compiles0, compile_s0 = clock.compiles, clock.total
    requests = [Request(rid=i, prompt=p, max_new_tokens=m, arrival_s=a)
                for i, (a, p, m) in enumerate(reqs)]

    trace_dir = bench.ROOT / ".bench_trace" / cell.name
    if cell.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        bench.start_trace(trace_dir)
    t_start = time.perf_counter()
    setup_s = t_start - t_process
    with spans.span("window"):
        engine.run(requests, respect_arrivals=True)
    t_end = time.perf_counter()
    if cell.trace:
        jax.profiler.stop_trace()
    compiles_in_window = clock.compiles - compiles0
    mem_peak = bench.memory_peak_bytes(devices)

    ttft = np.asarray([r.t_first - r.t_arrival for r in requests])
    tpot = np.asarray([(r.t_done - r.t_first) / max(len(r.generated) - 1, 1)
                       for r in requests])
    tokens = sum(len(r.generated) for r in requests)
    last_done = max(r.t_done for r in requests)
    window_s = last_done - requests[0].t_arrival
    lag = [r.t_admitted - r.t_arrival for r in requests]
    e2e = {"setup_s": setup_s, "tokens_per_s": tokens / window_s}
    for m in cell.end_to_end:
        kind, _, q = m["name"].partition("_p")
        if kind in ("ttft", "tpot") and q.endswith("_ms"):
            vals = ttft if kind == "ttft" else tpot
            e2e[m["name"]] = 1e3 * float(np.percentile(vals, int(q[:-3])))
    tail = {q: bench.percentile_with_tail(v)[0]
            for q, v in (("ttft", ttft), ("tpot", tpot))}
    bench.log(f"{cell.name}: set-up {setup_s:.3f} s ({compiles0} "
              f"compilations, {compile_s0:.3f} s); {len(requests)} "
              f"requests, {tokens} tokens in {window_s:.3f} s; ttft p50 "
              f"{1e3 * np.median(ttft):.1f} ms, tpot p50 "
              f"{1e3 * np.median(tpot):.2f} ms, admission wait max "
              f"{1e3 * max(lag):.1f} ms; tail with 10 beyond: {tail}; peak "
              f"{mem_peak / 1e9:.3f} GB; held-expert assignments "
              f"{engine.route_assignments}, (layer, expert) pairs hit "
              f"{engine.route_pairs}")

    # -- device trace -----------------------------------------------------
    summary = events = None
    if cell.trace:
        events = trace_reduce.load_events(trace_reduce.find_xplane(trace_dir))
        summary = trace_reduce.reduce(events, n_devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"trace": summary, "events": events, "config": cfg,
           "peaks": (bench.peaks_for(devices[0].device_kind)
                     if cell.trace else None)}
    if cell.trace:
        win = [s for s in events["spans"] if s[0] == "window"][0]
        t0_host = [s for s in spans.events if s[0] == "window"][0][1]
        # the engine's times are seconds after its tracer's own t0
        off_ns = win[1] + (engine.tracer.t0 - t0_host) * 1e9
        ctx["request_intervals_ns"] = [
            (r.t_arrival * 1e9 + off_ns, r.t_done * 1e9 + off_ns)
            for r in requests]
        ctx["prefill_s"] = [b - a for n, a, b in
                            engine.tracer.phases(depth=0, name="prefill")]
        decode = engine.tracer.phases(depth=0, name="decode")
        dec = [b - a for n, a, b in decode]
        ctx["decode_intervals_ns"] = [(a * 1e9 + off_ns, b * 1e9 + off_ns)
                                      for _, a, b in decode]
        steps = sum(int(s.tokens[0]) for s in engine.segments
                    if s.kind == "decode" and s.tokens)
        ctx["decode_s"], ctx["decode_steps"] = sum(dec), steps
        flop = F.expert_flops(cfg, engine.route_assignments)
        latent = 0
        for r in requests:
            lb = -(-len(r.prompt) // bucket) * bucket
            for j in range(1, len(r.generated)):
                flop += F.token_flops(cfg, lb + j)
                latent += F.latent_bytes(cfg, lb + j)
        ctx["decode_flops"], ctx["latent_bytes"] = flop, latent
        ctx["expert_bytes"] = F.expert_bytes(cfg, engine.route_pairs)

    # -- metering: per-request bills conserve the phase totals -------------
    traces, _ = timeline_traces(engine, seed=cell.seed % (2 ** 31))
    mcfg = PipelineConfig(track=TrackConfig(track=False))
    report = engine.attribute_requests(traces, t_shift=LEAD_S, config=mcfg)
    fused = engine.attribute_phases(traces, t_shift=LEAD_S, fuse=True,
                                    streaming=True, config=mcfg)
    totals = np.asarray([[p.energy_j for p in row] for row in fused.values()])
    meter_err = float(report.conservation_rel_err(totals))
    billed = len(report)

    # -- the reference, once the engine's state is freed ------------------
    served = [(r.prompt, r.generated, -(-len(r.prompt) // bucket) * bucket)
              for r in requests]
    del engine, params, report, fused
    gc.collect()
    rng = np.random.default_rng((cell.seed + 1) % (2 ** 63))
    longest = int(np.argmax([lb + len(g) for _, g, lb in served]))
    others = [i for i in range(len(served)) if i != longest]
    k = min(int(traffic["check_requests"]) - 1, len(others))
    pick = [longest] + sorted(rng.choice(others, k, replace=False).tolist())
    seqs = []
    for i in pick:
        prompt, gen, lb = served[i]
        toks = np.zeros((lb + len(gen) - 1,), np.int32)
        toks[lb - len(prompt):lb] = prompt
        toks[lb:] = gen[:-1]
        seqs.append((toks, lb - 1, np.asarray(gen, np.int32)))
    t_ref = time.perf_counter()
    ref_params = ref.weights(cfg, cell.seed)
    gaps = ref.served_gaps(cfg, ref_params, seqs)
    control = low = None
    if cell.control:
        # int8 weights in the program's place, at the same positions
        low = ref.served_gaps(cfg, ref_params, seqs, lower="int8")
        control = gap_stats(low)
        bench.log(f"{cell.name}: int8 control gap mean "
                  f"{control['mean']!r}, p90 {control['p90']!r}, widest "
                  f"{float(max(g.max() for g in low))!r}")
    del ref_params
    got = gap_stats(gaps)
    n_tok = int(sum(len(g) for g in gaps))
    bench.log(f"{cell.name}: reference read {n_tok} served tokens of "
              f"{len(pick)} requests in {time.perf_counter() - t_ref:.3f} "
              f"s; gap mean {got['mean']!r}, p90 {got['p90']!r}, widest "
              f"{float(max(g.max() for g in gaps))!r}")
    limit = float(traffic["limit_mean_logit_gap"])
    limit_p90 = float(traffic["limit_p90_logit_gap"])
    per_req_bad = sum(int(g.mean() > limit) for g in gaps)
    complete = all(len(r.generated) == r.max_new_tokens for r in requests)
    checks = [
        {"name": "served_logit_gap_mean", "value": got["mean"], "op": "<=",
         "limit": limit, "ok": got["mean"] <= limit},
        {"name": "served_logit_gap_p90", "value": got["p90"], "op": "<=",
         "limit": limit_p90, "ok": got["p90"] <= limit_p90},
        {"name": "meter_conservation_rel_err", "value": meter_err,
         "op": "<=", "limit": traffic["limit_meter_rel"],
         "ok": meter_err <= traffic["limit_meter_rel"]},
        {"name": "requests_billed", "value": billed, "op": "==",
         "limit": len(requests), "ok": billed == len(requests)},
        {"name": "compiles_in_window", "value": compiles_in_window,
         "op": "==", "limit": 0, "ok": compiles_in_window == 0},
    ]
    correct = complete and all(c["ok"] for c in checks)
    # the checked sequences and their gaps, token by token, for the tools
    # that set the limit (``run.py`` reads none of them)
    return {"correct": correct, "attempted": len(pick), "control": control,
            "failed": per_req_bad + int(not complete),
            "checks": checks, "memory_peak_bytes": mem_peak,
            "end_to_end": e2e, "ctx": ctx, "checked": seqs, "gaps": gaps,
            "control_gaps": low}
