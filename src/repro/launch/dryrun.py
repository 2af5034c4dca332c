"""Multi-pod dry-run: ``.lower().compile()`` every (arch × shape × mesh)
cell.

For each cell this driver:
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. lowers the right step (train_step / prefill / decode_step) with the
     sharding plan's in/out shardings and ShapeDtypeStruct inputs,
  3. compiles, records ``memory_analysis()`` + ``cost_analysis()``,
  4. parses the post-SPMD HLO for collective operand bytes, and
  5. appends everything to ``results/dryrun/<cell>.json`` for §Roofline.

Usage::

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape train_4k [--multi-pod] [--all] [--out results/dryrun]
"""
import argparse
import json
import os
import re
import time
import traceback
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, cell_is_runnable, get_arch, get_shape
from repro.distributed.sharding import make_plan
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_costs import analyze as hlo_analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models import Model
from repro.train.loop import make_train_step, pick_microbatches
from repro.train.optimizer import optimizer_for, schedule_for

# v5e constants for the roofline terms (see EXPERIMENTS.md §Roofline)
PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link

_COLL_RE = re.compile(
    r"^\s*(?:%\S+\s*=\s*)?"
    r"(\((?:[a-z0-9]+\[[^\]]*\][^,)]*,?\s*)+\)|[a-z0-9]+\[[^\]]*\]\S*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)",
    re.M)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(text):
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text):
    """Per-collective-type byte totals from post-SPMD optimized HLO.

    Shapes in the partitioned module are per-device; we report (a) raw
    result-shape bytes per op type and (b) an estimated per-chip link-byte
    cost using ring-algorithm factors (all-reduce ~ 2x shard bytes).
    """
    by_type = {}
    link_bytes = 0.0
    for m in _COLL_RE.finditer(hlo_text):
        shape_text, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_text)
        by_type[op] = by_type.get(op, 0) + b
        if op == "all-reduce":
            link_bytes += 2.0 * b
        else:
            link_bytes += float(b)
    return by_type, link_bytes


def _tree_bytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def lower_cell(arch_name, shape_name, *, multi_pod=False, compile_opts=None):
    """Lower + compile one cell; returns (record, compiled)."""
    arch = get_arch(arch_name)
    shape = get_shape(shape_name)
    ok, why = cell_is_runnable(arch, shape)
    if not ok:
        return {"arch": arch_name, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": why}, None

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(arch)
    model.mesh = mesh
    specs = input_specs(arch, shape, model)
    exact_params = sum(s.size for s in jax.tree.leaves(specs["params"]))
    plan = make_plan(mesh, exact_params)
    axes = model.param_logical_axes()
    param_sh = plan.param_shardings(axes, specs["params"])
    batch_sh = plan.batch_shardings(specs["batch"])
    scalar_sh = NamedSharding(mesh, P())

    t0 = time.time()
    if shape.kind == "train":
        opt = optimizer_for(arch)
        lr_fn = schedule_for(arch.name)
        micro = pick_microbatches(arch, shape, plan.dp_size())
        grad_hook = None
        scheme = os.environ.get("REPRO_GRAD_COMPRESS")   # §Perf knob
        if scheme:
            from repro.distributed.compression import make_grad_hook
            grad_hook = make_grad_hook(scheme)
        step_fn = make_train_step(model, opt, lr_fn, micro=micro,
                                  grad_hook=grad_hook)
        # optimizer slots inherit the param sharding rules
        opt_sh = _opt_shardings(mesh, plan, axes, specs["params"],
                                specs["opt_state"])
        jf = jax.jit(
            step_fn,
            in_shardings=(param_sh, opt_sh, batch_sh, scalar_sh),
            out_shardings=(param_sh, opt_sh, None),
            donate_argnums=(0, 1))
        with mesh:
            lowered = jf.lower(specs["params"], specs["opt_state"],
                               specs["batch"], specs["step"])
        extra = {"micro_batches": micro}
    elif shape.kind == "prefill":
        cache_sh = plan.cache_shardings(specs["cache"], shape.global_batch)
        jf = jax.jit(
            model.prefill,
            in_shardings=(param_sh, batch_sh, cache_sh),
            out_shardings=(None, cache_sh),
            donate_argnums=(2,))
        with mesh:
            lowered = jf.lower(specs["params"], specs["batch"],
                               specs["cache"])
        extra = {}
    else:  # decode
        cache_sh = plan.cache_shardings(specs["cache"], shape.global_batch)
        jf = jax.jit(
            model.decode_step,
            in_shardings=(param_sh, batch_sh, cache_sh, scalar_sh),
            out_shardings=(None, cache_sh),
            donate_argnums=(2,))
        with mesh:
            lowered = jf.lower(specs["params"], specs["batch"],
                               specs["cache"], specs["pos"])
        extra = {}
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile(compiler_options=compile_opts)
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    acc = hlo_analyze(hlo)            # trip-count-aware (see hlo_costs.py)
    coll_by_type = acc["collectives"]
    link_bytes = acc["collective_link_bytes"]
    del hlo

    n_chips = mesh.size
    flops_dev = float(acc["flops"])
    bytes_dev = float(acc["bytes"])
    n_active = arch.active_param_count()
    tokens = shape.tokens if shape.kind != "decode" else shape.global_batch
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * n_active * tokens

    record = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok", "kind": shape.kind,
        "n_chips": n_chips,
        "fsdp": plan.fsdp,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "param_bytes_global": _tree_bytes(specs["params"]),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
            "per_device_total": (mem.argument_size_in_bytes
                                 + mem.output_size_in_bytes
                                 + mem.temp_size_in_bytes
                                 - mem.alias_size_in_bytes),
        },
        "hlo_flops_per_device": flops_dev,
        "hlo_bytes_per_device": bytes_dev,
        "xla_cost_analysis": {          # raw XLA numbers (loops counted once)
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "collectives": coll_by_type,
        "collective_link_bytes_per_device": link_bytes,
        "roofline": {
            "compute_s": flops_dev / PEAK_FLOPS,
            "memory_s": bytes_dev / HBM_BW,
            "collective_s": link_bytes / ICI_BW,
        },
        "model_flops_global": model_flops,
        "useful_flops_ratio": (
            model_flops / (flops_dev * n_chips) if flops_dev else 0.0),
        **extra,
    }
    terms = record["roofline"]
    record["bottleneck"] = max(terms, key=terms.get)
    return record, compiled


def _opt_shardings(mesh, plan, axes_tree, param_structs, opt_structs):
    """Optimizer slots follow param shardings; factored adafactor slots drop
    the reduced dim; counters replicate."""
    scalar = NamedSharding(mesh, P())
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    if "m" in opt_structs:          # adamw: m/v mirror the params exactly
        param_sh = plan.param_shardings(axes_tree, param_structs)
        return {"m": param_sh, "v": param_sh, "count": scalar}

    def slot_sh(axes, p):           # adafactor
        shp = p.shape
        if len(axes) >= 2:
            return {
                "vr": NamedSharding(mesh, plan.spec_for(axes[:-1],
                                                        shp[:-1])),
                "vc": NamedSharding(mesh, plan.spec_for(
                    axes[:-2] + axes[-1:], shp[:-2] + shp[-1:])),
            }
        return {"v": NamedSharding(mesh, plan.spec_for(axes, shp))}

    slots = jax.tree.map(slot_sh, axes_tree, param_structs, is_leaf=is_axes)
    return {"slots": slots, "count": scalar}


def run_cells(cells, out_dir, meshes=(False, True)):
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    results = []
    for arch_name, shape_name in cells:
        for multi_pod in meshes:
            tag = f"{arch_name}__{shape_name}__" \
                  f"{'2x16x16' if multi_pod else '16x16'}"
            path = out / f"{tag}.json"
            if path.exists():
                rec = json.loads(path.read_text())
                print(f"[cached] {tag}: {rec['status']}")
                results.append(rec)
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                rec, compiled = lower_cell(arch_name, shape_name,
                                           multi_pod=multi_pod)
                del compiled
            except Exception as e:       # noqa: BLE001 — record + continue
                rec = {"arch": arch_name, "shape": shape_name,
                       "mesh": "2x16x16" if multi_pod else "16x16",
                       "status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()[-4000:]}
            path.write_text(json.dumps(rec, indent=1))
            status = rec["status"]
            msg = rec.get("bottleneck", rec.get("reason",
                                                rec.get("error", "")))[:80]
            print(f"[dryrun] {tag}: {status} {msg}", flush=True)
            results.append(rec)
    return results


def main():
    # placeholder host devices for the production meshes: must be set
    # before the first device query initializes the CPU backend
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true",
                    help="only the 2x16x16 mesh (default: both meshes)")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    if args.all:
        cells = [(a, s) for a in ARCHS for s in SHAPES]
    else:
        archs = [args.arch] if args.arch else list(ARCHS)
        shapes = [args.shape] if args.shape else list(SHAPES)
        cells = [(a, s) for a in archs for s in shapes]

    if args.multi_pod:
        meshes = (True,)
    elif args.single_pod_only:
        meshes = (False,)
    else:
        meshes = (False, True)
    results = run_cells(cells, args.out, meshes=meshes)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"of {len(results)} cells")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
