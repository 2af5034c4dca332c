"""Read a serving cell's served logit gaps token by token over several
seeds, with its int8 control's, and for the first seeds the router
choices behind them, in one process.

  python chipbench/tools/gap_study.py CELL SECONDS OUT_DIR SEED0 N \
      N_STUDY [N_SEQ]

Runs the cell's driver once per seed (SEED0, SEED0 + 1, ...) with the
control on and writes ``OUT_DIR/gaps.<seed>.npz``: each checked
sequence's served gaps (``prog.<i>``) and the control's (``int8.<i>``).
For the first N_STUDY seeds it also runs the configuration's reference
again over the first N_SEQ checked sequences (all by default), with the
router's choices of every MoE layer recorded, in four ways: float32 at
the highest precision (``f32``), int8 weights (``int8``), float32 with
one-pass bfloat16 products (``bf16``, the nearest the reference comes
to the program's precision) and int8 weights routed as ``f32`` chose
(``int8_forced``).  Per served position it stores each way's gap
against ``f32`` (``gap.<way>.<i>``), the number of MoE layers whose held
experts it chose differently from ``f32`` (``flips.<way>.<i>``), the
smallest margin of ``f32``'s router over the layers between its 6th and
7th choice (``margin.<i>``) and ``f32``'s best logit (``best.<i>``).
Prints one JSON line per seed.
"""
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import bench  # noqa: E402

WAYS = {"f32": (None, "highest", False), "int8": ("int8", "highest", False),
        "bf16": (None, "default", False),
        "int8_forced": ("int8", "highest", True)}


def _router_pass(ref):
    """A jitted pass of ``ref``'s forward that records the router's
    choices: -> (logits at ``cols``, chosen experts (MoE layers, T, k),
    6th-minus-7th selection margin (MoE layers, T))."""
    import jax
    import jax.numpy as jnp

    def moe(p, x, m, forced_idx):
        scores = jax.nn.sigmoid(x @ p["router"])
        sel = scores + p["bias"]
        top, top_idx = jax.lax.top_k(sel, m["k"] + 1)
        margin = top[:, m["k"] - 1] - top[:, m["k"]]
        idx = top_idx[:, :m["k"]] if forced_idx is None else forced_idx
        gate = jnp.take_along_axis(scores, idx, -1)
        gate = gate / jnp.sum(gate, -1, keepdims=True) * m["scale"]
        w = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None],
                                      idx].set(gate)
        y = ref._swiglu(x, p["shared"]["w_gate"], p["shared"]["w_up"],
                        p["shared"]["w_down"])
        for e in range(m["held"]):
            y = y + w[:, e, None] * ref._swiglu(x, p["w_gate"][e],
                                                p["w_up"][e], p["w_down"][e])
        return y, idx, margin

    @functools.partial(jax.jit, static_argnames=(
        "dims", "eps", "theta", "lower", "prec", "forced", "block"))
    def run(params, tokens, cols, forced_idx, *, dims, eps, theta, lower,
            prec, forced, block):
        m = dict(dims)
        with jax.default_matmul_precision(prec):
            emb = params["embed"].astype(jnp.float32)
            if lower == "int8":
                emb = ref._quant_int8(emb, axis=1)
            x = emb[tokens]

            def block_fn(x, p, ffn):
                p = ref._f32(p, lower)
                x = x + ref._attention(p["core"],
                                       ref._rms(x, p["norm1"], eps), m,
                                       theta, eps, block)
                return ffn(p, ref._rms(x, p["norm2"], eps), x)

            def dense(x, p):
                return block_fn(x, p, lambda p, h, x: x + ref._swiglu(
                    h, p["ffn"]["w_gate"], p["ffn"]["w_up"],
                    p["ffn"]["w_down"])), None

            def routed(x, inp):
                p, fi = inp

                def ffn(p, h, x):
                    y, idx, margin = moe(p["ffn"], h, m,
                                         fi if forced else None)
                    return x + y, (idx, margin)
                return block_fn(x, p, ffn)

            x, _ = jax.lax.scan(dense, x, params["lead"]["pos0"])
            x, (idx, margin) = jax.lax.scan(
                routed, x, (params["layers"]["pos0"], forced_idx))
            h = ref._rms(x, params["final_norm"].astype(jnp.float32), eps)
            head = params["lm_head"].astype(jnp.float32)
            if lower == "int8":
                head = ref._quant_int8(head, axis=0)
            return h[cols] @ head, idx, margin
    return run


def study(cell, params, seqs, t_pad: int) -> dict:
    """The four ways over ``seqs`` (as the driver checks them)."""
    import jax.numpy as jnp
    ref = cell.reference()
    cfg = cell.config
    m = ref._dims(cfg)
    m["scale"] = float(cfg["routed_scaling_factor"])
    dims = tuple(sorted(m.items()))
    n_moe = m["L"] - m["lead"]
    run = _router_pass(ref)
    out = {}
    for i, (tk, first, served) in enumerate(seqs):
        toks = np.zeros((t_pad,), np.int32)
        toks[:len(tk)] = tk
        cols = jnp.arange(first, first + len(served))
        res = {}
        f32_idx = jnp.zeros((n_moe, t_pad, m["k"]), jnp.int32)
        for way, (lower, prec, forced) in WAYS.items():
            logits, idx, margin = run(
                params, jnp.asarray(toks), cols, f32_idx, dims=dims,
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]), lower=lower, prec=prec,
                forced=forced, block=min(512, t_pad))
            if way == "f32":
                f32_idx = idx
            res[way] = (np.asarray(logits), np.asarray(idx[:, cols]),
                        np.asarray(margin[:, cols]))
        ref_logits, ref_idx, ref_margin = res["f32"]
        best = ref_logits.max(axis=1)
        held = np.arange(m["E"]) < m["held"]

        def held_set(idx):                       # (layers, n, E) bool
            hot = np.zeros(idx.shape[:2] + (m["E"],), bool)
            np.put_along_axis(hot, idx, True, axis=-1)
            return hot & held
        ref_held = held_set(ref_idx)
        for way, (logits, idx, _) in res.items():
            pick = logits.argmax(axis=1)
            out[f"gap.{way}.{i}"] = best - ref_logits[np.arange(len(pick)),
                                                      pick]
            out[f"flips.{way}.{i}"] = np.any(held_set(idx) != ref_held,
                                             axis=-1).sum(axis=0)
        out[f"margin.{i}"] = ref_margin.min(axis=0)
        out[f"best.{i}"] = best
    return out


def main(name, seconds, out_dir, seed0, n, n_study, n_seq=None):
    devices = bench.require_chips(1)
    bench.compile_cache_dir()
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    for i in range(int(n)):
        cell = bench.find_cell(name, int(seed0) + i, float(seconds), False)
        cell.control = True
        drv = bench.load_module(
            BENCH / "drivers" / f"{cell.traffic['driver']}.py", "drv")
        res = drv.run(cell, devices, time.perf_counter())
        arrays = {f"prog.{j}": g for j, g in enumerate(res["gaps"])}
        arrays.update({f"int8.{j}": g
                       for j, g in enumerate(res["control_gaps"])})
        if i < int(n_study):
            t0 = time.perf_counter()
            seqs = res["checked"][:int(n_seq) if n_seq else None]
            params = cell.reference().weights(cell.config, cell.seed)
            t_pad = cell.config["serving"]["max_len"]
            arrays.update(study(cell, params, seqs, t_pad))
            del params
            bench.log(f"{name}: router study of {len(seqs)} sequences in "
                      f"{time.perf_counter() - t0:.1f} s")
        np.savez(out / f"gaps.{cell.seed}.npz", **arrays)
        print(json.dumps({"seed": cell.seed, "correct": res["correct"],
                          "checks": res["checks"],
                          "control": res["control"],
                          "e2e": res["end_to_end"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(*sys.argv[1:]))
