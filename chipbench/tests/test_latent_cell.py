"""Tests of the latent-attention MoE cell (``moonlight-16b-a3b.docqa``) on
the CPU at small widths, and of its readers on a decode trace recorded on
a TPU v5e.

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest chipbench/tests -q
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import bench  # noqa: E402
import flops_latent  # noqa: E402
import trace_reduce  # noqa: E402

CELL = "moonlight-16b-a3b.docqa"
FIXTURE = BENCH / "fixtures" / "moonlight-16b-a3b.decode.json"
SEED = 2 ** 31 + 12345
READERS = ("decode_ms.latent_attn", "latent_attn_hbm", "decode_ms.experts",
           "experts_hbm")

# every kind of layer and the router's published semantics, at widths a
# CPU runs in seconds; 4 of 16 experts held, as 8 of 64 on the chip
SMALL = dict(num_hidden_layers=3, hidden_size=256, num_attention_heads=4,
             num_key_value_heads=4, kv_lora_rank=64, qk_nope_head_dim=32,
             qk_rope_head_dim=16, v_head_dim=32, intermediate_size=512,
             moe_intermediate_size=128, n_routed_experts=4,
             published_n_routed_experts=16, num_experts_per_tok=4,
             vocab_size=16384)
# in float32 the program's gaps read 0 at this size (its widest is
# rounding only); the int8 control's mean reads far above, its 90th
# percentile 0: at 3 layers few of its tokens move (CPU).  The chip
# cell's limits come from chip readings (PERF.md, tools/gap_study.py).
SMALL_LIMIT = 1e-4


def _cell(seconds=2.0):
    cell = bench.find_cell(CELL, SEED, seconds, False)
    cell.config.update(SMALL)
    cell.config["serving"].update(batch_slots=4, max_len=256,
                                  prefill_bucket=32, compute_dtype="float32",
                                  param_dtype="float32")
    cell.traffic.update(requests=6, limit_mean_logit_gap=SMALL_LIMIT,
                        limit_p90_logit_gap=SMALL_LIMIT,
                        prompt={"median": 48, "sigma": 0.5, "min": 16,
                                "max": 96},
                        output={"median": 48, "sigma": 0.5, "min": 24,
                                "max": 64})
    return cell


def _run(cell):
    drv = bench.load_module(BENCH / "drivers" / "serve_latent.py",
                            "drv_serve_latent")
    import jax
    return drv.run(cell, jax.devices(), time.perf_counter())


def test_the_cell_and_its_metrics_are_listed():
    spec = bench.benchmark_spec()
    cell = bench.find_cell(CELL, 1, 1.0, False)
    assert cell.chips == 1 and cell.traffic["driver"] == "serve_latent"
    names = {m["name"] for m in cell.per_layer}
    assert set(READERS) <= names
    assert {"prefill_ms", "decode_step_ms", "decode_mfu", "device_idle.serve",
            "queue_wait_ms"} <= names
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                    "setup_s"]
    c = {c["name"]: c for c in spec["configs"]}["moonlight-16b-a3b"]
    assert c["reduced"] == ["n_routed_experts"]


def test_driver_builds_the_registry_entry():
    """The model the driver builds from the configuration's published
    keys is the registry's entry with this chip's held experts and the
    serving dtypes: widths, router semantics, norms and RoPE agree."""
    import dataclasses
    from repro.configs import get_arch
    drv = bench.load_module(BENCH / "drivers" / "serve_latent.py",
                            "drv_serve_latent")
    cfg = bench.find_cell(CELL, 1, 1.0, False).config
    got = drv.build_arch(cfg)
    want = get_arch(cfg["name"])
    want = dataclasses.replace(
        want, source=got.source,
        param_dtype=cfg["serving"]["param_dtype"],
        compute_dtype=cfg["serving"]["compute_dtype"],
        moe=dataclasses.replace(want.moe,
                                held_experts=cfg["n_routed_experts"]))
    assert got == want


def test_latent_cell_is_correct_unbroken():
    res = _run(_cell())
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["token_altered", "half_left_out"])
def test_latent_cell_catches_fault(monkeypatch, fault):
    import jax.numpy as jnp
    import repro.serve.engine as eng
    orig = eng._make_masked_step

    def make(model):
        step = orig(model)

        def broken(params, cache, tok, pos, active, buf, w, *load):
            if fault == "half_left_out":
                # the upper half of the slots is never decoded
                half = jnp.arange(active.shape[0]) < active.shape[0] // 2
                active = active & half
            nxt, cache, buf, *load = step(params, cache, tok, pos, active,
                                          buf, w, *load)
            if fault == "token_altered":
                nxt = (nxt + 1) % model.cfg.vocab_size
                buf = buf.at[:, w].set(nxt)
            return (nxt, cache, buf, *load)
        return broken

    monkeypatch.setattr(eng, "_make_masked_step", make)
    res = _run(_cell())
    assert not res["correct"]


def test_latent_int8_control_fails_the_limit():
    cell = _cell()
    cfg = cell.config
    ref = cell.reference()
    params = ref.weights(cfg, SEED)
    rng = np.random.default_rng(0)
    seqs = []
    for n in (100, 160):
        toks = rng.integers(1, cfg["vocab_size"], n).astype(np.int32)
        seqs.append((toks, 0, np.zeros(n, np.int32)))
    ctrl = ref.served_gaps(cfg, params, seqs, lower="int8", block=64)
    drv = bench.load_module(BENCH / "drivers" / "serve_latent.py",
                            "drv_serve_latent")
    stats = drv.gap_stats(ctrl)
    # the control fails the cell by one of its limits
    assert stats["mean"] > cell.traffic["limit_mean_logit_gap"] \
        or stats["p90"] > cell.traffic["limit_p90_logit_gap"]


def test_counts_on_hand_computed_shapes():
    cfg = bench.find_cell(CELL, 1, 1.0, False).config
    # attention per layer: q 2048*16*192 + kv_a 2048*576
    # + kv_b 512*16*256 + o 16*128*2048 = 13.76M weights
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    dense = (27 * attn + 3 * 2048 * 11264
             + 26 * (2048 * 64 + 3 * 2048 * 2816) + 2048 * 163840)
    assert flops_latent.dense_weights_per_token(cfg) == dense
    assert flops_latent.token_flops(cfg, 10) == \
        2 * dense + 2 * 16 * (576 + 512) * 10 * 27
    assert flops_latent.expert_bytes(cfg, 2) == 2 * 3 * 2048 * 1408 * 2
    assert flops_latent.latent_bytes(cfg, 8192) * 16 == \
        16 * 8192 * 27 * 576 * 2           # the whole 16-slot cache


def _fixture_ctx():
    if not FIXTURE.exists():
        pytest.skip("no decode trace has been recorded on a TPU yet "
                    "(chipbench/tools/record_latent_fixture.py)")
    fx = json.loads(FIXTURE.read_text())
    devices = {}
    for plane, i, a, b in fx["ops"]:
        devices.setdefault(plane, []).append((fx["names"][i], a, b))
    ctx = {k: fx[k] for k in ("decode_steps", "decode_intervals_ns",
                              "latent_bytes", "expert_bytes", "peaks")}
    ctx["events"] = {"devices": devices, "spans": []}
    ctx["config"] = bench.find_cell(CELL, 1, 1.0, False).config
    return ctx


@pytest.mark.parametrize("metric", READERS)
def test_reader_on_the_chip_decode_trace(metric):
    ctx = _fixture_ctx()
    mod = bench.load_module(BENCH / "metrics" / f"{metric}.py",
                            "m_" + metric.replace(".", "_"))
    v = mod.read(ctx)
    assert v is not None and np.isfinite(v) and v > 0
    if metric.endswith("_hbm"):
        assert v < 100.0
    # another program's chip trace holds none of the cell's operations
    other = trace_reduce.load_events(BENCH / "fixtures"
                                     / "v5e_trace.xplane.pb")
    ops = [e for evs in other["devices"].values() for e in evs]
    span = [(min(a for _, a, _ in ops), max(b for _, _, b in ops))]
    assert mod.read(dict(ctx, events=other,
                         decode_intervals_ns=span)) is None
