"""Beyond-paper perf features: f8 KV cache, head-pinning knob, fusion-aware
HLO byte accounting."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced
from repro.models import Model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_f8_kv_cache_decode_accuracy(monkeypatch):
    """f8 KV cache must track the bf16-cache decode closely."""
    cfg = reduced(get_arch("llama3.2-3b"))
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 10), 0, cfg.vocab_size)

    def run():
        cache = model.init_cache(1, 32)
        step = jax.jit(model.decode_step)
        lg = None
        for i in range(10):
            lg, cache = step(params, {"tokens": toks[:, i:i + 1]}, cache,
                             jnp.asarray(i, jnp.int32))
        return np.asarray(lg[0, 0], np.float32)

    ref = run()
    monkeypatch.setenv("REPRO_KV_DTYPE", "float8_e4m3fn")
    f8 = run()
    # top-1 greedy decision preserved, logits close in probability space
    assert np.argmax(ref) == np.argmax(f8)
    p_ref = np.exp(ref - ref.max()) / np.exp(ref - ref.max()).sum()
    p_f8 = np.exp(f8 - f8.max()) / np.exp(f8 - f8.max()).sum()
    assert np.abs(p_ref - p_f8).max() < 0.05


def test_hlo_costs_fusion_slice_accounting():
    """A scanned dynamic-slice must charge per-slice bytes, not the whole
    buffer per step (the xlstm 13x correction)."""
    from jax import lax
    from repro.launch.hlo_costs import analyze

    def scanned_slices(big):
        def body(c, i):
            sl = lax.dynamic_slice_in_dim(big, i * 8, 8, axis=0)
            return c + jnp.sum(sl), None
        out, _ = lax.scan(body, jnp.zeros(()), jnp.arange(64))
        return out

    big = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
    a = analyze(jax.jit(scanned_slices).lower(big).compile().as_text())
    whole = 512 * 1024 * 4
    # 64 steps x per-slice (8x1024x4) traffic ~ one full pass; the old
    # accounting charged 64 x whole buffer
    assert a["bytes"] < 8 * whole, a["bytes"]


def test_attn_pin_preserves_numerics():
    """Head-pinned sharding is a layout hint only — identical outputs."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    code = textwrap.dedent("""
        import os, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs import get_arch, reduced
        from repro.models import Model
        cfg = reduced(get_arch("llama3.2-3b"))
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        batch = {"tokens": jnp.ones((8, 16), jnp.int32),
                 "labels": jnp.ones((8, 16), jnp.int32)}
        def run(pin):
            os.environ["REPRO_ATTN_HEAD_CONSTRAINT"] = pin
            model = Model(cfg)
            model.mesh = mesh
            params = model.init(jax.random.key(0))
            with mesh:
                loss, _ = jax.jit(model.forward_train)(params, batch)
            return float(loss)
        a, b = run("0"), run("1")
        assert abs(a - b) < 1e-3, (a, b)
        print("attn_pin numerics OK", a, b)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=420)
    assert r.returncode == 0, r.stdout + r.stderr


def test_ring_cache_bounds_local_layer_memory():
    cfg = reduced(get_arch("gemma2-27b"))
    model = Model(cfg)
    specs = model.cache_specs(2, 32)
    # pattern = (local, global): pos0 ring-bounded by window, pos1 full;
    # the sequence is the cache layout's last axis (G, B, Hkv, D, W)
    assert specs["pos0"]["kv"]["k"].shape[4] == cfg.sliding_window
    assert specs["pos1"]["kv"]["k"].shape[4] == 32
