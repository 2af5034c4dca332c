"""The latent decode kernel (``kernels/latent_decode``) in interpret mode
against its oracle, the decode path of ``mla_apply`` through it, and the
serve engine's count of the latent blocks a decode step reads."""
import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_arch, reduced
from repro.kernels.latent_decode import block_positions, ops
from repro.kernels.latent_decode.kernel import latent_decode_kernel
from repro.kernels.latent_decode.ref import latent_decode_ref

S = 3072
BLK = block_positions(S)        # 1024: three blocks a slot

# name: (layers G, layer read, heads, rank, cache rows C, positions)
CASES = {
    "ragged": (3, 2, 4, 32, 40, [0, BLK - 1, BLK, BLK + 476, S - 1]),
    "inactive_slots": (2, 1, 4, 32, 40, [0, 2000, 0, 0]),
    "layer0_unstacked": (1, 0, 4, 32, 40, [700, 0, S - 1]),
    "published_widths": (2, 1, 16, 512, 576, [BLK + 5, 17]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ref(case):
    """Every valid position of every slot is attended and nothing past
    it: the cache holds 1e3 past each slot's position, which any
    unmasked read would show.  Tolerance 5e-5 on outputs of order 1: the
    float32 query and probabilities enter each product as a bfloat16
    head plus its remainder, ~16 significant bits (errors ~1e-5)."""
    g, layer, h, rank, c, pos = CASES[case]
    b = len(pos)
    k1, k2 = jax.random.split(jax.random.key(len(case)))
    cache = jax.random.normal(k1, (g, b, c, S), jnp.float32)
    past = jnp.arange(S)[None, :] > jnp.asarray(pos)[:, None]     # (B, S)
    cache = jnp.where(past[None, :, None, :], 1e3, cache).astype(jnp.bfloat16)
    qc = 0.3 * jax.random.normal(k2, (b, h, c), jnp.float32)
    pos = jnp.asarray(pos, jnp.int32)
    kw = dict(rank=rank, scale=0.125)
    got = latent_decode_kernel(qc, cache, jnp.int32(layer), pos,
                               interpret=True, **kw)
    want = latent_decode_ref(qc, cache, jnp.int32(layer), pos, **kw)
    assert got.shape == (b, h, rank) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=5e-5)


def _mla_cfg():
    import dataclasses
    base = reduced(get_arch("moonlight-16b-a3b"))
    return dataclasses.replace(base, compute_dtype="float32")


def test_mla_decode_through_the_kernel(monkeypatch):
    """``mla_apply``'s one-token decode gives the same rows whether the
    cache read is the oracle or the kernel (steered here by replacing
    the oracle), for a stacked cache read at layer 1 with per-slot
    positions on both sides of a block edge (5e-5, as above)."""
    from repro.models.layers import init_params
    from repro.models.mla import mla_apply, mla_specs
    cfg = _mla_cfg()
    p = init_params(mla_specs(cfg), jax.random.key(1))
    b = 3
    cache = jax.random.normal(jax.random.key(2),
                              (2, b, cfg.mla.cache_width, 2 * BLK),
                              jnp.float32)
    x = jax.random.normal(jax.random.key(3), (b, 1, cfg.d_model))
    pos = jnp.asarray([5, BLK, 0], jnp.int32)

    def decode():
        return mla_apply(p, cfg, x, pos[:, None], cache=cache,
                         layer=jnp.int32(1), cache_index=pos)

    want, want_cache = decode()
    monkeypatch.setattr(ops, "latent_decode_ref",
                        functools.partial(latent_decode_kernel,
                                          interpret=True))
    got, got_cache = decode()
    np.testing.assert_array_equal(np.asarray(got_cache),
                                  np.asarray(want_cache))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=5e-5)


def _decode_span_stats(trace_dir):
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "repro.serve.decode":
                    st = dict(ev.stats)
                    out.append((ev.start_ns, int(st["n"]),
                                int(st["latent_blocks"]),
                                int(st["latent_blocks_held"])))
    return [o[1:] for o in sorted(out)]


def test_decode_span_counts_latent_blocks(tmp_path):
    """Two slots of a 2,048-position latent cache (two blocks each), a
    1,021-token prompt and a 5-token one, flushed every 4 steps.  Hand
    count: segment 1, 2 steps (the short request's), slot 0 at 1021 and
    1022, slot 1 at 5 and 6: one block each, 4; held 2 slots x 2 blocks
    x 2 steps = 8.  Segment 2, 4 steps, slot 0 alone at 1023..1026: 1 +
    2 + 2 + 2 = 7; held 2 x 2 x 4 = 16."""
    from repro.models import Model
    from repro.serve.engine import Request, ServeEngine
    cfg = _mla_cfg()
    model = Model(cfg)
    engine = ServeEngine(model, model.init(jax.random.key(0)),
                         batch_slots=2, max_len=2 * BLK, flush_interval=4)
    assert engine.latent_block == BLK

    def requests():
        rng = np.random.default_rng(0)
        return [Request(0, rng.integers(1, cfg.vocab_size, 1021), 7),
                Request(1, rng.integers(1, cfg.vocab_size, 5), 3)]

    engine.run(requests())                    # compiles every program
    with jax.profiler.trace(str(tmp_path)):
        engine.run(requests())
    assert _decode_span_stats(tmp_path) == [(2, 4, 8), (4, 7, 16)]


def test_dense_model_decode_span_has_no_latent_stats():
    from repro.models import Model
    from repro.serve.engine import ServeEngine
    cfg = reduced(get_arch("llama3.2-3b"))
    model = Model(cfg)
    engine = ServeEngine(model, model.init(jax.random.key(0)),
                         batch_slots=2, max_len=32)
    assert engine.latent_block == 0
    assert engine._latent_stats(np.zeros(2, np.int64),
                                np.ones(2, bool), 3) == {}
