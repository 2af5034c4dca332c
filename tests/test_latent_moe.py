"""Latent attention and the dropless held-expert MoE of
``moonlight-16b-a3b``, against the plain float32 reference
(``models/reference_mla.py``), at small widths on the CPU.

Every comparison runs in float32.  The served model and the reference
take different routes to the same numbers: absorbed against decompressed
attention, grouped products over sorted rows against dense masked
experts, a cache against whole sequences.  So they agree to float32
rounding summed over a few layers; each tolerance below says how far
that goes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import Model
from repro.models import reference_mla as R
from repro.models.layers import init_params
from repro.models.mla import mla_apply, mla_specs
from repro.models.moe import (DENSE_TOKENS, _add_shared, _moe_dropless,
                              _route, moe_specs)
from repro.serve.engine import (Request, ServeEngine, _make_masked_step,
                                _scatter_slot)


def _cfg(held=4, experts=8, top_k=3):
    base = reduced(get_arch("moonlight-16b-a3b"))
    return dataclasses.replace(
        base, compute_dtype="float32",
        moe=dataclasses.replace(base.moe, num_experts=experts, top_k=top_k,
                                held_experts=held, num_shared_experts=2))


def _params(model, seed=0):
    """Seeded weights with non-zero norm offsets and selection bias, so
    that (1 + w) and the bias are exercised."""
    params = model.init(jax.random.key(seed))
    leaves, tdef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, v) in enumerate(leaves):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if "norm" in name or name.endswith("bias"):
            v = 0.3 * jax.random.normal(jax.random.key(100 + i), v.shape)
        out.append(v)
    return jax.tree_util.tree_unflatten(tdef, out)


def test_masked_decode_matches_reference_logits():
    """Prefill on a batch-1 scratch cache, scatter into a slot, then the
    masked step at per-slot positions (one slot idle): every step's
    logits equal the reference's full forward pass over the same
    tokens.  Tolerance 2e-4 absolute on logits of order 1: float32
    rounding of two orders of summation over three layers."""
    cfg = _cfg()
    model = Model(cfg)
    params = _params(model)
    rng = np.random.default_rng(1)
    lens, steps, max_len = (4, 9, 13), 5, 32
    slots = len(lens) + 1
    prompts = [rng.integers(1, cfg.vocab_size, n) for n in lens]
    fed = rng.integers(1, cfg.vocab_size, (slots, steps))
    prefill = jax.jit(model.prefill)
    cache = model.init_cache(slots, max_len)
    for b, pr in enumerate(prompts):
        _, c1 = prefill(params, {"tokens": jnp.asarray(pr, jnp.int32)[None]},
                        model.init_cache(1, max_len))
        cache = _scatter_slot(cache, c1, jnp.asarray(b, jnp.int32))
    step = jax.jit(model.decode_step)
    active = np.arange(slots) < len(lens)
    want = [np.asarray(R.forward(cfg, params, np.concatenate([pr, fed[b]])))
            for b, pr in enumerate(prompts)]
    for t in range(steps):
        cur = jnp.asarray(np.where(active, np.asarray(lens + (0,)) + t, 0),
                          jnp.int32)
        tok = jnp.asarray(np.where(active, fed[:, t], 0), jnp.int32)
        logits, cache = step(params, {"tokens": tok[:, None],
                                      "positions": cur[:, None]}, cache, cur)
        for b in range(len(lens)):
            np.testing.assert_allclose(
                np.asarray(logits[b, 0]), want[b][lens[b] + t], atol=2e-4,
                rtol=0, err_msg=f"slot {b}, step {t}")


def test_engine_serves_the_reference_argmax():
    """``ServeEngine.run`` end to end: at every served position the
    reference's best logit exceeds the served token's by no more than
    float32 rounding (1e-4)."""
    cfg = _cfg()
    model = Model(cfg)
    params = _params(model, seed=2)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=g)
            for i, (n, g) in enumerate([(5, 7), (11, 5), (3, 9), (8, 6)])]
    eng = ServeEngine(model, params, batch_slots=3, max_len=64,
                      flush_interval=4)
    eng.run(reqs)
    for r in reqs:
        toks = np.concatenate([r.prompt, r.generated[:-1]])
        lg = np.asarray(R.forward(cfg, params, toks))[len(r.prompt) - 1:]
        gap = lg.max(1) - lg[np.arange(len(r.generated)), r.generated]
        assert gap.max() <= 1e-4, (r.rid, gap)


def test_absorbed_decode_matches_decompressed_attention():
    """One decode row read from the latent cache in the absorbed form
    equals the last row of decompressed causal attention over the whole
    sequence (1e-5: float32, a reassociated product)."""
    cfg = _cfg()
    p = init_params(mla_specs(cfg), jax.random.key(4))
    p["kv_norm"] = 0.3 * jax.random.normal(jax.random.key(5), (32,))
    t = 12
    x = jax.random.normal(jax.random.key(6), (2, t, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (2, t))
    full, _ = mla_apply(p, cfg, x, pos)
    cache = jnp.zeros((1, 2, cfg.mla.cache_width, 16), jnp.float32)
    _, cache = mla_apply(p, cfg, x[:, :-1], pos[:, :-1], cache=cache,
                         layer=0, cache_index=jnp.zeros((), jnp.int32))
    last = jnp.full((2,), t - 1, jnp.int32)
    one, cache = mla_apply(p, cfg, x[:, -1:], last[:, None], cache=cache,
                           layer=0, cache_index=last)
    assert cache.shape[2] == 32 + 8          # latent rank + rotary width
    np.testing.assert_allclose(np.asarray(one[:, 0]),
                               np.asarray(full[:, -1]), atol=1e-5, rtol=1e-5)


def _moe_params(cfg, seed=7):
    p = init_params(moe_specs(cfg), jax.random.key(seed))
    p["bias"] = 0.5 * jax.random.normal(jax.random.key(seed + 1),
                                        p["bias"].shape)
    return p


@pytest.mark.parametrize("tokens", [40, 300])    # masked dense; grouped
def test_held_shares_sum_to_the_uncut_layer(tokens):
    """Four chips' shares of an 8-expert layer (2 each), the shared
    experts counted once, add up to the uncut reference layer (guide:
    one chip's share of the experts), on both sides of DENSE_TOKENS.
    1e-5: float32 sums of 4 parts."""
    assert 40 <= DENSE_TOKENS < 300
    cfg = _cfg(held=0)
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.key(9), (tokens, cfg.d_model))
    whole = R.moe_layer(p, cfg.moe, x)
    shared = _add_shared(p, x, jnp.zeros_like(x))
    total = -3 * shared
    for i in range(4):
        share = dict(p)
        for n in ("w_gate", "w_up", "w_down"):
            share[n] = p[n][2 * i:2 * i + 2]
        y, _ = _moe_dropless(share, x, moe=cfg.moe, expert_offset=2 * i,
                             e_local=2)
        np.testing.assert_allclose(
            np.asarray(y - shared),
            np.asarray(R.moe_layer(share, cfg.moe, x, 2 * i, shared=False)),
            atol=1e-5, rtol=1e-5)
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)


def test_router_sigmoid_bias_selects_scores_weigh():
    """Sigmoid scores; the bias moves the choice but not the weights;
    the chosen scores are renormalised and scaled."""
    moe = _cfg().moe
    assert moe.scoring == "sigmoid" and moe.routed_scale == 2.446
    e = moe.num_experts
    router = jnp.eye(4, e)                       # logits = x[:, :e]
    logits = jnp.asarray([[3.0, 2.0, 1.0, 0.5]])
    bias = jnp.zeros((e,)).at[3].set(10.0)       # expert 3 always chosen
    p = {"router": router, "bias": bias}
    _, _, gate, idx = _route(p, logits, moe)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 3]
    s = jax.nn.sigmoid(logits[0])
    want = s[jnp.asarray([0, 1, 3])]
    want = want / want.sum() * 2.446
    order = np.argsort(np.asarray(idx[0]))
    np.testing.assert_allclose(np.asarray(gate[0])[order],
                               np.asarray(want), rtol=1e-6)


def test_long_prefill_drops_no_token():
    """2,048 identical tokens all route to the same experts, which a
    capacity buffer would overflow; the serving layer keeps every one:
    each row equals the reference layer's (1e-5)."""
    cfg = _cfg(held=0)
    p = _moe_params(cfg, seed=11)
    x = jnp.broadcast_to(jax.random.normal(jax.random.key(12),
                                           (1, cfg.d_model)),
                         (2048, cfg.d_model))
    y, load = _moe_dropless(p, x, moe=cfg.moe, expert_offset=0,
                            e_local=cfg.moe.num_experts)
    ref = R.moe_layer(p, cfg.moe, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)
    assert int(load.sum()) == 2048 * cfg.moe.top_k


@pytest.mark.parametrize("held", [0, 4])
def test_load_counter_matches_host_count(held):
    """The masked step's device-side counter equals a count made on the
    host from the per-layer loads of the same steps: the active slots'
    assignments to held experts, and the (layer, expert) pairs with at
    least one.  With every expert held, the engine's totals are also
    top_k per MoE layer per decoded token."""
    cfg = _cfg(held=held)
    model = Model(cfg)
    params = _params(model, seed=13)
    slots, max_len = 4, 32
    cache = model.init_cache(slots, max_len)
    step = _make_masked_step(model)
    plain = jax.jit(model.decode_step_load)
    rng = np.random.default_rng(14)
    active = jnp.asarray([True, False, True, True])
    pos = jnp.asarray([3, 0, 5, 1], jnp.int32)
    tok = jnp.asarray(rng.integers(1, cfg.vocab_size, slots), jnp.int32)
    buf = jnp.zeros((slots, 4), jnp.int32)
    load = jnp.zeros((2,), jnp.int32)
    host = np.zeros(2, np.int64)
    for w in range(4):
        cur = jnp.where(active, pos + w, 0)
        tok_c = jnp.where(active, tok, 0)
        _, _, lay = plain(params, {"tokens": tok_c[:, None],
                                   "positions": cur[:, None]}, cache, cur)
        lay = np.asarray(lay)[:, np.asarray(active)]
        host += [lay.sum(), (lay.sum(1) > 0).sum()]
        tok, cache, buf, load = step(params, cache, tok, pos, active, buf,
                                     jnp.asarray(w, jnp.int32), load)
    assert np.asarray(load).tolist() == host.tolist()
    assert host[0] > 0

    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=g)
            for i, (n, g) in enumerate([(5, 7), (9, 4), (3, 6)])]
    eng = ServeEngine(model, params, batch_slots=2, max_len=max_len,
                      flush_interval=3)
    eng.run(reqs)
    n_moe = cfg.num_layers - cfg.first_dense_layers
    decoded = sum(r.max_new_tokens - 1 for r in reqs)
    if not held:
        assert eng.route_assignments == cfg.moe.top_k * n_moe * decoded
    assert 0 < eng.route_pairs <= eng.route_assignments
