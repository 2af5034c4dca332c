"""The decode step's slot KV cache: written in place, one row per slot.

The caches ride in the layer loop's carry, so a step writes each slot's
new K/V row into the donated stacked buffer and reads each layer once;
no cache-sized copy, fill or per-layer write-back appears in the
compiled step.  Parity: for every cache kind the carry threads (full,
ring, cross, mamba state, latent), stepwise masked decode at per-slot
positions gives the logits of a prefill over the same tokens.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import Model
from repro.serve.engine import _make_masked_step, _scatter_slot


def _entry_and_loop_bodies(hlo: str):
    """(ENTRY computation, [each while body with the computations it
    calls]) of a compiled module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = "ENTRY" if line.startswith("ENTRY") \
                else line.split()[0].lstrip("%")
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)

    def closure(root):
        seen, todo = set(), [root]
        while todo:
            c = todo.pop()
            if c not in seen and c in comps:
                seen.add(c)
                todo += re.findall(r"calls=%?([\w.\-]+)",
                                   "\n".join(comps[c]))
        return "\n".join(line for c in seen for line in comps[c])

    entry = "\n".join(comps["ENTRY"])
    return entry, [closure(b) for b in
                   re.findall(r"body=%?([\w.\-]+)", entry)]


def _shape_of(text: str, name: str):
    m = re.search(rf"%{re.escape(name)} = \w+\[([\d,]*)\]", text)
    return tuple(int(d) for d in m.group(1).split(",") if d) if m else None


def test_decode_step_writes_cache_in_place():
    """``jit_serve_decode_step`` for a small minicpm-shaped model: the
    cache is aliased input to output, and nothing cache-sized is copied,
    filled or rewritten a layer at a time."""
    cfg = dataclasses.replace(get_arch("minicpm-2b"), num_layers=4,
                              d_model=256, num_heads=4, num_kv_heads=4,
                              d_ff=512, vocab_size=512,
                              param_dtype="bfloat16")
    slots, max_len = 8, 256
    model = Model(cfg)
    params = model.param_structs()
    cache = model.cache_specs(slots, max_len)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    hlo = _make_masked_step(model).lower(
        params, cache, i32(slots), i32(slots),
        jax.ShapeDtypeStruct((slots,), jnp.bool_), i32(slots, 16),
        i32()).compile().as_text()
    assert "HloModule jit_serve_decode_step" in hlo

    stacked = model.cache_specs(slots, max_len)["pos0"]["kv"]["k"].shape
    layer_size = int(np.prod(stacked[1:]))
    entry, bodies = _entry_and_loop_bodies(hlo)

    # every cache leaf is an entry parameter aliased to an output
    aliased = {int(p) for p in re.findall(
        r"\{[\d,]*\}: \((\d+), \{[\d,]*\}, \w+-alias\)", hlo.splitlines()[0])}
    cache_params = {int(n) for n in re.findall(
        r"parameter\((\d+)\).*?op_name=\"args\[1\]", entry)}
    assert len(cache_params) == len(jax.tree.leaves(cache)) == 2
    assert cache_params <= aliased, (cache_params, aliased)

    # the entry neither copies nor fills a stacked cache
    dims = ",".join(map(str, stacked))
    bad = re.findall(rf"= \w+\[{dims}\]\S* (?:copy|broadcast)\(", entry)
    assert not bad, bad

    # the layer loop writes rows, never a whole layer's cache
    assert bodies
    for body in bodies:
        for line in body.splitlines():
            m = re.search(r"dynamic-update-slice\(%?([\w.\-]+), %?([\w.\-]+)",
                          line)
            if m:
                upd = _shape_of(body, m.group(2))
                assert upd is not None and int(np.prod(upd)) < layer_size, \
                    line.strip()[:200]


def _decode_vs_prefill(arch, slot_lens, steps=4, max_len=32):
    # float32, so that the two paths agree to rounding; MoE layers
    # included: serving routes without capacity, so a prefill drops no
    # token that a one-token step would keep
    cfg = dataclasses.replace(reduced(get_arch(arch)),
                              compute_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.key(3))
    rng = np.random.default_rng(7)
    slots = len(slot_lens) + 1              # the last slot stays idle
    frames = None
    if cfg.encoder_layers:
        frames = jnp.asarray(rng.normal(
            0, 1, (slots, cfg.num_audio_frames, cfg.d_model)), jnp.bfloat16)

    def batch(b, toks):
        out = {"tokens": jnp.asarray(toks, jnp.int32)[None]}
        if frames is not None:
            out["audio_frames"] = frames[b:b + 1]
        return out

    prefill = jax.jit(model.prefill)
    step = jax.jit(model.decode_step)
    cache = model.init_cache(slots, max_len)
    prompts = [rng.integers(1, cfg.vocab_size, n) for n in slot_lens]
    fed = rng.integers(1, cfg.vocab_size, (slots, steps))
    for b, prompt in enumerate(prompts):       # admission, as the engine
        _, c1 = prefill(params, batch(b, prompt), model.init_cache(1, max_len))
        cache = _scatter_slot(cache, c1, jnp.asarray(b, jnp.int32))
    active = np.arange(slots) < len(slot_lens)
    lens = np.asarray(list(slot_lens) + [0])
    for t in range(steps):
        # the masked step: idle slots decode token 0 at position 0
        cur = jnp.asarray(np.where(active, lens + t, 0), jnp.int32)
        tok = jnp.asarray(np.where(active, fed[:, t], 0), jnp.int32)
        logits, cache = step(params, {"tokens": tok[:, None],
                                      "positions": cur[:, None]},
                             cache, cur)
    for b, prompt in enumerate(prompts):
        want, _ = prefill(params, batch(b, np.concatenate([prompt, fed[b]])),
                          model.init_cache(1, max_len))
        np.testing.assert_allclose(
            np.asarray(logits[b, 0], np.float32),
            np.asarray(want[0, -1], np.float32), atol=1e-5, rtol=1e-5,
            err_msg=f"{arch}: slot {b}")


@pytest.mark.parametrize("arch", [
    pytest.param("llama3.2-3b", id="full"),
    pytest.param("gemma2-27b", id="ring"),    # local layers: 8 positions
    pytest.param("whisper-base", id="cross"),
    pytest.param("jamba-1.5-large-398b", id="mamba"),
    pytest.param("moonlight-16b-a3b", id="latent"),
])
def test_masked_decode_per_slot_matches_prefill(arch):
    # per-slot positions; gemma2's longest slot wraps its ring
    _decode_vs_prefill(arch, slot_lens=(3, 7, 12))
