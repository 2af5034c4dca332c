"""Plain float32 reference of a latent-attention MoE decoder
(DeepSeek-V3 / Moonlight-16B-A3B), for tests against the served model.

Written from the published description, not from ``Model``: token
embedding, ``first_dense_layers`` layers of RMSNorm -> multi-head latent
attention (decompressed heads) -> residual, RMSNorm -> SwiGLU of width
d_ff -> residual, then the MoE layers with the same attention and an
expert FFN, a final RMSNorm and an untied (or tied) LM head.  The MoE
router scores every expert (sigmoid, or softmax), chooses the top k by
score plus the selection bias, and weights each chosen expert by its
renormalised, scaled score.  Every expert the weights hold is computed
densely for every token and masked to the chosen ones; the shared
experts are added.  No cache, no batching, no kernels; matrix products
at the highest precision.  Departures that the served model shares:
RoPE rotates halves (the published model interleaves pairs, a
relabelling of columns under random weights), and RMSNorm scales by
(1 + w).

``params`` is the served model's pytree (``Model.init``); any dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (T, H, D) at positions 0..T-1, rotate-half pairs."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mla(p, cfg, x):
    """Causal latent attention over a whole sequence x (T, d)."""
    a, h = cfg.mla, cfg.num_heads
    nope, rope, r = a.qk_nope_head_dim, a.qk_rope_head_dim, a.kv_lora_rank
    t = x.shape[0]
    q = (x @ p["wq"]).reshape(t, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg.rope_theta)],
                        -1)
    kv = x @ p["wkv_a"]
    c = _rms(kv[:, :r], p["kv_norm"], cfg.rms_eps)
    k_r = _rope(kv[:, None, r:], cfg.rope_theta)               # (T, 1, R)
    kvb = (c @ p["wkv_b"]).reshape(t, h, nope + a.v_head_dim)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_r, (t, h, rope))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(nope + rope))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kvb[..., nope:])
    return o.reshape(t, h * a.v_head_dim) @ p["wo"]


def swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def route(p, moe, x):
    """-> (T, E) weights: each chosen expert's gate, 0 elsewhere."""
    logits = x @ p["router"]
    if moe.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["bias"] if moe.selection_bias else scores
    else:
        scores = sel = jax.nn.softmax(logits, -1)
    _, idx = jax.lax.top_k(sel, moe.top_k)
    gate = jnp.take_along_axis(scores, idx, -1)
    gate = gate / jnp.sum(gate, -1, keepdims=True) * moe.routed_scale
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros(logits.shape).at[rows, idx].set(gate)


def moe_layer(p, moe, x, expert_offset=0, shared=True):
    """The experts ``p`` holds, [offset, offset + held), each computed
    for every token and masked to the tokens that chose it; plus the
    shared experts when ``shared``."""
    w = route(p, moe, x)
    y = jnp.zeros_like(x)
    for e in range(p["w_gate"].shape[0]):
        pe = {n: p[n][e] for n in ("w_gate", "w_up", "w_down")}
        y = y + w[:, expert_offset + e, None] * swiglu(pe, x)
    if shared and "shared" in p:
        y = y + swiglu(p["shared"], x)
    return y


def _layer(p, cfg, x, dense):
    x = x + mla(p["core"], cfg, _rms(x, p["norm1"], cfg.rms_eps))
    h = _rms(x, p["norm2"], cfg.rms_eps)
    ffn = swiglu(p["ffn"], h) if dense else moe_layer(p["ffn"], cfg.moe, h)
    return x + ffn


def forward(cfg, params, tokens):
    """Logits (T, V), float32, of the whole sequence ``tokens`` (T,)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        x = p["embed"][jnp.asarray(tokens)]
        for name, dense in (("lead", True), ("layers", False)):
            if name not in p:
                continue
            stack = p[name]["pos0"]
            for i in range(jax.tree.leaves(stack)[0].shape[0]):
                x = _layer(jax.tree.map(lambda a: a[i], stack), cfg, x,
                           dense)
        x = _rms(x, p["final_norm"], cfg.rms_eps)
        head = p["lm_head"] if "lm_head" in p else p["embed"].T
        return x @ head
