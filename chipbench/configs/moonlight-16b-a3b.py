"""Plain float32 reference for moonlight-16b-a3b, and its weights from a
seed.

The reference is the DeepSeek-V3 decoder of Moonlight-16B-A3B's published
config.json: token embedding; layer 0 of RMSNorm -> multi-head latent
attention -> residual, RMSNorm -> SwiGLU of width 11,264 -> residual;
26 layers with the same attention and a mixture of experts; a final
RMSNorm and an untied LM head.  Latent attention, decompressed:
q = x W_q (16 heads of 128 + 64), [c, k_r] = x W_kva (512 + 64), c
normalised, k_r one rotary key shared by the heads, [k_n, v] = c W_kvb,
scores (q_n k_n + q_r k_r) / sqrt(192), causal softmax, out = o W_o.
The MoE layer: sigmoid scores of the 64 router outputs in float32, the
top 6 of score plus the correction bias chosen, each weighted by its
score over the chosen scores' sum times 2.446; every expert held here is
computed for every token and masked to the tokens that chose it; the two
shared experts (one SwiGLU of width 2,816) are added.  It has no cache,
no batching of requests and no kernels: every sequence is run whole, in
float32, with float32 matrix products at the highest precision, and its
attention in blocks of queries so that 7.7k-token sequences fit.
Departures the configuration states under ``assumed``.

``weights`` makes the served bfloat16 weights from the seed in one
jitted call, in the layer-stacked layout the serving program takes; the
reference makes them again the same way.  ``lower`` = "int8" runs the
control: every matrix rounded to int8 with one scale per output column
(per vocabulary row for the embedding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
BIAS_STD = 0.01


def _dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "L": cfg["num_hidden_layers"],
            "lead": cfg["first_k_dense_replace"],
            "H": cfg["num_attention_heads"], "r": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
            "E": cfg["published_n_routed_experts"],
            "held": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "V": cfg["vocab_size"]}


def leaf_shapes(cfg: dict) -> dict:
    """{path: shape} of every weight, in the serving layout."""
    m = _dims(cfg)
    d, h = m["d"], m["H"]
    out = {"embed": (m["V"], d), "lm_head": (d, m["V"]),
           "final_norm": (d,)}
    for stack, n in (("lead/pos0", m["lead"]),
                     ("layers/pos0", m["L"] - m["lead"])):
        out.update({
            f"{stack}/norm1": (n, d),
            f"{stack}/core/wq": (n, d, h * (m["nope"] + m["rope"])),
            f"{stack}/core/wkv_a": (n, d, m["r"] + m["rope"]),
            f"{stack}/core/kv_norm": (n, m["r"]),
            f"{stack}/core/wkv_b": (n, m["r"], h * (m["nope"] + m["dv"])),
            f"{stack}/core/wo": (n, h * m["dv"], d),
            f"{stack}/norm2": (n, d),
        })
    n = m["L"] - m["lead"]
    out.update({
        "lead/pos0/ffn/w_gate": (m["lead"], d, m["ff"]),
        "lead/pos0/ffn/w_up": (m["lead"], d, m["ff"]),
        "lead/pos0/ffn/w_down": (m["lead"], m["ff"], d),
        "layers/pos0/ffn/router": (n, d, m["E"]),
        "layers/pos0/ffn/bias": (n, m["E"]),
        "layers/pos0/ffn/w_gate": (n, m["held"], d, m["f"]),
        "layers/pos0/ffn/w_up": (n, m["held"], d, m["f"]),
        "layers/pos0/ffn/w_down": (n, m["held"], m["f"], d),
        "layers/pos0/ffn/shared/w_gate": (n, d, m["fs"]),
        "layers/pos0/ffn/shared/w_up": (n, d, m["fs"]),
        "layers/pos0/ffn/shared/w_down": (n, m["fs"], d),
    })
    return out


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def weights(cfg: dict, seed: int):
    """The served bfloat16 weights, made on the device from the seed."""
    shapes = leaf_shapes(cfg)

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(sorted(shapes.items())):
            if "norm" in path:
                flat[path] = jnp.zeros(shape, jnp.bfloat16)
                continue
            std = BIAS_STD if path.endswith("bias") else INIT_STD
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            flat[path] = (z * std).astype(jnp.bfloat16)
        return _nest(flat)

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return make(key)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (T, H, D), positions 0..T-1, rotate-half pairs."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _quant_int8(w, axis):
    """Round to int8 with one scale per slice along ``axis`` (kept)."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _f32(p, lower):
    """A layer's weights in float32; int8-rounded matrices for the
    control (input axis -2: one scale per output column)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    if lower == "int8":
        p = jax.tree.map(lambda a: _quant_int8(a, axis=-2)
                         if a.ndim >= 2 else a, p)
    return p


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _attention(p, x, m, theta, eps, block):
    """Causal latent attention, decompressed, over x (T, d), in blocks of
    ``block`` queries."""
    h, nope, rope, r, dv = m["H"], m["nope"], m["rope"], m["r"], m["dv"]
    t = x.shape[0]
    q = (x @ p["wq"]).reshape(t, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kv = x @ p["wkv_a"]
    c = _rms(kv[:, :r], p["kv_norm"], eps)
    k_r = _rope(kv[:, None, r:], theta)
    kvb = (c @ p["wkv_b"]).reshape(t, h, nope + dv)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_r, (t, h, rope))], -1)
    v = kvb[..., nope:]
    scale = 1.0 / np.sqrt(nope + rope)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        causal = (i * block + jnp.arange(block))[:, None] \
            >= jnp.arange(t)[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(one, jnp.arange(t // block)).reshape(t, h * dv)
    return o @ p["wo"]


def _moe(p, x, m):
    """Sigmoid router over all E experts, the held experts dense and
    masked, plus the shared experts."""
    scores = jax.nn.sigmoid(x @ p["router"])                  # (T, E)
    _, idx = jax.lax.top_k(scores + p["bias"], m["k"])
    gate = jnp.take_along_axis(scores, idx, -1)
    gate = gate / jnp.sum(gate, -1, keepdims=True) * m["scale"]
    w = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None],
                                  idx].set(gate)
    y = _swiglu(x, p["shared"]["w_gate"], p["shared"]["w_up"],
                p["shared"]["w_down"])
    for e in range(m["held"]):
        y = y + w[:, e, None] * _swiglu(x, p["w_gate"][e], p["w_up"][e],
                                        p["w_down"][e])
    return y


@functools.partial(jax.jit, static_argnames=("dims", "eps", "theta",
                                             "lower", "block"))
def _hidden(params, tokens, *, dims, eps, theta, lower, block):
    """Final-norm hidden states (T, d) of one whole sequence."""
    m = dict(dims)
    emb = params["embed"].astype(jnp.float32)
    if lower == "int8":
        emb = _quant_int8(emb, axis=1)
    x = emb[tokens]

    def layer(dense):
        def body(x, p):
            p = _f32(p, lower)
            x = x + _attention(p["core"], _rms(x, p["norm1"], eps), m,
                               theta, eps, block)
            hh = _rms(x, p["norm2"], eps)
            f = p["ffn"]
            out = (_swiglu(hh, f["w_gate"], f["w_up"], f["w_down"])
                   if dense else _moe(f, hh, m))
            return x + out, None
        return body

    x, _ = jax.lax.scan(layer(True), x, params["lead"]["pos0"])
    x, _ = jax.lax.scan(layer(False), x, params["layers"]["pos0"])
    return _rms(x, params["final_norm"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("lower",))
def _logits_at(params, hidden, cols, *, lower):
    head = params["lm_head"].astype(jnp.float32)
    if lower == "int8":
        head = _quant_int8(head, axis=0)
    return hidden[cols] @ head


def served_gaps(cfg: dict, params, sequences, *, lower: str = None,
                block: int = 512):
    """Widest logit gaps over the served tokens of ``sequences``.

    sequences: [(tokens, first, served)]: the whole sequence as served
    (padded prompt, then every served token but the last), the index of
    the position whose logits chose the first served token, and the
    served tokens.  Returns one array per sequence: at each served
    position, the reference's best logit minus the logit of the token
    that was served (``lower=None``) or that the lower-precision control
    puts first (``lower="int8"``).
    """
    m = _dims(cfg)
    m["scale"] = float(cfg["routed_scaling_factor"])
    dims = tuple(sorted(m.items()))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    t_pad = -(-max(len(s[0]) for s in sequences) // block) * block
    out = []
    for tk, first, served in sequences:
        toks = np.zeros((t_pad,), np.int32)
        toks[:len(tk)] = tk
        cols = jnp.arange(first, first + len(served))
        with jax.default_matmul_precision("highest"):
            h = _hidden(params, jnp.asarray(toks), dims=dims, eps=eps,
                        theta=theta, lower=None, block=block)
            ref = np.asarray(_logits_at(params, h, cols, lower=None))
            if lower is not None:
                h = _hidden(params, jnp.asarray(toks), dims=dims, eps=eps,
                            theta=theta, lower=lower, block=block)
                pick = np.asarray(_logits_at(params, h, cols,
                                             lower=lower)).argmax(axis=1)
            else:
                pick = np.asarray(served)
        out.append(ref.max(axis=1) - ref[np.arange(len(pick)), pick])
    return out
