"""Plain float32 reference for minicpm-2b, and its weights from a seed.

The reference is a dense pre-norm decoder written from MiniCPM-2B's
published description (arXiv:2404.06395): token embedding tied to the LM
head, 40 layers of RMSNorm -> multi-head causal attention with rotary
position embedding (rotate-half pairs, theta 10,000) -> residual,
RMSNorm -> SwiGLU MLP -> residual, a final RMSNorm and the logits.  It
has no cache, no batching of requests and no kernels: every sequence is
run whole, in float32, with float32 matrix products at the highest
precision.  Departures the configuration states: the scale_emb,
scale_depth and dim_model_base scalings are left out (the system under
test has none), and RMSNorm scales by (1 + w).

``weights`` makes the served bfloat16 weights from the seed in one
jitted call, in the layer-stacked layout the serving program takes; the
reference makes them again the same way.  ``lower`` = "int8" runs the
control: every matrix rounded to int8 with one scale per output column
(per vocabulary row for the tied embedding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    nq = cfg["num_attention_heads"]
    return {"d": d, "L": cfg["num_hidden_layers"], "nq": nq,
            "nkv": cfg["num_key_value_heads"], "hd": d // nq,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def leaf_shapes(cfg: dict) -> dict:
    """{path: shape} of every weight, in the serving layout."""
    m = _dims(cfg)
    d, L, nq, nkv, hd, ff = (m["d"], m["L"], m["nq"], m["nkv"], m["hd"],
                             m["ff"])
    return {
        "embed": (m["V"], d),
        "final_norm": (d,),
        "layers/pos0/norm1": (L, d),
        "layers/pos0/core/wq": (L, d, nq * hd),
        "layers/pos0/core/wk": (L, d, nkv * hd),
        "layers/pos0/core/wv": (L, d, nkv * hd),
        "layers/pos0/core/wo": (L, nq * hd, d),
        "layers/pos0/norm2": (L, d),
        "layers/pos0/ffn/w_gate": (L, d, ff),
        "layers/pos0/ffn/w_up": (L, d, ff),
        "layers/pos0/ffn/w_down": (L, ff, d),
    }


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
            else:
                z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
                flat[path] = (z * INIT_STD).astype(jnp.bfloat16)
        return _nest(flat)

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return make(key)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (B, T, H, D), positions 0..T-1, rotate-half pairs."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _quant_int8(w, axis):
    """Round to int8 with one scale per slice along ``axis`` (kept)."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


@functools.partial(jax.jit, static_argnames=("dims", "eps", "theta", "lower"))
def _hidden(params, tokens, *, dims, eps, theta, lower):
    """Final-norm hidden states (B, T, d) of whole sequences."""
    d, nq, nkv, hd = dims
    emb = params["embed"].astype(jnp.float32)
    if lower == "int8":
        emb = _quant_int8(emb, axis=1)
    x = emb[tokens]
    b, t = tokens.shape
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        if lower == "int8":
            for grp, names in (("core", ("wq", "wk", "wv", "wo")),
                               ("ffn", ("w_gate", "w_up", "w_down"))):
                for n in names:
                    p[grp][n] = _quant_int8(p[grp][n], axis=0)
        h = _rms(x, p["norm1"], eps)
        q = (h @ p["core"]["wq"]).reshape(b, t, nq, hd)
        k = (h @ p["core"]["wk"]).reshape(b, t, nkv, hd)
        v = (h @ p["core"]["wv"]).reshape(b, t, nkv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, nq // nkv, axis=2)
        v = jnp.repeat(v, nq // nkv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + a.reshape(b, t, nq * hd) @ p["core"]["wo"]
        h = _rms(x, p["norm2"], eps)
        g = jax.nn.silu(h @ p["ffn"]["w_gate"]) * (h @ p["ffn"]["w_up"])
        return x + g @ p["ffn"]["w_down"], None

    x, _ = jax.lax.scan(layer, x, params["layers"]["pos0"])
    return _rms(x, params["final_norm"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("lower",))
def _logits_at(params, hidden, rows, cols, *, lower):
    emb = params["embed"].astype(jnp.float32)
    if lower == "int8":
        emb = _quant_int8(emb, axis=1)
    return hidden[rows, cols] @ emb.T


def served_gaps(cfg: dict, params, sequences, *, lower: str = None,
                block: int = 256):
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
    dims = (m["d"], m["nq"], m["nkv"], m["hd"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    t_max = max(len(s[0]) for s in sequences)
    t_pad = -(-t_max // block) * block
    toks = np.zeros((len(sequences), t_pad), np.int32)
    rows, cols = [], []
    for i, (tk, first, served) in enumerate(sequences):
        toks[i, :len(tk)] = tk
        rows += [i] * len(served)
        cols += list(range(first, first + len(served)))
    rows, cols = jnp.asarray(rows), jnp.asarray(cols)
    with jax.default_matmul_precision("highest"):
        h = _hidden(params, jnp.asarray(toks), dims=dims, eps=eps,
                    theta=theta, lower=None)
        ref = np.asarray(_logits_at(params, h, rows, cols, lower=None))
        if lower is not None:
            h = _hidden(params, jnp.asarray(toks), dims=dims, eps=eps,
                        theta=theta, lower=lower)
            low = np.asarray(_logits_at(params, h, rows, cols,
                                        lower=lower))
    served_all = np.concatenate([np.asarray(s[2]) for s in sequences])
    pick = served_all if lower is None else low.argmax(axis=1)
    gaps = ref.max(axis=1) - ref[np.arange(len(pick)), pick]
    out, off = [], 0
    for _, _, served in sequences:
        out.append(gaps[off:off + len(served)])
        off += len(served)
    return out
