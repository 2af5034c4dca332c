"""Mixture-of-Experts FFN with expert parallelism.

Dispatch strategy (TPU-native rethink, see DESIGN.md §4):
  * routing is computed redundantly on every model shard (cheap: one (N, E)
    matmul on the replicated activations),
  * each model shard owns E/ep experts; it sort-gathers the tokens routed to
    *its* experts into a capacity-bounded (E_local, C, d) buffer, runs the
    expert SwiGLU as one grouped einsum, scatters back, and
  * a single psum over the model axis combines per-shard partial outputs —
    the same collective a TP FFN would need, so EP costs no extra collective
    class (this is what makes the jamba/qwen3 dry-runs collective-lean).

Under ``shard_map`` the dispatch is local to each (pod, data) shard, which is
how production EP systems route per-device batches.  Without a mesh (CPU smoke
tests) the same local function runs on the full array with all experts.

Serving (``moe_serve``) drops no token: the assignments to this shard's
experts are sorted by expert and run through grouped products
(``lax.ragged_dot``), so the work follows the tokens routed here and a
long prefill loses nothing to a capacity buffer; a decode step's few
tokens run every held expert, masked.  Training keeps the capacity path
above.

The router is the configuration's (``MoEConfig``): softmax over the
experts, or sigmoid scores with a per-expert selection bias that only
chooses (DeepSeek-V3's noaux_tc); the chosen gates are renormalised, then
scaled as configured.  A deployment holds ``held`` of the experts: the
router keeps its full width, and the layer returns its own experts' part
plus the shared experts.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.layers import ParamSpec


def moe_specs(cfg):
    m = cfg.moe
    d = cfg.d_model
    f = m.expert_d_ff or cfg.d_ff
    e = m.held
    specs = {
        "router": ParamSpec((d, m.num_experts), ("embed", None),
                            init="small_normal"),
        "w_gate": ParamSpec((e, d, f), ("expert", "embed", None)),
        "w_up": ParamSpec((e, d, f), ("expert", "embed", None)),
        "w_down": ParamSpec((e, f, d), ("expert", None, "embed")),
    }
    if m.selection_bias:
        specs["bias"] = ParamSpec((m.num_experts,), (None,), init="zeros")
    if m.num_shared_experts:
        fs = f * m.num_shared_experts
        specs["shared"] = {
            "w_gate": ParamSpec((d, fs), ("embed", "mlp")),
            "w_up": ParamSpec((d, fs), ("embed", "mlp")),
            "w_down": ParamSpec((fs, d), ("mlp", "embed")),
        }
    return specs


def _route(p, x_flat, moe):
    """Router over every expert, in float32 -> (logits (N, E), the
    scores' distribution over the experts (N, E), gates (N, k), chosen
    experts (N, k))."""
    f32 = jnp.float32
    logits = x_flat.astype(f32) @ p["router"].astype(f32)     # (N, E)
    if moe.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["bias"].astype(f32) if moe.selection_bias \
            else scores
        _, idx = lax.top_k(sel, moe.top_k)
        gate = jnp.take_along_axis(scores, idx, axis=-1)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = lax.top_k(probs, moe.top_k)                # (N, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    if moe.routed_scale != 1.0:
        gate = gate * moe.routed_scale
    return logits, probs, gate, idx


def _capacity(n_tokens_local, moe):
    ideal = moe.top_k * n_tokens_local / moe.num_experts
    c = int(ideal * moe.capacity_factor) + 1
    return max(8, min(n_tokens_local, c))


def _moe_local(p, x_flat, *, moe, expert_offset, e_local, capacity,
               psum_axis=None):
    """Local-shard MoE: x_flat (N, d) replicated across the EP axis.

    Returns (partial_y (N, d), aux dict).  Partial outputs must be psum'd
    over the EP axis (done here when psum_axis is given).
    """
    n, d = x_flat.shape
    k = moe.top_k
    f32 = jnp.float32

    logits, probs, gate, idx = _route(p, x_flat, moe)

    # ---- aux losses (computed on replicated routing; identical per shard)
    me = jnp.mean(probs, axis=0)                              # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, moe.num_experts, dtype=f32), axis=1),
        axis=0) / k
    aux_lb = moe.num_experts * jnp.sum(me * ce)
    aux_z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = moe.router_aux_weight * aux_lb + moe.router_z_weight * aux_z

    # ---- assignment flattening; keep only this shard's experts
    flat_e = idx.reshape(-1)                                  # (N*k,)
    flat_w = gate.reshape(-1).astype(f32)
    flat_tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    local_e = flat_e - expert_offset
    mine = (local_e >= 0) & (local_e < e_local)
    sort_key = jnp.where(mine, local_e, e_local)              # drops sort last
    order = jnp.argsort(sort_key, stable=True)
    se = sort_key[order]                                  # sorted expert id
    counts = jnp.bincount(se, length=e_local + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(n * k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    dropped = (pos >= capacity) | (se == e_local)
    buf_e = jnp.where(dropped, e_local, se).astype(jnp.int32)  # OOB -> drop
    buf_p = jnp.where(dropped, 0, pos).astype(jnp.int32)
    tok_sorted = flat_tok[order]
    w_sorted = jnp.where(dropped, 0.0, flat_w[order])

    # ---- gather into (E_local, C, d), grouped expert SwiGLU, scatter back
    dt = x_flat.dtype
    buf = jnp.zeros((e_local, capacity, d), dt)
    buf = buf.at[buf_e, buf_p].set(x_flat[tok_sorted], mode="drop")
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dt)))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt))
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"].astype(dt))
    contrib = out_buf[jnp.where(dropped, 0, buf_e), buf_p]    # (N*k, d)
    contrib = contrib * w_sorted[:, None].astype(dt)
    y = jnp.zeros((n, d), dt).at[tok_sorted].add(
        jnp.where(dropped[:, None], jnp.zeros((), dt), contrib))

    # ---- shared experts (dense, model-sharded d_ff -> partial sums)
    y = _add_shared(p, x_flat, y)
    if psum_axis is not None:
        y = lax.psum(y, psum_axis)
    return y, aux


def _add_shared(p, x_flat, y):
    """``y`` plus the shared experts' output (one SwiGLU of their summed
    width), when the layer has them."""
    if "shared" not in p:
        return y
    dt = x_flat.dtype
    sp = p["shared"]
    sg = jax.nn.silu(x_flat @ sp["w_gate"].astype(dt))
    su = x_flat @ sp["w_up"].astype(dt)
    return y + (sg * su) @ sp["w_down"].astype(dt)


#: Up to this many tokens (a decode step's slots), every held expert
#: runs on every token, masked to the ones that chose it: that reads each
#: held expert's weights once, as the grouped products do, while a
#: grouped product pads each held expert's rows to a tile of 512 (41 us a
#: (layer, expert) pair that got a token on a v5e, PERF.md).
DENSE_TOKENS = 128


def _moe_dropless(p, x_flat, *, moe, expert_offset, e_local,
                  psum_axis=None):
    """Local-shard MoE for serving, dropping no token: x_flat (N, d).

    Up to ``DENSE_TOKENS`` tokens, each held expert's SwiGLU runs on
    every token and is weighted by the token's gate for it (0 unless
    chosen).  Past that, the N*k assignments are sorted by expert, those
    of experts outside [expert_offset, expert_offset + e_local) last;
    the held experts' SwiGLU runs as three grouped products over their
    rows only, and each row is added back to its token with its gate.
    Returns (partial y (N, d), load (N, e_local) int32: 1 where a token
    was routed to a held expert)."""
    n, d = x_flat.shape
    k = moe.top_k
    dt = x_flat.dtype
    f32 = jnp.float32
    _, _, gate, idx = _route(p, x_flat, moe)
    local = idx.reshape(-1) - expert_offset                   # (N*k,)
    mine = (local >= 0) & (local < e_local)
    key = jnp.where(mine, local, e_local)
    load = jnp.sum(jax.nn.one_hot(key.reshape(n, k), e_local,
                                  dtype=jnp.int32), axis=1)
    wg, wu, wd = (p[w].astype(dt) for w in ("w_gate", "w_up", "w_down"))
    if n <= DENSE_TOKENS:
        w = jnp.sum(jax.nn.one_hot(key.reshape(n, k), e_local, dtype=f32)
                    * gate[..., None], axis=1)                # (N, e_local)
        h = jax.nn.silu(jnp.einsum("nd,edf->nef", x_flat, wg)) \
            * jnp.einsum("nd,edf->nef", x_flat, wu)
        y = jnp.einsum("nef,efd->ned", h, wd)
        y = jnp.einsum("ned,ne->nd", y.astype(f32), w).astype(dt)
    else:
        order = jnp.argsort(key, stable=True)
        tok = (order // k).astype(jnp.int32)
        sizes = jnp.bincount(key, length=e_local + 1)[:e_local] \
            .astype(jnp.int32)
        xs = x_flat[tok]
        h = jax.nn.silu(lax.ragged_dot(xs, wg, sizes)) \
            * lax.ragged_dot(xs, wu, sizes)
        out = lax.ragged_dot(h, wd, sizes)                    # (N*k, d)
        held = mine[order]
        w = gate.reshape(-1)[order]
        # rows past the held groups are not computed: select, never
        # multiply
        contrib = jnp.where(held[:, None], out.astype(f32) * w[:, None],
                            0.0)
        y = jnp.zeros((n, d), f32).at[tok].add(contrib).astype(dt)
    y = _add_shared(p, x_flat, y)
    if psum_axis is not None:
        y = lax.psum(y, psum_axis)
    return y, load


def moe_apply(p, cfg, x, *, mesh=None, ep_axis="model",
              dp_axes=("pod", "data")):
    """x: (B, S, d) -> (y, aux_loss scalar)."""
    moe = cfg.moe
    b, s, d = x.shape

    if mesh is None or ep_axis not in mesh.axis_names:
        xf = x.reshape(b * s, d)
        y, aux = _moe_local(p, xf, moe=moe, expert_offset=0,
                            e_local=moe.held,
                            capacity=_capacity(b * s, moe))
        return y.reshape(b, s, d), aux

    e_local, dp_axes, dp = _ep_layout(moe, mesh, ep_axis, dp_axes, b)
    capacity = _capacity((b // dp) * s, moe)

    def shard_fn(p_loc, x_loc):
        off = lax.axis_index(ep_axis) * e_local
        xf = x_loc.reshape(-1, d)
        y, aux = _moe_local(p_loc, xf, moe=moe, expert_offset=off,
                            e_local=e_local, capacity=capacity,
                            psum_axis=ep_axis)
        return y.reshape(x_loc.shape), aux

    y, aux = _ep_shard_map(shard_fn, p, x, mesh, ep_axis, dp_axes, P())
    return y, aux


def _ep_layout(moe, mesh, ep_axis, dp_axes, b):
    """The expert-parallel layout -> (held experts a shard, the mesh's
    batch axes, their size): the held experts split over ``ep_axis``, a
    batch of ``b`` over ``dp_axes``, or replicated when it does not
    divide."""
    ep = mesh.shape[ep_axis]
    assert moe.held % ep == 0, \
        f"{moe.held} experts not divisible by EP={ep}"
    dp_axes = tuple(a for a in dp_axes if a in mesh.axis_names)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    if b % dp != 0:                 # tiny batches (long_500k) replicate
        dp_axes, dp = (), 1
    return moe.held // ep, dp_axes, dp


def _ep_shard_map(shard_fn, p, x, mesh, ep_axis, dp_axes, extra_spec):
    """Run ``shard_fn(p_local, x_local) -> (y, extra)`` with the experts
    and the shared experts' width split over ``ep_axis`` and the batch
    over ``dp_axes``."""
    # cast expert weights to compute dtype BEFORE shard_map so the FSDP
    # all-gather into the region moves bf16, not fp32 (halves gather temp)
    p = jax.tree.map(lambda w: w.astype(x.dtype), p)
    p_specs = jax.tree.map(lambda _: P(None), p)
    for name in ("w_gate", "w_up", "w_down"):
        p_specs[name] = P(ep_axis)
    if "shared" in p:
        p_specs["shared"] = {"w_gate": P(None, ep_axis),
                             "w_up": P(None, ep_axis),
                             "w_down": P(ep_axis, None)}
    x_spec = P(dp_axes if dp_axes else None, None, None)
    from repro.distributed.sharding import shard_map_compat
    return shard_map_compat(
        shard_fn, mesh=mesh,
        in_specs=(p_specs, x_spec),
        out_specs=(x_spec, extra_spec),
    )(p, x)


def moe_serve(p, cfg, x, *, mesh=None, ep_axis="model",
              dp_axes=("pod", "data")):
    """The serving MoE layer, dropping no token: x (B, S, d) -> (y,
    load (B*S, held) int32, each token's assignments to the held
    experts)."""
    moe = cfg.moe
    b, s, d = x.shape
    with jax.named_scope("moe"):
        if mesh is None or ep_axis not in mesh.axis_names:
            y, load = _moe_dropless(p, x.reshape(b * s, d), moe=moe,
                                    expert_offset=0, e_local=moe.held)
            return y.reshape(b, s, d), load
        e_local, dp_axes, _ = _ep_layout(moe, mesh, ep_axis, dp_axes, b)

        def shard_fn(p_loc, x_loc):
            off = lax.axis_index(ep_axis) * e_local
            y, load = _moe_dropless(p_loc, x_loc.reshape(-1, d), moe=moe,
                                    expert_offset=off, e_local=e_local,
                                    psum_axis=ep_axis)
            return y.reshape(x_loc.shape), load

        return _ep_shard_map(shard_fn, p, x, mesh, ep_axis, dp_axes,
                             P(dp_axes if dp_axes else None, ep_axis))
