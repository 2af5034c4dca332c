"""Config-driven model: dense / MoE / hybrid(mamba) / xLSTM / enc-dec / VLM.

One :class:`Model` covers all 10 assigned architectures.  Layers are stacked
per *pattern position* and iterated with ``lax.scan`` over pattern groups so
the HLO stays O(pattern) instead of O(num_layers) — essential for the
94-layer
qwen3-moe and 72-layer jamba dry-runs.

Interfaces (all functional, pjit-friendly):
  * ``forward_train(params, batch) -> (loss, metrics)``
  * ``prefill(params, batch) -> (logits, cache)``
  * ``decode_step(params, batch, cache, pos) -> (logits, cache)``
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import (ArchConfig, ATTN, ATTN_LOCAL, MAMBA, MLSTM,
                                SLSTM)
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import mla as MLA
from repro.models import xlstm as X
from repro.models import moe as MOE
from repro.models.layers import ParamSpec


def _block_specs(cfg: ArchConfig, kind: str, layer_pos: int, *,
                 cross: bool = False, dense: bool = False):
    """One block's ParamSpecs; ``dense``: a leading layer, whose FFN is a
    SwiGLU of width d_ff whatever the MoE layers hold."""
    d = cfg.d_model
    specs = {"norm1": ParamSpec((d,), ("embed",), init="zeros")}
    if kind in (ATTN, ATTN_LOCAL) and cfg.mla is not None:
        specs["core"] = MLA.mla_specs(cfg)
    elif kind in (ATTN, ATTN_LOCAL):
        specs["core"] = L.attention_specs(cfg)
    elif kind == MAMBA:
        specs["core"] = M.mamba_specs(cfg)
    elif kind == MLSTM:
        specs["core"] = X.mlstm_specs(cfg)
    elif kind == SLSTM:
        specs["core"] = X.slstm_specs(cfg)
    else:
        raise ValueError(kind)
    if cross:
        specs["cross_norm"] = ParamSpec((d,), ("embed",), init="zeros")
        specs["cross"] = L.attention_specs(cfg, cross=True)
    if _has_ffn(cfg, kind):
        specs["norm2"] = ParamSpec((d,), ("embed",), init="zeros")
        if _is_moe_layer(cfg, layer_pos) and not dense:
            specs["ffn"] = MOE.moe_specs(cfg)
        else:
            specs["ffn"] = L.mlp_specs(cfg)
    return specs


def _has_ffn(cfg, kind):
    return cfg.d_ff > 0 and kind in (ATTN, ATTN_LOCAL, MAMBA)


def _is_moe_layer(cfg, layer_pos):
    return cfg.moe is not None and layer_pos % cfg.moe_every == 0


def _stack_specs(specs, n):
    """Prefix every ParamSpec shape with the group dimension n."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def _layer(tree, layer):
    """Layer ``layer``'s entries of a stacked cache pytree."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, layer, keepdims=False), tree)


def _set_layer(tree, new, layer):
    """Write ``new`` as layer ``layer``'s entries of a stacked cache."""
    return jax.tree.map(
        lambda a, n: lax.dynamic_update_index_in_dim(
            a, n.astype(a.dtype), layer, 0), tree, new)


class Model:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.pattern = tuple(cfg.block_pattern)
        # leading dense layers run first, as a stack of their own
        self.n_lead = cfg.first_dense_layers
        n_body = cfg.num_layers - self.n_lead
        assert n_body % len(self.pattern) == 0, \
            f"{n_body} layers not divisible by pattern {self.pattern}"
        self.n_groups = n_body // len(self.pattern)
        if cfg.moe is not None:
            assert len(self.pattern) % cfg.moe_every == 0 or cfg.moe_every == 1
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)

    # ------------------------------------------------------------------
    # Parameter specs / init
    # ------------------------------------------------------------------
    def specs(self):
        cfg = self.cfg
        d = cfg.d_model
        specs = {
            "embed": ParamSpec((cfg.vocab_size, d), ("vocab", "embed")),
            "final_norm": ParamSpec((d,), ("embed",), init="zeros"),
            "layers": {},
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = ParamSpec((d, cfg.vocab_size),
                                         ("embed", "vocab"))
        cross = cfg.encoder_layers > 0
        for p_idx, kind in enumerate(self.pattern):
            specs["layers"][f"pos{p_idx}"] = _stack_specs(
                _block_specs(cfg, kind, p_idx, cross=cross), self.n_groups)
        if self.n_lead:
            specs["lead"] = {"pos0": _stack_specs(
                _block_specs(cfg, ATTN, 0, dense=True), self.n_lead)}
        if cfg.encoder_layers:
            specs["encoder"] = {
                "pos_embed": ParamSpec((cfg.num_audio_frames, d),
                                       (None, "embed")),
                "final_norm": ParamSpec((d,), ("embed",), init="zeros"),
                "layers": {"pos0": _stack_specs(
                    _block_specs(cfg, ATTN, 0), cfg.encoder_layers)},
            }
        return specs

    def init(self, key):
        return L.init_params(self.specs(), key, self.cfg.param_dtype)

    def param_structs(self):
        return L.param_structs(self.specs(), self.cfg.param_dtype)

    def param_logical_axes(self):
        return L.param_axes(self.specs())

    # ------------------------------------------------------------------
    # Block application
    # ------------------------------------------------------------------
    def _apply_block(self, kind, p, x, positions, *, layer=None,
                     cache=None, cache_index=None, enc_out=None,
                     causal=True):
        """One block -> (x, cache, aux loss, load).  ``cache``: this
        pattern position's stacked cache (every layer's entries on a
        leading axis) or None; the block reads and writes only layer
        ``layer``'s entries and returns the whole stacked cache, so the
        buffer is updated in place.  With a cache (serving) an MoE FFN
        drops no token and ``load`` is its (tokens, held experts)
        assignments; else None."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        load = None
        new_cache = dict(cache) if cache is not None else {}
        h = L.rms_norm(x, p["norm1"], cfg.rms_eps)
        if kind in (ATTN, ATTN_LOCAL) and cfg.mla is not None:
            out, lat = MLA.mla_apply(
                p["core"], cfg, h, positions, cache=new_cache.get("latent"),
                layer=layer, cache_index=cache_index)
            if lat is not None:
                new_cache["latent"] = lat
        elif kind in (ATTN, ATTN_LOCAL):
            window = cfg.sliding_window if kind == ATTN_LOCAL else 0
            out, nkv = L.attention_apply(
                p["core"], cfg, h, positions, layer_window=window,
                kv_cache=new_cache.get("kv"), layer=layer,
                cache_index=cache_index, causal=causal, mesh=self.mesh)
            if nkv is not None:
                new_cache["kv"] = nkv
        elif kind == MAMBA:
            mc = ({n: cache[n] for n in ("ssm", "conv")}
                  if cache is not None else None)
            st = _layer(mc, layer) if mc is not None else {}
            out, st = M.mamba_apply(
                p["core"], cfg, h, ssm_state=st.get("ssm"),
                conv_state=st.get("conv"))
            if mc is not None:
                new_cache.update(_set_layer(mc, st, layer))
        elif kind in (MLSTM, SLSTM):
            name = "mlstm" if kind == MLSTM else "slstm"
            apply = X.mlstm_apply if kind == MLSTM else X.slstm_apply
            st = _layer(cache[name], layer) if cache is not None else None
            out, st = apply(p["core"], cfg, h, state=st)
            if cache is not None:
                new_cache[name] = _set_layer(cache[name], st, layer)
        x = x + out

        has_cached_cross = cache is not None and "cross_k" in cache
        if "cross" in p and (enc_out is not None or has_cached_cross):
            hc = L.rms_norm(x, p["cross_norm"], cfg.rms_eps)
            dt = hc.dtype
            if enc_out is not None:
                b, f, _ = enc_out.shape
                ck, cv = (L.to_cache_layout(
                    (enc_out @ p["cross"][w].astype(dt)).reshape(
                        b, f, cfg.num_kv_heads, cfg.resolved_head_dim))
                    for w in ("wk", "wv"))
                if cache is not None:
                    new_cache["cross_k"] = _set_layer(cache["cross_k"], ck,
                                                      layer)
                    new_cache["cross_v"] = _set_layer(cache["cross_v"], cv,
                                                      layer)
            else:
                ck = _layer(cache["cross_k"], layer)
                cv = _layer(cache["cross_v"], layer)
            out, _ = L.attention_apply(p["cross"], cfg, hc, positions,
                                       cross_kv=(ck.astype(dt), cv.astype(dt)),
                                       mesh=self.mesh)
            x = x + out

        if "ffn" in p:
            hf = L.rms_norm(x, p["norm2"], cfg.rms_eps)
            if "router" in p["ffn"] and cache is not None:
                out, load = MOE.moe_serve(p["ffn"], cfg, hf, mesh=self.mesh)
            elif "router" in p["ffn"]:
                out, a = MOE.moe_apply(p["ffn"], cfg, hf, mesh=self.mesh)
                aux = aux + a
            else:
                out = L.mlp_apply(p["ffn"], hf)
            x = x + out
        return x, new_cache, aux, load

    mesh = None   # set by the distribution layer (None => local smoke mode)

    def _constrain_act(self, x):
        """Pin (B, S, d) activations to batch-DP sharding.  SPMD propagation
        loses the batch sharding through chunked scans without this."""
        if self.mesh is None:
            return x
        dp = tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)
        if not dp or x.shape[0] % self._dp_size() != 0:
            return x
        spec = jax.sharding.PartitionSpec(dp, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))

    def _dp_size(self):
        n = 1
        for a in ("pod", "data"):
            if a in self.mesh.axis_names:
                n *= self.mesh.shape[a]
        return n

    # ------------------------------------------------------------------
    # Stack runner
    # ------------------------------------------------------------------
    def _run_stack(self, stacked_params, x, positions, *, caches=None,
                   cache_index=None, enc_out=None, remat=None,
                   pattern=None):
        """Scan the stacked groups of ``pattern`` (the model's own by
        default) -> (x, aux loss, caches, load): load stacks the MoE
        blocks' serving loads, (MoE layers, tokens, held experts), or is
        None."""
        cfg = self.cfg
        pattern = self.pattern if pattern is None else pattern
        n_groups = jax.tree.leaves(stacked_params)[0].shape[0]
        remat = cfg.remat if remat is None else remat
        import os
        if os.environ.get("REPRO_GATHER_BF16") == "1":
            # §Perf knob: cast weights to compute dtype BEFORE the scan so
            # FSDP all-gathers move bf16 instead of fp32 (halves gather
            # bytes; grads/optimizer stay fp32)
            stacked_params = jax.tree.map(
                lambda w: w.astype(self.compute_dtype)
                if w.ndim >= 3 else w, stacked_params)

        def body(carry, scan_in):
            # the caches ride in the carry: each block writes its layer's
            # new entries into the stacked buffers in place
            xc, aux_sum, cc = carry
            pg, layer = scan_in
            loads = []
            for p_idx, kind in enumerate(pattern):
                key = f"pos{p_idx}"
                xc, nc, aux, load = self._apply_block(
                    kind, pg[key], xc, positions,
                    layer=layer, cache=cc[key] if cc is not None else None,
                    cache_index=cache_index, enc_out=enc_out)
                xc = self._constrain_act(xc)
                if cc is not None:
                    cc = {**cc, key: nc}
                aux_sum = aux_sum + aux
                if load is not None:
                    loads.append(load)
            return (xc, aux_sum, cc), (jnp.stack(loads) if loads else None)

        if remat:
            import os
            pol = os.environ.get("REPRO_REMAT_POLICY", "nothing")
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if pol == "dots"
                      else jax.checkpoint_policies.nothing_saveable)
            body = jax.checkpoint(body, policy=policy)
        (x, aux, caches), load = lax.scan(
            body, (x, jnp.zeros((), jnp.float32), caches),
            (stacked_params, jnp.arange(n_groups)))
        if load is not None:
            load = load.reshape((-1,) + load.shape[2:])
        return x, aux, caches, load

    def _run_layers(self, params, x, positions, *, caches=None,
                    cache_index=None, enc_out=None, remat=None):
        """The leading dense layers (their cache under ``"lead"``), then
        the pattern's stack -> (x, aux loss, caches, load)."""
        kw = dict(cache_index=cache_index, enc_out=enc_out, remat=remat)
        if not self.n_lead:
            return self._run_stack(params["layers"], x, positions,
                                   caches=caches, **kw)
        lead = None
        if caches is not None:
            caches = dict(caches)
            lead = caches.pop("lead")
        x, aux0, lead, _ = self._run_stack(
            params["lead"], x, positions, caches=lead, pattern=(ATTN,), **kw)
        x, aux, caches, load = self._run_stack(
            params["layers"], x, positions, caches=caches, **kw)
        if caches is not None:
            caches["lead"] = lead
        return x, aux0 + aux, caches, load

    # ------------------------------------------------------------------
    # Embedding / unembedding
    # ------------------------------------------------------------------
    def _embed(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = params["embed"].astype(self.compute_dtype)[tokens]
        if cfg.family == "vlm" and "vision_embeds" in batch:
            ve = batch["vision_embeds"].astype(self.compute_dtype)
            n_vis = ve.shape[1]
            pad = x.shape[1] - n_vis
            ve_full = jnp.pad(ve, ((0, 0), (0, pad), (0, 0)))
            is_vis = (jnp.arange(x.shape[1]) < n_vis)[None, :, None]
            x = jnp.where(is_vis, ve_full, x)
        return self._constrain_act(x)

    def _positions(self, batch, seq, offset=0):
        cfg = self.cfg
        b = batch["tokens"].shape[0]
        if "positions" in batch:
            return batch["positions"]
        pos = offset + jnp.arange(seq, dtype=jnp.int32)[None, :]
        pos = jnp.broadcast_to(pos, (b, seq))
        if cfg.mrope_sections is not None:
            pos = jnp.broadcast_to(pos[None], (3, b, seq))
        return pos

    def _logits(self, params, x, chunked_labels=None):
        """Either full logits (decode) or chunked CE loss (train)."""
        cfg = self.cfg
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(self.compute_dtype)
        if chunked_labels is None:
            logits = x @ head
            return L.softcap(logits.astype(jnp.float32), cfg.final_softcap)
        labels = chunked_labels
        b, s, _ = x.shape
        chunk = min(512, s)
        assert s % chunk == 0
        nc = s // chunk

        vocab_iota = jnp.arange(cfg.vocab_size, dtype=jnp.int32)

        @jax.checkpoint
        def chunk_loss(carry, idx):
            xc = self._constrain_act(
                lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=1))
            lc = lax.dynamic_slice_in_dim(labels, idx * chunk, chunk, axis=1)
            logits = L.softcap((xc @ head).astype(jnp.float32),
                               cfg.final_softcap)
            logz = jax.nn.logsumexp(logits, axis=-1)
            # SPMD-friendly gold-logit extraction: masked reduce instead of
            # take_along_axis so the vocab-sharded dim reduces with a psum.
            gold = jnp.sum(
                jnp.where(vocab_iota[None, None, :] == lc[..., None],
                          logits, 0.0), axis=-1)
            return carry + jnp.sum(logz - gold), None

        total, _ = lax.scan(chunk_loss, jnp.zeros((), jnp.float32),
                            jnp.arange(nc))
        return total / (b * s)

    # ------------------------------------------------------------------
    # Encoder (whisper)
    # ------------------------------------------------------------------
    def _encode(self, params, batch):
        cfg = self.cfg
        enc = params["encoder"]
        frames = batch["audio_frames"].astype(self.compute_dtype)
        x = frames + enc["pos_embed"].astype(self.compute_dtype)[None]
        b, f, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(f, dtype=jnp.int32)[None], (b, f))

        def body(carry, pg):
            xc, _ = carry
            xc, _, _, _ = self._apply_block(ATTN, pg["pos0"], xc, pos,
                                            causal=False)
            return (xc, jnp.zeros((), jnp.float32)), None

        (x, _), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                             enc["layers"])
        return L.rms_norm(x, enc["final_norm"], cfg.rms_eps)

    # ------------------------------------------------------------------
    # Public entry points
    # ------------------------------------------------------------------
    def forward_train(self, params, batch):
        cfg = self.cfg
        x = self._embed(params, batch)
        positions = self._positions(batch, x.shape[1])
        enc_out = self._encode(params, batch) if cfg.encoder_layers else None
        x, aux, _, _ = self._run_layers(params, x, positions,
                                        enc_out=enc_out)
        x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
        labels = batch.get("labels", batch["tokens"])
        ce = self._logits(params, x, chunked_labels=labels)
        loss = ce + aux
        return loss, {"ce": ce, "aux": aux}

    def cache_specs(self, batch_size, max_len):
        """ShapeDtypeStruct pytree for the decode cache."""
        import os
        cd = self.compute_dtype
        kv_dt = os.environ.get("REPRO_KV_DTYPE")   # §Perf knob (e.g. f8)
        cd = jnp.dtype(kv_dt) if kv_dt else cd
        caches = self._stack_cache_specs(self.pattern, self.n_groups,
                                         batch_size, max_len, cd)
        if self.n_lead:
            caches["lead"] = self._stack_cache_specs(
                (ATTN,), self.n_lead, batch_size, max_len, cd)
        return caches

    def _stack_cache_specs(self, pattern, g, batch_size, max_len, cd):
        cfg = self.cfg
        h, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
        caches = {}
        for p_idx, kind in enumerate(pattern):
            c = {}
            if kind in (ATTN, ATTN_LOCAL) and cfg.mla is not None:
                # [c, k_r] of every position, sequence on the lanes
                c["latent"] = jax.ShapeDtypeStruct(
                    (g, batch_size, cfg.mla.cache_width, max_len), cd)
            elif kind in (ATTN, ATTN_LOCAL):
                # Sliding-window layers use a ring cache bounded by the
                # window (position p -> slot p % W).
                eff = max_len
                if kind == ATTN_LOCAL and cfg.sliding_window:
                    eff = min(max_len, cfg.sliding_window)
                # layers.to_cache_layout: the sequence on the minor axis
                c["kv"] = {
                    "k": jax.ShapeDtypeStruct(
                        (g, batch_size, nkv, h, eff), cd),
                    "v": jax.ShapeDtypeStruct(
                        (g, batch_size, nkv, h, eff), cd),
                }
            elif kind == MAMBA:
                st = M.mamba_state_specs(cfg, batch_size)
                c.update({k: jax.ShapeDtypeStruct((g,) + v.shape, v.dtype)
                          for k, v in st.items()})
            elif kind == MLSTM:
                st = X.mlstm_state_specs(cfg, batch_size)
                c["mlstm"] = {k: jax.ShapeDtypeStruct((g,) + v.shape, v.dtype)
                              for k, v in st.items()}
            elif kind == SLSTM:
                st = X.slstm_state_specs(cfg, batch_size)
                c["slstm"] = {k: jax.ShapeDtypeStruct((g,) + v.shape, v.dtype)
                              for k, v in st.items()}
            if cfg.encoder_layers:
                f = cfg.num_audio_frames
                c["cross_k"] = jax.ShapeDtypeStruct(
                    (g, batch_size, nkv, h, f), cd)
                c["cross_v"] = jax.ShapeDtypeStruct(
                    (g, batch_size, nkv, h, f), cd)
            caches[f"pos{p_idx}"] = c
        return caches

    def init_cache(self, batch_size, max_len):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_specs(batch_size, max_len))

    def prefill(self, params, batch, cache):
        """Full-sequence forward writing the cache; returns last logits."""
        cfg = self.cfg
        x = self._embed(params, batch)
        s = x.shape[1]
        positions = self._positions(batch, s)
        enc_out = self._encode(params, batch) if cfg.encoder_layers else None
        x, _, cache, _ = self._run_layers(
            params, x, positions, caches=cache,
            cache_index=jnp.zeros((), jnp.int32), enc_out=enc_out)
        x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
        logits = self._logits(params, x[:, -1:])
        return logits, cache

    def decode_step(self, params, batch, cache, pos):
        """batch["tokens"]: (B, 1); pos: scalar int32 current length."""
        logits, cache, _ = self.decode_step_load(params, batch, cache, pos)
        return logits, cache

    def decode_step_load(self, params, batch, cache, pos):
        """``decode_step`` -> (logits, cache, load): load is each MoE
        layer's assignments of each row's token to the held experts,
        (MoE layers, B, held) int32, or None for a model without MoE."""
        cfg = self.cfg
        x = self._embed(params, batch)
        positions = self._positions(batch, 1, offset=pos)
        # cross kv comes from the cache during decode
        x, _, cache, load = self._run_layers(params, x, positions,
                                             caches=cache, cache_index=pos,
                                             remat=False)
        x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
        logits = self._logits(params, x)
        return logits, cache, load
