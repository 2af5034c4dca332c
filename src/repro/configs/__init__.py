"""Config registry: 10 assigned architectures + the 4 input-shape regimes.

Usage::

    from repro.configs import get_arch, get_shape, ARCHS, SHAPES, reduced
    cfg = get_arch("llama3.2-3b")
    tiny = reduced(cfg)             # CPU-smoke-testable version, same family
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import (  # noqa: F401
    ArchConfig, MLAConfig, MoEConfig, ShapeConfig, SHAPES,
    TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K,
    ATTN, ATTN_LOCAL, MAMBA, MLSTM, SLSTM,
    cell_is_runnable,
)

from repro.configs.qwen2_vl_2b import CONFIG as _qwen2_vl_2b
from repro.configs.qwen1_5_32b import CONFIG as _qwen1_5_32b
from repro.configs.llama3_2_3b import CONFIG as _llama3_2_3b
from repro.configs.minicpm_2b import CONFIG as _minicpm_2b
from repro.configs.gemma2_27b import CONFIG as _gemma2_27b
from repro.configs.moonlight_16b_a3b import CONFIG as _moonlight
from repro.configs.qwen3_moe_235b_a22b import CONFIG as _qwen3_moe
from repro.configs.jamba_1_5_large_398b import CONFIG as _jamba
from repro.configs.whisper_base import CONFIG as _whisper
from repro.configs.xlstm_1_3b import CONFIG as _xlstm

ARCHS: dict[str, ArchConfig] = {
    c.name: c
    for c in (
        _qwen2_vl_2b, _qwen1_5_32b, _llama3_2_3b, _minicpm_2b, _gemma2_27b,
        _moonlight, _qwen3_moe, _jamba, _whisper, _xlstm,
    )
}


def get_arch(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


def arch_from_hf(keys: dict, **overrides) -> ArchConfig:
    """An ``ArchConfig`` from a published ``config.json``'s keys (Llama-
    and DeepSeek-V3-style decoders); ``overrides`` replace fields after.
    MoE keys give the router's width as published: a deployment's share
    of the experts is ``held_experts``."""
    k = keys
    kw = dict(
        name=k.get("name", k.get("model_type", "hf")), family="dense",
        num_layers=k["num_hidden_layers"], d_model=k["hidden_size"],
        num_heads=k["num_attention_heads"],
        num_kv_heads=k.get("num_key_value_heads",
                           k["num_attention_heads"]),
        d_ff=k["intermediate_size"], vocab_size=k["vocab_size"],
        rope_theta=float(k.get("rope_theta", 10_000.0)),
        tie_embeddings=bool(k.get("tie_word_embeddings", True)),
        rms_eps=float(k.get("rms_norm_eps", 1e-6)))
    if k.get("kv_lora_rank"):
        if k.get("q_lora_rank") is not None:
            raise ValueError("compressed queries (q_lora_rank) are not "
                             "supported")
        kw["mla"] = MLAConfig(k["kv_lora_rank"], k["qk_nope_head_dim"],
                              k["qk_rope_head_dim"], k["v_head_dim"])
    if k.get("n_routed_experts"):
        if not (k.get("n_group", 1) == k.get("topk_group", 1) == 1
                and k.get("moe_layer_freq", 1) == 1
                and k.get("norm_topk_prob", True)):
            raise ValueError("grouped routing, sparse MoE layers and "
                             "unnormalised gates are not supported")
        kw.update(family="moe",
                  first_dense_layers=k.get("first_k_dense_replace", 0),
                  moe=MoEConfig(
                      num_experts=k["n_routed_experts"],
                      top_k=k["num_experts_per_tok"],
                      expert_d_ff=k["moe_intermediate_size"],
                      num_shared_experts=k.get("n_shared_experts") or 0,
                      scoring=k.get("scoring_func", "softmax"),
                      selection_bias=k.get("topk_method") == "noaux_tc",
                      routed_scale=float(
                          k.get("routed_scaling_factor", 1.0))))
    return dataclasses.replace(ArchConfig(**kw), **overrides)


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; available: {sorted(SHAPES)}")
    return SHAPES[name]


def reduced(cfg: ArchConfig) -> ArchConfig:
    """A tiny same-family config for CPU smoke tests.

    Keeps the block pattern, attention options, MoE/hybrid structure;
    shrinks depth/width/experts/vocab so one forward+train step runs on CPU.
    """
    n_layers = cfg.first_dense_layers + (
        max(2, 2 * len(cfg.block_pattern))
        if len(cfg.block_pattern) > 1 else 2)
    moe = None
    if cfg.moe is not None:
        moe = dataclasses.replace(
            cfg.moe, num_experts=4, top_k=min(cfg.moe.top_k, 2),
            expert_d_ff=32,
            num_shared_experts=min(cfg.moe.num_shared_experts, 1),
        )
    mla = None
    if cfg.mla is not None:
        mla = dataclasses.replace(cfg.mla, kv_lora_rank=32,
                                  qk_nope_head_dim=16, qk_rope_head_dim=8,
                                  v_head_dim=16)
    kv = min(cfg.num_kv_heads, 2)
    heads = max(4, (4 // kv) * kv)   # keep heads % kv == 0
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        num_layers=n_layers,
        encoder_layers=2 if cfg.encoder_layers else 0,
        d_model=64,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=16,
        d_ff=0 if cfg.d_ff == 0 else 128,
        vocab_size=512,
        sliding_window=min(cfg.sliding_window, 8) if cfg.sliding_window else 0,
        mrope_sections=(2, 3, 3) if cfg.mrope_sections else None,
        moe=moe,
        mla=mla,
        mamba_d_state=8,
        num_audio_frames=16,
        remat=False,
    )


SMOKE_SHAPE = ShapeConfig("smoke", seq_len=16, global_batch=2, kind="train")
SMOKE_DECODE = ShapeConfig("smoke_decode", seq_len=32, global_batch=2,
                           kind="decode")
SMOKE_PREFILL = ShapeConfig("smoke_prefill", seq_len=16, global_batch=2,
                            kind="prefill")
