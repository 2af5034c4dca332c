"""Operations and bytes of a latent-attention MoE decoder's decode step
(moonlight-16b-a3b), counted from the logical shapes of its work, never
from tile-padded shapes, so the count does not depend on how a kernel or
a grouped product implements it.

FLOPs are two per weight applied.  Attention is counted in the absorbed
form a decode step uses: per layer and head, the query against the
r + R = 576 cache rows of every filled position and the output over
their r = 512 latent rows; W_kvb's two halves are applied once each, as
weights.  The held experts' FLOPs come from the step's own counter of
token-to-held-expert assignments (the engine's ``route_assignments``),
since routing decides them; so do the bytes of held experts read, from
the (layer, held expert) pairs that got at least one token
(``route_pairs``).
"""
from __future__ import annotations


def _dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    return {"d": cfg["hidden_size"], "L": cfg["num_hidden_layers"],
            "lead": cfg["first_k_dense_replace"], "H": h,
            "r": cfg["kv_lora_rank"], "R": cfg["qk_rope_head_dim"],
            "nope": cfg["qk_nope_head_dim"], "dv": cfg["v_head_dim"],
            "ff": cfg["intermediate_size"],
            "E": cfg["published_n_routed_experts"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "V": cfg["vocab_size"]}


def dense_weights_per_token(cfg: dict) -> int:
    """Weights every token applies: attention in every layer, layer 0's
    SwiGLU, the router and the shared experts of every MoE layer, and the
    LM head (the routed experts are counted by assignment)."""
    m = _dims(cfg)
    d, h = m["d"], m["H"]
    attn = (d * h * (m["nope"] + m["R"]) + d * (m["r"] + m["R"])
            + m["r"] * h * (m["nope"] + m["dv"]) + h * m["dv"] * d)
    n_moe = m["L"] - m["lead"]
    return (m["L"] * attn + m["lead"] * 3 * d * m["ff"]
            + n_moe * (d * m["E"] + 3 * d * m["fs"]) + d * m["V"])


def token_flops(cfg: dict, context: int) -> int:
    """FLOPs of one decode token, routed experts aside, attending over
    ``context`` filled positions in every layer."""
    m = _dims(cfg)
    attn = 2 * m["H"] * (m["r"] + m["R"] + m["r"]) * context * m["L"]
    return 2 * dense_weights_per_token(cfg) + attn


def expert_weights(cfg: dict) -> int:
    """Weights of one routed expert (a SwiGLU of width f)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_flops(cfg: dict, assignments: int) -> int:
    return 2 * expert_weights(cfg) * assignments


def expert_bytes(cfg: dict, pairs: int, itemsize: int = 2) -> int:
    """Bytes of the held experts that got at least one token."""
    return expert_weights(cfg) * itemsize * pairs


def latent_bytes(cfg: dict, context: int, itemsize: int = 2) -> int:
    """Latent-cache bytes one slot's attention must read at ``context``
    filled positions: every layer's r + R rows of each."""
    m = _dims(cfg)
    return context * (m["r"] + m["R"]) * itemsize * m["L"]
