"""Operations each measured step needs, counted from the logical shapes
of its work, never from tile-padded shapes, so the count does not depend
on how a kernel is implemented.
"""
from __future__ import annotations


def decoder_token_flops(cfg: dict, context: int) -> int:
    """Forward FLOPs of one token through a dense decoder: two per
    weight of every layer and of the (tied) LM head, plus attention's
    two products over ``context`` cached positions."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // nq
    ff = cfg["intermediate_size"]
    per_layer = d * nq * hd + 2 * d * nkv * hd + nq * hd * d + 3 * d * ff
    head = d * cfg["vocab_size"]
    attn = 4 * L * nq * hd * context
    return 2 * (L * per_layer + head) + attn
