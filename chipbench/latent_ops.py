"""Device time of the decode step's latent-attention and held-expert
operations, found by what the profiler trace itself carries.

Each event on a TPU plane's ``XLA Ops`` line is named by its HLO
instruction, output and operand shapes included.  Only this
configuration has a latent cache (r + R = 576 rows a position of
``max_len`` positions, stored (layers, slots, 576, max_len)) and held
experts of (held, hidden, expert width) weights, which a decode step's
masked products read whole; a prefill's grouped products are
``ragged-dot`` custom calls, and their dispatch and combine move the
slots x top-k assignment rows.  Loop and call containers, whose
events span their bodies' operations, are left out; only events inside
the engine's decode regions count.
"""
from __future__ import annotations

import re

CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\s=]")


def latent_patterns(cfg: dict) -> list:
    w = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return [f",{w},{cfg['serving']['max_len']}]"]


def expert_patterns(cfg: dict) -> list:
    """The grouped products, the operations on the held experts'
    weights, and the dispatch and combine of slots x top-k assignment
    rows."""
    e, d, f = (cfg["n_routed_experts"], cfg["hidden_size"],
               cfg["moe_intermediate_size"])
    rows = cfg["serving"]["batch_slots"] * cfg["num_experts_per_tok"]
    return ["ragged-dot", f"{e},{d},{f}]", f"{e},{f},{d}]",
            f"[{rows},{d}]", f"s32[{rows}]"]


def decode_op_seconds(ctx: dict, patterns: list):
    """Seconds of device operations whose names hold any of
    ``patterns``, inside the decode regions, averaged over devices; None
    when the trace holds none."""
    ev, iv = ctx.get("events"), ctx.get("decode_intervals_ns")
    if not ev or not ev.get("devices") or not iv:
        return None
    iv = sorted(iv)
    total, found = 0.0, False
    for plane, ops in ev["devices"].items():
        for name, a, b in ops:
            if CONTAINERS.match(name) or not any(p in name for p in patterns):
                continue
            for lo, hi in iv:
                if hi <= a:
                    continue
                if lo >= b:
                    break
                total += (min(b, hi) - max(a, lo)) * 1e-9
                found = True
    return total / len(ev["devices"]) if found else None
