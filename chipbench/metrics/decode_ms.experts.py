"""Device milliseconds per decode step in the held experts' products
with their dispatch and combine: the operations on (held, hidden, expert
width) weights and any ``ragged-dot`` call, found in the trace
(``latent_ops``), inside the decode regions."""
import latent_ops


def read(ctx):
    cfg, steps = ctx.get("config"), ctx.get("decode_steps")
    if not cfg or not steps:
        return None
    s = latent_ops.decode_op_seconds(ctx, latent_ops.expert_patterns(cfg))
    return 1e3 * s / steps if s else None
