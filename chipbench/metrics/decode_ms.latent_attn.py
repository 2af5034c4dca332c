"""Device milliseconds per decode step in the operations that read the
latent cache (its 576-wide rows of every position), found by their
operand shapes in the trace (``latent_ops``), inside the decode
regions."""
import latent_ops


def read(ctx):
    cfg, steps = ctx.get("config"), ctx.get("decode_steps")
    if not cfg or not steps:
        return None
    s = latent_ops.decode_op_seconds(ctx, latent_ops.latent_patterns(cfg))
    return 1e3 * s / steps if s else None
