"""Share of HBM peak bandwidth in the held experts: the bytes of the
held experts that got at least one token in each decode step (the
engine's load counter, ``flops_latent.expert_bytes``) over the device
time of the experts' operations."""
import latent_ops


def read(ctx):
    cfg, need = ctx.get("config"), ctx.get("expert_bytes")
    if not cfg or not need or not ctx.get("peaks"):
        return None
    s = latent_ops.decode_op_seconds(ctx, latent_ops.expert_patterns(cfg))
    if not s:
        return None
    return 100.0 * need / (s * ctx["peaks"]["hbm_bytes_per_s"])
