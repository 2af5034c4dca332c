"""Share of HBM peak bandwidth in the decode step's latent-cache reads:
the latent bytes the active slots' attention must read (each slot's
filled positions x 576 values x 2 B x 27 layers, ``flops_latent``) over
the device time of the operations that read the latent cache."""
import latent_ops


def read(ctx):
    cfg, need = ctx.get("config"), ctx.get("latent_bytes")
    if not cfg or not need or not ctx.get("peaks"):
        return None
    s = latent_ops.decode_op_seconds(ctx, latent_ops.latent_patterns(cfg))
    if not s:
        return None
    return 100.0 * need / (s * ctx["peaks"]["hbm_bytes_per_s"])
