"""Public API: one decode query per slot over a stacked latent cache."""
from __future__ import annotations

import jax

from repro.kernels.latent_decode.kernel import latent_decode_kernel
from repro.kernels.latent_decode.ref import latent_decode_ref


def latent_decode(qc, cache, layer, pos, *, rank, scale):
    """qc: (B, H, C) absorbed queries; cache: (G, B, C, S), layer
    ``layer`` read; pos: (B,) int32, positions <= pos[b] valid.  ->
    o_lat (B, H, rank) float32.  The Pallas kernel on a TPU, which
    reads each slot's blocks only up to its position; the oracle
    elsewhere."""
    if jax.default_backend() == "tpu":
        return latent_decode_kernel(qc, cache, layer, pos, rank=rank,
                                    scale=scale)
    return latent_decode_ref(qc, cache, layer, pos, rank=rank, scale=scale)
