"""Pure-jnp oracle for one decode query per slot over a stacked latent
cache: the scores over every position, the positions past each slot's
own masked, one softmax, the output over the latent rows.  It is also
the path off the TPU."""
import jax
import jax.numpy as jnp
from jax import lax


def _layer_rows(cache, layer, rows):
    """Layer ``layer``'s first ``rows`` rows, (B, rows, S), of a stacked
    (G, B, C, S) cache, read in place by the product that takes it."""
    g, b, c, s = cache.shape
    return lax.dynamic_slice(cache, (layer, 0, 0, 0), (1, b, rows, s))[0]


def latent_decode_ref(qc, cache, layer, pos, *, rank, scale):
    """qc: (B, H, C) absorbed queries; cache: (G, B, C, S); layer: int32
    scalar; pos: (B,) int32, positions <= pos[b] valid.  -> o_lat
    (B, H, rank) float32, the softmax-weighted sum of the first ``rank``
    rows.  The scores read all C rows and the output the first ``rank``:
    two slices of the layer, each read in place by its product (one
    shared slice would be copied out, whole)."""
    f32 = jnp.float32
    c = cache.shape[2]
    scores = jnp.einsum("bhc,bcs->bhs", qc.astype(f32),
                        _layer_rows(cache, layer, c).astype(f32)) * scale
    valid = jnp.arange(cache.shape[-1])[None, :] <= jnp.reshape(pos, (-1, 1))
    probs = jax.nn.softmax(jnp.where(valid[:, None, :], scores, -1e30),
                           axis=-1)
    return jnp.einsum("bhs,bcs->bhc", probs,
                      _layer_rows(cache, layer, rank).astype(f32))
