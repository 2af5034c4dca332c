"""One decode query per slot over its own filled latent blocks.

Grid: (B,) slots, run in order.  The stacked (G, B, C, S) cache stays in
HBM; each slot's (C, blk) blocks 0 .. pos[b] // blk of layer ``layer``
are copied into a two-buffer VMEM ring, the next block (the next slot's
first, after a slot's last) in flight while one is read.  Each tile is
read once: the scores from all C rows, the output from the first
``rank``, with the running max, sum and accumulator in float32 (online
softmax).  Blocks past a slot's position are never read.

The products take bfloat16 operands with float32 accumulation.  The
float32 query and the probabilities are each split into a bfloat16 head
and its rounding remainder, stacked as 2H rows of one product, so the
cache tile (exact in bfloat16) meets both halves in one pass."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK = 1024     # positions a block: 1.2 MB of bfloat16 at C = 576


def block_positions(max_len: int) -> int:
    """Positions in each block the kernel reads of a ``max_len`` cache."""
    return math.gcd(max_len, BLOCK)


def _split(x):
    """float32 (M, N) -> bfloat16 (2M, N): x's rounding and remainder."""
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, lo], axis=0)


def _kernel(layer_ref, pos_ref, q_ref, cache_hbm, o_ref, buf, sem, issued,
            *, blk, rank, scale, n_slots, n_blocks):
    b = pl.program_id(0)
    g = layer_ref[0]
    h = o_ref.shape[0]

    def copy(slot, j, k):
        return pltpu.make_async_copy(
            cache_hbm.at[g, slot, :, pl.ds(pl.multiple_of(j * blk, blk), blk)],
            buf.at[k % 2], sem.at[k % 2])

    @pl.when(b == 0)
    def _():
        issued[0] = 0
        copy(0, 0, 0).start()

    pos = pos_ref[b]
    n = jnp.minimum(pos // blk + 1, n_blocks)      # no copy past the cache
    k0 = issued[0]
    q2 = _split(q_ref[...])                                  # (2H, C)

    def body(j, carry):
        m, l, acc = carry
        k = k0 + j

        @pl.when(j + 1 < n)
        def _():
            copy(b, j + 1, k + 1).start()

        @pl.when((j + 1 == n) & (b + 1 < n_slots))
        def _():
            copy(b + 1, 0, k + 1).start()

        copy(b, j, k).wait()
        t = buf[k % 2]                                        # (C, blk)
        s2 = jnp.dot(q2, t, preferred_element_type=jnp.float32)
        s = (s2[:h] + s2[h:]) * scale                         # (H, blk)
        at = j * blk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(at <= pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        o2 = lax.dot_general(_split(p), t[:rank],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                alpha * acc + o2[:h] + o2[h:])

    init = (jnp.full((h, 1), NEG_INF, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, rank), jnp.float32))
    _, l, acc = lax.fori_loop(0, n, body, init)
    issued[0] = k0 + n
    o_ref[...] = acc / l


def latent_decode_kernel(qc, cache, layer, pos, *, rank, scale,
                         interpret=False):
    """qc: (B, H, C) float32; cache: (G, B, C, S), read in place; layer:
    int32 scalar; pos: (B,) int32.  -> o_lat (B, H, rank) float32."""
    g, nb, c, s = cache.shape
    h = qc.shape[1]
    blk = block_positions(s)
    assert blk % 128 == 0, f"{s} positions are not whole 128-lane blocks"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[pl.BlockSpec((None, h, c), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, h, rank), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, c, blk), cache.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)])
    return pl.pallas_call(
        functools.partial(_kernel, blk=blk, rank=rank, scale=scale,
                          n_slots=nb, n_blocks=s // blk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, h, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos.astype(jnp.int32),
      qc.astype(jnp.float32), cache)
