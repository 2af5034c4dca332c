"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package: kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd public wrapper), ref.py (pure-jnp oracle).  All validated in
interpret=True mode on CPU; on TPU the same BlockSpecs drive MXU/VMEM.

  squarewave        — calibrated FMA workload (the paper's §IV-B generator)
  power_reconstruct — dE/dt + wraparound over (devices x samples) traces
  phase_integrate   — segmented per-phase energy integration
  fleet_attribute   — fused dE/dt + phase integration for streamed chunks
  grid_resample     — masked searchsorted + hold/linear regrid (alignment)
  xcorr_align       — lag-bank normalized cross-correlation (delay est.)
  flash_attention   — causal GQA flash attention (+gemma2 softcap)
  ssm_scan          — selective-scan (mamba) inner recurrence
  latent_decode     — one decode query per slot over its own filled
                      blocks of a stacked latent (MLA) cache
"""


def auto_block_rows(n_rows: int, block_rows, interpret: bool,
                    compiled_rows: int = 8) -> int:
    """Shared row-tiling policy for the fleet-facing kernels.

    ``block_rows=None`` auto-sizes: ``compiled_rows``-row VMEM tiles when
    compiled, the whole fleet in one grid step under interpret (per-step
    emulation overhead dwarfs any tiling benefit there).
    """
    if block_rows is None:
        block_rows = n_rows if interpret else compiled_rows
    return min(block_rows, n_rows)


def pad_rows(block_rows: int, *arrays):
    """Zero-pad each array's row axis to a multiple of ``block_rows``.

    Compiled kernels tile rows in fixed blocks; callers slice the first
    ``n`` output rows back, so the zero rows never surface.
    """
    pad = (-arrays[0].shape[0]) % block_rows
    if not pad:
        return arrays
    import jax.numpy as jnp
    return tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                 for a in arrays)
