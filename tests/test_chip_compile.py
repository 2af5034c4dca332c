"""Compile rehearsals of the fleet kernels and the latent decode kernel
for a TPU v5e, with no chip.

Interpret mode runs every kernel on the CPU and cannot see what the
chip's compiler refuses (boolean stores, unsupported gathers, block
shapes).  Each test here lowers one kernel at fleet width (2,560 rows x
4,096 samples, float32), or at moonlight-16b-a3b's serving shapes, for
a described v5e chip and checks that the compiled program holds the
Mosaic kernel (``tpu_custom_call``).

The topology is described only inside the module fixture: one process
at a time may load the TPU library, so nothing here may touch it while
the module is imported or collected.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROWS, SAMPLES = 2560, 4096


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 — any failure means no TPU
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a cache written by a chip-less compile cannot be read back
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32_BLOCK = ((ROWS, SAMPLES), jnp.float32)
F32_ROW = ((ROWS, 1), jnp.float32)
I32_ROW = ((ROWS, 1), jnp.int32)
PHASES = ((32, 2), jnp.float32)


def _power_reconstruct_fleet(e, t, w, n):
    from repro.kernels.power_reconstruct.kernel import \
        power_reconstruct_fleet_kernel
    return power_reconstruct_fleet_kernel(e, t, w, n, interpret=False)


def _power_reconstruct_rows(e, t, w):
    from repro.kernels.power_reconstruct.kernel import \
        power_reconstruct_rows_kernel
    return power_reconstruct_rows_kernel(e, t, w, interpret=False)


def _fleet_attribute(t, e, w, ph):
    from repro.kernels.fleet_attribute.kernel import fleet_attribute_kernel
    return fleet_attribute_kernel(t, e, w, ph, interpret=False)


def _phase_integrate(t, p, ph):
    from repro.kernels.phase_integrate.kernel import phase_integrate_kernel
    return phase_integrate_kernel(t, p, ph, interpret=False)


def _grid_resample(mode):
    def run(t, v, n, first, grid, d):
        from repro.kernels.grid_resample.kernel import grid_resample_kernel
        return grid_resample_kernel(t, v, n, first, grid, d, mode=mode,
                                    interpret=False)
    return run


def _xcorr_align(x, m, bank):
    from repro.kernels.xcorr_align.kernel import xcorr_align_kernel
    from repro.kernels.xcorr_align.ops import ROW_ALIGN
    return xcorr_align_kernel(x, m, bank, block_rows=ROW_ALIGN,
                              interpret=False)


CASES = {
    "power_reconstruct_fleet": (_power_reconstruct_fleet,
                                [F32_BLOCK, F32_BLOCK, F32_ROW, I32_ROW]),
    "power_reconstruct_rows": (_power_reconstruct_rows,
                               [F32_BLOCK, F32_BLOCK, F32_ROW]),
    "fleet_attribute": (_fleet_attribute,
                        [F32_BLOCK, F32_BLOCK, F32_ROW, PHASES]),
    "phase_integrate": (_phase_integrate, [F32_BLOCK, F32_BLOCK, PHASES]),
    "grid_resample_hold": (_grid_resample("hold"),
                           [F32_BLOCK, F32_BLOCK, I32_ROW, I32_ROW,
                            ((SAMPLES, 1), jnp.float32), F32_ROW]),
    "grid_resample_linear": (_grid_resample("linear"),
                             [F32_BLOCK, F32_BLOCK, I32_ROW, I32_ROW,
                              ((SAMPLES, 1), jnp.float32), F32_ROW]),
    "xcorr_align": (_xcorr_align,
                    [F32_BLOCK, F32_BLOCK, ((256, SAMPLES), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_fleet_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    text = _compiled_text(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text, name


def test_latent_decode_compiles_for_v5e(one_chip):
    """moonlight-16b-a3b's decode read: 16 slots, 16 heads, the stacked
    (26, 16, 576, 8192) bf16 cache handed whole to the kernel, which
    copies only its blocks: no temp buffer, and the custom call's
    operand is the cache itself (what ``decode_ms.latent_attn`` finds)."""
    from repro.kernels.latent_decode.kernel import latent_decode_kernel

    def run(qc, cache, layer, pos):
        return latent_decode_kernel(qc, cache, layer, pos, rank=512,
                                    scale=192 ** -0.5)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
            [((16, 16, 576), jnp.float32),
             ((26, 16, 576, 8192), jnp.bfloat16),
             ((), jnp.int32), ((16,), jnp.int32)]]
    compiled = jax.jit(run).lower(*args).compile()
    (call,) = [ln for ln in compiled.as_text().splitlines()
               if "tpu_custom_call" in ln]
    assert "bf16[26,16,576,8192]" in call
    assert compiled.memory_analysis().temp_size_in_bytes == 0
