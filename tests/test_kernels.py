"""Per-kernel allclose validation against the pure-jnp oracles, sweeping
shapes and dtypes (interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.phase_integrate.ops import phase_energies
from repro.kernels.phase_integrate.ref import phase_energies_ref
from repro.kernels.power_reconstruct.ops import reconstruct_power
from repro.kernels.power_reconstruct.ref import reconstruct_power_ref
from repro.kernels.squarewave.ops import (calibrated_fma_count,
                                          squarewave_load)
from repro.kernels.squarewave.ref import squarewave_ref
from repro.kernels.ssm_scan.ops import selective_scan
from repro.kernels.ssm_scan.ref import selective_scan_ref


# ---------------------------------------------------------------- squarewave
@pytest.mark.parametrize("shape", [(256, 128), (512, 256), (1024, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_squarewave(shape, dtype):
    x = jax.random.normal(jax.random.key(0), shape, dtype)
    out = squarewave_load(x, fma_chain=17, interpret=True)
    ref = squarewave_ref(x, fma_chain=17)
    rtol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=rtol)


def test_calibrated_fma_count_matches_balance():
    k32 = calibrated_fma_count(jnp.float32)
    k16 = calibrated_fma_count(jnp.bfloat16)
    # flops/byte = 2K/(2*itemsize) must equal the machine balance
    assert abs(2 * k32 / 8.0 - 197e12 / 819e9 * 1.0) < 1.0
    assert abs(k32 - 2 * k16) <= 2


# ---------------------------------------------------------- power_reconstruct
@pytest.mark.parametrize("n,s", [(8, 512), (16, 1024), (4, 4096)])
@pytest.mark.parametrize("wrap", [0.0, 50.0])
def test_power_reconstruct(n, s, wrap):
    rng = np.random.default_rng(int(n + s))
    t = np.cumsum(rng.uniform(0.5e-3, 1.5e-3, (n, s)), axis=1)
    t = t.astype(np.float32)
    p = rng.uniform(50, 250, (n, s)).astype(np.float32)
    dt = np.diff(t, axis=1, prepend=t[:, :1] - 1e-3)
    e = np.cumsum(p * dt, axis=1)
    if wrap:
        e = np.mod(e, wrap)
    out = reconstruct_power(jnp.array(e), jnp.array(t), wrap_period=wrap,
                            interpret=True)
    ref = reconstruct_power_ref(jnp.array(e), jnp.array(t),
                                wrap_period=wrap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-2)
    # reconstruction ~ recovers the true power away from wrap edges
    if not wrap:
        np.testing.assert_allclose(np.asarray(out)[:, 2:], p[:, 2:],
                                   rtol=0.35)


# ---------------------------------------------- power_reconstruct (per-row)
@pytest.mark.parametrize("n,s", [(8, 512), (16, 1024)])
def test_power_reconstruct_rows(n, s):
    """Heterogeneous wrap periods: per-row kernel vs per-row oracle, and
    vs the scalar-wrap kernel on homogeneous rows."""
    from repro.kernels.power_reconstruct.kernel import \
        power_reconstruct_rows_kernel
    from repro.kernels.power_reconstruct.ref import \
        reconstruct_power_rows_ref
    rng = np.random.default_rng(int(n + s))
    t = np.cumsum(rng.uniform(0.5e-3, 1.5e-3, (n, s)), axis=1)
    t = t.astype(np.float32)
    p = rng.uniform(50, 250, (n, s)).astype(np.float32)
    dt = np.diff(t, axis=1, prepend=t[:, :1] - 1e-3)
    e = np.cumsum(p * dt, axis=1)
    wrap = np.where(np.arange(n) % 2 == 0, 50.0, 0.0).astype(np.float32)
    e = np.where(wrap[:, None] > 0, np.mod(e, 50.0), e).astype(np.float32)
    out = power_reconstruct_rows_kernel(jnp.array(e), jnp.array(t),
                                        jnp.array(wrap)[:, None],
                                        interpret=True)
    ref = reconstruct_power_rows_ref(jnp.array(e), jnp.array(t),
                                     jnp.array(wrap)[:, None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-2)
    # homogeneous no-wrap rows agree with the legacy scalar-wrap kernel
    legacy = reconstruct_power(jnp.array(e[1::2]), jnp.array(t[1::2]),
                               wrap_period=0.0, interpret=True)
    np.testing.assert_allclose(np.asarray(out)[1::2], np.asarray(legacy),
                               rtol=1e-4, atol=1e-2)


# ------------------------------------------------------------ fleet_attribute
@pytest.mark.parametrize("n,s,p", [(8, 512, 8), (16, 300, 32)])
def test_fleet_attribute_fused(n, s, p):
    """Fused ΔE/Δt+integrate kernel == composition of the stage oracles."""
    from repro.kernels.fleet_attribute.kernel import fleet_attribute_kernel
    from repro.kernels.fleet_attribute.ref import fleet_attribute_ref
    rng = np.random.default_rng(int(n * s + p))
    t = np.cumsum(rng.uniform(0.5e-3, 1.5e-3, (n, s)),
                  axis=1).astype(np.float32)
    pw = rng.uniform(50, 250, (n, s)).astype(np.float32)
    dt = np.diff(t, axis=1, prepend=t[:, :1] - 1e-3)
    e = np.cumsum(pw * dt, axis=1).astype(np.float32)
    wrap = np.zeros((n, 1), np.float32)
    ph = np.sort(rng.uniform(t.min(), t.max(), (p, 2)).astype(np.float32),
                 axis=1)
    out = fleet_attribute_kernel(jnp.array(t), jnp.array(e),
                                 jnp.array(wrap), jnp.array(ph),
                                 interpret=True)
    ref = fleet_attribute_ref(jnp.array(t), jnp.array(e), jnp.array(wrap),
                              jnp.array(ph))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)


def test_fleet_kernels_pad_ragged_row_counts():
    """Row counts that are no multiple of the compiled row tile are
    zero-padded inside the kernels and sliced back (13 rows, 8-row
    tiles): the same energies as the oracles."""
    from repro.kernels.fleet_attribute.kernel import fleet_attribute_kernel
    from repro.kernels.fleet_attribute.ref import fleet_attribute_ref
    from repro.kernels.phase_integrate.kernel import phase_integrate_kernel
    rng = np.random.default_rng(13)
    n, s = 13, 256
    t = np.cumsum(rng.uniform(0.5e-3, 1.5e-3, (n, s)),
                  axis=1).astype(np.float32)
    pw = rng.uniform(50, 250, (n, s)).astype(np.float32)
    e = np.cumsum(pw * 1e-3, axis=1).astype(np.float32)
    ph = np.sort(rng.uniform(t.min(), t.max(), (32, 2)).astype(np.float32),
                 axis=1)
    args = [jnp.array(a) for a in (t, e, np.zeros((n, 1), np.float32), ph)]
    out = fleet_attribute_kernel(*args, block_rows=8, interpret=True)
    assert out.shape == (n, 32)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(fleet_attribute_ref(*args)),
                               rtol=1e-4, atol=1e-3)
    out = phase_integrate_kernel(jnp.array(t), jnp.array(pw),
                                 jnp.array(ph), block_rows=8,
                                 interpret=True)
    ref = phase_energies_ref(jnp.array(t), jnp.array(pw), jnp.array(ph))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ phase_integrate
@pytest.mark.parametrize("n,s,p", [(8, 256, 32), (16, 1000, 64)])
def test_phase_integrate(n, s, p):
    rng = np.random.default_rng(int(n * s + p))
    t = np.cumsum(rng.uniform(0.5e-3, 1.5e-3, (n, s)), axis=1)
    t = t.astype(np.float32)
    w = rng.uniform(50, 250, (n, s)).astype(np.float32)
    ph = np.sort(rng.uniform(t.min(), t.max(), (p, 2)).astype(np.float32),
                 axis=1)
    out = phase_energies(jnp.array(t), jnp.array(w), jnp.array(ph),
                         interpret=True)
    ref = phase_energies_ref(jnp.array(t), jnp.array(w), jnp.array(ph))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ flash_attention
@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (2, 4, 2, 256, 64), (1, 8, 8, 128, 64), (2, 8, 2, 256, 128),
])
@pytest.mark.parametrize("cap", [0.0, 50.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, hq, hkv, s, d, cap, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = flash_attention(q, k, v, causal=True, logit_cap=cap,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, logit_cap=cap)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


# ------------------------------------------------------------------ ssm_scan
@pytest.mark.parametrize("b,l,d,n", [(2, 64, 256, 16), (1, 128, 128, 8)])
def test_ssm_scan(b, l, d, n):
    ks = jax.random.split(jax.random.key(1), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, l, d))) * 0.1
    x = jax.random.normal(ks[1], (b, l, d))
    bm = jax.random.normal(ks[2], (b, l, n))
    cm = jax.random.normal(ks[3], (b, l, n))
    a = -jnp.exp(jax.random.normal(ks[4], (d, n)) * 0.1)
    h0 = jax.random.normal(ks[5], (b, d, n)) * 0.1
    y, h = selective_scan(dt, x, bm, cm, a, h0, interpret=True)
    yr, hr = selective_scan_ref(dt, x, bm, cm, a, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=3e-4, atol=3e-4)


def test_ssm_kernel_matches_model_layer():
    """The Pallas kernel implements the same recurrence as the model's
    chunked associative scan (drop-in replacement check)."""
    from repro.models.mamba import _chunk_scan
    b, s, d, n = 2, 64, 128, 16
    ks = jax.random.split(jax.random.key(2), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, s, d))) * 0.1
    x = jax.random.normal(ks[1], (b, s, d))
    bm = jax.random.normal(ks[2], (b, s, n))
    cm = jax.random.normal(ks[3], (b, s, n))
    a = -jnp.exp(jax.random.normal(ks[4], (d, n)) * 0.1)
    h0 = jnp.zeros((b, d, n))
    y_k, h_k = selective_scan(dt, x, bm, cm, a, h0, interpret=True)
    y_m, h_m = _chunk_scan(dt, bm, cm, a, x, h0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_m),
                               rtol=3e-4, atol=3e-4)
