"""Per-kernel allclose vs pure-jnp oracles; shape/dtype sweeps, run through
the Pallas interpreter (``interpret=True``) on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

K = jax.random.key


@pytest.mark.parametrize("n,block", [(2048, 512), (8192, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_blackscholes(n, block, dtype):
    spot = jax.random.uniform(K(0), (n,), dtype, 10, 100)
    strike = jax.random.uniform(K(1), (n,), dtype, 10, 100)
    rate = jnp.full((n,), 0.05, dtype)
    vol = jax.random.uniform(K(2), (n,), dtype, 0.1, 0.6)
    t = jax.random.uniform(K(3), (n,), dtype, 0.2, 2.0)
    calls = (jax.random.uniform(K(4), (n,)) > 0.5).astype(jnp.int32)
    got = ops.blackscholes(spot, strike, rate, vol, t, calls, block=block,
                           interpret=True)
    want = ref.blackscholes(spot, strike, rate, vol, t, calls)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("shape,rpb", [((66, 128), 64), ((130, 256), 32)])
def test_jacobi2d(shape, rpb):
    a = jax.random.normal(K(5), shape)
    np.testing.assert_allclose(ops.jacobi2d_step(a, rows_per_block=rpb,
                                                 interpret=True),
                               ref.jacobi2d(a), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("R,C", [(10, 128), (40, 512)])
def test_pathfinder(R, C):
    wall = jax.random.uniform(K(6), (R, C), minval=0, maxval=10)
    np.testing.assert_allclose(ops.pathfinder(wall, interpret=True),
                               ref.pathfinder(wall), rtol=1e-6)


@pytest.mark.parametrize("m,n,d,bm,bn", [(256, 128, 64, 128, 128),
                                         (512, 256, 128, 256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_streamcluster(m, n, d, bm, bn, dtype):
    p = jax.random.normal(K(7), (m, d), dtype)
    c = jax.random.normal(K(8), (n, d), dtype)
    tol = 1e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(ops.streamcluster_dist(p, c, bm=bm, bn=bn,
                                                      interpret=True),
                               ref.streamcluster_dist(p, c), rtol=tol, atol=tol)


@pytest.mark.parametrize("n", [2048, 8192])
def test_swaptions_cumnorminv(n):
    u = jax.random.uniform(K(9), (n,), minval=1e-5, maxval=1 - 1e-5)
    x = ops.cum_normal_inv(u, block=1024, interpret=True)
    np.testing.assert_allclose(x, ref.cum_normal_inv(u), rtol=1e-5, atol=1e-6)
    # sanity vs scipy-style inverse: cndf(inv(u)) ~= u
    back = 0.5 * (1 + jax.lax.erf(x / np.sqrt(2)))
    np.testing.assert_allclose(back, u, atol=5e-4)


@pytest.mark.parametrize("N,B,F", [(512, 256, 24), (1024, 512, 8)])
def test_canneal(N, B, F):
    locs = jax.random.randint(K(10), (N, 2), 0, 1000).astype(jnp.float32)
    fan = jax.random.randint(K(11), (B, F), -1, N)
    ca = jax.random.randint(K(12), (B, 2), 0, 1000).astype(jnp.float32)
    cb = jax.random.randint(K(13), (B, 2), 0, 1000).astype(jnp.float32)
    oa, ob = ops.canneal_swap_cost(locs, fan, ca, cb, interpret=True)
    ra, rb = ref.canneal_swap_cost(locs, fan, ca, cb)
    np.testing.assert_allclose(oa, ra, rtol=1e-6)
    np.testing.assert_allclose(ob, rb, rtol=1e-6)


@pytest.mark.parametrize("n,m", [(4096, 512), (2048, 256)])
def test_particlefilter(n, m):
    cdf = jnp.sort(jax.random.uniform(K(14), (n,)))
    u = jax.random.uniform(K(15), (m,))
    np.testing.assert_array_equal(
        ops.particlefilter_findindex(cdf, u, interpret=True),
        ref.particlefilter_findindex(cdf, u))


@pytest.mark.parametrize("S,bq,bk", [(256, 128, 128), (512, 128, 256)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(S, bq, bk, causal, dtype):
    B, H, D = 2, 2, 64
    q = jax.random.normal(K(16), (B, S, H, D), dtype)
    k = jax.random.normal(K(17), (B, S, H, D), dtype)
    v = jax.random.normal(K(18), (B, S, H, D), dtype)
    got = ops.flash_attention(q, k, v, bq=bq, bk=bk, causal=causal,
                              interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("S,bk,kv_len", [(256, 64, 100), (512, 128, 512)])
def test_decode_attention(S, bk, kv_len):
    B, H, D = 2, 4, 64
    q = jax.random.normal(K(19), (B, H, D))
    k = jax.random.normal(K(20), (B, S, H, D))
    v = jax.random.normal(K(21), (B, S, H, D))
    got = ops.decode_attention(q, k, v, jnp.full((B,), kv_len), bk=bk,
                               interpret=True)
    want = jax.vmap(lambda qq, kk, vv: ref.decode_attention(
        qq[None], kk[None], vv[None], kv_len)[0])(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("S,chunk", [(256, 64), (512, 128)])
def test_ssd_scan(S, chunk):
    b, H, P, N = 2, 4, 16, 32
    x = jax.random.normal(K(22), (b, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(K(23), (b, S, H)))
    A = -jnp.exp(jax.random.normal(K(24), (H,)) * 0.3)
    B_ = jax.random.normal(K(25), (b, S, N)) * 0.5
    C_ = jax.random.normal(K(26), (b, S, N)) * 0.5
    got = ops.ssd_scan(x, dt, A, B_, C_, chunk=chunk, interpret=True)
    want = ref.ssd_scan(x, dt, A, B_, C_, chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=4e-3, atol=4e-3)
