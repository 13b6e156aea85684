"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.knn_topk import knn_topk
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan

# minutes of JAX compile+run on CPU: opt-in via `-m slow` (see pytest.ini)
pytestmark = pytest.mark.slow



def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 3e-5


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 32, 3, 8, 16, 8),
    (1, 50, 2, 16, 8, 16),    # ragged
    (2, 64, 4, 4, 4, 64),     # single chunk
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, S, H, P, N, chunk, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (B, S, H))
                         ).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (H,)))
    Bm = jax.random.normal(jax.random.PRNGKey(3), (B, S, N), dtype)
    Cm = jax.random.normal(jax.random.PRNGKey(4), (B, S, N), dtype)
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, _ = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=0.05 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("B,S,R,blk", [(2, 16, 64, 32), (1, 33, 100, 64),
                                       (3, 8, 16, 16)])
def test_rglru_scan_sweep(B, S, R, blk):
    la = -jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(0), (B, S, R)))
    b = jax.random.normal(jax.random.PRNGKey(1), (B, S, R))
    h0 = jax.random.normal(jax.random.PRNGKey(2), (B, R))
    y, hT = rglru_scan(la, b, h0, block_r=blk, interpret=True)
    yr, hTr = ref.rglru_scan_ref(la, b, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hTr), atol=1e-5)


@pytest.mark.parametrize("m,n,d,k,bm,bn", [
    (50, 200, 10, 5, 32, 64),
    (128, 512, 20, 3, 128, 128),
    (7, 30, 4, 7, 8, 16),      # k > block remainder, ragged everywhere
])
def test_knn_topk_sweep(m, n, d, k, bm, bn):
    tx = jax.random.normal(jax.random.PRNGKey(3), (m, d))
    trx = jax.random.normal(jax.random.PRNGKey(4), (n, d))
    ty = jax.random.randint(jax.random.PRNGKey(5), (n,), 0, 4)
    dd, ll = knn_topk(tx, trx, ty, k=k, block_m=bm, block_n=bn, interpret=True)
    dr, lr = ref.knn_topk_ref(tx, trx, ty, k)
    np.testing.assert_allclose(np.asarray(dd), np.asarray(dr), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ll), np.asarray(lr))


@pytest.mark.parametrize("n,d,k,bm", [(300, 8, 5, 64), (1025, 16, 7, 256),
                                      (64, 4, 2, 64)])
def test_kmeans_assign_sweep(n, d, k, bm):
    x = jax.random.normal(jax.random.PRNGKey(6), (n, d))
    c = jax.random.normal(jax.random.PRNGKey(7), (k, d))
    s1, c1, e1 = kmeans_assign(x, c, block_m=bm, interpret=True)
    s2, c2, e2 = ref.kmeans_assign_ref(x, c)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-3)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    assert float(e1) == pytest.approx(float(e2), rel=1e-4, abs=1e-2)
    assert int(jnp.sum(c1)) == n


@pytest.mark.parametrize("shape", [(8, 32), (3, 7, 64), (2, 2, 2, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(8), shape, dtype)
    s = jax.random.normal(jax.random.PRNGKey(9), (shape[-1],), dtype)
    o = rmsnorm(x, s, block_rows=4, interpret=True)
    r = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=_tol(dtype))


def test_flash_custom_vjp_grads_match_reference():
    """The splash kernels' forward and custom_vjp backward (dq, dk/dv) in
    ``ops.flash_attention_op`` against the float32 oracle; bfloat16
    operands, so a relative L2 tolerance of a few bfloat16 ulps."""
    B, K, G, S, d = 1, 2, 2, 256, 128
    ks = jax.random.split(jax.random.PRNGKey(10), 4)
    q = jax.random.normal(ks[0], (B, K, G, S, d))
    k = jax.random.normal(ks[1], (B, K, S, d))
    v = jax.random.normal(ks[2], (B, K, S, d))
    w = jax.random.normal(ks[3], (B, K, G, S, d))

    def via_ref(q, k, v):
        o = ref.flash_attention_ref(q.reshape(B, K * G, S, d), k, v)
        return o.reshape(B, K, G, S, d)

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    assert rel(ops.flash_attention_op(q, k, v).astype(jnp.float32),
               via_ref(q, k, v)) < 1e-2
    for argnum in range(3):
        g1 = jax.grad(lambda *a: jnp.sum(
            ops.flash_attention_op(*a).astype(jnp.float32) * w),
            argnums=argnum)(q, k, v)
        g2 = jax.grad(lambda *a: jnp.sum(via_ref(*a) * w),
                      argnums=argnum)(q, k, v)
        assert rel(g1, g2) < 1e-2, argnum
