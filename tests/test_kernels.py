"""Every kernel entry point against an oracle, swept over shapes/dtypes.

spmv and coord_update are checked against float64 numpy oracles; the
bsls_draw Pallas kernel (interpreted on the CPU) against its pure-jnp
reference and the exponential mechanism's law."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparse.formats import PaddedCSR
from repro.kernels.bsls_draw.ops import two_level_draw
from repro.kernels.bsls_draw.ref import two_level_draw_ref
from repro.kernels.coord_update.ops import coord_update
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.spmv.ops import ell_matvec, ell_rmatvec


def _ell(idx, val, d):
    n, k = idx.shape
    return PaddedCSR(jnp.asarray(idx, jnp.int32), val,
                     jnp.full((n,), k, jnp.int32), (n, d))


# ---------------------------------------------------------------------------
# spmv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,d", [(64, 5, 40), (300, 17, 1000), (1000, 64, 500)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ell_matvec(n, k, d, dtype, rng):
    idx = rng.integers(0, d, (n, k))
    val = jnp.asarray(rng.normal(size=(n, k)), dtype)
    w = jnp.asarray(rng.normal(size=d), dtype)
    got = ell_matvec(_ell(idx, val, d), w)
    val64 = np.asarray(val, np.float64)
    want = (val64 * np.asarray(w, np.float64)[idx]).sum(axis=1)
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("n,k,d", [(64, 5, 40), (512, 16, 300), (100, 33, 2000)])
def test_ell_rmatvec(n, k, d, rng):
    idx = rng.integers(0, d, (n, k))
    val = rng.normal(size=(n, k)).astype(np.float32)
    q = rng.normal(size=n).astype(np.float32)
    got = ell_rmatvec(_ell(idx, jnp.asarray(val), d), jnp.asarray(q))
    want = np.zeros(d)
    np.add.at(want, idx.reshape(-1),
              (val.astype(np.float64) * q.astype(np.float64)[:, None])
              .reshape(-1))
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=1e-5,
                               atol=1e-5)


def test_spmv_vs_padded_csr(tiny_problem):
    """The padded entry points ≡ the exact host CSR products (float64)."""
    from repro.core.sparse.formats import host_to_padded
    X, y, _ = tiny_problem
    pcsr, _ = host_to_padded(X)
    w = np.random.default_rng(1).normal(size=X.shape[1])
    np.testing.assert_allclose(
        np.asarray(ell_matvec(pcsr, jnp.asarray(w, jnp.float32))),
        X.matvec(w), rtol=1e-5, atol=1e-5)
    q = np.random.default_rng(2).normal(size=X.shape[0])
    np.testing.assert_allclose(
        np.asarray(ell_rmatvec(pcsr, jnp.asarray(q, jnp.float32))),
        X.rmatvec(q), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# bsls_draw
# ---------------------------------------------------------------------------

def test_two_level_draw_matches_ref(rng):
    from repro.core.samplers.bsls_jax import tl_init
    st = tl_init(jnp.asarray(rng.normal(0, 2, 200), jnp.float32))
    for seed in range(5):
        key = jax.random.PRNGKey(seed)
        kg, km = jax.random.split(key)
        gg = jax.random.gumbel(kg, st.c.shape, jnp.float32)
        gm = jax.random.gumbel(km, (st.v.shape[1],), jnp.float32)
        assert int(two_level_draw(st.c, st.v, key)) == int(
            two_level_draw_ref(st.c, st.v, gg, gm))


def test_two_level_draw_distribution(rng):
    from repro.core.samplers.bsls_jax import tl_init
    d = 120
    st = tl_init(jnp.asarray(rng.normal(0, 1.5, d), jnp.float32))
    keys = jax.random.split(jax.random.PRNGKey(0), 4000)
    draws = np.array([int(two_level_draw(st.c, st.v, k)) for k in keys[:1500]])
    p = np.asarray(jax.nn.softmax(st.v.reshape(-1)[:d]))
    counts = np.bincount(draws, minlength=st.v.size)[:d]
    e = p * len(draws)
    m = e >= 5
    chi2 = ((counts[m] - e[m]) ** 2 / e[m]).sum() / max(m.sum() - 1, 1)
    assert chi2 < 1.6


# ---------------------------------------------------------------------------
# coord_update
# ---------------------------------------------------------------------------

def _coord_update_np(vbar, qbar, alpha, w, rows, x_col, mask, row_idx,
                     row_val, *, eta, d_tilde, w_m, inv_n):
    """Alg-2 lines 22-28 in float64 numpy, one lane at a time."""
    vbar, qbar, alpha = (np.array(a, np.float64) for a in (vbar, qbar, alpha))
    w = np.asarray(w, np.float64)
    row_val = np.asarray(row_val, np.float64)
    g_delta = 0.0
    for c in np.flatnonzero(mask):
        vbar[rows[c]] += eta * d_tilde * x_col[c] / w_m
    for c in np.flatnonzero(mask):
        r = rows[c]
        gamma = 1.0 / (1.0 + np.exp(-w_m * vbar[r])) - qbar[r]
        qbar[r] += gamma
        np.add.at(alpha, row_idx[c], gamma * inv_n * row_val[c])
        g_delta += w_m * gamma * inv_n * (row_val[c] @ w[row_idx[c]])
    return vbar, qbar, alpha, g_delta


@pytest.mark.parametrize("n,d,kc,kr", [(100, 300, 17, 7), (200, 500, 37, 11),
                                       (50, 64, 5, 3), (400, 1000, 130, 20)])
def test_coord_update_matches_ref(n, d, kc, kr, rng):
    vbar = rng.normal(size=n)
    qbar = 1.0 / (1.0 + np.exp(-vbar))
    alpha = rng.normal(size=d)
    w = rng.normal(size=d) * 0.1
    rows = rng.choice(n, kc, replace=False)
    mask = rng.random(kc) < 0.8
    x_col = np.where(mask, rng.normal(size=kc), 0.0)
    row_idx = rng.integers(0, d, (kc, kr))
    row_val = rng.normal(size=(kc, kr))
    kw = dict(eta=0.05, d_tilde=-8.0, w_m=0.9, inv_n=1.0 / n)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    ref = _coord_update_np(vbar, qbar, alpha, w, rows, x_col, mask,
                           row_idx, row_val, **kw)
    got = coord_update(f32(vbar), f32(qbar), f32(alpha), f32(w),
                       jnp.asarray(rows, jnp.int32), f32(x_col),
                       jnp.asarray(mask), jnp.asarray(row_idx, jnp.int32),
                       f32(row_val), **kw)
    for name, a, b in zip(("vbar", "qbar", "alpha"), ref[:3], got[:3]):
        np.testing.assert_allclose(np.asarray(b, np.float64), a, rtol=1e-5,
                                   atol=1e-6, err_msg=name)
    assert float(got[3]) == pytest.approx(ref[3], abs=1e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kv,hd,causal,window", [
    (2, 128, 4, 2, 32, True, 0),
    (1, 256, 8, 8, 16, True, 0),
    (2, 128, 4, 1, 64, False, 0),
    (1, 256, 6, 2, 32, True, 64),
    (1, 128, 2, 2, 16, True, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(b, s, h, kv, hd, causal, window, dtype, rng):
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, kv, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, kv, hd)), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64)
    want = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 0.06
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_kernel_vs_training_flash(rng):
    """Pallas kernel ≡ the pure-JAX custom-VJP flash used in training."""
    from repro.models.flash import flash_attention
    q = jnp.asarray(rng.normal(size=(2, 256, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 32)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_k=64)
    want = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
