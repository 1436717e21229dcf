"""Straight-line host oracle for the ``jax_sparse`` kernel pipeline.

``reference_fw`` replays ``jax_sparse.fw_scan``'s state machine eagerly —
no Pallas kernels, no ``lax.scan``, no incremental sampler bookkeeping:
the selection priorities are recomputed from |α| directly every step, and
the DP draw re-realizes ``kernels.bsls_draw.two_level_draw``'s
group-then-member Gumbel-max with the *same key stream* (one
``key, sel_key = split(key)`` per iteration; Gumbel shapes matching the
kernel's, so the same PRNG bits are consumed), so the selected coordinates
are bit-identical when the kernel pipeline is correct — for every
registered objective, private and non-private.

This is the per-loss correctness court of appeal the loss-parameterized
parity tests pin the engine against, the single-device sibling of
``repro.distributed.reference`` (same philosophy: eager execution gives an
independently-rounded trajectory; coords must still match exactly, weights
and gaps to float tolerance).

Direct |α| recomputation is exact, not an approximation: the engine's
two-level sampler refreshes exactly the coordinates whose α changed each
iteration (line 29 touches ``row_idx``; α changes nowhere else), so its
lazily-maintained priorities always equal ``em_scale·|α|`` on real
coordinates and −∞ on padding — what this oracle rebuilds from scratch.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.losses import get_loss
from repro.core.samplers.bsls_jax import NEG_INF, group_shape
from repro.core.sparse.formats import PaddedCSC, PaddedCSR


def _ell_rmatvec_ref(pcsr: PaddedCSR, q: jnp.ndarray) -> jnp.ndarray:
    """Eager Xᵀq over the padded ELL rows (padding lanes carry value 0)."""
    contrib = pcsr.values * q[:, None]
    return jnp.zeros((pcsr.shape[1],), pcsr.values.dtype).at[
        pcsr.indices.reshape(-1)].add(contrib.reshape(-1))


def reference_fw(pcsr: PaddedCSR, pcsc: PaddedCSC, y, *, lam: float,
                 steps: int, private: bool = False, em_scale: float = 1.0,
                 seed: int = 0, loss: str = "logistic"
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(w, gaps, coords) of the ``fw_scan`` schedule, replayed eagerly."""
    obj = get_loss(loss)
    n, d = pcsr.shape
    dtype = pcsr.values.dtype
    y = jnp.asarray(y, dtype)
    inv_n = 1.0 / n
    lam = jnp.asarray(lam, dtype)
    em_scale = jnp.asarray(em_scale, dtype)

    # fw_setup (Alg 2 lines 8-14); label-coupled objectives carry the full
    # row gradient in q̄ (no ȳ residual), mirroring jax_sparse.fw_setup
    vbar = jnp.zeros(n, dtype)
    if obj.separable:
        ybar = _ell_rmatvec_ref(pcsr, y) * inv_n
        qbar = obj.split_grad(vbar)
        alpha = _ell_rmatvec_ref(pcsr, qbar) * inv_n - ybar
    else:
        qbar = obj.grad(vbar, y)
        alpha = _ell_rmatvec_ref(pcsr, qbar) * inv_n

    g_grp, m_grp = group_shape(d)
    w = jnp.zeros(d, dtype)
    w_m = jnp.asarray(1.0, dtype)
    g_tilde = jnp.asarray(0.0, dtype)
    key = jax.random.PRNGKey(seed)
    gaps, coords = [], []
    for step in range(1, steps + 1):
        t = jnp.asarray(step, dtype)
        key, sel_key = jax.random.split(key)
        # ---- line 15: select coordinate (exact priorities from |α|) ------
        if private:
            v = jnp.full((g_grp * m_grp,), NEG_INF, dtype).at[:d].set(
                jnp.abs(alpha) * em_scale).reshape(g_grp, m_grp)
            c = jax.scipy.special.logsumexp(v, axis=1)
            kg, km = jax.random.split(sel_key)
            g = jnp.argmax(c + jax.random.gumbel(kg, c.shape, jnp.float32))
            noise = jax.random.gumbel(km, (1, m_grp), jnp.float32)
            j = g * m_grp + jnp.argmax(v[g] + noise[0])
        else:
            j = jnp.argmax(jnp.abs(alpha))
        j = jnp.minimum(j, d - 1)
        a_j = alpha[j]
        # ---- lines 16-21 -------------------------------------------------
        d_tilde = jnp.where(a_j == 0, lam, -lam * jnp.sign(a_j))
        gaps.append(g_tilde - d_tilde * a_j)
        coords.append(j.astype(jnp.int32))
        eta = 2.0 / (t + 2.0)
        w_m = w_m * (1.0 - eta)
        w = w.at[j].add(eta * d_tilde / w_m)
        g_tilde = g_tilde * (1.0 - eta) + eta * d_tilde * a_j
        # ---- lines 22-28 (the fused kernel's sweep, unrolled) ------------
        rows, x_col, mask = pcsc.col(j)
        row_idx = pcsr.indices[rows]
        row_val = pcsr.values[rows]
        dv = jnp.where(mask, eta * d_tilde * x_col / w_m, 0.0)
        vbar = vbar.at[rows].add(dv)
        margins = w_m * vbar[rows]
        hm = (obj.split_grad(margins) if obj.separable
              else obj.grad(margins, y[rows]))
        gamma = jnp.where(mask, hm - qbar[rows], 0.0)
        qbar = qbar.at[rows].add(gamma)
        contrib = (gamma * inv_n)[:, None] * row_val
        alpha = alpha.at[row_idx.reshape(-1)].add(contrib.reshape(-1))
        dots = jnp.einsum("ck,ck->c", row_val, w[row_idx])
        g_tilde = g_tilde + w_m * jnp.sum((gamma * inv_n) * dots)
    return w * w_m, jnp.stack(gaps), jnp.stack(coords)
