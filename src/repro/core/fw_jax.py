"""TPU-adapted Algorithm 2 — sparse-aware Frank-Wolfe as one ``lax.scan``.

Faithful port of the paper's sparse update structure onto fixed-shape padded
sparse formats (DESIGN.md §2):

  * coordinate selection — ``two_level`` (DP exponential mechanism via the
    hierarchical sampler, the TPU form of Alg 4) or ``group_argmax``
    (non-private lazy-bound argmax, the TPU form of Alg 3);
  * per-iteration work is a *static* ``K_col × K_row`` gather/scatter tile —
    the padded version of the paper's O(S_r·S_c) inner loop (lines 22-28);
  * the multiplicative-scale tricks (w_m, shared v̄ scale, incremental g̃)
    are identical to the host implementation.

The entire T-iteration optimization lowers to a single XLA while-loop, so it
can be jit/pjit-compiled, checkpointed mid-scan (via the trainer's chunked
driver), and dry-run on the production mesh.
"""
from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp

from repro.core.dp.accountant import per_step_epsilon
from repro.core.fw_dense import FWConfig, FWResult
from repro.core.samplers.bsls_jax import tl_init, tl_sample, tl_update
from repro.core.samplers.group_argmax import ga_get_next, ga_init, ga_update
from repro.core.sparse.formats import PaddedCSC, PaddedCSR, refuse_chunked


@dataclasses.dataclass(frozen=True)
class SparseJaxConfig(FWConfig):
    queue: str = "two_level"   # two_level (DP) | group_argmax (non-private)


def sparse_fw_jax(
    pcsr: PaddedCSR, pcsc: PaddedCSC, y: jnp.ndarray, config: SparseJaxConfig
) -> FWResult:
    refuse_chunked(pcsc, "the jax_dense backend")
    n, d = pcsr.shape
    lam = config.lam
    loss = config.loss_fn()
    h = loss.split_grad
    separable = loss.separable
    private = config.queue == "two_level"
    if private:
        eps_step = per_step_epsilon(config.epsilon, config.delta, config.steps)
        em_scale = eps_step * n / (2.0 * loss.lipschitz)
    else:
        em_scale = 1.0  # priorities are raw |α|

    dtype = pcsr.values.dtype

    # ---- first-iteration dense pass (paper Alg 2 lines 8-14) ----------------
    # Separable objectives use the ȳ decomposition; label-coupled ones carry
    # the full row gradient in q̄ (α = Xᵀq̄/N, no ȳ term).
    w0 = jnp.zeros(d, dtype)
    vbar0 = jnp.zeros(n, dtype)
    if separable:
        ybar = pcsr.rmatvec(y) / n
        qbar0 = h(vbar0)
        alpha0 = pcsr.rmatvec(qbar0) / n - ybar
    else:
        qbar0 = loss.grad(vbar0, y)
        alpha0 = pcsr.rmatvec(qbar0) / n

    if private:
        sampler0 = tl_init(jnp.abs(alpha0) * em_scale)
    else:
        sampler0 = ga_init(jnp.abs(alpha0))

    # §9 masked early stopping (``config`` is jit-static, so this is a
    # compile-time branch; the tol=0 program is untouched).
    masked = config.gap_tol > 0

    def step(carry, t_int):
        w, w_m, g_tilde, vbar, qbar, alpha, sampler, key, done, stop_at = carry
        t = t_int.astype(dtype)
        key_next, sel_key = jax.random.split(key)
        # ---- line 15: select coordinate -------------------------------------
        if private:
            j = tl_sample(sampler, sel_key)
            sampler_after_sel = sampler
        else:
            j, sampler_after_sel = ga_get_next(sampler)
        j = jnp.minimum(j, d - 1)
        a_j = alpha[j]
        # ---- lines 16-21 -----------------------------------------------------
        d_tilde = -lam * jnp.sign(a_j)
        d_tilde = jnp.where(a_j == 0, lam, d_tilde)
        gap = g_tilde - d_tilde * a_j
        eta = 2.0 / (t + 2.0)
        w_m_new = w_m * (1.0 - eta)
        w_new = w.at[j].add(eta * d_tilde / w_m_new)
        g_tilde_new = g_tilde * (1.0 - eta) + eta * d_tilde * a_j
        # ---- lines 22-28: propagate through rows holding feature j ----------
        rows, xvals, mask = pcsc.col(j)                   # (Kc,)
        dv = jnp.where(mask, eta * d_tilde * xvals / w_m_new, 0.0)
        vbar_new = vbar.at[rows].add(dv)
        margins = w_m_new * vbar_new[rows]
        hm = h(margins) if separable else loss.grad(margins, y[rows])
        gamma = jnp.where(mask, hm - qbar[rows], 0.0)
        qbar_new = qbar.at[rows].add(gamma)
        row_idx = pcsr.indices[rows]                      # (Kc, Kr)
        row_val = pcsr.values[rows]                       # (Kc, Kr) — 0 at padding
        contrib = (gamma / n)[:, None] * row_val
        alpha_new = alpha.at[row_idx.reshape(-1)].add(contrib.reshape(-1))
        # line 27: g̃ += Σᵢ (γᵢ/n)·⟨X[i,:], w̃⟩·w_m
        wg = w_new[row_idx]                               # (Kc, Kr)
        g_tilde_new = g_tilde_new + w_m_new * jnp.sum(
            (gamma / n) * jnp.einsum("ck,ck->c", row_val, wg))
        # ---- line 29: refresh queue priorities for touched coordinates ------
        flat_idx = row_idx.reshape(-1)
        fresh = jnp.abs(alpha_new[flat_idx]) * (em_scale if private else 1.0)
        if private:
            sampler_new = tl_update(sampler_after_sel, flat_idx, fresh)
        else:
            sampler_new = ga_update(sampler_after_sel, flat_idx, fresh)
        j = j.astype(jnp.int32)
        new = (w_new, w_m_new, g_tilde_new, vbar_new, qbar_new, alpha_new,
               sampler_new, key_next)
        if not masked:
            return new + (done, stop_at), (gap, j)
        newly = jnp.logical_and(~done, gap <= config.gap_tol)
        old = (w, w_m, g_tilde, vbar, qbar, alpha, sampler, key)
        kept = jax.tree_util.tree_map(
            lambda o, fresh_leaf: jnp.where(done, o, fresh_leaf), old, new)
        out = (jnp.where(done, jnp.asarray(0.0, dtype), gap),
               jnp.where(done, -1, j))
        return kept + (jnp.logical_or(done, newly),
                       jnp.where(newly, t_int, stop_at)), out

    carry0 = (
        w0, jnp.asarray(1.0, dtype), jnp.asarray(0.0, dtype),
        vbar0, qbar0, alpha0, sampler0, jax.random.PRNGKey(config.seed),
        jnp.asarray(False), jnp.asarray(0, jnp.int32),
    )
    ts = jnp.arange(1, config.steps + 1, dtype=jnp.int32)
    (w, w_m, *rest), (gaps, coords) = jax.lax.scan(step, carry0, ts)
    done, stop_at = rest[-2], rest[-1]
    stop_step = jnp.where(done, stop_at, jnp.asarray(config.steps, jnp.int32))
    w_true = w * w_m
    return FWResult(w=w_true, gaps=gaps, coords=coords,
                    losses=jnp.zeros_like(gaps), stop_step=stop_step,
                    stop_reason="max_steps")


sparse_fw_jax_jit = jax.jit(sparse_fw_jax, static_argnames=("config",))
