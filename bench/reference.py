"""Plain reference of the paper's Algorithm 2 (sparse Frank-Wolfe for
L1-constrained logistic regression), in float64 on the host.

It imports nothing of the program.  One step, from the state (w, margins m,
lazily refreshed row gradients q, coordinate gradients alpha):

* select j: non-private, argmax |alpha|; private, the exponential mechanism
  with log-weights ``scale * |alpha|`` realised as a two-level Gumbel-max
  (group by log-sum-exp, then member), the noise drawn from the fit's key
  stream exactly as the program's selection consumes it;
* gap = <w, alpha> + lam * |alpha_j|, d = -lam * sign(alpha_j) (lam at 0);
* eta = 2 / (t + 2); w <- (1 - eta) w + eta d e_j; m <- (1 - eta) m + eta d X[:, j];
* on the rows i of column j only: q_i <- sigmoid(m_i), and alpha gains
  X[i, :] * (change of q_i) / N.

Two ways to run it:

* ``replay`` follows a fit's own coordinates (teacher forcing) and measures,
  at each step, how far the fit's choice lies below the reference's best
  choice, then compares the fit's gaps and final w with its own.  A single
  rounding flip therefore costs one step's reading, not the rest of the run.
* ``free_run`` chooses its own coordinates; with ``dtype="bfloat16"`` it
  stores its state in bfloat16 after every update, which is the control: the
  reference put in the program's place one precision below the float32 the
  configuration states.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import numpy as np
import scipy.sparse as sp

NEG_INF = -np.inf


def em_log_weight_scale(epsilon: float, delta: float, steps: int, n: int,
                        lipschitz: float = 1.0) -> float:
    """eps' N / (2 L) with eps' = eps / sqrt(8 T log(1/delta)) (advanced
    composition over T exponential-mechanism selections)."""
    eps_step = epsilon / math.sqrt(8.0 * steps * math.log(1.0 / delta))
    return eps_step * n / (2.0 * lipschitz)


# The program's draw, as the private configurations state it (``draw`` in
# their files): the shape of the two-level table and the order in which each
# step's key is split.  The replay reads the fit's noise through these, so a
# change to either is a change of the benchmark's configuration.
KEY_SPLIT = ("per step: (key_next, sel) = split(key); (k_group, k_member) = "
             "split(sel); noise gumbel(k_group, (G,)) over groups and "
             "gumbel(k_member, (1, M))[0] over members, float32")


def group_shape(d: int, draw: dict) -> tuple:
    """(G, M) of the two-level table: about sqrt(D) groups of sqrt(D)
    members, M a multiple of ``draw["table_lane_multiple"]`` and G of
    ``draw["table_row_multiple"]``; item j sits at g * M + m."""
    rows, lanes = int(draw["table_row_multiple"]), int(
        draw["table_lane_multiple"])
    g0 = max(1, math.isqrt(max(d - 1, 0)) + 1)
    m = -(-((d + g0 - 1) // g0) // lanes) * lanes
    g = -(-max(1, (d + m - 1) // m) // rows) * rows
    return g, m


def gumbel_stream(seed: int, steps: int, d: int, draw: dict):
    """The selection noise of a private fit with ``FWConfig.seed == seed``,
    drawn in the order ``KEY_SPLIT`` states (the only order this reference
    replays; a configuration that states another is refused).  Returns
    float64 arrays of shape (T, G) and (T, M)."""
    import jax
    import jax.numpy as jnp
    if draw["key_split"] != KEY_SPLIT:
        raise ValueError(f"the reference replays the key split {KEY_SPLIT!r}"
                         f", not {draw['key_split']!r}")
    g, m = group_shape(d, draw)

    @jax.jit
    def stream(key):
        def step(key, _):
            key_next, sel = jax.random.split(key)
            kg, km = jax.random.split(sel)
            return key_next, (jax.random.gumbel(kg, (g,), jnp.float32),
                              jax.random.gumbel(km, (1, m), jnp.float32)[0])
        return jax.lax.scan(step, key, None, length=steps)[1]

    zg, zm = stream(jax.random.PRNGKey(seed))
    return np.asarray(zg, np.float64), np.asarray(zm, np.float64)


@dataclasses.dataclass
class Problem:
    """The design matrix and labels as the reference holds them."""

    csr: sp.csr_matrix
    csc: sp.csc_matrix
    y: np.ndarray

    @classmethod
    def from_arrays(cls, indptr, indices, data, y, shape) -> "Problem":
        csr = sp.csr_matrix((np.asarray(data, np.float64),
                             np.asarray(indices), np.asarray(indptr)),
                            shape=shape)
        return cls(csr=csr, csc=csr.tocsc(), y=np.asarray(y, np.float64))

    @property
    def shape(self):
        return self.csr.shape


@dataclasses.dataclass(frozen=True)
class Fit:
    """What a fit returned, on the host."""

    w: np.ndarray        # (D,)
    gaps: np.ndarray     # (T,)
    coords: np.ndarray   # (T,)


def _identity(x):
    return x


def _rounder(dtype: str):
    if dtype == "float64":
        return _identity
    import ml_dtypes
    low = np.dtype(getattr(ml_dtypes, dtype, None) or dtype)
    return lambda x: np.asarray(x).astype(low).astype(np.float64)


def _logsumexp_rows(v: np.ndarray) -> np.ndarray:
    top = v.max(axis=1)
    safe = np.where(np.isfinite(top), top, 0.0)
    with np.errstate(divide="ignore"):
        return safe + np.log(np.exp(v - safe[:, None]).sum(axis=1))


def _run(prob: Problem, *, lam: float, steps: int, scale: Optional[float],
         noise, coords: Optional[Sequence[int]], dtype: str):
    """Shared loop; ``coords`` given -> teacher forcing, else own choices."""
    rnd = _rounder(dtype)
    n, d = prob.shape
    csr, csc = prob.csr, prob.csc
    private = scale is not None
    if private:
        zg, zm = noise
        g_sz, m_sz = zg.shape[1], zm.shape[1]
    m = np.zeros(n)
    q = np.full(n, 0.5)
    alpha = rnd(csr.T @ (q - prob.y) / n)
    w = np.zeros(d)
    sel_gap = np.zeros(steps)
    gaps = np.zeros(steps)
    amax = np.zeros(steps)
    chosen = np.zeros(steps, np.int64)
    for t in range(1, steps + 1):
        if private:
            v = np.full(g_sz * m_sz, NEG_INF)
            v[:d] = rnd(scale * np.abs(alpha))
            v = v.reshape(g_sz, m_sz)
            zc = rnd(_logsumexp_rows(v)) + zg[t - 1]
            if coords is None:
                g_sel = int(np.argmax(zc))
                j = g_sel * m_sz + int(np.argmax(v[g_sel] + zm[t - 1]))
            else:
                j = int(coords[t - 1])
                g_sel, m_sel = divmod(j, m_sz)
                if not 0 <= g_sel < g_sz:
                    sel_gap[t - 1] = np.inf
                    break
                zv = v[g_sel] + zm[t - 1]
                sel_gap[t - 1] = max(zc.max() - zc[g_sel],
                                     zv.max() - zv[m_sel])
        else:
            a = np.abs(alpha)
            if coords is None:
                j = int(np.argmax(a))
            else:
                j = int(coords[t - 1])
                if not 0 <= j < d:
                    sel_gap[t - 1] = np.inf
                    break
                top = a.max()
                sel_gap[t - 1] = (top - a[j]) / top if top > 0 else 0.0
        j = min(j, d - 1)
        chosen[t - 1] = j
        amax[t - 1] = np.abs(alpha).max()
        a_j = alpha[j]
        d_t = lam if a_j == 0 else -lam * math.copysign(1.0, a_j)
        gaps[t - 1] = float(w @ alpha) - d_t * a_j
        eta = 2.0 / (t + 2.0)
        w = rnd(w * (1.0 - eta))
        w[j] = rnd(w[j] + eta * d_t)
        lo, hi = csc.indptr[j], csc.indptr[j + 1]
        rows, x = csc.indices[lo:hi], csc.data[lo:hi]
        m = m * (1.0 - eta)
        m[rows] += eta * d_t * x
        m = rnd(m)
        dq = rnd(1.0 / (1.0 + np.exp(-m[rows]))) - q[rows]
        q[rows] = rnd(q[rows] + dq)
        if rows.size:
            alpha = rnd(alpha + csr[rows].T @ (dq / n))
    return w, gaps, chosen, sel_gap, amax


def free_run(prob: Problem, *, lam: float, steps: int,
             scale: Optional[float] = None, noise=None,
             dtype: str = "float64") -> Fit:
    """The reference choosing its own coordinates (``dtype`` below float64
    makes it the control)."""
    w, gaps, chosen, _, _ = _run(prob, lam=lam, steps=steps, scale=scale,
                              noise=noise, coords=None, dtype=dtype)
    return Fit(w=w, gaps=gaps, coords=chosen)


def replay(prob: Problem, fit: Fit, *, lam: float, steps: int,
           scale: Optional[float] = None, noise=None) -> dict:
    """Follow ``fit``'s coordinates in float64 and return the numbers that
    decide ``correct`` for it:

    * ``sel_gap``: the widest gap, over the steps, by which the fit's chosen
      coordinate scored below the reference's best (private: in log-weight
      units of the perturbed two-level scores; non-private: |alpha| below
      max |alpha|, as a share of max |alpha|);
    * ``w_err``: ||w_fit - w_ref||_1 / lam;
    * ``gap_err``: the widest gap between the fit's reported FW gap and the
      reference's at one step, as a share of that step's lam * max |alpha|.
    """
    coords = np.asarray(fit.coords)
    if coords.shape[0] != steps:
        return {"sel_gap": math.inf, "w_err": math.inf, "gap_err": math.inf}
    w, gaps, _, sel_gap, amax = _run(prob, lam=lam, steps=steps,
                                     scale=scale, noise=noise, coords=coords,
                                     dtype="float64")
    fit_w = np.asarray(fit.w, np.float64)
    fit_gaps = np.asarray(fit.gaps, np.float64)
    numbers = {
        "sel_gap": sel_gap.max(),
        "w_err": np.abs(fit_w - w).sum() / lam,
        "gap_err": (np.abs(fit_gaps - gaps)
                    / np.maximum(lam * amax, 1e-300)).max(),
    }
    # a NaN anywhere reads as the worst possible number
    return {k: float(v) if np.isfinite(v) else math.inf
            for k, v in numbers.items()}
