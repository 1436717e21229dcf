"""The Frank-Wolfe coordinate update (paper Alg 2, lines 22-28).

One coordinate step touches:
  v̄[rows]  += η·d̃·x_col/w_m                  (line 23; v = w_m·v̄ implicitly)
  γ[i]      = h(w_m·v̄[i]) − q̄[i]             (line 24, logistic h = σ)
  q̄[rows]  += γ                               (line 25)
  α         += (γ/N)ᵀ · X[rows, :]            (line 26, scatter over row nnz)
  g̃        += w_m · Σᵢ (γᵢ/N)·⟨X[i,:], w⟩    (line 27)

Inputs use the padded layouts: ``rows/x_col/mask`` are column j's (Kc,) rows
from the PaddedCSC; ``row_idx/row_val`` are those rows' (Kc, Kr) entries from
the PaddedCSR.  Padding lanes carry mask=False and value 0.

XLA gathers and scatter-adds on every platform: Mosaic lowers neither, so a
Pallas form of this step cannot compile for the TPU.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.core.losses import get_loss


def coord_update(
    vbar: jnp.ndarray, qbar: jnp.ndarray, alpha: jnp.ndarray, w: jnp.ndarray,
    rows: jnp.ndarray, x_col: jnp.ndarray, mask: jnp.ndarray,
    row_idx: jnp.ndarray, row_val: jnp.ndarray,
    *, eta, d_tilde, w_m, inv_n, loss: str = "logistic", y_col=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Alg-2 lines 22-28 for one selected coordinate.

    Returns (v̄', q̄', α', g̃_increment); the caller folds the increment into
    its running gap estimate.  ``y_col`` is the selected column's labels,
    required when ``loss`` is label-coupled.
    """
    obj = get_loss(loss)
    if not obj.separable and y_col is None:
        raise ValueError(f"loss {loss!r} is label-coupled; pass y_col")
    dv = jnp.where(mask, eta * d_tilde * x_col / w_m, 0.0)
    vbar = vbar.at[rows].add(dv)
    margins = w_m * vbar[rows]
    # separable: γ = h(m) − q̄; label-coupled: γ = grad(m, y) − q̄
    hm = (obj.split_grad(margins) if obj.separable
          else obj.grad(margins, y_col))
    gamma = jnp.where(mask, hm - qbar[rows], 0.0)
    qbar = qbar.at[rows].add(gamma)
    contrib = (gamma * inv_n)[:, None] * row_val                 # (Kc, Kr)
    alpha = alpha.at[row_idx.reshape(-1)].add(contrib.reshape(-1))
    dots = jnp.einsum("ck,ck->c", row_val, w[row_idx])
    g_delta = w_m * jnp.sum((gamma * inv_n) * dots)
    return vbar, qbar, alpha, g_delta
