"""Padded-ELL sparse mat-vec: X·w and Xᵀ·q over a ``PaddedCSR``.

Semantics: ``indices``/``values`` are (N, K), each row padded to K lanes
with ``index = 0, value = 0`` (inert in sums, safe to gather).

  * ``ell_matvec``:  out[i]  = Σ_k values[i,k] · w[indices[i,k]]        → (N,)
  * ``ell_rmatvec``: out[j] += Σ_{i,k: indices[i,k]=j} values[i,k]·q[i] → (D,)

Both run as XLA gather / scatter-add on every platform: Mosaic has no
lowering for either, so a Pallas form could not compile for the TPU.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.sparse.formats import PaddedCSR


def ell_matvec(X: PaddedCSR, w: jnp.ndarray) -> jnp.ndarray:
    """X · w — gather + row reduction."""
    return X.matvec(w)


def ell_rmatvec(X: PaddedCSR, q: jnp.ndarray) -> jnp.ndarray:
    """Xᵀ · q — scatter-add over the padded lanes."""
    return X.rmatvec(q)
