"""TPU-adapted Big-Step Little-Step sampler (DESIGN.md §2).

The paper's Alg 4 walks a weighted-reservoir stream with cache-friendly group
skipping — a CPU trick.  The *math* it implements is: sample j with
P(j) ∝ exp(v_j), using per-group log-sum-exps as a two-level decomposition.
On TPU we sample that decomposition directly:

    P(j) = P(group g)·P(j | g) = softmax(c)_g · softmax(v_g)_j

with one Gumbel-max over the G ≈ √D group masses (a "big step") and one
Gumbel-max over the M ≈ √D members of the chosen group (the "little
steps"); ``group_shape`` aligns (G, M) to the TPU's (8, 128) tile.  Both
are O(√D) dense vector scans that the VPU runs at line rate; there is no
data-dependent control flow, so the whole FW iteration stays inside one
``lax.scan``.

State updates after a FW iteration touch ``S_c`` coordinates: we scatter the
new log-weights and recompute the affected groups' log-sum-exps via a masked
segment reduction — O(touched·M) lanes, exact (no incremental drift at all,
which is *stronger* than the paper's O(1) updates; on TPU the vector rebuild
is cheaper than scalar bookkeeping).

Law-exactness is by construction (law of total probability); tested by
chi-square against ``exponential_mechanism_probs`` and against the faithful
``BSLSSampler``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TwoLevelSamplerState:
    v: jnp.ndarray   # (G, M) log-weights, padded with NEG_INF
    c: jnp.ndarray   # (G,)   per-group log-sum-exp
    d: int           # true number of items (static)

    def tree_flatten(self):
        return (self.v, self.c), self.d

    @classmethod
    def tree_unflatten(cls, d, leaves):
        return cls(*leaves, d=d)

    @property
    def groups(self) -> int:
        return self.v.shape[0]

    @property
    def group_size(self) -> int:
        return self.v.shape[1]


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def group_shape(d: int) -> Tuple[int, int]:
    """(G, M) of the member table: ~√D groups of ~√D members, aligned to
    the TPU's (8, 128) tile — M a multiple of 128 lanes, G of 8 sublanes —
    so the ``bsls_draw`` kernel can fetch the tile holding one group.  Item
    j sits at flat slot j = g·M + m; the slots past D are NEG_INF padding."""
    g0 = max(1, math.isqrt(max(d - 1, 0)) + 1)   # ⌈√D⌉
    m = _round_up((d + g0 - 1) // g0, 128)
    g = _round_up(max(1, (d + m - 1) // m), 8)
    return g, m


def tl_init(log_weights: jnp.ndarray) -> TwoLevelSamplerState:
    d = log_weights.shape[0]
    g, m = group_shape(d)
    v = jnp.full((g * m,), NEG_INF, log_weights.dtype).at[:d].set(log_weights)
    v = v.reshape(g, m)
    c = jax.scipy.special.logsumexp(v, axis=1)
    return TwoLevelSamplerState(v=v, c=c, d=d)


def tl_sample(state: TwoLevelSamplerState, key: jax.Array) -> jnp.ndarray:
    """Draw j ~ softmax(v) via group-then-member Gumbel-max.  O(G + M)."""
    kg, km = jax.random.split(key)
    g = jnp.argmax(state.c + jax.random.gumbel(kg, state.c.shape))
    row = jnp.take(state.v, g, axis=0)
    j_in = jnp.argmax(row + jax.random.gumbel(km, row.shape))
    return g * state.group_size + j_in


def tl_update(
    state: TwoLevelSamplerState, idx: jnp.ndarray, new_log_weights: jnp.ndarray
) -> TwoLevelSamplerState:
    """Scatter new log-weights for ``idx`` (may contain duplicates/padding
    marked by idx >= d → dropped) and rebuild affected group sums exactly.

    For simplicity and exactness we recompute all G group log-sum-exps; the
    (G, M) logsumexp is one O(D) vector pass — only done once per FW
    iteration, versus O(√D) per *draw*, so the iteration stays sub-linear in
    wall terms that matter (the draw path) while updates remain a single
    fused reduction.  The Pallas kernel variant (kernels/bsls) tiles this.
    """
    m = state.group_size
    valid = idx < state.d
    safe_idx = jnp.where(valid, idx, 0)
    vals = jnp.where(valid, new_log_weights, state.v.reshape(-1)[safe_idx])
    v = state.v.reshape(-1).at[safe_idx].set(vals).reshape(state.v.shape)
    # exact rebuild of touched groups only (mask others to keep their old c).
    # NOTE: scatter must be .max (logical or), not .set — with duplicate
    # group ids a later invalid lane would overwrite a valid one.
    touched = jnp.zeros((state.groups,), bool).at[safe_idx // m].max(valid)
    c_new = jax.scipy.special.logsumexp(v, axis=1)
    c = jnp.where(touched, c_new, state.c)
    return TwoLevelSamplerState(v=v, c=c, d=state.d)


def tl_exact_probs(state: TwoLevelSamplerState) -> jnp.ndarray:
    flat = state.v.reshape(-1)[: state.d]
    return jax.nn.softmax(flat)
