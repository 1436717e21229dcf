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

State updates after a FW iteration: ``tl_rebuild`` rewrites the whole
(G, M) table from the step's final |α| in one dense O(D) pass and takes a
fresh log-sum-exp for every group whose row changed — exact (no incremental
drift at all, which is *stronger* than the paper's O(1) updates; on TPU a
dense vector pass per step is far cheaper than scattering the touched
coordinates one index at a time).  ``tl_update`` is the scatter form, for
callers that refresh a given index list.

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


def as_table(values: jnp.ndarray, g: int, m: int) -> jnp.ndarray:
    """(D,) per-item values laid out as a (G, M) table: item j at slot
    j = g·M + m, the slots past D NEG_INF padding."""
    d = values.shape[0]
    return jnp.pad(values, (0, g * m - d),
                   constant_values=NEG_INF).reshape(g, m)


def tl_init(log_weights: jnp.ndarray) -> TwoLevelSamplerState:
    d = log_weights.shape[0]
    v = as_table(log_weights, *group_shape(d))
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

    The scatter form of the refresh, for a step that names its touched
    coordinates (``fw_jax``).  Every group log-sum-exp is recomputed in one
    (G, M) pass; touched groups take theirs.  The ``jax_sparse`` scan
    refreshes with ``tl_rebuild`` instead: the scatters here cost one
    serialized slot per touched index, padding included.
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


def tl_rebuild(state: TwoLevelSamplerState,
               log_weights: jnp.ndarray) -> TwoLevelSamplerState:
    """Rebuild the table densely from all D current log-weights.

    One O(D) pass per FW step, after the coordinate update: entries whose
    weight did not move are rewritten with the value they hold, so the table
    equals the one ``tl_update`` leaves after scattering every touched
    coordinate's final weight.  A group whose row changed takes its fresh
    log-sum-exp; found by a dense row comparison, no scatter of group ids.
    Every other group keeps its ``c``.
    """
    v = as_table(log_weights, *state.v.shape)
    changed = jnp.any(v != state.v, axis=1)
    c = jnp.where(changed, jax.scipy.special.logsumexp(v, axis=1), state.c)
    return TwoLevelSamplerState(v=v, c=c, d=state.d)


def tl_exact_probs(state: TwoLevelSamplerState) -> jnp.ndarray:
    flat = state.v.reshape(-1)[: state.d]
    return jax.nn.softmax(flat)
