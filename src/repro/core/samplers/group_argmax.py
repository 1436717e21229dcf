"""TPU-native equivalent of the Fibonacci-heap queue (paper Alg 3).

The heap's insight — priorities may go stale as long as they only
*overestimate*, with lazy repair on pop — transfers to a flat two-level
structure: per-group stale maxima ``m_g`` (upper bounds on the group's true
max |α|).  ``get_next``:

  1. pick g* = argmax m_g          (O(√D))
  2. true max inside g*            (O(√D)), repair m_{g*} to the truth
  3. if the repaired m_{g*} still beats every other bound → done, else loop.

Exactly like Alg 3, each repair can only lower a bound, and the loop ends
when the best *verified* value dominates all remaining (over-)estimates — so
the returned index is the exact argmax.  Expected pops mirror the paper's
≤ 3‖w*‖₀ observation because only coordinates whose gradients grew carry
fresh bounds.

Bounds only ratchet upward on update; decreases are ignored — that is what
makes them stale-but-safe.  ``ga_rebuild`` refreshes once per FW step with a
dense O(D) pass (rewrite the priority table from |α|, ratchet each bound to
its row's max); ``ga_update`` is the scatter form for callers that refresh
a given index list.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.samplers.bsls_jax import NEG_INF, as_table


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GroupArgmaxState:
    p: jnp.ndarray      # (G, M) live priorities (|α| magnitudes), padded NEG_INF
    bound: jnp.ndarray  # (G,)   stale upper bounds on each group's max
    d: int

    def tree_flatten(self):
        return (self.p, self.bound), self.d

    @classmethod
    def tree_unflatten(cls, d, leaves):
        return cls(*leaves, d=d)

    @property
    def group_size(self) -> int:
        return self.p.shape[1]


def ga_init(priorities: jnp.ndarray) -> GroupArgmaxState:
    d = priorities.shape[0]
    g = max(1, math.isqrt(max(d - 1, 0)) + 1)
    m = (d + g - 1) // g
    p = as_table(priorities, g, m)
    return GroupArgmaxState(p=p, bound=jnp.max(p, axis=1), d=d)


def ga_update(state: GroupArgmaxState, idx: jnp.ndarray, priorities: jnp.ndarray) -> GroupArgmaxState:
    """Scatter live priorities; bounds only ratchet upward (stale-safe)."""
    m = state.group_size
    valid = idx < state.d
    safe_idx = jnp.where(valid, idx, 0)
    vals = jnp.where(valid, priorities, state.p.reshape(-1)[safe_idx])
    p = state.p.reshape(-1).at[safe_idx].set(vals).reshape(state.p.shape)
    bound = state.bound.at[safe_idx // m].max(jnp.where(valid, vals, NEG_INF))
    return GroupArgmaxState(p=p, bound=bound, d=state.d)


def ga_rebuild(state: GroupArgmaxState,
               priorities: jnp.ndarray) -> GroupArgmaxState:
    """Rewrite the table densely from all D current priorities and ratchet
    each bound to its row's max: one O(D) pass per FW step.

    Every bound already covers its row's unchanged entries, so the ratchet
    is ``ga_update``'s scatter-max with each touched coordinate's current
    priority.  Stale-safe: bounds stay upper bounds and never go down here.
    """
    p = as_table(priorities, *state.p.shape)
    bound = jnp.maximum(state.bound, jnp.max(p, axis=1))
    return GroupArgmaxState(p=p, bound=bound, d=state.d)


def ga_get_next(state: GroupArgmaxState) -> Tuple[jnp.ndarray, GroupArgmaxState]:
    """Lazy-repair argmax; returns (flat index, state with repaired bounds)."""

    def cond(carry):
        bound, _best_j, best_v, _pops = carry
        return jnp.max(bound) > best_v

    def body(carry):
        bound, best_j, best_v, pops = carry
        g = jnp.argmax(bound)
        row = jnp.take(state.p, g, axis=0)
        j_in = jnp.argmax(row)
        true_max = row[j_in]
        bound = bound.at[g].set(true_max)  # repair: bound → truth
        better = true_max > best_v
        best_j = jnp.where(better, g * state.group_size + j_in, best_j)
        best_v = jnp.where(better, true_max, best_v)
        return bound, best_j, best_v, pops + 1

    init = (state.bound, jnp.array(-1, jnp.int32), jnp.array(NEG_INF, state.p.dtype),
            jnp.array(0, jnp.int32))
    bound, best_j, _best_v, _pops = jax.lax.while_loop(cond, body, init)
    return best_j, GroupArgmaxState(p=state.p, bound=bound, d=state.d)
