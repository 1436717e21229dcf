"""jit'd wrapper: one DP exponential-mechanism draw via big step (XLA) +
little step (Pallas scalar-prefetch kernel)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import platform_kernel
from repro.kernels.bsls_draw.kernel import little_step_pallas


@jax.jit
def two_level_draw(c: jnp.ndarray, v: jnp.ndarray,
                   key: jax.Array) -> jnp.ndarray:
    """Draw ``j ~ softmax(v.flatten())`` via group-then-member Gumbel-max.

    Args:
      c: (G,) group log-sum-exps (big-step table).
      v: (G, M) member log-weights, padding = -inf; tile-aligned as
        ``samplers.bsls_jax.group_shape`` lays it out.
      key: PRNG key; split into the two noise draws (O(√D) variates total,
        mirroring the paper's O(log D) threshold draws in spirit — sub-linear).
    """
    kg, km = jax.random.split(key)
    g = jnp.argmax(c + jax.random.gumbel(kg, c.shape, jnp.float32)).astype(jnp.int32)
    noise = jax.random.gumbel(km, (1, v.shape[1]), jnp.float32)
    return platform_kernel(little_step_pallas, g, v, noise)
