"""Pallas TPU kernel for the Big-Step-Little-Step draw (two-level EM sample).

The paper's Alg 4 walks groups sequentially with a reservoir threshold — a
cache trick, not part of the sampled law (DESIGN.md §2).  The TPU form draws
the same distribution in two Gumbel-max scans:

  big step    g = argmax(c + γ_G)       over the G group masses
  little step m = argmax(v[g] + γ_M)    over the M members of group g

This kernel implements the *little step* with the canonical Pallas
scalar-prefetch pattern: the winning group id (computed from the small c
vector) is prefetched, and the BlockSpec ``index_map`` uses it to DMA only
the 8-row tile of the (G, M) member table that holds row g — O(√D) bytes
moved per draw, never the full table.  That is the kernel-level realization
of the paper's sub-linear-per-iteration claim: selection cost is O(√D), not
O(D).

The table is tile-aligned (``samplers.bsls_jax.group_shape``: G a multiple
of 8 sublanes, M of 128 lanes), which is what Mosaic requires of a block; a
(1, M) block of a (G, M) table is refused, so the kernel fetches the (8, M)
tile and picks row ``g % 8`` inside it.

The big step runs in plain XLA in ops.py (c is √D floats — a single VPU
vector op; a kernel would add nothing).

VMEM per draw: one (8, M) tile + one (1, M) noise row ≈ 9·√D·4 B (for the
paper's largest D = 20.2M: 9·4608·4 ≈ 166 KB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8    # rows per (8, 128) TPU tile


def _little_step_kernel(g_ref, v_tile_ref, noise_ref, out_ref):
    g = g_ref[0]
    row = v_tile_ref[pl.ds(g % SUBLANES, 1), :]     # (1, M): group g's row
    m = jnp.argmax(row + noise_ref[...], axis=1)[0].astype(jnp.int32)
    out_ref[0] = g * row.shape[1] + m


@functools.partial(jax.jit, static_argnames=("interpret",))
def little_step_pallas(g: jnp.ndarray, v: jnp.ndarray, noise: jnp.ndarray,
                       *, interpret: bool) -> jnp.ndarray:
    """Flat index of the member draw inside prefetched group ``g``.

    Args:
      g: () int32 — winning group from the big step.
      v: (G, M) member log-weights (padded with -inf past D), G a multiple
        of 8 and M of 128.
      noise: (1, M) Gumbel noise for the little step.
      interpret: run through the Pallas interpreter (platforms without a
        TPU); ``kernels.platform_kernel`` chooses it.
    """
    _, m_sz = v.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[
            # index_map sees (grid ids..., *prefetch refs); pick g's tile.
            pl.BlockSpec((SUBLANES, m_sz),
                         lambda i, g_ref: (g_ref[0] // SUBLANES, 0)),
            pl.BlockSpec((1, m_sz), lambda i, g_ref: (0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    return pl.pallas_call(
        _little_step_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        interpret=interpret,
    )(g.reshape(1).astype(jnp.int32), v, noise)[0]
