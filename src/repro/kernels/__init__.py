"""Kernels of the DP-LASSO hot path.

Each subpackage has an ops.py (the public entry point the solvers call):

  spmv/            padded-ELL X·w and Xᵀ·q — the setup sweep (Alg 2 lines
                   8-14).  XLA gather/scatter-add on every platform.
  coord_update/    Alg-2 inner loop (lines 22-28): one coordinate's v̄/q̄/α/g̃
                   propagation.  XLA on every platform.
  bsls_draw/       Alg-4's sub-linear EM draw as big step (XLA, √D scan) +
                   little step (scalar-prefetch Pallas kernel that DMAs only
                   the winning group's tile — O(√D) bytes per draw).
  flash_attention/ online-softmax attention forward for the LM-side archs.

Mosaic, the TPU's Pallas compiler, lowers neither a gather nor a
scatter-add, so spmv and coord_update stay in XLA: one form on every
platform, never a kernel on one and a reference on another.

A Pallas kernel is compiled when its program is lowered for a TPU and runs
through the Pallas interpreter everywhere else (the CPU tests) —
:func:`platform_kernel` makes that choice; nothing else sets ``interpret``.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax


def platform_kernel(kernel: Callable, *args):
    """``kernel(*args, interpret=...)``, compiled for a TPU lowering and
    interpreted for any other platform.

    The choice is made when the enclosing program is lowered
    (``lax.platform_dependent``), so it follows the device the program is
    built for — including a TPU described without one attached.
    """
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel, interpret=False),
        default=functools.partial(kernel, interpret=True))
