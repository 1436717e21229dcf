"""The five builtin solver backends (DESIGN.md §4).

  dense        Alg 1 — dense-work FW, one lax.scan (repro.core.fw_dense).
               Accepts a dense device matrix or a PaddedCSR.
  jax_dense    Alg 2 state machine on device, dense vector updates: the pure
               jnp scan from repro.core.fw_jax (full-width scatter/logsumexp
               refreshes each iteration).
  host_sparse  Alg 2 faithful sequential host implementation with exact FLOP
               accounting (repro.core.fw_sparse; queues = Alg 3 / Alg 4 /
               ablations).
  jax_sparse   Alg 2 on device through ``repro.kernels`` (spmv /
               coord_update / bsls_draw) — the production sparse path.
  jax_shard    Alg 2 under feature sharding: the shard_map collective
               schedule of repro.distributed over an (a × b) BlockSparse
               grid named by FWConfig.mesh (DESIGN.md §8) — the scale-out
               path; a 1×1 mesh reproduces the host oracle exactly.

Each adapter normalizes its engine's native signature/result onto the shared
``(data, y, FWConfig) -> FWResult`` contract.  Imported lazily by
``registry._ensure_builtins``.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.solvers.config import FWConfig, FWResult
from repro.core.solvers.prepared import PreparedDataset
from repro.core.solvers.registry import QUEUE_ALIASES, register


def _normalize_stop(res: FWResult, config: FWConfig) -> FWResult:
    """Fill stop_reason for jitted scans that can only report stop_step as a
    device scalar: a run that ended before T with gap_tol set stopped on the
    certificate (the masked scans have no other way to stop early)."""
    stop = res.stop_step_or(config.steps)
    res.stop_step = stop
    if stop < config.steps and res.stop_reason == "max_steps":
        res.stop_reason = "gap_tol"
    return res


@register("dense", data_format="dense", queues=QUEUE_ALIASES["selection"],
          default_queue=None, supports_screening=True, supports_path=True,
          doc="Alg 1 baseline: dense-work FW (O(nnz + D)/iter), device scan")
def _dense_backend(data, y, config: FWConfig) -> FWResult:
    from repro.core.fw_dense import (dense_fw_jit, dense_fw_screened,
                                     dense_fw_stopping)
    if config.queue is not None:  # queue name chosen → translate to selection
        config = dataclasses.replace(config, selection=config.queue, queue=None)
    y = jnp.asarray(y, jnp.float32)
    if config.screen_every > 0:   # §13: mutable-geometry chunked driver
        return dense_fw_screened(data, y, config)
    if config.early_stopping:     # §9: host-driven chunked masked scan
        return dense_fw_stopping(data, y, config)
    return _normalize_stop(dense_fw_jit(data, y, config), config)


@register("jax_dense", data_format="padded", queues=QUEUE_ALIASES["device"],
          default_queue="group_argmax", supports_max_seconds=False,
          doc="Alg 2 device scan, dense vector updates (pure jnp, no kernels)")
def _jax_dense_backend(data, y, config: FWConfig) -> FWResult:
    from repro.core.fw_jax import sparse_fw_jax_jit
    if config.max_seconds is not None:
        raise ValueError(
            "jax_dense runs as one compiled scan and cannot watch a wall "
            "clock; use gap_tol, or the dense/host_sparse/jax_sparse "
            "backends for max_seconds")
    pcsr, pcsc = data.pair if isinstance(data, PreparedDataset) else data
    res = sparse_fw_jax_jit(pcsr, pcsc, jnp.asarray(y, jnp.float32), config)
    return _normalize_stop(res, config)


@register("host_sparse", data_format="host", queues=QUEUE_ALIASES["host"],
          default_queue="fib_heap",
          doc="Alg 2 faithful host loop (Alg 3/4 queues, exact FLOP audit)")
def _host_sparse_backend(data, y, config: FWConfig) -> FWResult:
    from repro.core.fw_sparse import sparse_fw
    res = sparse_fw(
        data, np.asarray(y, np.float64), lam=config.lam, steps=config.steps,
        loss=config.loss, queue=config.queue, epsilon=config.epsilon,
        delta=config.delta, seed=config.seed, gap_tol=config.gap_tol,
        max_seconds=config.max_seconds)
    gaps = jnp.asarray(res.gaps, jnp.float32)
    return FWResult(w=jnp.asarray(res.w, jnp.float32), gaps=gaps,
                    coords=jnp.asarray(res.coords, jnp.int32),
                    losses=jnp.zeros_like(gaps),
                    stop_step=res.stop_step if res.stop_step is not None
                    else config.steps,
                    stop_reason=res.stop_reason)


@register("jax_shard", data_format="blocks", queues=QUEUE_ALIASES["shard"],
          default_queue="argmax", supports_max_seconds=False,
          doc="Alg 2 under feature sharding: shard_map collective schedule "
              "over BlockSparse blocks (FWConfig.mesh = (rows, features); "
              "1×1 reproduces the host oracle exactly)")
def _jax_shard_backend(data, y, config: FWConfig) -> FWResult:
    from repro.core.solvers.jax_shard import shard_fw
    return shard_fw(data, y, config)


@register("jax_sparse", data_format="padded", queues=QUEUE_ALIASES["device"],
          default_queue="group_argmax", supports_screening=True,
          supports_path=True,
          doc="Alg 2 device scan through repro.kernels "
              "(spmv + coord_update + bsls_draw)")
def _jax_sparse_backend(data, y, config: FWConfig) -> FWResult:
    from repro.core.solvers.jax_sparse import jax_sparse_fw
    setup = None
    if isinstance(data, PreparedDataset):
        # dataset-store path: replay the cached fw_setup state (bit-exact)
        setup = data.setup_for(y, config.loss)
        pcsr, pcsc = data.pair
        # §11: the store's autotuned layout/chunk winner, when one exists —
        # parity-gated at tuning time, so iterates are bit-identical
        rec = data.tuning_for("jax_sparse", config.loss)
        if rec is not None:
            if rec.ell_width is not None:
                pcsc = data.tuned_pcsc(rec)
            if config.chunk_steps is None and rec.chunk_steps is not None:
                config = dataclasses.replace(config,
                                             chunk_steps=rec.chunk_steps)
    else:
        pcsr, pcsc = data
    return jax_sparse_fw(pcsr, pcsc, jnp.asarray(y, jnp.float32), config,
                         setup=setup)
