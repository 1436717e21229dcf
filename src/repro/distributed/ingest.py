"""Block ingestion for the ``jax_shard`` backend (DESIGN.md §8).

``ShardSource`` is what the solver registry's ``blocks`` coercion returns:
a thin handle over the user's data that defers the (a × b) block build until
the mesh geometry is known (it lives on ``FWConfig.mesh``, not on the data),
then memoizes one ``BlockSparse`` per grid so sweeps, the fit service and
repeated solves never re-bucket.

Two construction paths:

  * **in-memory** — any matrix the registry can turn into a ``HostCSR``
    (dense, padded pair, HostCSR) goes through the vectorized
    ``build_block_sparse``;
  * **dataset store** — shards stream one mmap ``HostCSR`` view at a time
    into ``BlockAssembler`` (two passes: lane counts, then fills with
    running per-column/row pointers), so the store's npy shards map onto
    device blocks **without densifying through one concatenated host
    matrix**.  The finished layout persists under the store's ``cache/``
    guarded by its content hash (alongside the padded/setup caches) and is
    mmap-read on warm opens.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from repro.core.sparse.formats import HostCSR
from repro.distributed.block_sparse import (BlockAssembler, BlockSparse,
                                            build_block_sparse)


def _shard_coo(row_start: int, csr: HostCSR):
    """(global rows, cols, vals) COO view of one store shard."""
    rows = np.repeat(
        np.arange(csr.shape[0], dtype=np.int64) + row_start,
        np.diff(csr.indptr))
    return rows, csr.indices, csr.data


def blocks_from_store(store, a: int, b: int) -> BlockSparse:
    """Map a ``DatasetStore``'s shards onto an (a × b) ``BlockSparse``.

    Streams the mmap shard views through ``BlockAssembler`` (one shard
    resident per pass) and persists the result in the store's content-hash-
    guarded block-layout cache; warm calls read the padded block arrays
    straight off mmap.  Lane order is identical to
    ``build_block_sparse(store.to_host_csr(), a, b)`` by the assembler's
    running-fill-pointer construction.
    """
    cached = store.blocks_load(a, b)
    if cached is not None:
        return cached
    n, d = store.shape
    asm = BlockAssembler(n, d, a, b)
    for row_start, csr, _ in store.iter_shards():
        rows, cols, _ = _shard_coo(row_start, csr)
        asm.count(rows, cols)
    asm.alloc()
    for row_start, csr, _ in store.iter_shards():
        asm.fill(*_shard_coo(row_start, csr))
    blocks = asm.finish()
    store.blocks_save(a, b, blocks)
    return blocks


@dataclasses.dataclass
class ShardSource:
    """Deferred block coercion: one of (csr, store) + a per-grid memo."""

    shape: Tuple[int, int]
    csr: Optional[HostCSR] = None
    store: Optional[object] = None            # repro.data.store.DatasetStore
    _blocks: Dict[Tuple[int, int], BlockSparse] = dataclasses.field(
        default_factory=dict)

    @classmethod
    def from_any(cls, X) -> "ShardSource":
        """Coerce any registry-accepted ``X`` into a ``ShardSource``."""
        if isinstance(X, cls):
            return X
        from repro.data.store import DatasetStore
        if isinstance(X, DatasetStore):
            return cls(shape=X.shape, store=X)
        from repro.core.solvers.registry import as_host_csr
        from repro.core.sparse.formats import refuse_chunked
        if isinstance(X, tuple) and len(X) == 2:
            refuse_chunked(X[1], "the jax_shard backend")
        csr = as_host_csr(X)
        return cls(shape=csr.shape, csr=csr)

    def blocks(self, a: int, b: int) -> BlockSparse:
        """The (a × b) grid, each block resident on its device of the
        ``jax_shard`` mesh (built and placed once per grid)."""
        key = (int(a), int(b))
        if key not in self._blocks:
            if self.store is not None:
                host = blocks_from_store(self.store, a, b)
            else:
                host = build_block_sparse(self.csr, a, b)
            import jax

            from repro.core.solvers.jax_shard import make_shard_mesh
            from repro.distributed.fw_shard import dist_fw_shardings
            shardings, _ = dist_fw_shardings(host, make_shard_mesh(a, b))
            self._blocks[key] = jax.device_put(host, shardings)
        return self._blocks[key]
