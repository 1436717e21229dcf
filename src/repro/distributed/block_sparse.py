"""2-D block-sharded padded sparse design matrix.

The distributed Frank-Wolfe (DESIGN.md §8) shards the design matrix over the
production mesh: **rows → ("pod","data"), features → "model"**.  Each device
(a, b) holds the (N/A × D/B) block X[rows_a, cols_b] in both padded layouts:

  * block CSC — for the selected column j's local rows (v̄/q̄ updates);
  * block CSR — for the touched rows' local columns (α-shard updates).

Row ids inside a block are *local* (0..N_loc) and column ids are *local*
(0..D_loc): every per-device kernel indexes only its own shards, so the only
cross-device traffic left in the FW step is the γ/dv lane exchange and the
α-delta reduction (see fw_shard.py).

Padding is per-layout-global (one static Kc/Kr for every block) because XLA
needs one shape; ``waste`` reports the padded/true-nnz ratio so benchmarks
can audit the overhead the same way PaddedCSR.padding_overhead does.

Construction is a vectorized two-pass COO bucketing (``BlockAssembler``):
pass 1 counts lanes per block column/row (fixing Kc/Kr), pass 2 scatters
values into the preallocated padded arrays.  Because the assembler consumes
COO fragments incrementally with running fill pointers, a sharded on-disk
``DatasetStore`` maps straight onto device blocks one mmap shard at a time
(``repro.distributed.ingest``) — no concatenation into one host matrix, and
lane order is identical to feeding the whole matrix at once.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparse.formats import HostCSR, lane_padded


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BlockSparse:
    """All leaves lead with (A, B) = (data shards, model shards)."""

    csc_rows: jnp.ndarray   # (A, B, D_loc, Kc) int32 local row ids
    csc_vals: jnp.ndarray   # (A, B, D_loc, Kc) f32
    csr_cols: jnp.ndarray   # (A, B, N_loc, Kr) int32 local col ids
    csr_vals: jnp.ndarray   # (A, B, N_loc, Kr) f32
    shape: Tuple[int, int]  # global (N, D) — static
    padded: Tuple[int, int]  # (N_pad, D_pad) — static

    def tree_flatten(self):
        return ((self.csc_rows, self.csc_vals, self.csr_cols, self.csr_vals),
                (self.shape, self.padded))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, shape=aux[0], padded=aux[1])

    @property
    def grid(self) -> Tuple[int, int]:
        return self.csc_rows.shape[0], self.csc_rows.shape[1]

    @property
    def waste(self) -> float:
        true = float(jnp.sum(self.csc_vals != 0))
        return float(self.csc_vals.size) / max(true, 1.0)


def block_layout(n: int, d: int, a: int, b: int) -> Tuple[int, int]:
    """Per-device block shape (N_loc, D_loc) of an (a × b) grid."""
    return -(-n // a), -(-d // b)


def _run_ranks(sorted_key: np.ndarray) -> np.ndarray:
    """Rank of each element within its equal-key run (key already sorted)."""
    m = sorted_key.size
    if m == 0:
        return np.zeros(0, np.int64)
    run_start = np.zeros(m, np.int64)
    new_run = np.flatnonzero(sorted_key[1:] != sorted_key[:-1]) + 1
    run_start[new_run] = new_run
    return np.arange(m, dtype=np.int64) - np.maximum.accumulate(run_start)


class BlockAssembler:
    """Streaming COO → (a × b) padded block grid, in two vectorized passes.

    Feed COO fragments in global row order (``count`` them all, ``alloc``,
    then ``fill`` the same fragments in the same order).  Lane order inside
    each block column (row) is the global row (stored column) order — the
    running fill pointers carry it across fragments, so shard-at-a-time
    assembly is bit-identical to whole-matrix assembly.
    """

    def __init__(self, n: int, d: int, a: int, b: int):
        self.n, self.d, self.a, self.b = n, d, a, b
        self.n_loc, self.d_loc = block_layout(n, d, a, b)
        self._col_counts = np.zeros(a * b * self.d_loc, np.int64)
        self._row_counts = np.zeros(a * b * self.n_loc, np.int64)
        self._arrays = None

    def _keys(self, rows: np.ndarray, cols: np.ndarray):
        ai, il = np.divmod(np.asarray(rows, np.int64), self.n_loc)
        bj, jl = np.divmod(np.asarray(cols, np.int64), self.d_loc)
        block = ai * self.b + bj
        return block * self.d_loc + jl, block * self.n_loc + il, il, jl

    def count(self, rows: np.ndarray, cols: np.ndarray) -> None:
        col_key, row_key, _, _ = self._keys(rows, cols)
        self._col_counts += np.bincount(col_key,
                                        minlength=self._col_counts.size)
        self._row_counts += np.bincount(row_key,
                                        minlength=self._row_counts.size)

    def alloc(self) -> None:
        """Fix (Kc, Kr) from the counts and allocate the padded arrays."""
        a, b = self.a, self.b
        self.kc = lane_padded(self._col_counts.max(initial=0))
        self.kr = max(1, int(self._row_counts.max(initial=0)))
        self._arrays = (
            np.zeros((a, b, self.d_loc, self.kc), np.int32),
            np.zeros((a, b, self.d_loc, self.kc), np.float32),
            np.zeros((a, b, self.n_loc, self.kr), np.int32),
            np.zeros((a, b, self.n_loc, self.kr), np.float32),
        )
        self._col_fill = np.zeros_like(self._col_counts)
        self._row_fill = np.zeros_like(self._row_counts)

    def fill(self, rows: np.ndarray, cols: np.ndarray,
             vals: np.ndarray) -> None:
        if self._arrays is None:
            raise RuntimeError("call alloc() after the counting pass")
        col_key, row_key, il, jl = self._keys(rows, cols)
        vals = np.asarray(vals, np.float64)
        for key, fill, lane_k, dest_i, dest_v, local in (
            (col_key, self._col_fill, self.kc,
             self._arrays[0], self._arrays[1], il),
            (row_key, self._row_fill, self.kr,
             self._arrays[2], self._arrays[3], jl),
        ):
            order = np.argsort(key, kind="stable")   # keep arrival order
            k_sorted = key[order]
            lane = fill[k_sorted] + _run_ranks(k_sorted)
            flat = k_sorted * lane_k + lane
            dest_i.reshape(-1)[flat] = local[order]
            dest_v.reshape(-1)[flat] = vals[order]
            fill += np.bincount(key, minlength=fill.size)

    def finish(self) -> BlockSparse:
        """The grid as host arrays; ``ShardSource.blocks`` places each
        block on its own device of the mesh."""
        csc_rows, csc_vals, csr_cols, csr_vals = self._arrays
        return BlockSparse(
            csc_rows=csc_rows, csc_vals=csc_vals,
            csr_cols=csr_cols, csr_vals=csr_vals,
            shape=(self.n, self.d),
            padded=(self.n_loc * self.a, self.d_loc * self.b),
        )


def build_block_sparse(X: HostCSR, a: int, b: int) -> BlockSparse:
    """Split a HostCSR into an (a × b) block grid of padded layouts."""
    n, d = X.shape
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(X.indptr))
    asm = BlockAssembler(n, d, a, b)
    asm.count(rows, X.indices)
    asm.alloc()
    asm.fill(rows, X.indices, X.data)
    return asm.finish()


def block_specs(n: int, d: int, a: int, b: int, kc: int, kr: int) -> BlockSparse:
    """ShapeDtypeStruct stand-in for dry-runs (no allocation)."""
    n_loc, d_loc = block_layout(n, d, a, b)
    f = jax.ShapeDtypeStruct
    return BlockSparse(
        csc_rows=f((a, b, d_loc, kc), jnp.int32),
        csc_vals=f((a, b, d_loc, kc), jnp.float32),
        csr_cols=f((a, b, n_loc, kr), jnp.int32),
        csr_vals=f((a, b, n_loc, kr), jnp.float32),
        shape=(n, d), padded=(n_loc * a, d_loc * b),
    )
