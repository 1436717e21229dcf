"""Sparse matrix containers used throughout the framework.

Two families:

* ``HostCSR`` / ``HostCSC`` — exact variable-length compressed formats in
  numpy.  These back the *faithful* sequential algorithms (paper Alg 2/3/4)
  where per-row / per-column iteration order matters and shapes may be ragged.

* ``PaddedCSR`` / ``PaddedCSC`` — fixed-shape ELL-style padded layouts in JAX
  arrays.  TPUs want static shapes and contiguous vector lanes, so each row
  (column) is padded to the max nnz; padding entries carry ``index = 0`` and
  ``value = 0`` which makes gathers safe and contributes nothing to reductions.
  This is the §Hardware-adaptation replacement for the paper's linked CSR: the
  asymptotic nnz-proportional work is preserved (padded nnz, see
  ``padding_overhead``) while every op lowers to gather / segment-sum that the
  VPU executes at line rate.

* ``ChunkedCSC`` — the column side with bytes bounded by nnz: every column's
  entries back to back in one flat array, read 128 rows at a time.
  ``host_to_padded`` builds it instead of ``PaddedCSC`` when the flat column
  table would not fit the device (DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Shape = Tuple[int, int]

# TPU vector lanes.  A (D, K) column table whose K is a multiple of this is
# laid out row-major on the chip, so a FW step reads column j as one
# contiguous row; any other K gets a column-major layout and the scan copies
# the whole table into row-major order first (a second resident copy).
LANES = 128


def lane_padded(k: int) -> int:
    """Pad width ``k`` rounded up to whole 128-lane vectors."""
    return -(-max(int(k), 1) // LANES) * LANES


# ---------------------------------------------------------------------------
# Host (numpy, exact) formats
# ---------------------------------------------------------------------------


class HostCSR:
    """Compressed sparse row; numpy; exact (no padding)."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray, data: np.ndarray, shape: Shape):
        self.indptr = np.asarray(indptr, dtype=np.int64)
        self.indices = np.asarray(indices, dtype=np.int64)
        self.data = np.asarray(data, dtype=np.float64)
        self.shape = (int(shape[0]), int(shape[1]))
        if self.indptr.shape != (self.shape[0] + 1,):
            raise ValueError("bad indptr length")

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def row(self, i: int) -> Tuple[np.ndarray, np.ndarray]:
        lo, hi = self.indptr[i], self.indptr[i + 1]
        return self.indices[lo:hi], self.data[lo:hi]

    def matvec(self, w: np.ndarray) -> np.ndarray:
        out = np.zeros(self.shape[0])
        for i in range(self.shape[0]):
            idx, val = self.row(i)
            out[i] = val @ w[idx]
        return out

    def rmatvec(self, q: np.ndarray) -> np.ndarray:
        out = np.zeros(self.shape[1])
        for i in range(self.shape[0]):
            idx, val = self.row(i)
            out[idx] += val * q[i]
        return out

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape)
        for i in range(self.shape[0]):
            idx, val = self.row(i)
            out[i, idx] = val
        return out

    def tocsc(self) -> "HostCSC":
        n, d = self.shape
        counts = np.zeros(d + 1, dtype=np.int64)
        for j in self.indices:
            counts[j + 1] += 1
        indptr = np.cumsum(counts)
        indices = np.empty(self.nnz, dtype=np.int64)
        data = np.empty(self.nnz)
        fill = indptr[:-1].copy()
        for i in range(n):
            lo, hi = self.indptr[i], self.indptr[i + 1]
            for p in range(lo, hi):
                j = self.indices[p]
                indices[fill[j]] = i
                data[fill[j]] = self.data[p]
                fill[j] += 1
        return HostCSC(indptr, indices, data, self.shape)


class HostCSC:
    """Compressed sparse column; numpy; exact."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray, data: np.ndarray, shape: Shape):
        self.indptr = np.asarray(indptr, dtype=np.int64)
        self.indices = np.asarray(indices, dtype=np.int64)
        self.data = np.asarray(data, dtype=np.float64)
        self.shape = (int(shape[0]), int(shape[1]))
        if self.indptr.shape != (self.shape[1] + 1,):
            raise ValueError("bad indptr length")

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def col(self, j: int) -> Tuple[np.ndarray, np.ndarray]:
        lo, hi = self.indptr[j], self.indptr[j + 1]
        return self.indices[lo:hi], self.data[lo:hi]

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape)
        for j in range(self.shape[1]):
            idx, val = self.col(j)
            out[idx, j] = val
        return out


def coo_to_host(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, shape: Shape) -> HostCSR:
    """Build a HostCSR from COO triplets (duplicates are summed)."""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float64)
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    # merge duplicates
    if rows.size:
        keep = np.ones(rows.size, dtype=bool)
        same = (rows[1:] == rows[:-1]) & (cols[1:] == cols[:-1])
        if same.any():
            # accumulate into the first of each run
            out_r, out_c, out_v = [], [], []
            i = 0
            while i < rows.size:
                k = i + 1
                acc = vals[i]
                while k < rows.size and rows[k] == rows[i] and cols[k] == cols[i]:
                    acc += vals[k]
                    k += 1
                out_r.append(rows[i])
                out_c.append(cols[i])
                out_v.append(acc)
                i = k
            rows = np.array(out_r, dtype=np.int64)
            cols = np.array(out_c, dtype=np.int64)
            vals = np.array(out_v)
        del keep
    counts = np.bincount(rows, minlength=shape[0])
    indptr = np.concatenate([[0], np.cumsum(counts)])
    return HostCSR(indptr, cols, vals, shape)


def dense_to_host(x: np.ndarray) -> HostCSR:
    rows, cols = np.nonzero(x)
    return coo_to_host(rows, cols, x[rows, cols], x.shape)


# ---------------------------------------------------------------------------
# Padded (JAX, fixed-shape) formats
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PaddedCSR:
    """ELL-style padded CSR: ``indices/values`` are (N, K) with K = max row nnz.

    Padding: ``index = 0, value = 0`` — safe for gathers, inert in sums.
    ``nnz`` keeps true per-row counts for masked iteration and FLOP audits.
    """

    indices: jnp.ndarray  # (N, K) int32 column ids
    values: jnp.ndarray   # (N, K) float
    nnz: jnp.ndarray      # (N,)  int32
    shape: Shape          # static (N, D)

    def tree_flatten(self):
        return (self.indices, self.values, self.nnz), self.shape

    @classmethod
    def tree_unflatten(cls, shape, leaves):
        return cls(*leaves, shape=shape)

    def matvec(self, w: jnp.ndarray) -> jnp.ndarray:
        """X · w — gather + row reduction; O(N·K) lanes of work."""
        return jnp.einsum("nk,nk->n", self.values, w[self.indices])

    def rmatvec(self, q: jnp.ndarray) -> jnp.ndarray:
        """Xᵀ · q — scatter-add over padded lanes; O(N·K)."""
        flat_idx = self.indices.reshape(-1)
        flat_val = (self.values * q[:, None]).reshape(-1)
        return jnp.zeros(self.shape[1], self.values.dtype).at[flat_idx].add(flat_val)

    def to_dense(self) -> jnp.ndarray:
        n, d = self.shape
        out = jnp.zeros((n, d), self.values.dtype)
        rows = jnp.broadcast_to(jnp.arange(n)[:, None], self.indices.shape)
        return out.at[rows.reshape(-1), self.indices.reshape(-1)].add(self.values.reshape(-1))

    @property
    def padding_overhead(self) -> float:
        """padded-lanes / true-nnz; 1.0 = no waste."""
        true = float(jnp.sum(self.nnz))
        return float(self.indices.size) / max(true, 1.0)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PaddedCSC:
    """Padded CSC: per-column row ids.  Column j's rows = ``indices[j]``.

    The pad width K is the max column nnz rounded up to whole lanes
    (``lane_padded``), so the chip keeps the table row-major."""

    indices: jnp.ndarray  # (D, K) int32 row ids
    values: jnp.ndarray   # (D, K) float
    nnz: jnp.ndarray      # (D,)  int32
    shape: Shape          # static (N, D)

    def tree_flatten(self):
        return (self.indices, self.values, self.nnz), self.shape

    @classmethod
    def tree_unflatten(cls, shape, leaves):
        return cls(*leaves, shape=shape)

    def col(self, j) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Rows/values/mask of column j (traced-index friendly)."""
        idx = jnp.take(self.indices, j, axis=0)
        val = jnp.take(self.values, j, axis=0)
        k = jnp.take(self.nnz, j)
        mask = jnp.arange(idx.shape[0]) < k
        return idx, val, mask


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ChunkedCSC:
    """nnz-bounded CSC: column j's rows are ``indices[indptr[j]:indptr[j+1]]``.

    The entries of all columns lie back to back, followed by one chunk
    (``LANES``) of tail padding, so a chunk read never runs off the end —
    ``dynamic_slice`` would otherwise clamp its start and read the wrong
    entries.  ``chunk(j, c)`` masks the slots past the column's end to
    ``index = 0, value = 0``, so chunk c of a column equals chunk c of the
    flat ``PaddedCSC`` table element for element."""

    indptr: jnp.ndarray   # (D + 1,) int32 column starts
    indices: jnp.ndarray  # (nnz + LANES,) int32 row ids
    values: jnp.ndarray   # (nnz + LANES,) float
    nnz: jnp.ndarray      # (D,) int32 true column counts
    shape: Shape          # static (N, D)

    def tree_flatten(self):
        return (self.indptr, self.indices, self.values, self.nnz), self.shape

    @classmethod
    def tree_unflatten(cls, shape, leaves):
        return cls(*leaves, shape=shape)

    def chunk(self, j, c, rows: int = LANES
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Rows/values/mask of rows ``rows·c … rows·c + rows − 1`` of column
        j (traced ``j`` and ``c``; ``rows`` at most the tail padding)."""
        if rows > LANES:
            raise ValueError(f"a chunk is at most {LANES} rows, got {rows}")
        start = jnp.take(self.indptr, j) + c * rows
        idx = jax.lax.dynamic_slice_in_dim(self.indices, start, rows)
        val = jax.lax.dynamic_slice_in_dim(self.values, start, rows)
        mask = c * rows + jnp.arange(rows) < jnp.take(self.nnz, j)
        return (jnp.where(mask, idx, 0), jnp.where(mask, val, 0), mask)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TieredCSC:
    """Two-tier padded CSC: the autotuner's exact-arithmetic ELL split.

    Power-law column popularity makes a single pad width pay for its tail:
    the rcv1-like regime has max column nnz ~8× its 99th percentile, so the
    flat ``PaddedCSC`` tile spends >100× the true nnz in padded lanes.  The
    tiered layout keeps a narrow ``(D, k)`` primary table for the common case
    and a full-width ``(H, K)`` heavy table holding the few columns whose
    nnz exceeds ``k`` verbatim; per-step dispatch (``lax.cond`` on the true
    column count) picks the tier.  No entry is dropped and padding stays
    ``index = 0, value = 0``, so every tile pass computes the same sums as
    the flat layout — the tuner's bitwise parity probe pins that per dataset.
    """

    indices: jnp.ndarray        # (D, k) light-tier row ids (heavy cols truncated)
    values: jnp.ndarray         # (D, k)
    nnz: jnp.ndarray            # (D,) TRUE per-column counts (never clamped)
    heavy_slot: jnp.ndarray     # (D,) int32 row in the heavy table (0 if light)
    heavy_indices: jnp.ndarray  # (H, K) full-width rows of the heavy columns
    heavy_values: jnp.ndarray   # (H, K)
    shape: Shape                # static (N, D)

    def tree_flatten(self):
        return ((self.indices, self.values, self.nnz, self.heavy_slot,
                 self.heavy_indices, self.heavy_values), self.shape)

    @classmethod
    def tree_unflatten(cls, shape, leaves):
        return cls(*leaves, shape=shape)

    @property
    def width(self) -> int:
        """Light-tier pad width k (the tuner's search knob)."""
        return int(self.indices.shape[1])

    @property
    def full_width(self) -> int:
        """Heavy-tier pad width = the flat layout's exact max column nnz."""
        return int(self.heavy_indices.shape[1])

    def is_heavy(self, j) -> jnp.ndarray:
        return jnp.take(self.nnz, j) > self.width

    def col_light(self, j) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Column j through the narrow tier (valid when nnz[j] <= width)."""
        idx = jnp.take(self.indices, j, axis=0)
        val = jnp.take(self.values, j, axis=0)
        k = jnp.take(self.nnz, j)
        mask = jnp.arange(idx.shape[0]) < k
        return idx, val, mask

    def col_heavy(self, j) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Column j through the full-width tier (exact for every column)."""
        slot = jnp.take(self.heavy_slot, j)
        idx = jnp.take(self.heavy_indices, slot, axis=0)
        val = jnp.take(self.heavy_values, slot, axis=0)
        k = jnp.take(self.nnz, j)
        mask = jnp.arange(idx.shape[0]) < k
        return idx, val, mask


def tiered_from_padded(pcsc: PaddedCSC, width: int) -> TieredCSC:
    """Split a flat ``PaddedCSC`` into the two-tier layout at ``width``.

    Exact by construction: columns with nnz <= width move to the narrow
    table unchanged (their truncated lanes were all padding); wider columns
    keep their full lanes in the heavy table and are dispatched there.
    """
    refuse_chunked(pcsc, "the tiered layout")
    full = int(pcsc.indices.shape[1])
    width = int(width)
    if not 1 <= width < full:
        raise ValueError(f"tier width must be in [1, {full}), got {width}")
    ci = np.asarray(pcsc.indices)
    cv = np.asarray(pcsc.values)
    cn = np.asarray(pcsc.nnz)
    heavy_cols = np.flatnonzero(cn > width)
    h = max(1, heavy_cols.size)            # keep the table non-empty (jit-safe)
    heavy_idx = np.zeros((h, full), ci.dtype)
    heavy_val = np.zeros((h, full), cv.dtype)
    heavy_slot = np.zeros(ci.shape[0], np.int32)
    if heavy_cols.size:
        heavy_idx[: heavy_cols.size] = ci[heavy_cols]
        heavy_val[: heavy_cols.size] = cv[heavy_cols]
        heavy_slot[heavy_cols] = np.arange(heavy_cols.size, dtype=np.int32)
    return TieredCSC(
        indices=jnp.asarray(ci[:, :width]), values=jnp.asarray(cv[:, :width]),
        nnz=jnp.asarray(cn), heavy_slot=jnp.asarray(heavy_slot),
        heavy_indices=jnp.asarray(heavy_idx),
        heavy_values=jnp.asarray(heavy_val), shape=pcsc.shape)


def _pad_rows(indptr: np.ndarray, indices: np.ndarray, data: np.ndarray, n_major: int, k: int):
    out_idx = np.zeros((n_major, k), dtype=np.int32)
    out_val = np.zeros((n_major, k), dtype=np.float32)
    nnz = np.diff(indptr).astype(np.int32)
    for i in range(n_major):
        lo, hi = indptr[i], indptr[i + 1]
        out_idx[i, : hi - lo] = indices[lo:hi]
        out_val[i, : hi - lo] = data[lo:hi]
    return out_idx, out_val, nnz


def dense_to_padded(x: np.ndarray) -> Tuple[PaddedCSR, PaddedCSC]:
    """Convert a dense numpy matrix into both padded layouts."""
    csr = dense_to_host(np.asarray(x))
    return host_to_padded(csr)


def col_layout(pcsc) -> str:
    """Name of a column layout: ``"flat"``, ``"tiered"`` or ``"chunked"``."""
    if isinstance(pcsc, ChunkedCSC):
        return "chunked"
    return "tiered" if isinstance(pcsc, TieredCSC) else "flat"


def refuse_chunked(pcsc, who: str) -> None:
    """TypeError when ``pcsc`` is the nnz-bounded layout, for the paths that
    read the flat column table (``who`` names the path)."""
    if isinstance(pcsc, ChunkedCSC):
        raise TypeError(
            f"{who} needs the flat PaddedCSC column table and does not take "
            "the nnz-bounded ChunkedCSC; its flat table would not fit the "
            "device (DESIGN.md §5) — use the jax_sparse backend")


def flat_col_bytes(col_nnz: np.ndarray) -> int:
    """Device bytes of the flat ``PaddedCSC`` table for these column counts:
    D × ``lane_padded(max nnz)`` × (4 B row id + 4 B value)."""
    col_nnz = np.asarray(col_nnz)
    k = lane_padded(int(col_nnz.max()) if col_nnz.size else 1)
    return int(col_nnz.shape[0]) * k * 8


def choose_col_layout(col_nnz: np.ndarray, row_bytes: int,
                      device_bytes) -> str:
    """``"flat"`` when the flat column table fits ``device_bytes`` beside
    the row table (``row_bytes``), else ``"chunked"``.  ``device_bytes``
    None (a device that reports no memory, such as the CPU) keeps the flat
    layout.  Pure: it allocates nothing."""
    if device_bytes is None:
        return "flat"
    fits = flat_col_bytes(col_nnz) + int(row_bytes) <= int(device_bytes)
    return "flat" if fits else "chunked"


def _device_bytes():
    """Memory of the device the layout goes to, or None if unreported."""
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("bytes_limit")


def host_to_chunked(csr: HostCSR) -> ChunkedCSC:
    """The nnz-bounded column layout of ``csr``, vectorised: a stable sort of
    the entries by column keeps each column's rows in increasing order, the
    order ``HostCSR.tocsc`` gives."""
    n, d = csr.shape
    order = np.argsort(csr.indices, kind="stable")
    counts = np.bincount(csr.indices, minlength=d)
    indptr = np.zeros(d + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(csr.indptr))
    indices = np.zeros(csr.nnz + LANES, np.int32)
    values = np.zeros(csr.nnz + LANES, np.float32)
    indices[: csr.nnz] = rows[order]
    values[: csr.nnz] = csr.data[order]
    return ChunkedCSC(jnp.asarray(indptr.astype(np.int32)),
                      jnp.asarray(indices), jnp.asarray(values),
                      jnp.asarray(counts.astype(np.int32)), (n, d))


def col_bytes(pcsc) -> int:
    """Device bytes of a column layout's arrays."""
    return int(sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(pcsc)))


def host_to_padded(csr: HostCSR) -> Tuple[PaddedCSR, "PaddedCSC | ChunkedCSC"]:
    """The row table and a column layout chosen from the input: the flat
    ``PaddedCSC`` when its table fits the device beside the row table
    (``choose_col_layout``), else the nnz-bounded ``ChunkedCSC``."""
    from repro import obs
    n, d = csr.shape
    k_row = int(max(1, np.max(np.diff(csr.indptr)) if csr.nnz else 1))
    col_nnz = np.bincount(csr.indices, minlength=d)
    layout = choose_col_layout(col_nnz, n * k_row * 8, _device_bytes())
    with obs.span("layout.build", col_layout=layout, nnz=csr.nnz) as sp:
        ri, rv, rn = _pad_rows(csr.indptr, csr.indices, csr.data, n, k_row)
        pcsr = PaddedCSR(jnp.asarray(ri), jnp.asarray(rv), jnp.asarray(rn),
                         (n, d))
        if layout == "chunked":
            pcsc = host_to_chunked(csr)
        else:
            csc = csr.tocsc()
            k_col = lane_padded(np.max(np.diff(csc.indptr)) if csc.nnz else 1)
            ci, cv, cn = _pad_rows(csc.indptr, csc.indices, csc.data, d, k_col)
            pcsc = PaddedCSC(jnp.asarray(ci), jnp.asarray(cv),
                             jnp.asarray(cn), (n, d))
        sp.set(col_bytes=col_bytes(pcsc))
    return pcsr, pcsc
