"""Synthetic twins of the paper's Table-2 datasets, made from a seed.

The law is that of the program's ``data/synthetic.make_sparse_classification``
(kept here so that a change to the program cannot move the benchmark's
data):

* N rows with Poisson(nnz_per_row) distinct columns each (at least one);
* columns drawn by successive weighted sampling without replacement from a
  1/r^1.1 popularity law over the D columns;
* values uniform in [0.1, 1] with a random sign, scaled by the column's
  idf, then each row scaled to unit L2 norm;
* labels from a planted logistic model on ``informative`` columns of
  middling popularity (log-spread between rank 10 and D/4), with a share
  ``label_noise`` of them flipped.

The row sampling is vectorised: a row's columns are the first k distinct
values of i.i.d. draws from the popularity law, which is the law of
successive sampling without replacement.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Twin:
    """A generated dataset as exact CSR arrays (float64) and labels."""

    indptr: np.ndarray    # (N + 1,) int64
    indices: np.ndarray   # (nnz,) int64, sorted within each row
    data: np.ndarray      # (nnz,) float64
    y: np.ndarray         # (N,) float64 in {0, 1}
    shape: tuple

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])


def _rows_of_distinct(rng, cdf: np.ndarray, k: np.ndarray) -> list:
    """For each row i, the first k[i] distinct draws from ``cdf``."""
    d = cdf.shape[0]
    out = [None] * k.shape[0]
    todo = np.arange(k.shape[0])
    width = int(2 * k.max()) + 64
    while todo.size:
        draws = np.minimum(np.searchsorted(cdf, rng.random((todo.size, width)),
                                           side="right"), d - 1)
        order = np.argsort(draws, axis=1, kind="stable")
        srt = np.take_along_axis(draws, order, axis=1)
        first_sorted = np.ones_like(srt, dtype=bool)
        first_sorted[:, 1:] = srt[:, 1:] != srt[:, :-1]
        first = np.zeros_like(first_sorted)
        np.put_along_axis(first, order, first_sorted, axis=1)
        rank = np.cumsum(first, axis=1)
        want = k[todo][:, None]
        keep = first & (rank <= want)
        enough = rank[:, -1] >= want[:, 0]
        for r in np.flatnonzero(enough):
            out[todo[r]] = np.sort(draws[r][keep[r]])
        todo = todo[~enough]
        width *= 2
    return out


def make_twin(n: int, d: int, nnz_per_row: float, informative: int,
              seed: int, label_noise: float = 0.05,
              column_exponent: float = 1.1) -> Twin:
    """The dataset of ``seed`` at shape (n, d); see the module docstring."""
    rng = np.random.default_rng(seed % 2 ** 64)
    col_p = 1.0 / np.arange(1, d + 1) ** column_exponent
    cdf = np.cumsum(col_p / col_p.sum())
    k = np.minimum(np.maximum(1, rng.poisson(max(nnz_per_row, 1), size=n)), d)
    cols_of = _rows_of_distinct(rng, cdf, k)
    counts = np.array([c.shape[0] for c in cols_of], np.int64)
    indptr = np.concatenate([[0], np.cumsum(counts)])
    cols = np.concatenate(cols_of).astype(np.int64)
    rows = np.repeat(np.arange(n), counts)
    vals = (rng.uniform(0.1, 1.0, size=cols.shape[0])
            * rng.choice([-1.0, 1.0], size=cols.shape[0]))
    df = np.bincount(cols, minlength=d).astype(np.float64)
    idf = np.log1p(n / np.maximum(df, 1.0))
    vals = vals * (idf / idf.max())[cols]
    norm = np.sqrt(np.maximum(np.bincount(rows, weights=vals ** 2,
                                          minlength=n), 1e-12))
    vals = vals / norm[rows]

    lo, hi = min(10, d - 1), max(d // 4, min(10, d - 1) + 1)
    cand = np.unique(np.geomspace(lo, hi, num=4 * informative).astype(int))
    info = rng.choice(cand, size=min(informative, cand.shape[0]),
                      replace=False)
    w_true = np.zeros(d)
    w_true[info] = rng.normal(0.0, 2.0, size=info.shape[0])
    margins = np.bincount(rows, weights=vals * w_true[cols], minlength=n)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-margins))).astype(np.float64)
    flip = rng.random(n) < label_noise
    y[flip] = 1.0 - y[flip]
    return Twin(indptr=indptr, indices=cols, data=vals, y=y, shape=(n, d))


def permute_rows(twin: Twin, seed: int) -> Twin:
    """``twin`` with its rows (and labels) in an order drawn from ``seed``:
    the same matrix, the same work for every Frank-Wolfe step, another
    order of the rows in every layout."""
    n = twin.shape[0]
    order = np.random.default_rng([seed % 2 ** 64, 3]).permutation(n)
    counts = np.diff(twin.indptr)[order]
    take = np.concatenate([np.arange(twin.indptr[i], twin.indptr[i + 1])
                           for i in order]) if n else np.zeros(0, np.int64)
    return Twin(indptr=np.concatenate([[0], np.cumsum(counts)]),
                indices=twin.indices[take], data=twin.data[take],
                y=twin.y[order], shape=twin.shape)


def run_data(dataset: dict, seed: int) -> Twin:
    """The data of one run: the configuration's base twin (made from its
    own ``seed``) with its rows in an order drawn from the run's seed."""
    base = make_twin(dataset["n"], dataset["d"], dataset["nnz_per_row"],
                     dataset["informative"], seed=dataset["seed"],
                     label_noise=dataset["label_noise"],
                     column_exponent=dataset["column_exponent"])
    return permute_rows(base, seed)
