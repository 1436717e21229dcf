"""The benchmark's data generator keeps the law of the paper's twins."""
import numpy as np
import pytest

from bench.datagen import make_twin, permute_rows


def shape_stats(twin) -> dict:
    """Shape statistics of a twin: nnz per row, column skew, label balance."""
    n, d = twin.shape
    col_nnz = np.bincount(twin.indices, minlength=d)
    row_nnz = np.diff(twin.indptr)
    sq = np.bincount(np.repeat(np.arange(n), row_nnz),
                     weights=twin.data ** 2, minlength=n)
    return {"nnz_per_row": float(row_nnz.mean()),
            "max_col_nnz": int(col_nnz.max()),
            "p90_col_nnz": float(np.percentile(col_nnz, 90)),
            "row_norm_max_err": float(np.abs(np.sqrt(sq) - 1.0).max()),
            "max_abs_value": float(np.abs(twin.data).max()),
            "positive_share": float(twin.y.mean())}


@pytest.fixture(scope="module")
def twin():
    return make_twin(2000, 4000, 40.0, 64, seed=2 ** 31 + 3)


def test_shape_statistics(twin):
    st = shape_stats(twin)
    assert twin.shape == (2000, 4000)
    assert abs(st["nnz_per_row"] - 40.0) < 1.0
    assert st["row_norm_max_err"] < 1e-12
    assert st["max_abs_value"] <= 1.0
    assert 0.3 < st["positive_share"] < 0.7
    # 1/r^1.1 popularity: column 0 sits in nearly every row, the tail is thin
    assert st["max_col_nnz"] > 0.9 * twin.shape[0]
    assert st["p90_col_nnz"] < 40


def test_rows_hold_distinct_sorted_columns(twin):
    for i in range(0, twin.shape[0], 97):
        cols = twin.indices[twin.indptr[i]:twin.indptr[i + 1]]
        assert cols.size >= 1 and np.all(np.diff(cols) > 0)


def test_seed_makes_the_data():
    a = make_twin(300, 1200, 15.0, 25, seed=7)
    b = make_twin(300, 1200, 15.0, 25, seed=7)
    c = make_twin(300, 1200, 15.0, 25, seed=8)
    assert np.array_equal(a.indices, b.indices) and np.array_equal(a.y, b.y)
    assert not np.array_equal(a.data, c.data)


def test_column_law_matches_successive_sampling():
    """First-k-distinct draws have the law of sampling without
    replacement: the share of rows holding column r follows 1/r^1.1."""
    t = make_twin(4000, 200, 5.0, 8, seed=11)
    share = np.bincount(t.indices, minlength=200) / 4000
    assert share[0] > share[1] > share[3] > share[15] > share[150]


def test_row_permutation_keeps_the_matrix():
    a = make_twin(300, 1200, 15.0, 25, seed=7)
    b = permute_rows(a, 2 ** 31 + 1)
    order = np.random.default_rng([2 ** 31 + 1, 3]).permutation(300)
    assert np.array_equal(b.y, a.y[order])
    for i in (0, 17, 299):
        src = order[i]
        got = slice(b.indptr[i], b.indptr[i + 1])
        want = slice(a.indptr[src], a.indptr[src + 1])
        assert np.array_equal(b.indices[got], a.indices[want])
        assert np.array_equal(b.data[got], a.data[want])
