"""Dataset store subsystem: streaming libsvm I/O, sharded mmap round-trips,
deterministic splits, column stats, the persisted fw_setup cache, and the
named-dataset registry (DESIGN.md §7).

The load-bearing guarantees:
  * text → store → mmap → HostCSR is **bit-for-bit** identical to the
    in-memory matrix (float64 values survive the %.17g text round trip);
  * the cached setup state replays exactly, so warm solves are the same
    state machine as cold ones (solver-level parity is pinned in
    tests/test_solvers.py).
"""
import io
import os

import numpy as np
import pytest

from repro.core.sparse.formats import HostCSR
from repro.data.sparse_io import iter_libsvm, write_libsvm
from repro.data.store import DatasetRef, DatasetStore
from repro.data.synthetic import make_sparse_classification


@pytest.fixture(scope="module")
def problem():
    X, y, _ = make_sparse_classification(n=140, d=520, nnz_per_row=9,
                                         informative=12, seed=5)
    return X, y


@pytest.fixture(scope="module")
def store(problem, tmp_path_factory):
    X, y = problem
    root = tmp_path_factory.mktemp("ds") / "store"
    # small shards + small chunks so sharding and chunk-splitting both fire
    return DatasetStore.from_arrays(str(root), X, y, rows_per_shard=33,
                                    chunk_rows=17)


# ---------------------------------------------------------------------------
# sparse_io
# ---------------------------------------------------------------------------


def test_libsvm_text_round_trip_bit_for_bit(problem):
    X, y = problem
    buf = io.StringIO()
    write_libsvm(buf, X, y)
    buf.seek(0)
    chunks = list(iter_libsvm(buf, chunk_rows=13))
    assert sum(c.n_rows for c in chunks) == X.shape[0]
    cols = np.concatenate([c.cols for c in chunks])
    vals = np.concatenate([c.vals for c in chunks])
    ys = np.concatenate([c.y for c in chunks])
    np.testing.assert_array_equal(cols, X.indices)
    np.testing.assert_array_equal(vals, X.data)  # %.17g is float64-exact
    np.testing.assert_array_equal(ys, y)


def test_libsvm_parser_tolerates_comments_qid_and_signs():
    text = ("# a comment line\n"
            "+1 qid:3 2:0.5 7:-1.25  # trailing comment\n"
            "\n"
            "-1 1:3\n")
    chunks = list(iter_libsvm(io.StringIO(text), chunk_rows=10))
    assert len(chunks) == 1
    c = chunks[0]
    np.testing.assert_array_equal(c.y, [1.0, 0.0])
    np.testing.assert_array_equal(c.cols, [1, 6, 0])   # 1-based -> 0-based
    np.testing.assert_array_equal(c.vals, [0.5, -1.25, 3.0])


def test_libsvm_zero_based_mode():
    c = next(iter_libsvm(io.StringIO("1 0:2.0 5:1.0\n"), zero_based=True))
    np.testing.assert_array_equal(c.cols, [0, 5])


# ---------------------------------------------------------------------------
# store: round trip, mmap views, manifest, stats
# ---------------------------------------------------------------------------


def test_store_round_trip_bit_for_bit(problem, store):
    X, y = problem
    Z = store.to_host_csr()
    np.testing.assert_array_equal(Z.indptr, X.indptr)
    np.testing.assert_array_equal(Z.indices, X.indices)
    np.testing.assert_array_equal(Z.data, X.data)
    np.testing.assert_array_equal(store.labels(), y)
    assert store.shape == X.shape and store.nnz == X.nnz


def test_store_full_libsvm_ingestion_path(problem, tmp_path):
    """text file → streaming parse → store → mmap equals the source matrix."""
    X, y = problem
    svm = tmp_path / "ds.svm"
    write_libsvm(str(svm), X, y)
    st = DatasetStore.write(str(tmp_path / "st"),
                            iter_libsvm(str(svm), chunk_rows=29),
                            n_cols=X.shape[1], rows_per_shard=50)
    Z = st.to_host_csr()
    np.testing.assert_array_equal(Z.data, X.data)
    np.testing.assert_array_equal(Z.indices, X.indices)
    np.testing.assert_array_equal(st.labels(), y)


def test_store_shards_are_mmap_views(store):
    assert store.n_shards > 1           # rows_per_shard=33 over 140 rows
    rows = 0
    for i in range(store.n_shards):
        sh = store.shard(i)
        assert isinstance(sh.data, np.memmap) or \
            isinstance(np.asarray(sh.data).base, np.memmap)
        assert sh.indptr[0] == 0
        rows += sh.shape[0]
        assert sh.shape[0] == store.manifest["shards"][i]["rows"]
        assert sh.nnz == store.manifest["shards"][i]["nnz"]
    assert rows == store.n


def test_store_manifest_and_content_hash(problem, store, tmp_path):
    X, y = problem
    m = store.manifest
    assert m["n"] == X.shape[0] and m["d"] == X.shape[1]
    assert m["nnz"] == X.nnz and len(m["shards"]) == store.n_shards
    # same data -> same hash, regardless of shard/chunk geometry
    st2 = DatasetStore.from_arrays(str(tmp_path / "again"), X, y,
                                   rows_per_shard=1000, chunk_rows=7)
    assert st2.content_hash == store.content_hash
    # a one-bit perturbation changes it
    Xp = HostCSR(X.indptr, X.indices, X.data.copy(), X.shape)
    Xp.data[0] += 1e-9
    st3 = DatasetStore.from_arrays(str(tmp_path / "pert"), Xp, y,
                                   rows_per_shard=1000)
    assert st3.content_hash != store.content_hash


def test_store_open_missing_and_reopen(store, tmp_path):
    with pytest.raises(FileNotFoundError):
        DatasetStore.open(str(tmp_path / "nope"))
    st = DatasetStore.open(store.root)
    assert st.content_hash == store.content_hash
    np.testing.assert_array_equal(st.labels(), store.labels())


def test_column_stats_match_direct_computation(problem, store):
    X, y = problem
    stats = store.col_stats()
    d = X.shape[1]
    np.testing.assert_array_equal(
        stats.df, np.bincount(X.indices, minlength=d))
    np.testing.assert_allclose(
        stats.norm_sq,
        np.bincount(X.indices, weights=X.data ** 2, minlength=d))
    np.testing.assert_allclose(
        stats.col_sum, np.bincount(X.indices, weights=X.data, minlength=d))
    y_rep = np.repeat(y, np.diff(X.indptr))
    np.testing.assert_allclose(
        stats.col_y_sum,
        np.bincount(X.indices, weights=X.data * y_rep, minlength=d))


# ---------------------------------------------------------------------------
# splits & row materialization
# ---------------------------------------------------------------------------


def test_split_deterministic_disjoint_and_salted(store):
    tr1, te1 = store.split(0.25, salt=0)
    tr2, te2 = store.split(0.25, salt=0)
    np.testing.assert_array_equal(tr1, tr2)
    np.testing.assert_array_equal(te1, te2)
    assert set(tr1).isdisjoint(te1)
    assert len(tr1) + len(te1) == store.n
    assert 0.05 < len(te1) / store.n < 0.5      # ≈ 0.25 at n=140
    _, te_salted = store.split(0.25, salt=1)
    assert not np.array_equal(te1, te_salted)


def test_take_matches_dense_slicing(problem, store):
    X, y = problem
    rows = np.array([0, 3, 34, 35, 100, 139])   # crosses shard boundaries
    Xs, ys = store.take(rows)
    np.testing.assert_array_equal(Xs.to_dense(), X.to_dense()[rows])
    np.testing.assert_array_equal(ys, y[rows])
    with pytest.raises(IndexError):
        store.take([store.n])


def test_take_preserves_caller_order(problem, store):
    """A shuffled (and repeating) row list comes back in that exact order."""
    X, y = problem
    rng = np.random.default_rng(3)
    rows = rng.permutation(store.n)[:25]
    rows = np.concatenate([rows, rows[:3]])     # duplicates allowed
    Xs, ys = store.take(rows)
    np.testing.assert_array_equal(Xs.to_dense(), X.to_dense()[rows])
    np.testing.assert_array_equal(ys, y[rows])


# ---------------------------------------------------------------------------
# solver setup cache & out-of-core setup
# ---------------------------------------------------------------------------


def test_setup_cache_persists_and_replays_bitwise(problem, store):
    import jax.numpy as jnp

    from repro.core.solvers.jax_sparse import fw_setup_jit
    X, y = problem
    prep = store.prepared()
    s1 = prep.setup_for(y, "logistic")
    path = store._setup_cache_path("logistic")
    assert os.path.exists(path)
    # a fresh open must hit the disk cache and replay identical bits
    st2 = DatasetStore.open(store.root)
    s2 = st2.prepared().setup_for(y, "logistic")
    for a, b in zip(s1, s2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the cache content equals a direct fw_setup on the padded pair
    ref = fw_setup_jit(prep.pcsr, jnp.asarray(y, jnp.float32),
                       loss="logistic")
    for a, b in zip(s1, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_padded_cache_replays_bitwise(store):
    """Warm opens mmap the persisted ELL lanes — identical to a cold build."""
    prep1 = store.prepared()
    assert os.path.exists(store._padded_meta_path())
    st2 = DatasetStore.open(store.root)
    prep2 = st2.prepared()
    for p1, p2 in ((prep1.pcsr, prep2.pcsr), (prep1.pcsc, prep2.pcsc)):
        np.testing.assert_array_equal(np.asarray(p1.indices),
                                      np.asarray(p2.indices))
        np.testing.assert_array_equal(np.asarray(p1.values),
                                      np.asarray(p2.values))
        np.testing.assert_array_equal(np.asarray(p1.nnz), np.asarray(p2.nnz))
        assert p1.shape == p2.shape


def test_setup_cache_ignores_foreign_labels(problem, store):
    X, y = problem
    prep = store.prepared()
    cached = prep.setup_for(y, "logistic")
    flipped = 1.0 - y
    fresh = prep.setup_for(flipped, "logistic")
    assert not np.array_equal(np.asarray(cached[2]), np.asarray(fresh[2]))


def test_setup_streamed_matches_kernel_setup(problem, store):
    import jax.numpy as jnp

    from repro.core.solvers.jax_sparse import fw_setup_jit
    X, y = problem
    v0, q0, a0 = store.setup_streamed("logistic")
    ref = fw_setup_jit(store.prepared().pcsr, jnp.asarray(y, jnp.float32),
                       loss="logistic")
    np.testing.assert_allclose(np.asarray(a0), np.asarray(ref[2]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(q0), np.asarray(ref[1]), atol=1e-6)
    assert float(np.abs(np.asarray(v0)).max()) == 0.0


# ---------------------------------------------------------------------------
# DatasetRef & registry
# ---------------------------------------------------------------------------


def test_dataset_ref_validation():
    with pytest.raises(ValueError, match="exactly one"):
        DatasetRef()
    with pytest.raises(ValueError, match="exactly one"):
        DatasetRef(name="a", path="b")
    with pytest.raises(ValueError, match="unknown split"):
        DatasetRef(name="a", split="validation")


def test_dataset_ref_split_resolution(problem, store):
    X, y = problem
    Xt, yt = DatasetRef(path=store.root, split="test", test_frac=0.3,
                        salt=2).resolve()
    _, te = store.split(0.3, salt=2)
    np.testing.assert_array_equal(Xt.to_dense(), X.to_dense()[te])
    np.testing.assert_array_equal(yt, y[te])
    src, y_all = DatasetRef(path=store.root).resolve()
    assert isinstance(src, DatasetStore)
    np.testing.assert_array_equal(y_all, y)


def test_registry_generates_then_caches(tmp_path):
    from repro.data.registry import (DatasetSpec, available_datasets, load,
                                     register_dataset)
    assert "rcv1_like" in available_datasets()
    register_dataset(DatasetSpec("tiny_test", n=60, d=120, nnz_per_row=5.0,
                                 informative=6, rows_per_shard=25))
    st1 = load("tiny_test", root=str(tmp_path))
    assert st1.n == 60 and st1.d == 120 and st1.n_shards == 3
    created = st1.manifest["created_unix"]
    st2 = load("tiny_test", root=str(tmp_path))   # cache hit: no rebuild
    assert st2.manifest["created_unix"] == created
    assert st2.content_hash == st1.content_hash
    # spec change invalidates via the fingerprint
    register_dataset(DatasetSpec("tiny_test", n=60, d=120, nnz_per_row=5.0,
                                 informative=6, rows_per_shard=25, seed=9))
    st3 = load("tiny_test", root=str(tmp_path))
    assert st3.content_hash != st1.content_hash
    with pytest.raises(ValueError, match="unknown dataset"):
        load("not_registered", root=str(tmp_path))


def test_fit_service_accepts_dataset_store(problem, store):
    """FitService(store) serves fits off the cached prepared dataset."""
    from repro.core.dp.accountant import PrivacyAccountant
    from repro.core.solvers import FWConfig, solve
    from repro.serve.fit_service import FitRequest, FitService
    X, y = problem
    cfg = FWConfig(backend="jax_sparse", lam=8.0, steps=12, queue="bsls",
                   epsilon=1.0, delta=1e-6)
    svc = FitService(store, accountants={
        "t0": PrivacyAccountant(epsilon=4.0, delta=1e-6, total_steps=200)})
    svc.submit(FitRequest(uid=0, tenant="t0", config=cfg))
    done = svc.run()
    assert done[0].status == "done"
    ref = solve(X, y, cfg)
    np.testing.assert_array_equal(np.asarray(done[0].result.coords),
                                  np.asarray(ref.coords))


def test_setup_streamed_matches_kernel_setup_label_coupled(problem, store):
    """huber is label-coupled: setup_streamed's q̄₀ = a + b·y affine path
    (exact for binary labels) must agree with the kernel fw_setup."""
    import jax.numpy as jnp

    from repro.core.solvers.jax_sparse import fw_setup_jit
    X, y = problem
    v0, q0, a0 = store.setup_streamed("huber")
    ref = fw_setup_jit(store.prepared().pcsr, jnp.asarray(y, jnp.float32),
                       loss="huber")
    np.testing.assert_allclose(np.asarray(a0), np.asarray(ref[2]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(q0), np.asarray(ref[1]), atol=1e-6)
    assert float(np.abs(np.asarray(v0)).max()) == 0.0
