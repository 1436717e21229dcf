"""The nnz-bounded column layout (``ChunkedCSC``, DESIGN.md §5): exact
round trips, chunk for chunk equal to the flat ``PaddedCSC``, the same
iterates on every ``fw_scan_chunk`` path, the layout rule, and the paths
that refuse it."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.solvers import FWConfig, solve
from repro.core.solvers.batched import grid, solve_many
from repro.core.sparse import formats
from repro.core.sparse.formats import (LANES, ChunkedCSC, PaddedCSC,
                                       choose_col_layout, coo_to_host,
                                       flat_col_bytes, host_to_chunked,
                                       host_to_padded, lane_padded)

N, D, NNZ_PER_ROW = 300, 5000, 30
# planted columns: more than one chunk, exactly one chunk, two chunks, and
# the last column (its entries end at the tail padding) one row past a chunk
HEAVY = {0: 200, 1: LANES, 2: 2 * LANES, D - 1: LANES + 1}
PRIVATE = dict(queue="two_level", epsilon=50.0, delta=1.0 / N ** 2)
GB = 10 ** 9


def _matrix(seed=0):
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(N), NNZ_PER_ROW)
    cols = rng.integers(3, D - 1, size=rows.size)
    for j, k in HEAVY.items():
        rows = np.concatenate([rows, rng.choice(N, size=k, replace=False)])
        cols = np.concatenate([cols, np.full(k, j)])
    vals = rng.uniform(0.1, 1.0, rows.size) * rng.choice([-1.0, 1.0],
                                                         rows.size)
    csr = coo_to_host(rows, cols, vals, (N, D))
    x0 = np.zeros(N)
    for j in (0, 1, 2):
        lo, hi = csr.indptr[:-1], csr.indptr[1:]
        for i in range(N):
            idx, val = csr.indices[lo[i]:hi[i]], csr.data[lo[i]:hi[i]]
            x0[i] += val[idx == j].sum() * (1 if j != 1 else -1)
    y = (x0 + 0.3 * rng.normal(size=N) > 0).astype(np.float64)
    return csr, y


@pytest.fixture(scope="module")
def problem():
    return _matrix()


@pytest.fixture(scope="module")
def pairs(problem):
    csr, _ = problem
    pcsr, flat = host_to_padded(csr)
    assert isinstance(flat, PaddedCSC)          # the CPU keeps the flat one
    return pcsr, flat, host_to_chunked(csr)


def _bytes(a):
    return np.asarray(a).tobytes()


# ---------------------------------------------------------------- the layout
@pytest.mark.parametrize("seed", [0, 1])
def test_chunked_round_trips_host_csr(seed):
    csr, _ = _matrix(seed)
    ch = host_to_chunked(csr)
    indptr, idx = np.asarray(ch.indptr), np.asarray(ch.indices)
    val, nnz = np.asarray(ch.values), np.asarray(ch.nnz)
    ref = csr.tocsc()
    assert idx.shape == val.shape == (csr.nnz + LANES,)
    np.testing.assert_array_equal(indptr, ref.indptr)
    np.testing.assert_array_equal(nnz, np.diff(ref.indptr))
    np.testing.assert_array_equal(idx[:csr.nnz], ref.indices)
    np.testing.assert_array_equal(val[:csr.nnz],
                                  ref.data.astype(np.float32))
    assert not idx[csr.nnz:].any() and not val[csr.nnz:].any()
    assert (nnz == 0).sum() > 0                 # empty columns are kept
    assert nnz[D - 1] == HEAVY[D - 1] and indptr[-1] == csr.nnz


def test_every_chunk_equals_the_flat_tables(pairs):
    """Chunk c of every column, the last against the tail padding, equals
    chunk c of the flat table element for element (masked slots 0, 0)."""
    _, flat, ch = pairs
    k = int(flat.indices.shape[1])
    cols = jnp.arange(D)
    for c in range(k // LANES):
        got = jax.vmap(lambda j: ch.chunk(j, c))(cols)
        want = tuple(a[:, c * LANES:(c + 1) * LANES]
                     for a in (flat.indices, flat.values))
        want_mask = (c * LANES + jnp.arange(LANES))[None, :] < flat.nnz[:, None]
        for g, w in zip(got, want + (want_mask,)):
            assert _bytes(g) == _bytes(w), c


def test_chunk_rows_are_bounded_by_the_tail():
    csr, _ = _matrix()
    with pytest.raises(ValueError, match="at most"):
        host_to_chunked(csr).chunk(0, 0, rows=2 * LANES)


# ------------------------------------------------------ same iterates, exactly
@pytest.mark.parametrize("private", [False, True], ids=["nonprivate", "dp"])
@pytest.mark.parametrize("stop", ["fixed", "gap_tol"])
def test_solve_is_bitwise_equal_on_both_layouts(problem, pairs, private,
                                                stop):
    _, y = problem
    pcsr, flat, ch = pairs
    kw = dict(PRIVATE) if private else {}
    if stop == "gap_tol":
        kw["gap_tol"] = 1e-4
    cfg = FWConfig(backend="jax_sparse", lam=8.0, steps=60, seed=3, **kw)
    a, b = solve((pcsr, flat), y, cfg), solve((pcsr, ch), y, cfg)
    assert _bytes(a.coords) == _bytes(b.coords)
    assert _bytes(a.w) == _bytes(b.w) and _bytes(a.gaps) == _bytes(b.gaps)
    coords = np.asarray(a.coords)
    nnz = np.asarray(ch.nnz)[coords[coords >= 0]]
    assert (nnz > LANES).any()                  # multi-chunk steps ran


def test_solve_many_matches_per_config_solves(problem, pairs):
    _, y = problem
    pcsr, _, ch = pairs
    cfgs = grid(FWConfig(backend="jax_sparse", lam=8.0, steps=40, **PRIVATE),
                lam=(4.0, 8.0), seed=(1, 2))
    batched = solve_many((pcsr, ch), y, cfgs, plan="vmap")
    for cfg, got in zip(cfgs, batched):
        one = solve((pcsr, ch), y, cfg)
        assert _bytes(got.coords) == _bytes(one.coords)
        assert _bytes(got.w) == _bytes(one.w)


def test_nonprivate_follows_the_float64_host_reference(problem, pairs):
    csr, y = problem
    pcsr, _, ch = pairs
    cfg = dict(lam=8.0, steps=60)
    host = solve(csr, y, FWConfig(backend="host_sparse", **cfg))
    dev = solve((pcsr, ch), y, FWConfig(backend="jax_sparse", **cfg))
    np.testing.assert_array_equal(np.asarray(dev.coords),
                                  np.asarray(host.coords))
    np.testing.assert_allclose(np.asarray(dev.w), np.asarray(host.w),
                               atol=1e-4)


def test_private_run_passes_the_benchmark_replay(problem, pairs):
    """The benchmark's float64 reference replays a private fit on the
    nnz-bounded layout within the ``rcv1-dp.solve`` limits."""
    from bench import correct, harness, reference
    csr, y = problem
    pcsr, _, ch = pairs
    limits = json.loads((harness.BENCH_DIR / "limits"
                         / "rcv1-dp.solve.json").read_text())
    config = json.loads((harness.BENCH_DIR / "configs"
                         / "rcv1-dp.json").read_text())
    config["dataset"].update(n=N, d=D)
    config["steps"] = 80
    res = solve((pcsr, ch), y, FWConfig(
        backend="jax_sparse", lam=config["lam"], steps=config["steps"],
        queue="two_level", epsilon=config["epsilon"], delta=1.0 / N ** 2,
        seed=11))
    prob = reference.Problem.from_arrays(csr.indptr, csr.indices, csr.data,
                                         y, csr.shape)
    ok, shown = correct.check(prob, config,
                              [(correct.host_fit(res), config["lam"], 11)],
                              limits, {})
    assert ok, shown


# ---------------------------------------------------------------- the rule
RCV1 = (20_242, 47_236)        # N, D; its widest column holds every row
NEWS20 = (19_996, 1_355_191)


@pytest.mark.parametrize("shape, device_bytes, want", [
    (RCV1, 16 * GB, "flat"),
    (NEWS20, 16 * GB, "chunked"),
    (NEWS20, None, "flat"),                     # no memory stats: the CPU
    (RCV1, 7 * GB, "chunked"),
])
def test_layout_rule_from_column_counts(shape, device_bytes, want):
    n, d = shape
    col_nnz = np.ones(d, np.int64)
    col_nnz[0] = n
    row_bytes = n * 548 * 8
    assert choose_col_layout(col_nnz, row_bytes, device_bytes) == want


def test_flat_table_bytes_at_the_published_shapes():
    for (n, d), gb in ((RCV1, 7.69), (NEWS20, 217.9)):
        col_nnz = np.zeros(d, np.int64)
        col_nnz[0] = n
        assert flat_col_bytes(col_nnz) == d * lane_padded(n) * 8
        assert round(flat_col_bytes(col_nnz) / GB, 2 if gb < 10 else 1) == gb


def test_host_to_padded_builds_the_chunked_layout_when_flat_does_not_fit(
        problem, monkeypatch):
    csr, _ = problem
    flat = flat_col_bytes(np.bincount(csr.indices, minlength=D))
    monkeypatch.setattr(formats, "_device_bytes", lambda: flat)
    with obs.session() as tel:
        pcsr, pcsc = host_to_padded(csr)
    assert isinstance(pcsc, ChunkedCSC)
    (build,) = [e for e in tel.events if e.get("name") == "layout.build"]
    assert build["attrs"] == {"col_layout": "chunked", "nnz": csr.nnz,
                              "col_bytes": (D + 1 + D) * 4
                              + (csr.nnz + LANES) * 8}
    from repro.obs.report import render
    assert "layout.build" in render(tel.events)


# ---------------------------------------------------- counts, stats, store
def test_solve_scan_counts_column_entries(problem, pairs):
    _, y = problem
    pcsr, flat, ch = pairs
    cfg = FWConfig(backend="jax_sparse", lam=8.0, steps=30)
    for pcsc, kind in ((flat, "flat"), (ch, "chunked")):
        with obs.session() as tel:
            res = solve((pcsr, pcsc), y, cfg)
        (scan,) = [e for e in tel.events if e.get("name") == "solve.scan"]
        nnz = np.asarray(ch.nnz)[np.asarray(res.coords)]
        assert scan["attrs"]["col_layout"] == kind
        assert scan["attrs"]["col_nnz"] == nnz.sum()
        assert scan["attrs"]["chunks"] == (-(-nnz // LANES)).sum()


def test_as_host_csr_takes_the_chunked_pair(problem, pairs):
    from repro.core.solvers.registry import as_host_csr, as_padded
    csr, _ = problem
    pcsr, _, ch = pairs
    assert as_padded((pcsr, ch)) == (pcsr, ch)
    got = as_host_csr((pcsr, ch))
    for field in ("indptr", "indices"):
        np.testing.assert_array_equal(getattr(got, field),
                                      getattr(csr, field))
    np.testing.assert_array_equal(got.data,
                                  csr.data.astype(np.float32))


def test_data_stats_takes_the_chunked_pair(pairs):
    from repro.core.solvers.planner import data_stats
    pcsr, flat, ch = pairs
    a, b = data_stats((pcsr, flat)), data_stats((pcsr, ch))
    assert b.kc == 2 * LANES and a.kc == lane_padded(2 * LANES)
    assert (a.n, a.d, a.nnz, a.kr) == (b.n, b.d, b.nnz, b.kr)


@pytest.mark.parametrize("kind", ["flat", "chunked", "legacy"])
def test_store_padded_cache_round_trips_the_layout(tmp_path, problem,
                                                   monkeypatch, kind):
    from repro.data.sparse_io import chunks_from_arrays
    from repro.data.store import DatasetStore
    csr, y = problem
    if kind == "chunked":
        monkeypatch.setattr(formats, "_device_bytes", lambda: 1)
    store = DatasetStore.write(str(tmp_path / "ds"),
                               chunks_from_arrays(csr, y), n_cols=D)
    built = store.prepared().pair
    meta_path = store._padded_meta_path()
    if kind == "legacy":                        # written before the kind
        meta = json.loads(open(meta_path).read())
        assert meta.pop("col_layout") == "flat"
        with open(meta_path, "w") as f:
            json.dump(meta, f)
    loaded = DatasetStore.open(str(tmp_path / "ds"))._padded_load()
    assert loaded is not None
    want = ChunkedCSC if kind == "chunked" else PaddedCSC
    assert isinstance(built[1], want) and isinstance(loaded[1], want)
    for a, b in zip(jax.tree_util.tree_leaves(built),
                    jax.tree_util.tree_leaves(loaded)):
        assert _bytes(a) == _bytes(b)


# ------------------------------------------------------------ the refusals
def _refusals():
    def jax_dense(pair, y):
        solve(pair, y, FWConfig(backend="jax_dense", lam=8.0, steps=5))

    def screening(pair, y):
        solve(pair, y, FWConfig(backend="jax_sparse", lam=8.0, steps=8,
                                screen_every=1, chunk_steps=4, **PRIVATE))

    def repack(pair, y):
        from repro.core.solvers.screening import repack_pair
        repack_pair(*pair, np.ones(D, bool))

    def tiers(pair, y):
        formats.tiered_from_padded(pair[1], LANES)

    def autotune(pair, y):
        from repro.core.solvers.autotune import tune_jax_sparse
        tune_jax_sparse(*pair, y, steps=4)

    def jax_shard(pair, y):
        solve(pair, y, FWConfig(backend="jax_shard", lam=8.0, steps=5))

    return dict(jax_dense=jax_dense, screening=screening, repack=repack,
                tiers=tiers, autotune=autotune, jax_shard=jax_shard)


@pytest.mark.parametrize("path", sorted(_refusals()))
def test_flat_table_paths_refuse_the_chunked_layout(problem, pairs, path):
    _, y = problem
    pcsr, _, ch = pairs
    with pytest.raises(TypeError, match="ChunkedCSC"):
        _refusals()[path]((pcsr, ch), y)
