"""Unified solver engine: registry behavior + cross-backend parity.

Parity logic (DESIGN.md §4): on a *dense* design matrix every iteration
touches every row, so Algorithm 2's lazy q̄ refresh never goes stale and all
four backends must take identical steps — dense (Alg 1), jax_dense,
host_sparse and jax_sparse agree on coords exactly and on weights/gaps to
float tolerance.  On a genuinely sparse problem Alg 1 may diverge from Alg 2
at near-ties (lazy refresh, paper Fig 1), but the three Alg-2 backends are
the *same* state machine and must still agree with each other.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.solvers import (FWConfig, available_backends, get_backend,
                                grid, resolve_queue, solve, solve_many)

ALL_BACKENDS = ("dense", "jax_dense", "host_sparse", "jax_sparse")
ALG2_BACKENDS = ("jax_dense", "host_sparse", "jax_sparse")


@pytest.fixture(scope="module")
def dense_problem():
    rng = np.random.default_rng(3)
    n, d = 80, 48
    X = rng.normal(size=(n, d)) / np.sqrt(d)
    w_star = np.zeros(d)
    w_star[rng.choice(d, 8, replace=False)] = rng.normal(0, 2, 8)
    y = (X @ w_star + 0.1 * rng.normal(size=n) > 0).astype(np.float64)
    return X, y


@pytest.fixture(scope="module")
def dense_runs(dense_problem):
    X, y = dense_problem
    cfg = FWConfig(lam=6.0, steps=80)
    return {b: solve(X, y, dataclasses.replace(cfg, backend=b))
            for b in ALL_BACKENDS}


def test_registry_lists_all_builtins():
    assert set(ALL_BACKENDS) <= set(available_backends())


def test_registry_rejects_unknown_backend(dense_problem):
    X, y = dense_problem
    with pytest.raises(ValueError, match="unknown solver backend"):
        solve(X, y, FWConfig(backend="quantum_annealer", steps=2))
    with pytest.raises(ValueError):
        get_backend("nope")


def test_registry_rejects_unknown_queue(dense_problem):
    X, y = dense_problem
    with pytest.raises(ValueError, match="does not support queue"):
        solve(X, y, FWConfig(backend="jax_sparse", queue="bogus", steps=2))


def test_queue_alias_translation():
    # one config, retargeted across backends, resolves to the native names
    cfg = FWConfig(queue="bsls")
    assert resolve_queue(get_backend("host_sparse"), cfg).queue == "bsls"
    assert resolve_queue(get_backend("jax_sparse"), cfg).queue == "two_level"
    cfg = FWConfig(queue="fib_heap")
    assert resolve_queue(get_backend("jax_dense"), cfg).queue == "group_argmax"
    assert resolve_queue(get_backend("dense"), cfg).queue == "argmax"


def test_all_backends_parity_on_dense_problem(dense_runs):
    """Acceptance: non-private weights and gaps agree within 1e-4 (4 ways)."""
    ref = dense_runs["dense"]
    for b in ALL_BACKENDS:
        r = dense_runs[b]
        np.testing.assert_array_equal(
            np.asarray(r.coords), np.asarray(ref.coords),
            err_msg=f"{b}: coordinate sequence diverged from dense")
        np.testing.assert_allclose(np.asarray(r.w), np.asarray(ref.w),
                                   atol=1e-4, err_msg=f"{b}: weights")
        np.testing.assert_allclose(np.asarray(r.gaps), np.asarray(ref.gaps),
                                   atol=1e-4, err_msg=f"{b}: gaps")


def test_all_backends_shrink_gap(dense_runs):
    for b, r in dense_runs.items():
        gaps = np.asarray(r.gaps)
        assert gaps[-1] < gaps[0] / 20.0, b


def test_alg2_backends_identical_on_sparse_problem(tiny_problem):
    """The three Alg-2 engines are one state machine: same steps on real
    sparse data, where Alg 1 may legitimately diverge (lazy q̄ refresh)."""
    X, y, _ = tiny_problem
    cfg = FWConfig(lam=8.0, steps=60)
    runs = {b: solve(X, y, dataclasses.replace(cfg, backend=b))
            for b in ALG2_BACKENDS}
    ref = runs["host_sparse"]
    for b in ALG2_BACKENDS:
        r = runs[b]
        np.testing.assert_array_equal(np.asarray(r.coords),
                                      np.asarray(ref.coords), err_msg=b)
        np.testing.assert_allclose(np.asarray(r.w), np.asarray(ref.w),
                                   atol=1e-4, err_msg=b)
        np.testing.assert_allclose(np.asarray(r.gaps), np.asarray(ref.gaps),
                                   atol=1e-4, err_msg=b)
    # Alg 1 still collapses the gap toward the same optimum (paper Fig 1)
    dense = solve(X, y, dataclasses.replace(cfg, backend="dense"))
    assert float(dense.gaps[-1]) < float(dense.gaps[0]) / 4.0
    assert float(ref.gaps[-1]) < float(ref.gaps[0]) / 4.0


def test_private_queues_run_everywhere(tiny_problem):
    """queue='bsls' retargets to each backend's DP exponential mechanism."""
    X, y, _ = tiny_problem
    for b in ALL_BACKENDS:
        r = solve(X, y, FWConfig(backend=b, lam=8.0, steps=20, queue="bsls",
                                 epsilon=1.0, delta=1e-6))
        w = np.asarray(r.w)
        assert np.isfinite(w).all(), b
        assert int((w != 0).sum()) <= 21, b


def test_solve_accepts_padded_pair(tiny_problem):
    from repro.core.sparse.formats import host_to_padded
    X, y, _ = tiny_problem
    pair = host_to_padded(X)
    direct = solve(X, y, FWConfig(backend="jax_sparse", lam=8.0, steps=25))
    padded = solve(pair, y, FWConfig(backend="jax_sparse", lam=8.0, steps=25))
    np.testing.assert_array_equal(np.asarray(direct.coords),
                                  np.asarray(padded.coords))


def test_solve_kwarg_overrides(dense_problem):
    X, y = dense_problem
    r = solve(X, y, backend="host_sparse", lam=6.0, steps=10)
    assert np.asarray(r.gaps).shape == (10,)


# ---------------------------------------------------------------------------
# QUEUE_ALIASES regression pin — a registry edit cannot silently retarget a
# queue.  Every (backend × accepted alias) pair is written out literally; if
# the table changes, this test must change with it, on purpose.
# ---------------------------------------------------------------------------

EXPECTED_QUEUE_RESOLUTION = {
    "dense": {
        "argmax": "argmax", "fib_heap": "argmax", "group_argmax": "argmax",
        "noisy_max": "noisy_max",
        "gumbel": "gumbel", "bsls": "gumbel", "two_level": "gumbel",
    },
    "host_sparse": {
        "fib_heap": "fib_heap", "argmax": "argmax", "noisy_max": "noisy_max",
        "bsls": "bsls", "group_argmax": "fib_heap", "two_level": "bsls",
        "gumbel": "bsls",
    },
    "jax_dense": {
        "two_level": "two_level", "group_argmax": "group_argmax",
        "bsls": "two_level", "gumbel": "two_level",
        "fib_heap": "group_argmax", "argmax": "group_argmax",
    },
    "jax_sparse": {
        "two_level": "two_level", "group_argmax": "group_argmax",
        "bsls": "two_level", "gumbel": "two_level",
        "fib_heap": "group_argmax", "argmax": "group_argmax",
    },
}

EXPECTED_DEFAULT_QUEUE = {"dense": None, "host_sparse": "fib_heap",
                          "jax_dense": "group_argmax",
                          "jax_sparse": "group_argmax"}


@pytest.mark.parametrize("backend_name", sorted(EXPECTED_QUEUE_RESOLUTION))
def test_queue_alias_table_pinned(backend_name):
    backend = get_backend(backend_name)
    expected = EXPECTED_QUEUE_RESOLUTION[backend_name]
    # the accepted alias *set* is pinned too: a new/removed alias must show
    # up here, not slip through resolution silently
    assert set(backend.queues) == set(expected), backend_name
    for alias, native in expected.items():
        got = resolve_queue(backend, FWConfig(queue=alias)).queue
        assert got == native, f"{backend_name}: {alias} -> {got} != {native}"
    assert resolve_queue(backend, FWConfig(queue=None)).queue == \
        EXPECTED_DEFAULT_QUEUE[backend_name]


# ---------------------------------------------------------------------------
# batched sweeps: solve_many / grid
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sweep_problem():
    from repro.data.synthetic import make_sparse_classification
    X, y, _ = make_sparse_classification(
        n=150, d=600, nnz_per_row=10, informative=15, seed=11)
    return X, y


def _assert_same_result(b, s, msg):
    np.testing.assert_array_equal(np.asarray(b.coords), np.asarray(s.coords),
                                  err_msg=f"{msg}: coords")
    np.testing.assert_allclose(np.asarray(b.w), np.asarray(s.w), atol=1e-4,
                               err_msg=f"{msg}: w")
    np.testing.assert_allclose(np.asarray(b.gaps), np.asarray(s.gaps),
                               atol=1e-4, err_msg=f"{msg}: gaps")


def test_grid_cartesian_product():
    cfgs = grid(FWConfig(backend="jax_sparse", steps=10),
                lam=(1.0, 2.0, 3.0), epsilon=(0.1, 1.0), seed=7)
    assert len(cfgs) == 6
    assert [c.lam for c in cfgs] == [1.0, 1.0, 2.0, 2.0, 3.0, 3.0]
    assert [c.epsilon for c in cfgs] == [0.1, 1.0] * 3
    assert all(c.seed == 7 and c.steps == 10 for c in cfgs)
    with pytest.raises(ValueError, match="unknown FWConfig field"):
        grid(lambda_=(1.0,))
    assert len(grid(lam=5.0)) == 1  # scalars only -> a single config


def test_solve_many_private_sweep_matches_sequential(sweep_problem):
    """Acceptance: a vmapped ≥8-config λ/ε jax_sparse sweep takes the same
    steps as per-config sequential solve() on the same keys (1e-4)."""
    X, y = sweep_problem
    configs = grid(FWConfig(backend="jax_sparse", steps=30, queue="bsls",
                            delta=1e-6),
                   lam=(4.0, 8.0, 16.0, 32.0), epsilon=(0.5, 2.0))
    assert len(configs) == 8
    batched = solve_many(X, y, configs)
    for i, cfg in enumerate(configs):
        _assert_same_result(batched[i], solve(X, y, cfg), f"config {i} ({cfg.lam}, {cfg.epsilon})")


def test_solve_many_nonprivate_sweep_matches_sequential(sweep_problem):
    X, y = sweep_problem
    configs = grid(FWConfig(backend="jax_sparse", steps=30),
                   lam=(4.0, 8.0, 12.0))
    batched = solve_many(X, y, configs)
    for i, cfg in enumerate(configs):
        _assert_same_result(batched[i], solve(X, y, cfg), f"lam={cfg.lam}")


def test_solve_many_varied_seeds_use_distinct_keys(sweep_problem):
    """Each config's PRNG stream is its own — identical configs with
    different seeds must (generically) select different DP coordinates."""
    X, y = sweep_problem
    configs = grid(FWConfig(backend="jax_sparse", steps=25, queue="bsls",
                            lam=8.0, epsilon=1.0), seed=(0, 1, 2, 3))
    batched = solve_many(X, y, configs)
    for i, cfg in enumerate(configs):
        _assert_same_result(batched[i], solve(X, y, cfg), f"seed={cfg.seed}")
    coord_seqs = {tuple(np.asarray(r.coords)) for r in batched}
    assert len(coord_seqs) > 1


def test_solve_many_mixed_backends_preserve_order(sweep_problem):
    """Non-batchable backends drain through the sequential fallback; results
    come back in submission order regardless of grouping."""
    X, y = sweep_problem
    configs = [FWConfig(backend="host_sparse", lam=8.0, steps=12),
               FWConfig(backend="jax_sparse", lam=8.0, steps=12),
               FWConfig(backend="jax_sparse", lam=4.0, steps=12),
               FWConfig(backend="jax_dense", lam=8.0, steps=12)]
    results = solve_many(X, y, configs)
    assert len(results) == 4
    for cfg, res in zip(configs, results):
        _assert_same_result(res, solve(X, y, cfg), cfg.backend)
    # host_sparse/jax_sparse/jax_dense agree on this state machine anyway:
    _assert_same_result(results[0], results[1], "alg2 cross-check")


def test_solve_many_empty_and_singleton(sweep_problem):
    X, y = sweep_problem
    assert solve_many(X, y, []) == []
    one = solve_many(X, y, [FWConfig(backend="jax_sparse", lam=8.0, steps=10)])
    assert len(one) == 1
    _assert_same_result(
        one[0], solve(X, y, FWConfig(backend="jax_sparse", lam=8.0, steps=10)),
        "singleton")


# ---------------------------------------------------------------------------
# dataset-ref solving (DESIGN.md §7): solve(DatasetRef/DatasetStore) must be
# the *same state machine* as solve(X_in_memory) — the store hands back
# bit-identical arrays (mmap round trip) and replays the cached fw_setup
# state the in-memory path would have computed.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stored_problem(sweep_problem, tmp_path_factory):
    from repro.data.store import DatasetStore
    X, y = sweep_problem
    root = tmp_path_factory.mktemp("solver_store") / "ds"
    store = DatasetStore.from_arrays(str(root), X, y, rows_per_shard=64)
    return store, X, y


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_solve_from_store_identical_iterates(stored_problem, backend):
    """Acceptance: identical coords and weights vs in-memory, per backend."""
    store, X, y = stored_problem
    cfg = FWConfig(backend=backend, lam=8.0, steps=25)
    from_store = solve(store, config=cfg)
    in_memory = solve(X, y, cfg)
    np.testing.assert_array_equal(np.asarray(from_store.coords),
                                  np.asarray(in_memory.coords))
    np.testing.assert_array_equal(np.asarray(from_store.w),
                                  np.asarray(in_memory.w))
    np.testing.assert_array_equal(np.asarray(from_store.gaps),
                                  np.asarray(in_memory.gaps))


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_solve_from_store_private_identical(stored_problem, backend):
    """DP queues too: same PRNG keys + same data ⇒ same draws."""
    store, X, y = stored_problem
    cfg = FWConfig(backend=backend, lam=8.0, steps=20, queue="bsls",
                   epsilon=1.0, delta=1e-6)
    from_store = solve(store, config=cfg)
    in_memory = solve(X, y, cfg)
    np.testing.assert_array_equal(np.asarray(from_store.coords),
                                  np.asarray(in_memory.coords))
    np.testing.assert_array_equal(np.asarray(from_store.w),
                                  np.asarray(in_memory.w))


def test_solve_from_store_warm_cache_identical(stored_problem):
    """A fresh open replays the persisted fw_setup state bit-for-bit."""
    from repro.data.store import DatasetStore
    store, X, y = stored_problem
    cfg = FWConfig(backend="jax_sparse", lam=8.0, steps=25)
    solve(store, config=cfg)                      # populates cache/
    warm = DatasetStore.open(store.root)
    r_warm = solve(warm, config=cfg)
    r_mem = solve(X, y, cfg)
    np.testing.assert_array_equal(np.asarray(r_warm.coords),
                                  np.asarray(r_mem.coords))
    np.testing.assert_array_equal(np.asarray(r_warm.w), np.asarray(r_mem.w))


def test_solve_dataset_ref_split_matches_subset(stored_problem):
    from repro.data.store import DatasetRef
    store, X, y = stored_problem
    ref = DatasetRef(path=store.root, split="train")
    cfg = FWConfig(backend="host_sparse", lam=8.0, steps=15)
    train_rows, _ = store.split(ref.test_frac, ref.salt)
    X_sub, y_sub = store.take(train_rows)
    _assert_same_result(solve(ref, config=cfg), solve(X_sub, y_sub, cfg),
                        "train split ref")


def test_solve_many_from_store_matches_sequential(stored_problem):
    store, X, y = stored_problem
    configs = grid(FWConfig(backend="jax_sparse", steps=20, queue="bsls",
                            delta=1e-6),
                   lam=(4.0, 8.0), epsilon=(0.5, 2.0))
    batched = solve_many(store, configs=configs)
    for i, cfg in enumerate(configs):
        _assert_same_result(batched[i], solve(X, y, cfg), f"store cfg {i}")


def test_solve_requires_labels_for_plain_matrices(sweep_problem):
    X, _ = sweep_problem
    with pytest.raises(TypeError, match="y is required"):
        solve(X, config=FWConfig(backend="host_sparse", steps=2))


# ---------------------------------------------------------------------------
# pluggable objectives (DESIGN.md §10): every registered loss must run on
# every backend with exact cross-backend step parity, match the straight-line
# reference oracle on both selection paths, and keep the fused batched sweep.
# ---------------------------------------------------------------------------

import jax.numpy as jnp  # noqa: E402

from repro.core.losses import OBJECTIVES, Objective  # noqa: E402

REGISTERED_LOSSES = sorted(OBJECTIVES)
FIVE_BACKENDS = ALL_BACKENDS + ("jax_shard",)


def _cfg_for(backend: str, **kw) -> FWConfig:
    if backend == "jax_shard":
        kw.setdefault("mesh", (1, 1))
    return FWConfig(backend=backend, **kw)


@pytest.mark.parametrize("loss", REGISTERED_LOSSES)
def test_all_backends_parity_per_loss(dense_problem, loss):
    """Acceptance: identical non-private steps 5 ways, for every objective
    (on a dense design, where Alg 1's lazy refresh never goes stale)."""
    X, y = dense_problem
    runs = {b: solve(X, y, _cfg_for(b, lam=6.0, steps=50, loss=loss))
            for b in FIVE_BACKENDS}
    ref = runs["dense"]
    for b, r in runs.items():
        np.testing.assert_array_equal(
            np.asarray(r.coords), np.asarray(ref.coords),
            err_msg=f"{loss}/{b}: coords diverged from dense")
        np.testing.assert_allclose(np.asarray(r.w), np.asarray(ref.w),
                                   atol=1e-4, err_msg=f"{loss}/{b}: weights")
        assert np.asarray(r.gaps)[-1] < np.asarray(r.gaps)[0], f"{loss}/{b}"


@pytest.mark.parametrize("loss", REGISTERED_LOSSES)
def test_private_parity_per_loss(dense_problem, loss):
    """DP path per loss: the two jit engines consume the same key stream and
    must take bit-identical steps; the host EM realization draws different
    bits of the same law (documented), so it is checked for validity only."""
    X, y = dense_problem
    kw = dict(lam=6.0, steps=25, loss=loss, queue="bsls", epsilon=1.0,
              delta=1e-6)
    a = solve(X, y, _cfg_for("jax_dense", **kw))
    b = solve(X, y, _cfg_for("jax_sparse", **kw))
    np.testing.assert_array_equal(np.asarray(a.coords), np.asarray(b.coords),
                                  err_msg=f"{loss}: private jax engines")
    host = solve(X, y, _cfg_for("host_sparse", **kw))
    assert np.isfinite(np.asarray(host.w)).all(), loss


@pytest.mark.parametrize("loss", REGISTERED_LOSSES)
@pytest.mark.parametrize("private", [False, True])
def test_jax_sparse_matches_reference_oracle(sweep_problem, loss, private):
    """Acceptance: the kernel pipeline replays the straight-line host oracle
    bit-for-bit on coords — per loss, private and non-private, on genuinely
    sparse data."""
    from repro.core.solvers.jax_sparse import em_scale_for
    from repro.core.solvers.reference import reference_fw
    from repro.core.sparse.formats import host_to_padded
    X, y = sweep_problem
    cfg = FWConfig(backend="jax_sparse", lam=8.0, steps=30, loss=loss,
                   queue="bsls" if private else None, epsilon=1.0,
                   delta=1e-6)
    r = solve(X, y, cfg)
    pcsr, pcsc = host_to_padded(X)
    resolved = resolve_queue(get_backend("jax_sparse"), cfg)
    w, gaps, coords = reference_fw(
        pcsr, pcsc, y, lam=cfg.lam, steps=cfg.steps, private=private,
        em_scale=em_scale_for(resolved, X.shape[0]), seed=cfg.seed, loss=loss)
    np.testing.assert_array_equal(np.asarray(r.coords), np.asarray(coords),
                                  err_msg=f"{loss} private={private}")
    np.testing.assert_allclose(np.asarray(r.w), np.asarray(w), atol=1e-4)
    np.testing.assert_allclose(np.asarray(r.gaps), np.asarray(gaps),
                               atol=1e-4)


@pytest.mark.parametrize("loss", REGISTERED_LOSSES)
def test_early_stop_prefix_identical_per_loss(sweep_problem, loss):
    """gap_tol runs are bit-identical prefixes of the fixed-T program, for
    every (smooth) objective."""
    X, y = sweep_problem
    full = solve(X, y, FWConfig(backend="jax_sparse", lam=8.0, steps=40,
                                loss=loss))
    tol = float(np.asarray(full.gaps)[len(np.asarray(full.gaps)) // 2])
    stopped = solve(X, y, FWConfig(backend="jax_sparse", lam=8.0, steps=40,
                                   loss=loss, gap_tol=tol))
    stop = stopped.stop_step_or()
    assert 0 < stop < 40, loss
    np.testing.assert_array_equal(
        np.asarray(stopped.coords)[:stop], np.asarray(full.coords)[:stop],
        err_msg=f"{loss}: early-stop prefix")
    assert np.all(np.asarray(stopped.coords)[stop:] == -1), loss


def test_solve_many_nonlogistic_grid_runs_fused(sweep_problem, monkeypatch):
    """Regression (ISSUE 6 satellite): a loss="squared" 8-config grid runs as
    ONE fused vmapped compiled scan — the old engine silently dropped every
    non-logistic group to the slow path (`fused = loss == "logistic"`) —
    with exact parity to per-config sequential solve()."""
    from repro.core.solvers import batched
    X, y = sweep_problem
    calls = []
    real = batched._sweep_scan_jit

    def counting(*args, **kwargs):
        calls.append(kwargs)
        return real(*args, **kwargs)

    monkeypatch.setattr(batched, "_sweep_scan_jit", counting)
    configs = grid(FWConfig(backend="jax_sparse", steps=20, loss="squared",
                            queue="bsls", delta=1e-6),
                   lam=(4.0, 8.0, 16.0, 32.0), epsilon=(0.5, 2.0))
    assert len(configs) == 8
    results = solve_many(X, y, configs, plan="vmap")
    assert len(calls) == 1, "grid must run as one compiled vmapped scan"
    assert calls[0]["loss"] == "squared"
    for i, cfg in enumerate(configs):
        _assert_same_result(results[i], solve(X, y, cfg),
                            f"squared grid cfg {i}")


@pytest.mark.parametrize("loss", REGISTERED_LOSSES)
def test_solve_many_per_loss_matches_sequential(sweep_problem, loss):
    """The batched sweep takes the same steps as sequential solve() for
    every registered objective (private grid, mixed λ/ε)."""
    X, y = sweep_problem
    configs = grid(FWConfig(backend="jax_sparse", steps=15, loss=loss,
                            queue="bsls", delta=1e-6),
                   lam=(4.0, 16.0), epsilon=(0.5, 2.0))
    batched_rs = solve_many(X, y, configs)
    for i, cfg in enumerate(configs):
        _assert_same_result(batched_rs[i], solve(X, y, cfg),
                            f"{loss} cfg {i}")


def test_solve_from_store_warm_cache_huber(stored_problem):
    """DatasetRef/warm-cache replay for a label-coupled loss: a fresh open
    replays the per-loss persisted fw_setup state and labels bit-for-bit."""
    from repro.data.store import DatasetStore
    store, X, y = stored_problem
    for cfg in (FWConfig(backend="jax_sparse", lam=8.0, steps=20,
                         loss="huber"),
                FWConfig(backend="jax_sparse", lam=8.0, steps=20,
                         loss="huber", queue="bsls", epsilon=1.0,
                         delta=1e-6)):
        solve(store, config=cfg)                  # populates cache/
        warm = DatasetStore.open(store.root)
        r_warm = solve(warm, config=cfg)
        r_mem = solve(X, y, cfg)
        np.testing.assert_array_equal(np.asarray(r_warm.coords),
                                      np.asarray(r_mem.coords))
        np.testing.assert_array_equal(np.asarray(r_warm.w),
                                      np.asarray(r_mem.w))


# ---------------------------------------------------------------------------
# gap-certificate validity gate: a non-smooth objective has no FW duality-gap
# bound, so gap_tol early stopping must be refused up front.
# ---------------------------------------------------------------------------


def _nonsmooth_probe():
    return Objective(
        name="_abs_probe", value=lambda m, y: jnp.abs(m - y),
        grad=lambda m, y: jnp.sign(m - y), split_grad=None,
        grad_np=lambda m, y: np.sign(m - y), lipschitz=1.0,
        smooth=False, curvature_note="|r| has no curvature bound at 0")


def test_gap_tol_refused_for_nonsmooth_objective(sweep_problem):
    from repro.core.losses import register_objective
    X, y = sweep_problem
    register_objective(_nonsmooth_probe())
    try:
        with pytest.raises(ValueError, match="not smooth"):
            solve(X, y, FWConfig(backend="jax_sparse", steps=5,
                                 loss="_abs_probe", gap_tol=1e-3))
        with pytest.raises(ValueError, match="not smooth"):
            solve_many(X, y, [FWConfig(backend="jax_sparse", steps=5,
                                       loss="_abs_probe", gap_tol=1e-3)])
        # fixed-T (no certificate requested) is allowed
        r = solve(X, y, FWConfig(backend="host_sparse", steps=5,
                                 loss="_abs_probe"))
        assert np.isfinite(np.asarray(r.w)).all()
    finally:
        OBJECTIVES.pop("_abs_probe", None)


def test_gap_tol_allowed_for_every_registered_loss():
    from repro.core.solvers.config import check_gap_certificate
    for loss in REGISTERED_LOSSES:
        check_gap_certificate(FWConfig(loss=loss, gap_tol=1e-4))
    with pytest.raises(KeyError, match="unknown loss"):
        check_gap_certificate(FWConfig(loss="nope", gap_tol=0.0))
