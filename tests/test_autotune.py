"""§11 layout/chunk autotuner: the tiered-CSC split, the exactness gate, the
store-persisted tuning cache, and the planner feed.

The hard invariant everything here orbits: **a tuned layout produces
bit-identical iterates to the untuned one, on every backend, private and
non-private** — the autotuner changes how fast the paper's iteration runs,
never which iterates it takes (so the DP selection distribution is
untouched, per Khanna et al.).
"""
import dataclasses
import os

import numpy as np
import pytest

from repro.core.solvers import FWConfig, solve
from repro.core.solvers.autotune import (TUNE_VERSION, TuningRecord, autotune,
                                         candidate_widths, probe_parity)
from repro.core.sparse.formats import (TieredCSC, host_to_padded,
                                       tiered_from_padded)
from repro.data.store import DatasetStore
from repro.data.synthetic import make_sparse_classification


@pytest.fixture(scope="module")
def problem():
    # heavy-tailed column popularity (the synthetic generator's power law)
    # so the padded CSC has a real tail for the tuner to split
    X, y, _ = make_sparse_classification(n=220, d=900, nnz_per_row=12,
                                         informative=20, seed=11)
    return X, y


@pytest.fixture(scope="module")
def padded(problem):
    X, _ = problem
    return host_to_padded(X)


@pytest.fixture()
def store(problem, tmp_path):
    X, y = problem
    return DatasetStore.from_arrays(str(tmp_path / "ds"), X, y,
                                    rows_per_shard=64)


# ---------------------------------------------------------------------------
# TieredCSC layout
# ---------------------------------------------------------------------------


def test_tiered_split_reconstructs_every_column(padded):
    _, pcsc = padded
    cn = np.asarray(pcsc.nnz)
    width = max(8, int(np.percentile(cn, 90)))
    tiered = tiered_from_padded(pcsc, width)
    assert isinstance(tiered, TieredCSC)
    assert tiered.width == width
    assert tiered.full_width == pcsc.indices.shape[1]
    np.testing.assert_array_equal(np.asarray(tiered.nnz), cn)  # never clamped
    for j in [0, 1, int(cn.argmax()), pcsc.shape[1] - 1]:
        heavy = cn[j] > width
        assert bool(tiered.is_heavy(j)) == heavy
        idx, val, mask = (tiered.col_heavy(j) if heavy
                          else tiered.col_light(j))
        k = int(cn[j])
        # real lanes match the flat layout; everything masked-off is padding
        np.testing.assert_array_equal(np.asarray(idx)[:k],
                                      np.asarray(pcsc.indices)[j, :k])
        np.testing.assert_array_equal(np.asarray(val)[:k],
                                      np.asarray(pcsc.values)[j, :k])
        assert bool(np.asarray(mask)[:k].all())
        assert not np.asarray(mask)[k:].any()
        assert not np.asarray(val)[k:].any()


def test_tiered_width_bounds_rejected(padded):
    _, pcsc = padded
    full = int(pcsc.indices.shape[1])
    with pytest.raises(ValueError):
        tiered_from_padded(pcsc, 0)
    with pytest.raises(ValueError):
        tiered_from_padded(pcsc, full)


def test_candidate_widths_bounded_and_below_full(padded):
    _, pcsc = padded
    cands = candidate_widths(pcsc)
    full = int(pcsc.indices.shape[1])
    assert len(cands) <= 4
    assert all(8 <= w < full for w in cands)
    assert cands == sorted(cands)


def test_probe_parity_gates_a_corrupted_layout(problem, padded):
    """The exactness gate must reject a layout that changes the arithmetic
    (here: every stored value scaled, so any selected column computes
    different sums)."""
    import jax.numpy as jnp
    X, y = problem
    pcsr, pcsc = padded
    width = candidate_widths(pcsc)[-1]
    good = tiered_from_padded(pcsc, width)
    assert probe_parity(pcsr, pcsc, good, y, loss="logistic", steps=8)
    bad = dataclasses.replace(
        good, values=jnp.asarray(np.asarray(good.values) * 1.5),
        heavy_values=jnp.asarray(np.asarray(good.heavy_values) * 1.5))
    assert not probe_parity(pcsr, pcsc, bad, y, loss="logistic", steps=8)


def test_probe_parity_refuses_chunked_driver_drift(problem, padded,
                                                   monkeypatch):
    """The gate covers the chunked driver too: a tier that matches the flat
    layout in the whole-run scan but drifts by one ulp in a chunked run's
    gap trace (the step compiles in a different context there) is
    refused."""
    import repro.core.solvers.autotune as at
    X, y = problem
    pcsr, pcsc = padded
    tier = tiered_from_padded(pcsc, candidate_widths(pcsc)[-1])
    assert probe_parity(pcsr, pcsc, tier, y, loss="logistic", steps=8)
    real = at._chunked_once

    def drifting(pcsr_, csc, *args, **kwargs):
        w, gaps, coords = real(pcsr_, csc, *args, **kwargs)
        if csc is tier:
            gaps = gaps.at[-1].set(np.nextafter(gaps[-1], np.inf))
        return w, gaps, coords

    monkeypatch.setattr(at, "_chunked_once", drifting)
    assert not probe_parity(pcsr, pcsc, tier, y, loss="logistic", steps=8)


# ---------------------------------------------------------------------------
# tuned-layout parity across backends (the ISSUE's hard invariant)
# ---------------------------------------------------------------------------


def _bits(res):
    return tuple(np.asarray(a).tobytes() for a in (res.w, res.gaps,
                                                   res.coords))


@pytest.mark.parametrize("queue", ["group_argmax", "two_level"])
@pytest.mark.parametrize("backend", ["jax_sparse", "jax_dense", "dense",
                                     "host_sparse", "jax_shard"])
def test_tuned_store_bit_identical_on_every_backend(store, problem, backend,
                                                    queue):
    """Solving through the store before vs after autotuning is bitwise the
    same on every backend — private and non-private."""
    X, y = problem
    cfg = dict(backend=backend, steps=12, lam=15.0, queue=queue,
               epsilon=1.0, delta=1e-6, seed=3)
    before = solve(store, **cfg)
    rec = autotune(store, steps=6, probe_steps=8)
    assert rec.pass_parity
    # force a *new* PreparedDataset so the tuned path is really exercised
    store._prepared = None
    after = solve(store, **cfg)
    assert _bits(before) == _bits(after)


def test_tuned_layout_matches_raw_matrix_solve(store, problem):
    X, y = problem
    autotune(store, steps=6, probe_steps=8)
    store._prepared = None
    cfg = dict(backend="jax_sparse", steps=15, lam=20.0, queue="two_level",
               epsilon=1.0, delta=1e-6)
    assert _bits(solve(store, **cfg)) == _bits(solve(X, y, **cfg))


def test_tuned_chunked_driver_matches_default(store, problem):
    """gap_tol configs route through the chunked driver with the tuned
    chunk_steps default — still bit-identical to the untuned store."""
    X, y = problem
    cfg = dict(backend="jax_sparse", steps=24, lam=15.0, gap_tol=1e-9,
               queue="group_argmax")
    before = solve(store, **cfg)
    autotune(store, steps=6, probe_steps=8)
    store._prepared = None
    after = solve(store, **cfg)
    assert _bits(before) == _bits(after)


# ---------------------------------------------------------------------------
# persistence + replay
# ---------------------------------------------------------------------------


def test_warm_open_replays_record_without_research(store, monkeypatch):
    rec = autotune(store, steps=6, probe_steps=8)
    assert rec.content_hash == store.content_hash
    assert os.path.exists(os.path.join(
        store.root, "cache",
        f"autotune-jax_sparse-logistic-{rec.platform}.json"))
    # a re-opened store must replay the persisted record, never re-search
    import repro.core.solvers.autotune as at

    def boom(*a, **k):
        raise AssertionError("warm open re-ran the search")

    monkeypatch.setattr(at, "tune_jax_sparse", boom)
    reopened = DatasetStore.open(store.root)
    rec2 = autotune(reopened, steps=6, probe_steps=8)
    assert rec2 == rec
    # and the prepared dataset resolves it through the loader hook
    prep = reopened.prepared()
    assert prep.tuning_for("jax_sparse", "logistic",
                           platform=rec.platform) == rec


def test_force_retunes_and_content_hash_guards(store, tmp_path,
                                              monkeypatch):
    # The scan's cost follows a column's nnz, not the layout's width, so a
    # tier and the flat table time within noise of each other on the CPU;
    # a per-iteration cost fixed by the layout's width keeps the winner
    # independent of the machine's load.
    import repro.core.solvers.autotune as at
    monkeypatch.setattr(at, "_time_layout",
                        lambda pcsr, csc, *a, **k: float(csc.indices.shape[1]))
    rec = autotune(store, steps=6, probe_steps=8)
    # force=True ignores the cache and searches again to the same knobs
    rec2 = autotune(store, steps=6, probe_steps=8, force=True)
    assert rec2.ell_width == rec.ell_width
    # a record for different content must not replay
    stale = dataclasses.replace(rec, content_hash="0" * 64)
    store.autotune_save(stale)
    assert store.autotune_load("jax_sparse", "logistic",
                               rec.platform) is None


def test_tuning_record_json_round_trip():
    rec = TuningRecord(content_hash="abc", platform="cpu",
                       backend="jax_sparse", loss="logistic", ell_width=128,
                       chunk_steps=32, mesh=(2, 4),
                       per_iter_default_ms=2.0, per_iter_tuned_ms=1.0)
    back = TuningRecord.from_json(rec.to_json())
    assert back == rec
    assert back.speedup == pytest.approx(2.0)
    # unknown versions and junk refuse to deserialize rather than misread
    assert TuningRecord.from_json({**rec.to_json(),
                                   "version": TUNE_VERSION + 1}) is None
    assert TuningRecord.from_json({"nonsense": 1}) is None


def test_jax_shard_autotune_records_and_replays(store):
    rec = autotune(store, backend="jax_shard", steps=4)
    assert rec.backend == "jax_shard"
    assert rec.mesh is None          # single-device container: 1×1 wins
    assert autotune(store, backend="jax_shard", steps=4) == rec


# ---------------------------------------------------------------------------
# planner feed
# ---------------------------------------------------------------------------


def test_autotune_feeds_measured_costs_to_planner(store):
    from repro.core.solvers.planner import (clear_costbook, measured_cost,
                                            store_stats)
    clear_costbook()
    try:
        rec = autotune(store, steps=6, probe_steps=8, force=True)
        got = measured_cost("jax_sparse", "sequential", rec.platform,
                            store_stats(store))
        assert got == pytest.approx(rec.per_iter_tuned_ms / 1e3)
    finally:
        clear_costbook()
