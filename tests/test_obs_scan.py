"""Instrumentation of the Algorithm-2 scan (DESIGN.md §12).

* the step's parts carry ``jax.named_scope`` names into the lowered
  programs, single and vmapped, private and non-private;
* the work counters on ``solve.scan`` and ``group.vmap`` equal a numpy
  recount from the fits' coordinates and the host matrix's column counts,
  and are counted when the collector settles, not inside the solve;
* with the collector off the fixed-T solve blocks on nothing and copies
  nothing to the host;
* ``service.wait_s`` times submit -> batch start per tenant.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.solvers import FWConfig, grid, solve, solve_many
from repro.core.solvers import batched, jax_sparse
from repro.core.solvers.jax_sparse import TILE_ROWS

SCOPES = ("fw.select", "fw.step", "fw.coord_update", "fw.queue_refresh")
PRIVATE = dict(queue="two_level", epsilon=1.0, delta=1e-6)


@pytest.fixture(scope="module")
def problem():
    """600 rows, so the popular columns span up to 5 chunks of 128 rows."""
    from repro.data.synthetic import make_sparse_classification
    X, y, _ = make_sparse_classification(
        n=600, d=80, nnz_per_row=8, informative=8, seed=3)
    return X, y


@pytest.fixture(scope="module")
def padded(problem):
    from repro.core.solvers.registry import as_padded
    X, y = problem
    pcsr, pcsc = as_padded(X)
    setup = jax_sparse.fw_setup_jit(pcsr, jnp.asarray(y, jnp.float32),
                                    loss="logistic")
    return pcsr, pcsc, setup


def _chunks(X, coords) -> np.ndarray:
    """Per-step row chunks recounted from the host CSR: ceil(nnz_j/128)."""
    col_nnz = np.bincount(X.indices, minlength=X.shape[1])
    return np.ceil(col_nnz[np.asarray(coords)] / TILE_ROWS).astype(int)


def _spans(tel, name):
    return [e for e in tel.events if e["ev"] == "span" and e["name"] == name]


@pytest.mark.parametrize("private", (False, True),
                         ids=("nonprivate", "private"))
def test_scan_programs_carry_the_step_scopes(padded, private):
    pcsr, pcsc, setup = padded
    key = jax.random.PRNGKey(0)
    single = jax_sparse.fw_scan_jit.lower(
        pcsr, pcsc, *setup, 5.0, 1.0, key, 0.0, None, steps=3,
        loss="logistic", private=private).as_text(debug_info=True)
    swept = batched._sweep_scan_jit.lower(
        pcsr, pcsc, *setup, jnp.ones(2), jnp.ones(2),
        jnp.stack([key, key]), None, steps=3, loss="logistic",
        private=private).as_text(debug_info=True)
    setup_text = jax_sparse.fw_setup_jit.lower(
        pcsr, jnp.zeros(pcsr.shape[0]), loss="logistic").as_text(
            debug_info=True)
    for text in (single, swept):
        found = set(re.findall(r"fw\.\w+", text))
        assert set(SCOPES) <= found, found
        assert "fw.stop_mask" not in found          # fixed-T: no §9 merge
    assert "fw.setup" in setup_text


def test_early_stop_chunk_carries_the_stop_mask_scope(padded):
    pcsr, pcsc, setup = padded
    carry = jax_sparse.fw_carry_init(pcsr.shape[1], jnp.float32, *setup,
                                     1.0, jax.random.PRNGKey(0),
                                     private=False)
    text = jax_sparse.fw_scan_chunk_jit.lower(
        pcsr, pcsc, carry, 5.0, 1.0, 1e-3, 0, None, steps=3,
        loss="logistic", private=False, early_stop=True).as_text(
            debug_info=True)
    assert "fw.stop_mask" in text and "fw.coord_update" in text


@pytest.mark.parametrize("private", (False, True),
                         ids=("nonprivate", "private"))
def test_solve_scan_counts_chunks_and_times_enqueue(problem, private):
    X, y = problem
    cfg = FWConfig(backend="jax_sparse", lam=6.0, steps=40,
                   **(PRIVATE if private else {}))
    with obs.session() as tel:
        res = solve(X, y, cfg)
    (scan,) = _spans(tel, "solve.scan")
    per = _chunks(X, res.coords)
    assert per.max() > 1                      # some step ran several chunks
    assert scan["attrs"]["chunks"] == per.sum()
    assert scan["attrs"]["steps"] == 40
    assert scan["attrs"]["tile_rows"] == TILE_ROWS
    assert 0 <= scan["attrs"]["enqueue_s"] <= scan["dur_s"]


@pytest.mark.parametrize("private", (False, True),
                         ids=("nonprivate", "private"))
def test_group_vmap_counts_lane_and_run_chunks(problem, private):
    X, y = problem
    cfgs = grid(FWConfig(backend="jax_sparse", steps=30,
                         **(PRIVATE if private else {})),
                lam=(2.0, 6.0, 20.0))
    with obs.session() as tel:
        res = solve_many(X, y, cfgs, plan="vmap")
    (group,) = _spans(tel, "group.vmap")
    per = np.stack([_chunks(X, r.coords) for r in res])      # (lanes, T)
    assert group["attrs"]["size"] == 3
    assert group["attrs"]["tile_rows"] == TILE_ROWS
    assert group["attrs"]["lane_chunks"] == per.sum()
    assert group["attrs"]["run_chunks"] == per.max(axis=0).sum()
    assert group["attrs"]["run_chunks"] * 3 >= group["attrs"]["lane_chunks"]


def test_chunk_counts_wait_for_the_collector_to_settle(problem,
                                                        monkeypatch):
    """The counts' host copies run when the collector settles, after the
    measured region, not inside the solve or the vmapped group."""
    X, y = problem
    counted = []
    real = jax_sparse.step_chunks

    def step_chunks(col_nnz, coords):
        counted.append(np.shape(coords))
        return real(col_nnz, coords)

    monkeypatch.setattr(jax_sparse, "step_chunks", step_chunks)
    cfg = FWConfig(backend="jax_sparse", lam=6.0, steps=20)
    tel = obs.enable()
    try:
        single = solve(X, y, cfg)
        lanes = solve_many(X, y, grid(cfg, lam=(2.0, 6.0)), plan="vmap")
        assert counted == []
        (scan,) = _spans(tel, "solve.scan")
        (group,) = _spans(tel, "group.vmap")
        assert "chunks" not in scan["attrs"]
        assert "lane_chunks" not in group["attrs"]
    finally:
        obs.disable()
    assert sorted(counted) == [(2, 20), (20,)]
    assert scan["attrs"]["chunks"] == _chunks(X, single.coords).sum()
    assert group["attrs"]["lane_chunks"] == sum(
        _chunks(X, r.coords).sum() for r in lanes)


def test_collector_off_blocks_on_nothing_and_copies_nothing(
        problem, padded, monkeypatch):
    """The disabled path of the fixed-T solve: no device wait, no host copy
    of the layout's column counts, no chunk count."""
    X, y = problem
    pcsr, pcsc, setup = padded
    calls = {"block": 0, "nnz": 0}
    real_block, real_chunks = jax.block_until_ready, jax_sparse.step_chunks

    def block(x):
        calls["block"] += 1
        return real_block(x)

    def step_chunks(col_nnz, coords):
        calls["nnz"] += 1
        return real_chunks(col_nnz, coords)

    monkeypatch.setattr(jax, "block_until_ready", block)
    monkeypatch.setattr(jax_sparse, "step_chunks", step_chunks)
    cfg = FWConfig(backend="jax_sparse", lam=6.0, steps=10, **PRIVATE)
    y32 = jnp.asarray(y, jnp.float32)
    off = jax_sparse.jax_sparse_fw(pcsr, pcsc, y32, cfg, setup=setup)
    real_block(off.w)
    assert calls == {"block": 0, "nnz": 0}
    with obs.session():
        on = jax_sparse.jax_sparse_fw(pcsr, pcsc, y32, cfg, setup=setup)
    assert calls["block"] == 1 and calls["nnz"] == 1
    for field in ("w", "gaps", "coords"):
        assert (np.asarray(getattr(on, field)).tobytes()
                == np.asarray(getattr(off, field)).tobytes())


def test_service_wait_is_recorded_per_tenant(problem):
    from repro.core.dp.accountant import PrivacyAccountant
    from repro.serve import FitRequest, FitService
    X, y = problem
    svc = FitService(X, y, accountants={
        t: PrivacyAccountant(epsilon=4.0, delta=1e-6, total_steps=200)
        for t in ("acme", "globex")})
    tenants = ("acme", "acme", "globex")
    with obs.session() as tel:
        for uid, tenant in enumerate(tenants):
            svc.submit(FitRequest(uid=uid, tenant=tenant, config=FWConfig(
                backend="jax_sparse", steps=8, lam=4.0 + uid, **PRIVATE)))
        done = svc.run()
    hist = {r["labels"]["tenant"]: r for r in tel.metrics.snapshot()
            if r["name"] == "service.wait_s"}
    assert {t: h["count"] for t, h in hist.items()} == {"acme": 2,
                                                        "globex": 1}
    longest = max(r.latency_s for r in done)
    for h in hist.values():
        assert 0.0 <= h["min"] <= h["max"] <= longest
    assert not any(r["name"] == "service.queue_depth"
                   for r in tel.metrics.snapshot())
