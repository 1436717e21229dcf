"""Ingest bench — dataset store vs in-memory data path (DESIGN.md §7).

Measures the lifecycle the store exists for, per Table-2 regime:

  * **ingest** — libsvm text → streaming parse → sharded store (+ column
    stats + content hash), the one-time O(NS) cost;
  * **cold prepare** — first open: mmap shards, build the padded device
    layout, run the ``fw_setup`` spmv sweep (persisted to ``cache/``);
  * **warm prepare** — a fresh open of the same store: mmap + padding again
    but the setup sweep is *replayed from disk* — this is the per-process
    steady state every later solve/tenant pays;
  * **in-memory baseline** — what every solve pays today without the store:
    ``as_padded`` coercion + the ``fw_setup`` sweep on an in-memory matrix.

Acceptance (ISSUE 3): warm prepare < in-memory coercion+setup — the cached
column stats / setup state make the O(NS) sweep an ingest-time cost.  A
parity audit asserts the solve-from-store coordinate sequence is identical
to the in-memory solve (same config, same keys).
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np


def _block(setup):
    for arr in setup:
        np.asarray(arr)


def run(datasets=("rcv1_like", "url_small_like"), steps: int = 40,
        backend: str = "jax_sparse", loss: str = "logistic"):
    import jax.numpy as jnp

    from repro.core.solvers import FWConfig, solve
    from repro.core.solvers.jax_sparse import fw_setup_jit
    from repro.core.solvers.registry import as_padded
    from repro.data.registry import get_spec
    from repro.data.sparse_io import iter_libsvm, write_libsvm
    from repro.data.store import DatasetStore

    out = {"steps": steps, "backend": backend, "datasets": {}}
    cfg = FWConfig(backend=backend, lam=20.0, steps=steps, queue="bsls",
                   epsilon=1.0, delta=1e-6)
    for name in datasets:
        spec = get_spec(name)
        X, y = spec.generate()
        tmp = tempfile.mkdtemp(prefix=f"bench_ingest_{name}_")
        try:
            svm_path = os.path.join(tmp, f"{name}.svm")
            write_libsvm(svm_path, X, y)

            # ---- ingest: streaming text -> sharded store -----------------
            t0 = time.time()
            store = DatasetStore.write(
                os.path.join(tmp, "store"), iter_libsvm(svm_path),
                n_cols=X.shape[1], rows_per_shard=spec.rows_per_shard)
            ingest_s = time.time() - t0

            # ---- warm up the fw_setup compile (untimed) so every prepare
            # number below — in-memory, cold store, warm store — measures
            # steady-state work, not first-call tracing ---------------------
            pcsr, _ = as_padded(X)
            _block(fw_setup_jit(pcsr, jnp.asarray(y, jnp.float32),
                                loss=loss))

            # ---- in-memory baseline: what every solve re-pays without the
            # store (padding coercion + the O(nnz) setup spmv sweep) --------
            t0 = time.time()
            pcsr, _ = as_padded(X)
            setup = fw_setup_jit(pcsr, jnp.asarray(y, jnp.float32),
                                 loss=loss)
            _block(setup)
            inmem_prepare_s = time.time() - t0
            t0 = time.time()
            r_mem = solve(X, y, cfg)
            np.asarray(r_mem.w)
            inmem_solve_s = time.time() - t0

            # ---- cold store: mmap + padding + setup sweep (persisted) ----
            t0 = time.time()
            cold = DatasetStore.open(store.root)
            prep = cold.prepared()
            _block(prep.setup_for(cold.labels(), loss))
            cold_prepare_s = time.time() - t0
            t0 = time.time()
            r_cold = solve(cold, config=cfg)
            np.asarray(r_cold.w)
            cold_solve_s = time.time() - t0

            # ---- warm store: fresh open, setup replayed from cache/ ------
            t0 = time.time()
            warm = DatasetStore.open(store.root)
            prep = warm.prepared()
            _block(prep.setup_for(warm.labels(), loss))
            warm_prepare_s = time.time() - t0
            t0 = time.time()
            r_warm = solve(warm, config=cfg)
            np.asarray(r_warm.w)
            warm_solve_s = time.time() - t0

            parity = bool(
                np.array_equal(np.asarray(r_mem.coords),
                               np.asarray(r_warm.coords))
                and np.array_equal(np.asarray(r_mem.coords),
                                   np.asarray(r_cold.coords)))
            row = {
                "n": store.n, "d": store.d, "nnz": store.nnz,
                "shards": store.n_shards,
                "libsvm_mb": round(os.path.getsize(svm_path) / 2**20, 2),
                "ingest_s": round(ingest_s, 3),
                "ingest_rows_per_s": round(store.n / max(ingest_s, 1e-9)),
                "cold_prepare_s": round(cold_prepare_s, 3),
                "warm_prepare_s": round(warm_prepare_s, 3),
                "inmem_prepare_s": round(inmem_prepare_s, 3),
                "cold_solve_s": round(cold_solve_s, 3),
                "warm_solve_s": round(warm_solve_s, 3),
                "inmem_solve_s": round(inmem_solve_s, 3),
                "warm_setup_speedup": round(
                    inmem_prepare_s / max(warm_prepare_s, 1e-9), 2),
                "pass_warm_setup_faster": bool(
                    warm_prepare_s < inmem_prepare_s),
                "pass_parity": parity,
            }
            out["datasets"][name] = row
            print(f"[ingest] {name}: ingest {ingest_s:.2f}s "
                  f"({row['ingest_rows_per_s']} rows/s, "
                  f"{store.n_shards} shards)  "
                  f"prepare cold/warm/inmem "
                  f"{cold_prepare_s:.2f}/{warm_prepare_s:.2f}/"
                  f"{inmem_prepare_s:.2f}s  "
                  f"parity={parity}", flush=True)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return out


if __name__ == "__main__":
    import json
    print(json.dumps(run(), indent=1))
