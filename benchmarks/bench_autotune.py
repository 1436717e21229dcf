"""Autotune bench — §11 layout/chunk search on the device hot path.

The flat padded CSC pays the exact max column nnz on *every* step; on
power-law text designs (the paper's Table-2 regime) that is ~8× the 99th
percentile column, which is why BENCH_shard found the flagship kernels 3×
behind the blocked engine on the same device.  This bench runs the §11
autotuner on each dataset twin and reports what the tiered split buys:

  * ``per_iter_ms_default`` / ``per_iter_ms_tuned`` — steady-state kernel
    scan times (warmed compiles, best-of-N, worst case over the private and
    non-private selection rules — both from the tuner's own search);
  * ``tuned_over_default`` — the gate metric: the acceptance bar is ≤ 0.8
    on the rcv1 twin (the tuner must never *pick* a slower layout, so this
    is ≤ 1.0 by construction; < 1 means the search found a real win);
  * ``pass_tuned_parity`` — the exactness invariant, re-verified here
    independently of the tuner's internal gate: (w, gaps, coords) of the
    tuned layout are **bitwise** equal to the flat layout's, private and
    non-private, so the DP selection distribution is untouched.

Output: one row per dataset into BENCH_autotune.json
(``run.py --only autotune``; uploaded as a CI artifact and gated by
``benchmarks.check`` against the committed baseline).
"""
from __future__ import annotations

import time


def run(datasets=("rcv1",), steps: int = 24, lam: float = 20.0):
    from benchmarks.common import load_problem
    from repro.core.solvers.autotune import probe_parity, tune_jax_sparse
    from repro.core.sparse.formats import host_to_padded, tiered_from_padded

    out = {"steps": steps, "lam": lam, "datasets": {}}
    for name in datasets:
        prob = load_problem(name)
        pcsr, pcsc = host_to_padded(prob.X)
        t0 = time.time()
        rec = tune_jax_sparse(pcsr, pcsc, prob.y, steps=steps, lam=lam,
                              probe_steps=steps)
        tune_s = time.time() - t0
        if rec.ell_width is not None:
            winner = tiered_from_padded(pcsc, rec.ell_width)
            parity = probe_parity(pcsr, pcsc, winner, prob.y,
                                  loss="logistic", steps=steps, lam=lam)
        else:
            parity = True            # flat layout won: nothing to compare
        row = {
            "n": prob.X.shape[0], "d": prob.X.shape[1],
            "pad_width": int(pcsc.indices.shape[1]),
            "ell_width": rec.ell_width,
            "chunk_steps": rec.chunk_steps,
            "per_iter_ms_default": round(rec.per_iter_default_ms, 3),
            "per_iter_ms_tuned": round(rec.per_iter_tuned_ms, 3),
            "tuned_over_default": round(
                rec.per_iter_tuned_ms / max(rec.per_iter_default_ms, 1e-9),
                3),
            "tuned_speedup": round(rec.speedup, 2),
            "tune_seconds": round(tune_s, 1),
            "pass_tuned_parity": bool(parity),
        }
        out["datasets"][name] = row
        print(f"[autotune] {name}: pad {row['pad_width']} -> tier "
              f"{row['ell_width']}, {row['per_iter_ms_default']} -> "
              f"{row['per_iter_ms_tuned']} ms/iter "
              f"({row['tuned_speedup']}x)  parity="
              f"{row['pass_tuned_parity']}", flush=True)
    return out


if __name__ == "__main__":
    import json
    print(json.dumps(run(), indent=1))
