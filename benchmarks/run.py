"""Benchmark orchestrator — one bench per paper table/figure (deliverable d).

  Fig 1    bench_convergence   Alg 1 vs Alg 2 gap traces
  Fig 2/4  bench_flops         FLOPs-reduction factor
  Fig 3    bench_heap_pops     heap pops / ‖w*‖₀
  Table 3  bench_speedup       DP wall-clock speedup (Alg 2+4, ablation)
  Table 4  bench_accuracy      accuracy/AUC/sparsity at ε = 0.1
  (sweeps) bench_sweep         sequential solve() vs batched solve_many()
  (store)  bench_ingest        dataset-store ingest + cold/warm prepare
  (shard)  bench_shard         jax_sparse vs jax_shard + step-parity audit
  (§11)    bench_autotune      layout/chunk autotuner gains + parity gate
  (§13)    bench_screening     DP iterative screening vs plain chunked solve
  (§14)    bench_path          warm λ-path vs per-λ from-scratch solves
  §Roofline roofline_table     three-term model from dryrun_results.json

The suite itself — names, runners, perf-gate rules — lives in
``benchmarks.suite`` (shared with ``check.py``, so ``--only`` and the gate
can never drift apart again).

``python -m benchmarks.run [--fast] [--only NAME] [--backend B]`` — results
to BENCH_<name>.json per bench + aggregate bench_results.json + stdout
summary.  ``--only`` is a substring filter over ``suite.names()`` and
rejects a filter that matches nothing.  The whole run executes under a
``repro.obs`` telemetry session: solver spans, planner drift and cache
counters land in ``BENCH_telemetry.jsonl`` next to the result JSONs (render
with ``python -m repro.obs.report BENCH_telemetry.jsonl``).  ``--backend``
retargets the Alg-2 side of the registry-aware benches (fig1 convergence,
table4 accuracy) onto any engine from
``repro.core.solvers.available_backends()``; the FLOP/heap-audit benches are
pinned to the host engine (see docs/BENCHMARKS.md).
"""
from __future__ import annotations

import argparse
import json
import time
import traceback


def main():
    from benchmarks.suite import SUITE, names

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="fewer steps/datasets")
    ap.add_argument("--only", default=None,
                    help="substring filter over the suite names: "
                         + ", ".join(names()))
    ap.add_argument("--out", default="bench_results.json")
    ap.add_argument("--dryrun-json", default="dryrun_results.json")
    ap.add_argument("--backend", default=None,
                    help="solver registry backend for the Alg-2 side of "
                         "registry-aware benches (default: host_sparse; the "
                         "sweep bench defaults to jax_sparse, the only "
                         "engine with a batched fast path)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.core.solvers import available_backends

    if args.backend is not None and args.backend not in available_backends():
        ap.error(f"--backend {args.backend!r} not in {available_backends()}")
    if args.only and not any(args.only in n for n in names()):
        ap.error(f"--only {args.only!r} matches no bench; choose a "
                 f"substring of: {', '.join(names())}")

    fast = args.fast
    from repro import obs
    results, failures = {}, []
    with obs.session(jsonl_path="BENCH_telemetry.jsonl",
                     meta={"harness": "benchmarks.run",
                           "fast": fast, "only": args.only or ""}):
        for spec in SUITE:
            name = spec.name
            if args.only and args.only not in name:
                continue
            t0 = time.time()
            print(f"[bench] {name} ...", flush=True)
            try:
                with obs.span("bench", bench=name):
                    results[name] = spec.run(fast, args.backend,
                                             args.dryrun_json)
                results[name]["bench_seconds"] = round(time.time() - t0, 1)
                with open(f"BENCH_{name}.json", "w") as f:
                    json.dump(results[name], f, indent=1)
                print(f"[bench] {name} done in "
                      f"{results[name]['bench_seconds']}s "
                      f"→ BENCH_{name}.json", flush=True)
            except Exception as e:  # noqa: BLE001
                failures.append({"bench": name, "error": str(e)})
                traceback.print_exc()
    with open(args.out, "w") as f:
        json.dump({"results": results, "failures": failures}, f, indent=1)
    print("telemetry artifact → BENCH_telemetry.jsonl "
          "(render: python -m repro.obs.report BENCH_telemetry.jsonl)")

    # ---- summary ---------------------------------------------------------
    print("\n=== benchmark summary ===")
    for name, r in results.items():
        if "datasets" in r:
            for ds, row in r["datasets"].items():
                passes = {k: v for k, v in row.items()
                          if k.startswith("pass") or k.endswith("gt1")}
                keys = [k for k in ("flops_reduction_total", "speedup_alg2+4",
                                    "accuracy_pct", "pops_over_nnz_ratio",
                                    "final_gap_rel_diff", "sweep_speedup",
                                    "ingest_s", "warm_setup_speedup",
                                    "shard_over_sparse", "block_waste",
                                    "tuned_over_default", "tuned_speedup",
                                    "screen_speedup", "selected_coords",
                                    "path_speedup")
                        if k in row]
                kv = {k: row[k] for k in keys}
                for eps_k in ("eps_1.0", "eps_0.1"):
                    if eps_k in row:
                        kv[f"speedup@{eps_k[4:]}"] = row[eps_k]["speedup_alg2+4"]
                print(f"  {name:18s} {ds:8s} {kv} {passes}")
        elif "points" in r:
            sp = ", ".join(f"D={p['d']}: {p['speedup']}x" for p in r["points"])
            print(f"  {name:18s} {sp} (monotone={r['monotone_in_d']})")
        elif "rows" in r:
            print(f"  {name:18s} {len(r['rows'])} roofline rows "
                  f"(see EXPERIMENTS.md §Roofline)")
        elif "skipped" in r:
            print(f"  {name:18s} SKIPPED: {r['skipped']}")
    if failures:
        print(f"  {len(failures)} benches FAILED")
        raise SystemExit(1)
    print("all benches ok →", args.out)


if __name__ == "__main__":
    main()
