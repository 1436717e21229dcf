"""One registry of benchmark suite entries — names, runners, perf-gate rules.

``run.py`` (which benches exist, what ``--only`` accepts, fast/full knobs)
and ``check.py`` (which ``BENCH_*.json`` artifacts are gated, by what rules)
used to carry separately hand-maintained tables, and they drifted: at one
point ``docs/BENCHMARKS.md`` documented ``--only`` names ``run.py`` did not
recognize.  This module is now the single source of truth — ``run.py``
builds its suite from :data:`SUITE` and validates ``--only`` against
:func:`names`; ``check.py`` derives its ``SPEC`` from :func:`gate_spec`;
``docs/BENCHMARKS.md`` lists the same names.

Each :class:`BenchSpec` bundles:

  * ``name``  — the suite key; the artifact is ``BENCH_<name>.json``;
  * ``title`` — one-liner for ``--help`` and the docs table;
  * ``run``   — ``(fast, backend, dryrun_json) -> result doc`` with lazy
    imports, so listing the suite never imports jax;
  * ``gate``  — ``check.py`` rule tuples (empty = artifact is informational,
    not gated).  Rule kinds: ``("flags",)`` | ``("min"|"max", metric, bound)``
    | ``("rel_min"|"rel_max", metric, factor)`` (relative bands are skipped
    in ``--mode full``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Tuple


@dataclasses.dataclass(frozen=True)
class BenchSpec:
    name: str
    title: str
    run: Callable[[bool, str, str], dict]
    gate: Tuple[tuple, ...] = ()


def _fig1(fast, backend, dryrun_json):
    from benchmarks import bench_convergence
    return bench_convergence.run(
        datasets=("rcv1",) if fast else ("rcv1", "news20"),
        steps=150 if fast else 300, backend=backend or "host_sparse")


def _fig2_4(fast, backend, dryrun_json):
    from benchmarks import bench_flops
    return bench_flops.run(
        datasets=("rcv1",) if fast else ("rcv1", "news20", "kdda"),
        steps=150 if fast else 300)


def _fig3(fast, backend, dryrun_json):
    from benchmarks import bench_heap_pops
    return bench_heap_pops.run(
        datasets=("rcv1",) if fast else ("rcv1", "url"),
        steps=200 if fast else 400)


def _table3(fast, backend, dryrun_json):
    from benchmarks import bench_speedup
    return bench_speedup.run(
        datasets=("rcv1", "url") if fast else
        ("rcv1", "news20", "url", "web", "kdda"),
        steps=100 if fast else 200)


def _table4(fast, backend, dryrun_json):
    from benchmarks import bench_accuracy
    return bench_accuracy.run(
        datasets=("rcv1",) if fast else ("rcv1", "news20", "url"),
        steps=800 if fast else 2000, backend=backend or "host_sparse")


def _sweep(fast, backend, dryrun_json):
    from benchmarks import bench_sweep
    return bench_sweep.run(
        datasets=("rcv1", "news20", ("rcv1", "huber")),
        lams=(10.0, 20.0, 40.0, 80.0), epsilons=(0.5, 2.0),
        steps=40 if fast else 120, backend=backend or "jax_sparse")


def _shard(fast, backend, dryrun_json):
    from benchmarks import bench_shard
    return bench_shard.run(
        datasets=("rcv1",) if fast else ("rcv1", "news20"),
        steps=30 if fast else 80)


def _autotune(fast, backend, dryrun_json):
    from benchmarks import bench_autotune
    return bench_autotune.run(
        datasets=("rcv1",) if fast else ("rcv1", "news20"),
        steps=20 if fast else 40)


def _screening(fast, backend, dryrun_json):
    from benchmarks import bench_screening
    return bench_screening.run(
        datasets=("rcv1",) if fast else ("rcv1", "url"),
        steps=240 if fast else 320)


def _path(fast, backend, dryrun_json):
    from benchmarks import bench_path
    return bench_path.run(
        datasets=("rcv1",) if fast else ("rcv1", "url"),
        steps=120 if fast else 240)


def _ingest(fast, backend, dryrun_json):
    from benchmarks import bench_ingest
    return bench_ingest.run(
        datasets=("rcv1_like",) if fast else ("rcv1_like", "url_small_like"),
        steps=30 if fast else 80, backend=backend or "jax_sparse")


def _scaling(fast, backend, dryrun_json):
    from benchmarks import bench_scaling
    return bench_scaling.run(
        d_values=(10_000, 100_000) if fast else
        (10_000, 100_000, 400_000, 800_000),
        steps=100 if fast else 150)


def _roofline(fast, backend, dryrun_json):
    from benchmarks import roofline_table
    return roofline_table.run(dryrun_json)


SUITE: Tuple[BenchSpec, ...] = (
    BenchSpec("fig1_convergence", "Fig 1: Alg 1 vs Alg 2 gap traces", _fig1),
    BenchSpec("fig2_4_flops", "Fig 2/4: FLOPs-reduction factor", _fig2_4),
    BenchSpec("fig3_heap_pops", "Fig 3: heap pops / ‖w*‖₀", _fig3),
    BenchSpec("table3_speedup",
              "Table 3: DP wall-clock speedup (Alg 2+4, ablation)", _table3),
    BenchSpec("table4_accuracy",
              "Table 4: accuracy/AUC/sparsity at ε = 0.1", _table4),
    BenchSpec("sweep", "batched solve_many() vs sequential solve() loop",
              _sweep, gate=(
                  ("flags",),
                  # the §9 tentpole invariant: gap-adaptive batched
                  # scheduling must beat the fixed-T sequential loop it
                  # replaced, on every dataset
                  ("min", "sweep_speedup", 1.0),
                  ("rel_min", "sweep_speedup", 0.5),
              )),
    BenchSpec("shard", "jax_sparse vs jax_shard + step-parity audit",
              _shard, gate=(
                  ("flags",),
                  # jax_shard per-iter cost relative to jax_sparse on the
                  # 1×1 CPU mesh (lower is better; same-run timing ratio)
                  ("rel_max", "shard_over_sparse", 3.0),
              )),
    BenchSpec("autotune", "§11 layout/chunk autotuner gains + parity gate",
              _autotune, gate=(
                  ("flags",),   # pass_tuned_parity: bitwise, never a timing
                  # the §11 search must never pick a layout slower than the
                  # flat default.  No win is demanded: on the CPU every tier
                  # drifts the gap by an ulp (its lax.cond compiles the step
                  # in a second context), so the parity gate keeps the flat
                  # layout; whether tiers pay is a chip question (ROADMAP A4)
                  ("min", "tuned_speedup", 1.0),
              )),
    BenchSpec("screening", "§13 DP iterative screening vs plain chunked solve",
              _screening, gate=(
                  ("flags",),   # pass_utility (equal-ε accuracy audit)
                                # + pass_coords (original-index contract)
                  # the §13 tentpole invariant: mid-solve screening must make
                  # the private solve ≥ 1.5× faster at equal total ε
                  ("min", "screen_speedup", 1.5),
                  ("rel_min", "screen_speedup", 0.5),
              )),
    BenchSpec("path", "§14 warm-started λ-path vs per-λ from-scratch solves",
              _path, gate=(
                  ("flags",),   # pass_utility + pass_gap + pass_eps_split
                  # the §14 tentpole invariant: the homotopy path must solve
                  # the whole λ-grid ≥ 2× faster than independent per-λ
                  # solves at equal total ε
                  ("min", "path_speedup", 2.0),
                  ("rel_min", "path_speedup", 0.5),
              )),
    BenchSpec("ingest", "dataset-store ingest + cold/warm prepare",
              _ingest, gate=(
                  ("flags",),
                  # warm store opens must keep skipping the setup sweep
                  ("min", "warm_setup_speedup", 2.0),
                  ("rel_min", "warm_setup_speedup", 0.25),
              )),
    BenchSpec("scaling_beyond", "speedup vs D beyond the paper's grid",
              _scaling),
    BenchSpec("roofline", "three-term cost model from dryrun_results.json",
              _roofline),
)


def names() -> Tuple[str, ...]:
    return tuple(s.name for s in SUITE)


def gate_spec() -> Dict[str, List[tuple]]:
    """check.py's SPEC: gated artifact file → rule list."""
    return {f"BENCH_{s.name}.json": list(s.gate) for s in SUITE if s.gate}
