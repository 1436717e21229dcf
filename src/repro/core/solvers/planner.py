"""Cost-model execution planner for the solver stack (DESIGN.md §9).

Three questions every solve/sweep has to answer before any XLA program runs:

  1. **Which backend?**  ``FWConfig(backend="auto")`` asks the planner to
     pick from the problem's shape: per-iteration work of Algorithm 1 is
     O(nnz + D) while Algorithm 2's padded tile is O(K_c·K_r + √D), so the
     crossover is a pure cost-model question — answered with the same
     three-term roofline machinery the dry-run audit uses
     (``repro.roofline.analysis.roofline_terms``), fed with per-iteration
     FLOP/byte counts instead of whole-model numbers.

  2. **Vmapped or sequential grid execution?**  A vmapped sweep is one
     program but pays every lane every step; re-entering the per-config scan
     is many dispatches but each lane stops exactly when it converges.  On
     accelerators the vmap lanes are nearly free (vector units are wide and
     idle); on CPU-interpret containers each lane costs ~a full sequential
     step (measured: the BENCH_sweep 0.7× regression this module exists to
     fix).  The planner picks per platform, and **measured** per-iteration
     costs recorded by the batched driver (``record_cost``) override the
     model whenever a matching observation exists.

  3. **What chunk length?**  Chunked execution (gap-adaptive early stopping,
     cohort retirement, ``max_seconds``) trades host round-trips against
     wasted post-convergence steps; ``steps/8`` clamped to [8, 256] keeps
     both under ~15%.

The planner never changes results — every plan runs the same state machine
with the same keys; only scheduling differs.  ε-accounting is likewise
untouched: admission charges by the resolved queue, not by the engine that
realizes it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core.solvers.config import FWConfig
from repro.roofline.analysis import chip_peaks, roofline_terms

# Effective per-chip rates fed to roofline_terms.  An accelerator's come from
# repro.roofline.analysis.CHIP_PEAKS by device kind (an unknown chip raises);
# the CPU numbers are deliberately conservative (one wide core of a shared CI
# container) — only *ratios* between candidate plans matter here, not
# absolute seconds.
CPU_PEAK_FLOPS = 2.0e10
CPU_HBM_BW = 1.5e10
# Measured lane overhead of vmapping the kernel scan on CPU interpret mode:
# one extra lane costs ~this fraction of a full sequential step (the
# BENCH_sweep 0.7× finding: 8 lanes ≈ 8 × 1.4 sequential steps).
CPU_VMAP_LANE_OVERHEAD = 1.4
ACCEL_VMAP_LANE_OVERHEAD = 0.15


@dataclasses.dataclass(frozen=True)
class ProblemStats:
    """Shape facts the cost model consumes (cheap to derive, never solves)."""

    n: int
    d: int
    nnz: int
    kc: int   # max column nnz (Alg-2 tile height)
    kr: int   # max row nnz (Alg-2 tile width)

    @property
    def density(self) -> float:
        return self.nnz / max(self.n * self.d, 1)


# manifest-derived stats per store, keyed by content hash: deriving them is
# already O(1) metadata reads, but fit services re-ask on every admission
_STORE_STATS: Dict[str, "ProblemStats"] = {}


def store_stats(store) -> ProblemStats:
    """:class:`ProblemStats` for a ``DatasetStore`` from its metadata alone.

    n/d/nnz sit in the manifest; the ingest-pass column stats give the exact
    max column nnz (``df`` counts one hit per stored entry); the max row nnz
    comes from the manifest when the ingest recorded it, else from one O(N)
    sweep over the mmap'd shard indptrs.  Nothing here materializes values
    or indices — stats for an 8M×20M store cost a few metadata reads.
    """
    key = store.content_hash
    got = _STORE_STATS.get(key)
    if got is not None:
        return got
    kc = store.manifest.get("col_nnz_max")
    if kc is None:
        df = store.col_stats().df
        kc = int(df.max()) if df.size else 1
    kr = store.manifest.get("row_nnz_max")
    if kr is None:
        kr = 1
        for i in range(store.n_shards):
            indptr = np.load(store._shard_base(i) + ".indptr.npy",
                             mmap_mode="r")
            if indptr.shape[0] > 1:
                kr = max(kr, int(np.diff(indptr).max()))
    stats = ProblemStats(n=store.n, d=store.d, nnz=store.nnz,
                         kc=max(int(kc), 1), kr=max(int(kr), 1))
    _STORE_STATS[key] = stats
    return stats


def data_stats(X) -> ProblemStats:
    """Derive :class:`ProblemStats` from any layout ``solve`` accepts."""
    from repro.core.solvers.prepared import PreparedDataset
    from repro.core.sparse.formats import (ChunkedCSC, HostCSR, PaddedCSC,
                                           PaddedCSR, TieredCSC)
    if isinstance(X, PreparedDataset):
        X = X.pair
    if (isinstance(X, tuple) and len(X) == 2
            and isinstance(X[0], PaddedCSR)
            and isinstance(X[1], (PaddedCSC, TieredCSC, ChunkedCSC))):
        pcsr, pcsc = X
        n, d = pcsr.shape
        # a tiered CSC's cost-relevant tile height is the true max column
        # nnz — the full-width heavy tier, not the narrow light table; the
        # nnz-bounded layout has no width, so its largest column nnz
        if isinstance(pcsc, TieredCSC):
            kc = pcsc.full_width
        elif isinstance(pcsc, ChunkedCSC):
            kc = max(1, int(np.max(np.asarray(pcsc.nnz), initial=0)))
        else:
            kc = int(pcsc.indices.shape[1])
        return ProblemStats(n=n, d=d, nnz=int(np.sum(np.asarray(pcsr.nnz))),
                            kc=kc, kr=int(pcsr.indices.shape[1]))
    if isinstance(X, HostCSR):
        row_nnz = np.diff(X.indptr)
        col_nnz = np.bincount(X.indices, minlength=X.shape[1])
        return ProblemStats(n=X.shape[0], d=X.shape[1], nnz=X.nnz,
                            kc=int(col_nnz.max()) if X.nnz else 1,
                            kr=int(row_nnz.max()) if X.nnz else 1)
    if getattr(X, "content_hash", None) is not None and hasattr(X, "manifest"):
        return store_stats(X)        # O(1) from metadata, never materializes
    if hasattr(X, "resolve"):                       # DatasetRef
        resolved, _ = X.resolve()
        return data_stats(resolved)
    arr = np.asarray(X)
    if arr.ndim == 2:
        nnz_mask = arr != 0
        row = nnz_mask.sum(axis=1)
        col = nnz_mask.sum(axis=0)
        return ProblemStats(n=arr.shape[0], d=arr.shape[1],
                            nnz=int(nnz_mask.sum()),
                            kc=int(col.max()) if col.size else 1,
                            kr=int(row.max()) if row.size else 1)
    raise TypeError(f"cannot derive problem stats from {type(X).__name__}")


# ---------------------------------------------------------------------------
# per-iteration cost model (FLOPs / bytes per FW step, by backend)
# ---------------------------------------------------------------------------


def step_costs(stats: ProblemStats, backend: str) -> Tuple[float, float]:
    """(flops, bytes) of one FW iteration — the paper's complexity table
    turned into roofline inputs.  Coefficients follow the analytic counts in
    ``fw_dense.dense_fw_flops`` / ``fw_sparse.sparse_fw_flops_estimate``."""
    n, d, nnz = stats.n, stats.d, stats.nnz
    if backend == "dense":
        flops = 4.0 * nnz + 4.0 * n + 6.0 * d
        bytes_ = 4.0 * (2.0 * nnz + 2.0 * n + 3.0 * d)
        return flops, bytes_
    # Alg-2 family: K_c×K_r fused tile + two-level/√D selection + O(K) queue
    # refresh.  jax_dense additionally touches the D-wide sampler state.
    tile = float(stats.kc) * float(stats.kr)
    sqrt_d = math.sqrt(max(d, 1))
    flops = 6.0 * tile + 4.0 * stats.kc + 3.0 * sqrt_d
    bytes_ = 4.0 * (3.0 * tile + 4.0 * stats.kc + 2.0 * sqrt_d)
    if backend == "jax_dense":
        flops += 2.0 * d
        bytes_ += 8.0 * d
    if backend == "jax_shard":
        # the blocked schedule trades the tile for per-shard lanes plus the
        # collective term (charged separately by callers that know the mesh)
        bytes_ += 4.0 * stats.kc
    return flops, bytes_


def step_time_model(stats: ProblemStats, backend: str,
                    platform: str) -> float:
    """Modeled seconds per FW iteration on ``platform`` (roofline bound).

    Off the CPU the peaks are those of the local device's kind
    (``CHIP_PEAKS``); a chip without published peaks raises."""
    flops, bytes_ = step_costs(stats, backend)
    if platform == "cpu":
        peaks = dict(peak_flops=CPU_PEAK_FLOPS, hbm_bw=CPU_HBM_BW,
                     ici_bw=float("inf"))      # one host: no link term
    else:
        import jax
        peaks = dataclasses.asdict(chip_peaks(jax.devices()[0].device_kind))
    terms = roofline_terms(flops=flops, bytes_accessed=bytes_,
                           collective_bytes=0.0, chips=1, **peaks)
    return float(terms["t_bound_s"])


# ---------------------------------------------------------------------------
# measured-cost book: observations beat the model
# ---------------------------------------------------------------------------

# (backend, mode, platform, loss, n-bucket, d-bucket) -> smoothed s/step/lane
# Keyed per objective: the per-row gradient map changes the fused kernel's
# arithmetic (and label-coupled objectives add a gather), so observations of
# one loss never steer another's mode choice.
_COSTBOOK: Dict[tuple, float] = {}
# keys whose first (compile-tainted) observation has been discarded
_WARMED: set = set()


def _bucket(x: int) -> int:
    return int(math.log2(max(x, 1)))


def _cost_key(backend: str, mode: str, platform: str,
              stats: ProblemStats, loss: str = "logistic") -> tuple:
    return (backend, mode, platform, loss, _bucket(stats.n), _bucket(stats.d))


def record_cost(backend: str, mode: str, platform: str, stats: ProblemStats,
                seconds_per_step_lane: float, *,
                loss: str = "logistic") -> None:
    """Feed an observed per-step-per-lane time back into the planner (the
    batched drivers call this after every chunk/group).

    The very first observation per key is discarded: it times the XLA
    compile of a fresh program, which is orders of magnitude above steady
    state and would poison the mode choice for dozens of EWMA updates.
    """
    key = _cost_key(backend, mode, platform, stats, loss)
    if key not in _WARMED:
        _WARMED.add(key)
        return
    prev = _COSTBOOK.get(key)
    _COSTBOOK[key] = (seconds_per_step_lane if prev is None
                      else 0.7 * prev + 0.3 * seconds_per_step_lane)
    _gauge_drift(backend, mode, platform, stats, loss, seconds_per_step_lane)


def record_measured(backend: str, mode: str, platform: str,
                    stats: ProblemStats, seconds_per_step_lane: float, *,
                    loss: str = "logistic") -> None:
    """High-priority observation: the autotuner's warmed, best-of-N timings.

    Unlike :func:`record_cost` there is no first-observation discard (the
    tuner already excluded compiles) and no EWMA blending with whatever was
    there — a deliberate steady-state measurement simply becomes the book
    entry the next plan reads.
    """
    key = _cost_key(backend, mode, platform, stats, loss)
    _WARMED.add(key)
    _COSTBOOK[key] = float(seconds_per_step_lane)
    _gauge_drift(backend, mode, platform, stats, loss, seconds_per_step_lane)


def _gauge_drift(backend: str, mode: str, platform: str, stats: ProblemStats,
                 loss: str, seconds_per_step_lane: float) -> None:
    """Predicted-vs-measured gauge: measured seconds/step over the roofline
    model's prediction (> 1 means the model is optimistic).  Only evaluated
    when a collector is active — the model itself costs a few hundred flops
    we refuse to pay on the disabled path."""
    if not obs.enabled():
        return
    model = step_time_model(stats, backend, platform)
    if model > 0.0:
        obs.gauge("planner.drift", seconds_per_step_lane / model,
                  backend=backend, mode=mode, loss=loss)
    obs.observe("planner.step_seconds", seconds_per_step_lane,
                backend=backend, mode=mode)


def measured_cost(backend: str, mode: str, platform: str,
                  stats: ProblemStats, *,
                  loss: str = "logistic") -> Optional[float]:
    return _COSTBOOK.get(_cost_key(backend, mode, platform, stats, loss))


def clear_costbook() -> None:
    _COSTBOOK.clear()
    _WARMED.clear()


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SolvePlan:
    """How a sweep group executes — never *what* it computes.

    ``mode``: "vmap" runs the group as one vmapped chunked scan with
    power-of-two cohort retirement; "sequential" re-enters the width-free
    per-config chunk program (one compile for any grid size).  ``chunk_steps``
    of None defers to the per-config/planner default.
    """

    mode: str = "auto"                   # auto | vmap | sequential
    chunk_steps: Optional[int] = None
    backend: Optional[str] = None        # filled for backend="auto" configs
    notes: str = ""

    def resolved_mode(self, platform: Optional[str] = None) -> str:
        if self.mode != "auto":
            return self.mode
        return "sequential" if _platform(platform) == "cpu" else "vmap"


def _platform(platform: Optional[str] = None) -> str:
    if platform is not None:
        return platform
    import jax
    return jax.devices()[0].platform


def default_chunk(steps: int) -> int:
    return max(1, min(max(8, steps // 8), 256, steps))


# Warm λ-segments of a homotopy path re-solve from the previous λ's iterate,
# so they need only a fraction of the cold budget; steps/4 keeps the warm
# budget comfortably above the observed continuation cost on the benchmark
# twins while making a K-λ path cost ~(1 + (K-1)/4)·T instead of K·T.
PATH_WARM_DIV = 4


def path_budgets(steps: int, n_lambdas: int) -> Tuple[int, ...]:
    """Planner-predicted per-λ iteration budgets for a warm-started path.

    The first λ solves cold at the config's full ``steps`` budget; every
    later λ continues from the previous solution and gets the warm fraction
    (``steps // PATH_WARM_DIV``, clamped to [8, steps]).  Deterministic and
    shape-free by design: fit-service admission must price the exact same
    budgets the drivers later run (DESIGN.md §14).
    """
    if n_lambdas <= 0:
        return ()
    steps = int(steps)
    warm = max(1, min(steps, max(8, steps // PATH_WARM_DIV)))
    return (steps,) + (warm,) * (n_lambdas - 1)


def cohort_widths(width: int) -> Tuple[int, ...]:
    """Allowed vmap-cohort widths: powers of two down from the grid size.
    Retiring converged configs re-enters the next bucket instead of
    compiling one program per survivor count."""
    widths = []
    w = 1
    while w < width:
        widths.append(w)
        w *= 2
    widths.append(width)
    return tuple(sorted(set(widths), reverse=True))


def choose_backend(stats: ProblemStats, config: FWConfig,
                   platform: Optional[str] = None) -> str:
    """Resolve ``backend="auto"`` from the cost model.

    A config that names a mesh wants the sharded engine; otherwise the
    roofline-modeled per-iteration time decides between the Alg-1 dense scan
    (wins on small/dense designs where O(nnz + D) ≈ O(K_c·K_r)) and the
    Alg-2 kernel pipeline (wins everywhere the paper cares about — the
    sparse D ≫ N regime).
    """
    if config.mesh is not None and config.mesh != (1, 1):
        return "jax_shard"
    plat = _platform(platform)

    def per_iter(backend: str) -> float:
        # observed steady-state time beats the roofline model whenever the
        # tuner/driver has recorded one for this (backend, shape, loss) key
        got = measured_cost(backend, "sequential", plat, stats,
                            loss=config.loss)
        return got if got is not None else step_time_model(stats, backend,
                                                           plat)

    return "dense" if per_iter("dense") < per_iter("jax_sparse") \
        else "jax_sparse"


def group_mode(stats: ProblemStats, group_size: int,
               plan: Optional[SolvePlan] = None,
               platform: Optional[str] = None,
               loss: str = "logistic", backend: str = "jax_sparse") -> str:
    """vmap vs sequential for one sweep group: measured costs win, then the
    lane-overhead model, then the platform default.

    ``backend`` keys the cost-book lookup — a group running on the sharded
    engine must read (and its driver must record) ``jax_shard`` entries, not
    pollute/consult the ``jax_sparse`` book.
    """
    if plan is not None and plan.mode != "auto":
        return plan.mode
    if group_size < 2:
        return "sequential"
    plat = _platform(platform)
    seq = measured_cost(backend, "sequential", plat, stats, loss=loss)
    vm = measured_cost(backend, "vmap", plat, stats, loss=loss)
    if seq is not None and vm is not None:
        return "vmap" if vm < seq else "sequential"
    # First-order model: a B-lane vmap step costs lane·B sequential-step-
    # equivalents vs B + ~5% dispatch overhead for the loop — B cancels, so
    # without measurements the choice is a per-platform constant.  The grid
    # size matters again only through the measured branch above, which is
    # where the real signal lives.
    lane = (CPU_VMAP_LANE_OVERHEAD if plat == "cpu"
            else ACCEL_VMAP_LANE_OVERHEAD)
    return "vmap" if lane < 1.05 else "sequential"


def plan_for(X, configs: Sequence[FWConfig],
             platform: Optional[str] = None) -> SolvePlan:
    """One plan for a ``solve_many`` call (stats derived once from ``X``)."""
    stats = data_stats(X)
    plat = _platform(platform)
    steps = configs[0].steps if configs else 0
    backend = configs[0].backend if configs else "jax_sparse"
    mode = group_mode(stats, len(configs), platform=plat,
                      loss=configs[0].loss if configs else "logistic",
                      backend=backend if backend != "auto" else "jax_sparse")
    return SolvePlan(mode=mode, chunk_steps=default_chunk(steps) if steps
                     else None,
                     notes=f"platform={plat} n={stats.n} d={stats.d} "
                           f"nnz={stats.nnz} grid={len(configs)}")
