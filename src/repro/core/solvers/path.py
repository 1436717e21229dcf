"""Regularization-path (homotopy) solving — a full λ-path for ~one solve's
cost (DESIGN.md §14).

``solve_path(X, y, lambdas=(λ₀ > λ₁ > ...), config=...)`` solves a strictly
decreasing λ-sequence by warm-starting each λ from the previous λ's full
solver carry.  The enabling fact is structural: the Frank-Wolfe gap
certificate is ``g_t = g̃ − d̃·α_j`` with ``d̃ = ±λ`` — the carried state
(iterate ``w``/``w_m``, gradient caches ``v̄``/``q̄``/``α``, the gap
estimator ``g̃``, the sampler, the PRNG key) is **λ-independent**, so a
converged carry at λ_{k-1} is a valid, nearly-converged starting carry at
λ_k.  Since λ is already a *traced* scalar of the chunked scan programs
(``jax_sparse.fw_scan_chunk``), every λ-segment re-enters the **same
compiled chunk** — zero recompiles across the path — and continues the
global 2/(t+2) step schedule instead of restarting it (η = 1 at t = 0 would
throw the warm iterate away).

Budgets and accounting are deterministic and planner-owned, mirroring the
§13 ``screen_plan`` idiom so fit-service admission can price the exact run:

  * ``planner.path_budgets(steps, K)`` gives the per-λ iteration budgets —
    the first λ solves cold at the full ``config.steps``, later λs get the
    warm fraction.  Segment k occupies the **fixed global step slots**
    [S_{k-1}, S_k) with S_k = Σ_{i≤k} budgets, even when the gap certificate
    stops it early (frozen steps are no-ops that consume neither arithmetic
    nor DP noise) — which keeps the η schedule deterministic and makes the
    fused-across-tenants group shape bit-identical to the sequential one.
  * For private runs the whole path is **one mechanism**: T_total = Σ T_k
    selections at the uniform advanced-composition rate
    ``ε' = ε / sqrt(8·T_total·log(1/δ))``.  Each λ-segment's share is
    ``ε_k = ε·sqrt(T_k/T_total)`` — chosen exactly so that
    ``per_step_epsilon(ε_k, δ, T_k) = ε'`` for every k: the EM log-weight
    scale is *identical across segments* and the sampler state carries over
    unchanged.  The split is computed up-front (``path_plan``), charged at
    admission, and recorded in the audit ledger.

The result is a :class:`PathResult`: one per-λ :class:`FWResult` each with
its own gap trace/certificate, coordinate trail, and stop report.  Backends
without a re-enterable chunked driver refuse ``lambdas`` charge-free via the
registry's ``supports_path`` flag (``dense`` and ``jax_sparse`` support it).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.dp.accountant import em_log_weight_scale, per_step_epsilon
from repro.core.solvers.config import (FWConfig, FWResult,
                                       check_gap_certificate)
from repro.core.solvers.planner import path_budgets
from repro.core.solvers.prepared import PreparedDataset
from repro.core.solvers.registry import (_COERCE, check_path_support,
                                         get_backend, resolve_data,
                                         resolve_queue)
from repro.core.solvers.stopping import (assemble_outputs, drive_chunks,
                                         resolve_chunk)


def check_path_config(config: FWConfig) -> None:
    """Validate a λ-path config — loudly and up front, so the fit service
    rejects bad paths before charging any DP budget."""
    lambdas = config.lambdas
    if lambdas is None or len(lambdas) == 0:
        raise ValueError("a λ-path needs a non-empty lambdas sequence "
                         "(FWConfig(lambdas=(λ₀, λ₁, ...)))")
    if any(l <= 0 for l in lambdas):
        raise ValueError(f"path lambdas must be positive; got {lambdas}")
    if any(b >= a for a, b in zip(lambdas, lambdas[1:])):
        raise ValueError(
            "path lambdas must be strictly decreasing (the warm start "
            f"continues from inside the shrinking L1 ball); got {lambdas}")
    if config.screen_every > 0:
        raise ValueError(
            "screening (screen_every > 0) cannot be combined with a λ-path: "
            "coordinates screened out at one λ may re-enter at a smaller λ, "
            "so the §13 drop rule is unsound mid-path — screen per λ "
            "separately or set screen_every=0")
    if config.max_seconds is not None:
        raise ValueError(
            "max_seconds is ambiguous for a multi-λ path (per segment or "
            "whole path?) and would break the deterministic up-front "
            "ε split — use gap_tol for per-λ early stopping instead")


@dataclasses.dataclass(frozen=True)
class PathPlan:
    """Deterministic execution/accounting plan of one λ-path (§14).

    Pure arithmetic on the config — admission, the drivers, and the audit
    ledger all reproduce the same plan, which is what makes the up-front
    charge honest (mirrors ``screening.ScreenPlan``).
    """

    lambdas: Tuple[float, ...]
    budgets: Tuple[int, ...]       # per-λ iteration budgets (planner)
    offsets: Tuple[int, ...]       # global step slot each segment starts at
    total_steps: int               # Σ budgets = EM selections composed
    eps_per_step: float            # uniform per-selection rate ε'; 0.0 if
                                   # the plan was built non-private
    eps_lambdas: Tuple[float, ...]  # per-λ ε share: ε_k = ε·sqrt(T_k/T_tot)


def path_plan(config: FWConfig, *, private: bool) -> PathPlan:
    """Budgets + deterministic ε split for ``config.lambdas`` (§14).

    ``private`` mirrors ``screen_plan``: the fit service prices with
    ``private=True`` (it only charges private queues anyway); non-private
    plans carry the full ε per segment (unused — no mechanism runs).
    """
    check_path_config(config)
    lambdas = config.lambdas
    budgets = path_budgets(config.steps, len(lambdas))
    offsets, acc = [], 0
    for b in budgets:
        offsets.append(acc)
        acc += b
    total = acc
    if not private:
        return PathPlan(lambdas=lambdas, budgets=budgets,
                        offsets=tuple(offsets), total_steps=total,
                        eps_per_step=0.0,
                        eps_lambdas=(config.epsilon,) * len(lambdas))
    eps_step = per_step_epsilon(config.epsilon, config.delta, total)
    eps_lams = tuple(config.epsilon * math.sqrt(b / total) for b in budgets)
    return PathPlan(lambdas=lambdas, budgets=budgets, offsets=tuple(offsets),
                    total_steps=total, eps_per_step=eps_step,
                    eps_lambdas=eps_lams)


def segment_config(config: FWConfig, plan: PathPlan, k: int) -> FWConfig:
    """The standalone single-λ config equivalent to path segment ``k``:
    λ_k at budget T_k and ε share ε_k.  Segment 0 of a path is bit-identical
    to ``solve(X, y, segment_config(cfg, plan, 0))`` — the parity contract
    ``tests/test_path.py`` pins; later segments differ only by their warm
    starting carry."""
    return dataclasses.replace(
        config, lam=plan.lambdas[k], steps=plan.budgets[k],
        epsilon=plan.eps_lambdas[k], lambdas=None)


class PathResult:
    """A solved λ-path: one :class:`FWResult` per λ, plus the plan that
    priced it.  Sequence-like over (λ, result) positions."""

    def __init__(self, lambdas: Tuple[float, ...],
                 results: Sequence[FWResult], plan: PathPlan):
        self.lambdas = tuple(lambdas)
        self.results = tuple(results)
        self.plan = plan

    def __len__(self) -> int:
        return len(self.results)

    def __iter__(self):
        return iter(self.results)

    def __getitem__(self, k: int) -> FWResult:
        return self.results[k]

    @property
    def final(self) -> FWResult:
        """The smallest-λ (last) solution."""
        return self.results[-1]

    def __repr__(self) -> str:
        return (f"PathResult(K={len(self.results)}, "
                f"lambdas={self.lambdas}, "
                f"total_steps={self.plan.total_steps})")


def _final_gap(result: FWResult) -> float:
    gaps = result.gaps_valid
    return float(gaps[-1]) if gaps.shape[0] else float("nan")


def _emit_lambda_event(k: int, lam: float, plan: PathPlan, result: FWResult,
                       seconds: float) -> None:
    from repro import obs
    if not obs.enabled():
        return
    obs.event("path.lambda", index=k, lam=float(lam),
              budget=plan.budgets[k], offset=plan.offsets[k],
              stop_step=result.stop_step_or(plan.budgets[k]),
              stop_reason=result.stop_reason, gap=_final_gap(result),
              eps_lambda=float(plan.eps_lambdas[k]), seconds=seconds)


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


def path_em_scale(config: FWConfig, plan: PathPlan, n_rows: int) -> float:
    """EM log-weight scale for a private path — **one** value for every
    segment, by construction of the ε split: per_step_epsilon(ε_k, δ, T_k)
    is the same uniform rate for all k.  Evaluated through segment 0's
    (ε₀, T₀) so it is bitwise the scale a standalone ``solve`` of
    ``segment_config(cfg, plan, 0)`` computes (the parity contract)."""
    if config.queue != "two_level":
        return 1.0
    return em_log_weight_scale(
        epsilon=plan.eps_lambdas[0], delta=config.delta,
        steps=plan.budgets[0], n_rows=n_rows,
        lipschitz=config.loss_fn().lipschitz)


def jax_sparse_path(pcsr, pcsc, y, config: FWConfig, plan: PathPlan = None,
                    setup=None) -> PathResult:
    """Warm-started λ-path through the ``jax_sparse`` kernel pipeline.

    One :class:`jax_sparse.FWCarry` is threaded across every λ-segment;
    between segments only the §9 stopping flags (``done``/``stop_at``) are
    reset — ``w``, the gradient caches, the sampler, and the PRNG key carry
    over untouched.  Every segment re-enters the **same** compiled
    ``fw_scan_chunk`` (λ, the EM scale, gap_tol, and the global offset are
    all traced), so the path costs zero extra compiles over a single solve.
    """
    from repro import obs
    from repro.core.solvers.jax_sparse import (fw_carry_init_jit,
                                               fw_scan_chunk_jit,
                                               fw_setup_jit)
    from repro.core.solvers.planner import data_stats, record_cost

    private = config.queue == "two_level"
    if plan is None:
        plan = path_plan(config, private=private)
    n, d = pcsr.shape
    dtype = pcsr.values.dtype
    em_scale = path_em_scale(config, plan, n)
    y_scan = None if config.loss_fn().separable else jnp.asarray(y)
    if setup is None:
        with obs.span("solve.setup", loss=config.loss):
            setup = fw_setup_jit(pcsr, y, loss=config.loss)
    carry = fw_carry_init_jit(d, dtype, *setup, em_scale,
                              jax.random.PRNGKey(config.seed),
                              private=private)
    platform = jax.devices()[0].platform
    stats = data_stats((pcsr, pcsc))

    results: List[FWResult] = []
    for k, lam_k in enumerate(plan.lambdas):
        budget, seg_off = plan.budgets[k], plan.offsets[k]
        if k:
            # warm restart: un-freeze the stopping flags, keep everything else
            carry = carry._replace(done=jnp.asarray(False),
                                   stop_at=jnp.asarray(0, jnp.int32))

        def advance(carry, t0, c, _lam=lam_k, _off=seg_off):
            return fw_scan_chunk_jit(
                pcsr, pcsc, carry, _lam, em_scale, config.gap_tol,
                _off + t0, y_scan, steps=c, loss=config.loss,
                private=private, early_stop=True)

        t_seg = time.perf_counter()
        chunk = resolve_chunk(dataclasses.replace(config, steps=budget))
        carry, outs, stop_step, stop_reason = drive_chunks(
            advance, carry, steps=budget, chunk=chunk, max_seconds=None,
            done_of=lambda cy: cy.done,
            stop_at_of=lambda cy, _off=seg_off: cy.stop_at - _off)
        jax.block_until_ready(carry.w)
        dt = time.perf_counter() - t_seg
        record_cost("jax_sparse", "sequential", platform, stats,
                    dt / max(stop_step, 1), loss=config.loss)
        gaps, coords = assemble_outputs(outs, budget, (0.0, -1))
        result = FWResult(w=carry.w * carry.w_m, gaps=gaps, coords=coords,
                          losses=jnp.zeros_like(gaps), stop_step=stop_step,
                          stop_reason=stop_reason)
        results.append(result)
        _emit_lambda_event(k, lam_k, plan, result, dt)
    return PathResult(plan.lambdas, results, plan)


def dense_path(X, y, config: FWConfig, plan: PathPlan = None) -> PathResult:
    """Warm-started λ-path on the Alg-1 dense engine.

    The dense carry is just ``(w, key, done, stop_at)`` — the gradient is
    recomputed from w each step, so the warm start is the iterate alone.
    Alg 1 derives its noise scales from the (static) config, so each segment
    re-enters a per-(λ_k, T_k, ε_k) compiled chunk — correctness-first;
    the zero-recompile fast path is ``jax_sparse``.
    """
    from repro.core.fw_dense import _carry0, _dense_chunk_jit, _n_cols

    if config.queue is not None:   # registry queue name → Alg-1 selection
        config = dataclasses.replace(config, selection=config.queue,
                                     queue=None)
    private = config.selection in ("noisy_max", "gumbel")
    if plan is None:
        plan = path_plan(config, private=private)
    y = jnp.asarray(y, jnp.float32)
    carry = _carry0(X, _n_cols(X), config)

    results: List[FWResult] = []
    for k, lam_k in enumerate(plan.lambdas):
        budget, seg_off = plan.budgets[k], plan.offsets[k]
        seg_cfg = segment_config(config, plan, k)
        if k:
            carry = (carry[0], carry[1], jnp.asarray(False),
                     jnp.asarray(0, jnp.int32))

        def advance(carry, t0, c, _cfg=seg_cfg, _off=seg_off):
            return _dense_chunk_jit(X, y, carry, _off + t0,
                                    config=_cfg, chunk=c)

        t_seg = time.perf_counter()
        carry, outs, stop_step, stop_reason = drive_chunks(
            advance, carry, steps=budget, chunk=resolve_chunk(seg_cfg),
            max_seconds=None, done_of=lambda cy: cy[2],
            stop_at_of=lambda cy, _off=seg_off: cy[3] - _off)
        dt = time.perf_counter() - t_seg
        gaps, coords, losses = assemble_outputs(outs, budget, (0.0, -1, 0.0))
        result = FWResult(w=carry[0], gaps=gaps, coords=coords,
                          losses=losses, stop_step=stop_step,
                          stop_reason=stop_reason)
        results.append(result)
        _emit_lambda_event(k, lam_k, plan, result, dt)
    return PathResult(plan.lambdas, results, plan)


def run_path(backend, data, y, config: FWConfig) -> PathResult:
    """Dispatch one already-coerced, queue-resolved path config to its
    backend driver (what ``solve_path`` and the batched group runner call;
    benches call it directly to keep coercion out of timed regions)."""
    if backend.name == "jax_sparse":
        setup = None
        if isinstance(data, PreparedDataset):
            # dataset-store path: cached fw_setup replay + §11 tuned layout
            setup = data.setup_for(y, config.loss)
            pcsr, pcsc = data.pair
            rec = data.tuning_for("jax_sparse", config.loss)
            if rec is not None:
                if rec.ell_width is not None:
                    pcsc = data.tuned_pcsc(rec)
                if config.chunk_steps is None and rec.chunk_steps is not None:
                    config = dataclasses.replace(
                        config, chunk_steps=rec.chunk_steps)
        else:
            pcsr, pcsc = data
        return jax_sparse_path(pcsr, pcsc, jnp.asarray(y, jnp.float32),
                               config, setup=setup)
    if backend.name == "dense":
        return dense_path(data, y, config)
    raise ValueError(     # unreachable past check_path_support; kept loud
        f"backend {backend.name!r} has no path driver")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def solve_path(X, y=None, lambdas=None, config: Optional[FWConfig] = None,
               **overrides) -> PathResult:
    """Solve a full regularization path in ~one solve's budget (§14).

    ``lambdas`` (or ``config.lambdas``) is the strictly decreasing
    λ-sequence; everything else — data layouts accepted, queue translation,
    ``backend="auto"`` planning — behaves exactly like :func:`solve`.
    Returns a :class:`PathResult` of per-λ :class:`FWResult`\\ s.
    """
    from repro import obs
    config = config or FWConfig()
    if overrides:
        config = dataclasses.replace(config, **overrides)
    if lambdas is not None:
        config = dataclasses.replace(config, lambdas=tuple(lambdas))
    if config.lambdas is None:
        raise ValueError("solve_path needs a λ-sequence: pass lambdas=... "
                         "or a config with lambdas set")
    with obs.span("solve_path", loss=config.loss,
                  n_lambdas=len(config.lambdas)) as sp:
        check_gap_certificate(config)
        check_path_config(config)
        X, y = resolve_data(X, y)
        if config.backend == "auto":
            with obs.span("solve.plan"):
                from repro.core.solvers.planner import (choose_backend,
                                                        data_stats)
                config = dataclasses.replace(
                    config, backend=choose_backend(data_stats(X), config))
        backend = get_backend(config.backend)
        check_path_support(backend, config)
        config = resolve_queue(backend, config)
        sp.set(backend=backend.name, queue=config.queue)
        obs.count("path.solves", backend=backend.name)
        with obs.span("solve.coerce", layout=backend.data_format):
            data = _COERCE[backend.data_format](X)
        with obs.span("solve.run", backend=backend.name):
            return run_path(backend, data, y, config)
