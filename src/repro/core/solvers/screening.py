"""DP iterative screening between solver chunks (DESIGN.md §13).

The paper's per-iteration cost is dominated by terms in the padded feature
count D (the √D·log D selection term, the O(D)-wide masked-scan freezes, the
w/α scatter lanes), and every compiled chunk of the §9 driver pays for the
*full* padded D even after most features are provably inactive.  Following
the iterative-screening idea of Khanna et al. (*Differentially Private
Iterative Screening Rules for Linear Regression*, PAPERS.md), this module
discards inactive features **mid-solve**, at the chunk boundaries the
stopping driver already re-enters:

  1. **query** — the screening score of coordinate j is |α_j|, the same
     gradient statistic the FW selection step ranks.  A private round
     releases the decision through per-coordinate Laplace noise
     ``Lap(Δ₁/ε_round)`` where ``Δ₁ = 2·L·Kr/N`` bounds the L1 sensitivity
     of the α vector under a one-row change (a row touches at most Kr
     coordinates, each by ≤ 2L/N, L the loss's Lipschitz bound — the same
     per-coordinate sensitivity the EM draws use).  Keeping a *threshold
     decision* computed from the noisy vector is post-processing, so each
     round is ε_round-DP.
  2. **rule** — keep j iff its noisy score is within ``margin`` of the noisy
     max, where ``margin = TAIL_LOG_MASS/em_scale + NOISE_SLACK·b``: the
     first term bounds the selection-probability mass the EM sampler could
     ever put on a dropped coordinate (a coordinate τ em-units below the max
     is selected with odds ≤ e^{-τ} per draw), the second absorbs the
     screening noise itself.  The support of w and a minimum survivor floor
     are always kept, so the continued problem *contains* the solution path
     built so far.
  3. **repack** — survivors are compacted into a fresh padded ELL/CSC pair
     (pad widths shrink to the survivors' true maxima), the carry is
     column-subset, and the sampler state is rebuilt from the live |α|
     values — value-exact, because both sampler inits are pure functions of
     the priority vector.

ε-composition: a run planning R screening rounds at total budget ε splits
it as ``ε_screen = screen_eps_frac·ε`` (spread over the R rounds by the
same advanced-composition rule the EM draws use) and runs the solve's
selection mechanism at ``ε_solve = ε − ε_screen``.  Both sub-budgets are
charged up-front at admission (``FitService``), so the composed release is
(ε, δ)-DP no matter where the run actually stops.  Non-private runs screen
noise-free (no ε split, no charge).

Exactness of continuation: with supp(w) ⊆ survivors, X_S·w_S = X·w, so
v̄/q̄ are untouched by the repack and the restricted α_S dynamics are
exactly the full dynamics observed on S.  What screening *does* change is
the selection domain — a dropped coordinate can never be chosen again — so
the §9 parity-vs-prefix contract holds only until the first round fires
(``screen_every=0``, the default, keeps every existing program bit-exact).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.dp.accountant import per_step_epsilon
from repro.core.solvers.config import FWConfig
from repro.core.sparse.formats import (PaddedCSC, PaddedCSR, TieredCSC,
                                       lane_padded, refuse_chunked,
                                       tiered_from_padded)

# Survivor floor: never screen below max(DEFAULT_MIN_KEEP, √D₀) coordinates —
# the later FW iterations need a working set, and √D is the natural group
# granularity of the two-level sampler.
DEFAULT_MIN_KEEP = 16
# Keep margin in units of the Laplace scale b: a true score more than
# NOISE_SLACK·b below the threshold is dropped despite the noise w.h.p.,
# one above survives w.h.p. (P[|Lap(b)| > 4b] ≈ 1.8%).
NOISE_SLACK = 4.0
# Keep margin in EM log-weight units: a coordinate TAIL_LOG_MASS em-units
# below the max carries ≤ e^-TAIL_LOG_MASS ≈ 1e-3 of the max's selection
# odds per draw, so the dropped set is (numerically) invisible to the
# sampler the solve would have run.
TAIL_LOG_MASS = 7.0
# Non-private rule: keep scores within this fraction of the max (plus the
# support/floor guarantees) — no noise, no ε charge.
NP_KEEP_FRACTION = 0.5


@dataclasses.dataclass(frozen=True)
class ScreenPlan:
    """The ε ledger of one screened run, fixed before the first iteration.

    ``rounds`` is planned deterministically from (steps, chunk, screen_every)
    — never from how far the run actually gets — so admission can charge the
    whole composed release up-front.  Early stopping only *under*-uses it.
    """

    rounds: int          # screening rounds the schedule can fire
    eps_solve: float     # budget left to the selection mechanism
    eps_screen: float    # total screening budget (0 when rounds == 0)
    eps_round: float     # per-round pure-DP budget (advanced composition)


def check_screen_config(config: FWConfig) -> None:
    """Refuse malformed screening knobs up front (charge-free in the fit
    service): ``screen_every`` must be a non-negative chunk count and the ε
    fraction must leave both phases a positive budget."""
    if config.screen_every < 0:
        raise ValueError(
            f"screen_every must be >= 0, got {config.screen_every}")
    if config.screen_every == 0:
        return
    if not 0.0 < config.screen_eps_frac < 1.0:
        raise ValueError(
            "screen_eps_frac must be in (0, 1) so both the screening "
            f"queries and the solve keep a positive ε share; got "
            f"{config.screen_eps_frac}")


def screening_rounds(steps: int, chunk: int, screen_every: int) -> int:
    """Rounds the chunk schedule can fire: one per ``screen_every`` interior
    chunk boundaries (the final boundary ends the run — nothing to repack)."""
    if screen_every <= 0:
        return 0
    n_chunks = -(-steps // max(chunk, 1))
    return max(0, (n_chunks - 1) // screen_every)


def screen_plan(config: FWConfig, *, private: bool) -> ScreenPlan:
    """Split ``config.epsilon`` between screening rounds and the solve.

    The R rounds compose like R extra mechanism invocations at their own
    advanced-composition rate: ``ε_round = ε_screen/√(8R·log(1/δ))`` — the
    same currency ``per_step_epsilon`` denominates the EM draws in, which is
    what lets ``FitService._charged_steps`` price both phases in one pool.
    Non-private runs (and schedules that can never fire) keep the full ε
    for the solve.
    """
    check_screen_config(config)
    from repro.core.solvers.stopping import resolve_chunk
    rounds = screening_rounds(config.steps, resolve_chunk(config),
                              config.screen_every)
    if not private or rounds == 0:
        return ScreenPlan(rounds=rounds, eps_solve=config.epsilon,
                          eps_screen=0.0, eps_round=0.0)
    eps_screen = config.epsilon * config.screen_eps_frac
    eps_solve = config.epsilon - eps_screen
    return ScreenPlan(
        rounds=rounds, eps_solve=eps_solve, eps_screen=eps_screen,
        eps_round=per_step_epsilon(eps_screen, config.delta, rounds))


def solve_epsilon(config: FWConfig) -> float:
    """ε available to the selection mechanism of a *private* screened run
    (the full ``config.epsilon`` when screening is off or can never fire).
    The single place the DP backends read the split from."""
    if config.screen_every <= 0:
        return config.epsilon
    return screen_plan(config, private=True).eps_solve


# ---------------------------------------------------------------------------
# geometry repack: column-subset the padded pair, exactly
# ---------------------------------------------------------------------------


def _csc_full_arrays(pcsc) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Full-width numpy (indices, values, nnz) of any CSC layout — the §11
    tiered split is re-flattened (heavy rows overwrite their truncated light
    copies) so the repack sees every entry exactly once."""
    refuse_chunked(pcsc, "the screening repack")
    if isinstance(pcsc, TieredCSC):
        d = pcsc.indices.shape[0]
        full = pcsc.full_width
        ci = np.zeros((d, full), np.int32)
        cv = np.zeros((d, full), np.float32)
        ci[:, : pcsc.width] = np.asarray(pcsc.indices)
        cv[:, : pcsc.width] = np.asarray(pcsc.values)
        cn = np.asarray(pcsc.nnz)
        heavy = np.flatnonzero(cn > pcsc.width)
        if heavy.size:
            slots = np.asarray(pcsc.heavy_slot)[heavy]
            ci[heavy] = np.asarray(pcsc.heavy_indices)[slots]
            cv[heavy] = np.asarray(pcsc.heavy_values)[slots]
        return ci, cv, cn
    return (np.asarray(pcsc.indices), np.asarray(pcsc.values),
            np.asarray(pcsc.nnz))


def repack_csr(pcsr: PaddedCSR, keep: np.ndarray) -> PaddedCSR:
    """Column-subset repack of the padded ELL rows.

    Surviving entries are remapped to the compacted column ids and compacted
    to the front of each row (stable order — the per-row entry order every
    kernel reduction sees is preserved); the pad width shrinks to the
    survivors' true max row nnz.  Dropped/padding lanes become the canonical
    inert (index=0, value=0) padding.
    """
    keep = np.asarray(keep, bool)
    sel = np.flatnonzero(keep)
    remap = np.zeros(keep.size, np.int64)
    remap[sel] = np.arange(sel.size)
    ri = np.asarray(pcsr.indices)
    rv = np.asarray(pcsr.values)
    rn = np.asarray(pcsr.nnz)
    lane = np.arange(ri.shape[1])[None, :]
    live = (lane < rn[:, None]) & keep[ri]
    new_idx = np.where(live, remap[ri], 0).astype(np.int32)
    new_val = np.where(live, rv, 0).astype(rv.dtype)
    order = np.argsort(~live, axis=1, kind="stable")
    rn_new = live.sum(axis=1).astype(np.int32)
    k_row = max(1, int(rn_new.max()) if rn_new.size else 1)
    new_idx = np.take_along_axis(new_idx, order, axis=1)[:, :k_row]
    new_val = np.take_along_axis(new_val, order, axis=1)[:, :k_row]
    return PaddedCSR(jnp.asarray(new_idx), jnp.asarray(new_val),
                     jnp.asarray(rn_new), (pcsr.shape[0], int(sel.size)))


def repack_pair(
    pcsr: PaddedCSR, pcsc, keep: np.ndarray
) -> Tuple[PaddedCSR, Union[PaddedCSC, TieredCSC]]:
    """Repack both padded layouts to the surviving columns.

    The CSC side is a row (= column-major) subset with the pad width cut to
    the survivors' max column nnz; a §11 tiered input is re-tiered at its
    original light width when the survivors still exceed it (the tuner's
    choice outlives the repack), else collapses to the flat layout.
    """
    keep = np.asarray(keep, bool)
    sel = np.flatnonzero(keep)
    new_csr = repack_csr(pcsr, keep)
    ci, cv, cn = _csc_full_arrays(pcsc)
    ci2, cv2, cn2 = ci[sel], cv[sel], cn[sel].astype(np.int32)
    k_col = lane_padded(cn2.max() if cn2.size else 1)
    flat = PaddedCSC(jnp.asarray(ci2[:, :k_col].astype(np.int32)),
                     jnp.asarray(cv2[:, :k_col].astype(np.float32)),
                     jnp.asarray(cn2), (pcsr.shape[0], int(sel.size)))
    if isinstance(pcsc, TieredCSC) and pcsc.width < k_col:
        return new_csr, tiered_from_padded(flat, pcsc.width)
    return new_csr, flat


def repack_dense(X, keep: np.ndarray):
    """Column-subset an Alg-1 design (dense device matrix or PaddedCSR)."""
    if isinstance(X, PaddedCSR):
        return repack_csr(X, keep)
    return jnp.asarray(X)[:, np.flatnonzero(np.asarray(keep, bool))]


def repack_carry(carry, keep: np.ndarray, em_scale, private: bool):
    """Column-subset a ``jax_sparse.FWCarry`` to the survivors.

    w/α are sliced; v̄/q̄/g̃ are row-space and — because supp(w) is always
    kept — already equal to the restricted problem's state.  The sampler is
    *rebuilt* from the live |α| values, which is value-exact: the scan's
    per-step refresh (``tl_rebuild``) rewrites the whole table from |α| and
    takes each changed group's logsumexp from it, and the lazy argmax
    ratchet re-derives its bounds from the same priorities, so both inits
    reproduce the state the restricted run would hold.
    """
    from repro.core.samplers.bsls_jax import tl_init
    from repro.core.samplers.group_argmax import ga_init
    sel = jnp.asarray(np.flatnonzero(np.asarray(keep, bool)))
    w = carry.w[sel]
    alpha = carry.alpha[sel]
    if private:
        sampler = tl_init(jnp.abs(alpha) * jnp.asarray(em_scale, alpha.dtype))
    else:
        sampler = ga_init(jnp.abs(alpha))
    return carry._replace(w=w, alpha=alpha, sampler=sampler)


# ---------------------------------------------------------------------------
# the per-run orchestrator
# ---------------------------------------------------------------------------


class Screener:
    """Bookkeeping of one screened run: the DP keep rule, the cumulative
    original-index map, round/ε accounting, and the obs trail.

    Backends own the representation-specific glue (what a "score" or a
    "repack" is for their carry); this class owns everything that must not
    drift between them: when a round is due, how the noisy decision is made,
    and how results map back to the original feature space.
    """

    def __init__(self, config: FWConfig, *, d: int, n_rows: int,
                 row_width: int, em_scale: float, private: bool):
        check_screen_config(config)
        if config.screen_every <= 0:
            raise ValueError("Screener requires screen_every > 0")
        self.config = config
        self.private = bool(private)
        self.plan = screen_plan(config, private=private)
        self.d0 = int(d)
        self.sel = np.arange(self.d0, dtype=np.int64)   # current -> original
        self.rounds_done = 0
        lipschitz = config.loss_fn().lipschitz
        # L1 sensitivity of the α release under a one-row change: ≤ row_width
        # touched coordinates, each moved by ≤ 2L/N.
        self.sensitivity = 2.0 * lipschitz * int(row_width) / max(int(n_rows), 1)
        self.noise_b = (self.sensitivity / self.plan.eps_round
                        if self.private and self.plan.rounds else 0.0)
        self.em_scale = float(em_scale)
        self.min_keep = max(DEFAULT_MIN_KEEP, math.isqrt(self.d0))

    # ------------------------------------------------------------- schedule
    @property
    def d_current(self) -> int:
        return int(self.sel.size)

    def due(self, n_chunks: int) -> bool:
        """Is a round due at the boundary after chunk ``n_chunks``?  (The
        driver only asks at boundaries the run will continue past.)"""
        return (self.rounds_done < self.plan.rounds
                and n_chunks % self.config.screen_every == 0)

    # ----------------------------------------------------------------- rule
    def screen(self, scores: np.ndarray,
               support: np.ndarray) -> Optional[np.ndarray]:
        """Run one screening round over the current-space ``scores`` (|α|).

        Returns the keep mask, or None when every coordinate survives (the
        round is still consumed — its noisy query was asked and its ε
        spent).  ``support`` marks coordinates that must survive (supp(w)).
        """
        scores = np.asarray(scores, np.float64)
        support = np.asarray(support, bool)
        d = scores.shape[0]
        if self.private:
            rng = np.random.default_rng(
                (int(self.config.seed) & 0xFFFFFFFF, self.rounds_done,
                 0x5C12EE))
            noisy = scores + rng.laplace(0.0, self.noise_b, d)
            margin = (TAIL_LOG_MASS / max(self.em_scale, 1e-12)
                      + NOISE_SLACK * self.noise_b)
            keep = noisy >= noisy.max() - margin
        else:
            noisy = scores
            keep = scores >= NP_KEEP_FRACTION * scores.max()
        keep |= support
        floor = min(self.min_keep, d)
        if int(keep.sum()) < floor:
            # rank by the same (noisy) release — post-processing, no extra ε
            top = np.argpartition(noisy, d - floor)[d - floor:]
            keep[top] = True
        if keep.all():
            self.rounds_done += 1
            if obs.enabled():
                obs.event("screen.round", round=self.rounds_done,
                          survivors=d, dropped=0,
                          eps_round=self.plan.eps_round, repacked=False)
            return None
        return keep

    def commit(self, keep: np.ndarray, *, repack_seconds: float) -> dict:
        """Record a fired round: fold ``keep`` into the original-index map
        and emit the survivor/timing trail.  Returns the round's obs facts
        (the driver forwards them to the ``chunks.respec`` event)."""
        keep = np.asarray(keep, bool)
        kept = np.flatnonzero(keep)
        dropped = int(keep.size - kept.size)
        self.sel = self.sel[kept]
        self.rounds_done += 1
        if obs.enabled():
            obs.event("screen.round", round=self.rounds_done,
                      survivors=int(kept.size), dropped=dropped,
                      eps_round=self.plan.eps_round,
                      repack_seconds=round(repack_seconds, 6), repacked=True)
            obs.gauge("screen.survivors", int(kept.size))
            obs.observe("screen.repack_seconds", repack_seconds)
            obs.count("screen.rounds")
        return {"round": self.rounds_done, "survivors": int(kept.size),
                "dropped": dropped}

    # ------------------------------------------------------------ index map
    def map_coords(self, coords) -> jnp.ndarray:
        """Chunk-output coordinates (current space) → original feature ids,
        -1 sentinels passing through.  Must be applied with the ``sel``
        active when the chunk *ran* — the driver's ``out_map`` hook fires
        before the boundary's repack, which is exactly that."""
        c = np.asarray(coords)
        safe = np.clip(c, 0, max(self.sel.size - 1, 0))
        return jnp.asarray(np.where(c >= 0, self.sel[safe], -1)
                           .astype(np.int32))

    def expand(self, w) -> jnp.ndarray:
        """Survivor-space iterate → original D₀-length vector (zeros on the
        screened-out coordinates, which the kept-support invariant makes
        exact, not approximate)."""
        w = np.asarray(w)
        full = np.zeros(self.d0, w.dtype)
        full[self.sel] = w
        return jnp.asarray(full)
