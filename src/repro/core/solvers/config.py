"""Shared solver configuration/result types (DESIGN.md §4).

``FWConfig`` is the single configuration dataclass every registered backend
consumes, and ``FWResult`` the single result pytree every backend returns.
Both classes used to live in ``repro.core.fw_dense``; they are defined here
so the registry, the backends, and user code all share one vocabulary, and
re-exported from ``fw_dense`` for backward compatibility.

The config is a frozen (hashable) dataclass so it can ride through ``jax.jit``
as a static argument — every field is a Python scalar.

Queue vs. selection: Algorithm 1 (the ``dense`` backend) names its coordinate
rule ``selection`` (argmax | noisy_max | gumbel); the sparse backends name
theirs ``queue`` (fib_heap | bsls | ... on host, two_level | group_argmax on
device).  ``FWConfig`` carries both; ``queue=None`` means "this backend's
non-private default".  The registry translates equivalent names between
backends (see ``registry.QUEUE_ALIASES``) so one config can be re-targeted by
changing only ``backend=``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.losses import Loss, get_loss

# FWResult.stop_reason values (DESIGN.md §9):
STOP_MAX_STEPS = "max_steps"      # ran the full T iterations
STOP_GAP_TOL = "gap_tol"          # duality-gap certificate reached gap_tol
STOP_MAX_SECONDS = "max_seconds"  # wall-clock budget exhausted


@dataclasses.dataclass(frozen=True)
class FWConfig:
    """One Frank-Wolfe run, declaratively.

    ``repro.core.solvers.solve(X, y, FWConfig(backend=...))`` is the single
    entry point; see ``registry.available_backends()`` for the choices.
    """

    backend: str = "dense"       # dense | jax_dense | host_sparse | jax_sparse
                                 # | jax_shard | auto (planner picks, §9)
    lam: float = 50.0            # L1 radius λ (paper default for speed runs)
    steps: int = 4000            # T (paper default)
    loss: str = "logistic"
    selection: str = "argmax"    # Alg-1 rule: argmax | noisy_max | gumbel
    queue: Optional[str] = None  # Alg-2 rule; None → backend non-private default
    epsilon: float = 1.0
    delta: float = 1e-6
    seed: int = 0
    # jax_shard only: (row shards, feature shards) of the device mesh the
    # blocked solve runs on; None → 1×1 (single device — must reproduce the
    # host oracle exactly, which is what makes parity testable everywhere).
    # Other backends ignore it.  A tuple keeps the config hashable/static.
    mesh: Optional[Tuple[int, int]] = None
    # Gap-adaptive early stopping (DESIGN.md §9).  gap_tol > 0 stops the run
    # once the FW duality-gap estimate g_t falls to ≤ gap_tol: the step that
    # produced the certificate is still applied, every later step is a frozen
    # no-op (bit-identical to a run of exactly stop_step iterations).  0.0
    # (the default) disables stopping and reproduces the fixed-T program.
    gap_tol: float = 0.0
    # Wall-clock budget in seconds; None → unlimited.  Enforced per-iteration
    # by the host loops (host_sparse) and at chunk boundaries by the chunked
    # drivers (dense, jax_sparse); unsupported inside the single-scan
    # jax_dense / jax_shard programs, which reject it loudly.
    max_seconds: Optional[float] = None
    # Scan-chunk length for the chunked early-stopping drivers and the
    # batched cohort scheduler; None → planner default (steps/8 clamped to
    # [8, 256]).  Chunking never changes iterates — only how often the host
    # checks for convergence/timeouts and retires finished configs.
    chunk_steps: Optional[int] = None
    # DP iterative screening (DESIGN.md §13).  screen_every = k > 0 runs a
    # privatized screening query every k chunk boundaries: coordinates whose
    # (noisy) |α| score falls far enough below the max are dropped and the
    # padded problem geometry is repacked to the survivors, so later chunks
    # pay O(D_surviving) instead of O(D).  0 (the default) disables screening
    # and reproduces today's programs bit-for-bit.  Unlike chunking, a fired
    # screen *changes the trajectory* (dropped coordinates can no longer be
    # selected), so the §9 parity-vs-prefix contract applies only while
    # screening is off or has not fired.
    screen_every: int = 0
    # Fraction of config.epsilon reserved for the screening queries when the
    # run is private; the solve's selection mechanism runs at the remaining
    # (1 - frac)·ε.  Composed under the same advanced-composition currency as
    # the EM draws — see screening.screen_plan.  Ignored while screening is
    # off or for non-private runs (which screen noise-free, charge-free).
    screen_eps_frac: float = 0.25
    # Regularization path — homotopy solving (DESIGN.md §14).  A strictly
    # decreasing λ-sequence turns the config into one warm-started path
    # solve: each λ continues from the previous λ's iterate/active set
    # inside the same compiled chunk program (``solve_path``; ``lam`` is
    # ignored).  ``steps`` is the first λ's cold budget; later λs get the
    # planner's warm fraction (``planner.path_budgets``), and for private
    # runs ``epsilon`` is split across the whole path at one uniform
    # advanced-composition rate (``path.path_plan``), charged up-front at
    # fit-service admission.  None (the default) keeps this an ordinary
    # single-λ config and changes nothing.
    lambdas: Optional[Tuple[float, ...]] = None

    def __post_init__(self):
        # normalize any λ-sequence to a tuple of floats: the config must stay
        # hashable (jit-static, sweep-group key) even when callers pass lists
        if self.lambdas is not None:
            object.__setattr__(self, "lambdas",
                               tuple(float(l) for l in self.lambdas))

    def loss_fn(self) -> Loss:
        return get_loss(self.loss)

    @property
    def early_stopping(self) -> bool:
        """True when this config can stop before ``steps`` iterations."""
        return self.gap_tol > 0.0 or self.max_seconds is not None


def check_gap_certificate(config: FWConfig) -> None:
    """Refuse ``gap_tol`` stopping when the objective cannot certify it.

    The FW duality gap g_t upper-bounds primal suboptimality only for
    smooth (curvature-bounded) objectives; an ``Objective`` registered with
    ``smooth=False`` has no valid gap certificate, so a config asking to
    stop on one is a contract error — refused up front (charge-free in the
    fit service) rather than silently mis-stopping.  Also surfaces unknown
    loss names early (``KeyError`` from the objective registry).
    """
    obj = config.loss_fn()
    if config.gap_tol > 0.0 and not obj.smooth:
        note = obj.curvature_note or "no curvature bound"
        raise ValueError(
            f"loss {config.loss!r} is not smooth ({note}): the FW gap "
            "certificate is invalid, so gap_tol early stopping is "
            "unavailable — run fixed steps or use max_seconds on a host "
            "backend")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FWResult:
    w: jnp.ndarray          # final iterate (D,)
    gaps: jnp.ndarray       # FW gap g_t per iteration (T,); 0 after stop_step
    coords: jnp.ndarray     # selected coordinate per iteration (T,); -1 after
                            # stop_step (frozen steps select nothing)
    losses: jnp.ndarray     # mean loss per iteration (T,); zeros if untracked
    # Gap-adaptive stopping report (DESIGN.md §9).  ``stop_step`` is the
    # number of FW iterations actually applied (== len(gaps) for a full run);
    # ``w`` is exactly the iterate a run of ``stop_step`` steps produces.
    # None means "the backend predates stopping" and is normalized by
    # ``stop_step_or`` / the registry adapters.
    stop_step: Optional[Union[int, jnp.ndarray]] = None
    stop_reason: str = STOP_MAX_STEPS  # max_steps | gap_tol | max_seconds

    def tree_flatten(self):
        return ((self.w, self.gaps, self.coords, self.losses, self.stop_step),
                self.stop_reason)

    @classmethod
    def tree_unflatten(cls, stop_reason, leaves):
        return cls(*leaves, stop_reason=stop_reason)

    @property
    def nnz(self) -> jnp.ndarray:
        return jnp.sum(self.w != 0)

    def stop_step_or(self, default: Optional[int] = None) -> int:
        """``stop_step`` as a Python int; falls back to len(gaps)."""
        if self.stop_step is None:
            return int(default if default is not None else self.gaps.shape[0])
        return int(self.stop_step)

    @property
    def gaps_valid(self) -> jnp.ndarray:
        """The gap trace up to (and including) the stopping step."""
        return self.gaps[: self.stop_step_or()]

    @property
    def coords_valid(self) -> jnp.ndarray:
        return self.coords[: self.stop_step_or()]
