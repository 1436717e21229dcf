"""Unified Frank-Wolfe solver engine (DESIGN.md §4).

One API over every implementation of the paper's algorithms:

    from repro.core.solvers import FWConfig, solve
    res = solve(X, y, FWConfig(backend="jax_sparse", lam=30.0, steps=500))
    print(res.nnz, res.gaps[-1])

Backends (``available_backends()``): ``dense`` (Alg 1), ``jax_dense`` (Alg 2,
pure-jnp device scan), ``host_sparse`` (Alg 2, faithful host loop),
``jax_sparse`` (Alg 2 through ``repro.kernels``), ``jax_shard`` (Alg 2
under feature sharding on ``FWConfig.mesh`` — DESIGN.md §8).  New backends
register via ``register``.

Sweeps — many (λ, ε) problems over one design matrix — go through
``solve_many``/``grid`` (solvers.batched): compatible ``jax_sparse`` and
``jax_shard`` configs run on one shared setup + compiled scan (vmapped
where the mesh allows), everything else drains sequentially on shared
coerced data:

    results = solve_many(X, y, grid(lam=(10., 30.), epsilon=(0.1, 1.0),
                                    backend="jax_sparse", queue="bsls"))

Gap-adaptive scheduling (DESIGN.md §9): ``FWConfig.gap_tol``/``max_seconds``
stop any backend early on the duality-gap certificate (surfaced as
``FWResult.stop_step``/``stop_reason``), sweeps retire converged configs
between chunks, and ``solvers.planner`` picks backend + execution mode from
a roofline cost model (``backend="auto"``, ``solve_many(plan=...)``).

Regularization paths (DESIGN.md §14): a strictly decreasing λ-sequence
solves as one warm-started homotopy run for roughly one solve's cost —
``solve_path(X, y, lambdas=(80., 40., 20.), config=cfg)`` (equivalently
``FWConfig(lambdas=...)`` through ``solve``/``solve_many``/``FitService``)
returns a ``PathResult`` of per-λ ``FWResult``s with gap certificates and
a deterministic up-front ε split across the path.
"""
from repro.core.solvers.batched import grid, solve_many  # noqa: F401
from repro.core.solvers.config import FWConfig, FWResult  # noqa: F401
from repro.core.solvers.path import (PathPlan, PathResult,  # noqa: F401
                                     check_path_config, path_plan, solve_path)
from repro.core.solvers.planner import SolvePlan, plan_for  # noqa: F401
from repro.core.solvers.registry import (QUEUE_ALIASES, Backend,  # noqa: F401
                                         available_backends, backend_doc,
                                         get_backend, register, resolve_queue,
                                         solve)
