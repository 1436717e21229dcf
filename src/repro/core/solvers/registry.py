"""Backend registry — the single entry point for every Frank-Wolfe solver.

    from repro.core.solvers import FWConfig, solve
    result = solve(X, y, FWConfig(backend="jax_sparse", lam=30.0, steps=500))

Backends register themselves with :func:`register`; the builtin four
(``dense``, ``jax_dense``, ``host_sparse``, ``jax_sparse``) are attached
lazily on first lookup so importing this module never drags in every solver
(and so ``fw_dense`` can import ``solvers.config`` without a cycle).

Each backend declares which data layout it consumes (``dense`` | ``host`` |
``padded``); :func:`solve` coerces the user's ``X`` — a ``HostCSR``, a dense
numpy/JAX matrix, a pre-built ``(PaddedCSR, PaddedCSC)`` pair, or a
``repro.data.store`` ``DatasetStore``/``DatasetRef`` — into that layout
once, up front.  Dataset refs also carry their own labels, so ``y`` may be
omitted; the store path reads shards off mmap and reuses the store's cached
padded layout and fw_setup state (DESIGN.md §7).  Queue names are translated
between backends via ``QUEUE_ALIASES`` so the same ``FWConfig`` can be
re-targeted by changing only ``backend=`` (DESIGN.md §4 documents the name
map).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.solvers.config import (FWConfig, FWResult,
                                       check_gap_certificate)
from repro.core.solvers.prepared import PreparedDataset
from repro.core.sparse.formats import (ChunkedCSC, HostCSR, PaddedCSC,
                                       PaddedCSR, dense_to_host,
                                       host_to_padded)


@dataclasses.dataclass(frozen=True)
class Backend:
    """A registered solver: adapter fn + the data layout and queues it speaks."""

    name: str
    fn: Callable  # (data, y, config) -> FWResult
    data_format: str                 # dense | host | padded
    queues: Mapping[str, str]        # accepted queue name -> native name
    default_queue: Optional[str]     # used when config.queue is None
    doc: str = ""
    # §9: single-compiled-scan engines cannot watch a host clock, so they
    # reject FWConfig.max_seconds; declared here so admission layers (the
    # fit service) can refuse such configs *before* charging DP budget.
    supports_max_seconds: bool = True
    # §13: chunk-boundary screening needs a host-driven chunk loop with
    # mutable geometry; engines without one refuse screen_every up front
    # (again so admission can reject charge-free).
    supports_screening: bool = False
    # §14: warm-started λ-path (homotopy) solving needs a re-enterable
    # chunked driver whose carry survives across λ segments; engines
    # without one refuse FWConfig.lambdas up front (charge-free).
    supports_path: bool = False

    def prepare(self, X):
        """Coerce ``X`` into this backend's data layout (what solve() does
        internally); use it to hoist conversion out of timed/hot loops."""
        return _COERCE[self.data_format](X)


_REGISTRY: Dict[str, Backend] = {}
_BUILTINS_LOADED = False

# Equivalent coordinate-selection rules across implementations: left column is
# what the user may write, per-backend maps pick the native realization.
# (fib_heap ≡ group_argmax ≡ argmax: exact max of |α|.  bsls ≡ two_level ≡
# gumbel: the DP exponential mechanism.)
QUEUE_ALIASES: Mapping[str, Mapping[str, str]] = {
    "host": {
        "fib_heap": "fib_heap", "argmax": "argmax", "noisy_max": "noisy_max",
        "bsls": "bsls", "group_argmax": "fib_heap", "two_level": "bsls",
        "gumbel": "bsls",
    },
    "device": {
        "two_level": "two_level", "group_argmax": "group_argmax",
        "bsls": "two_level", "gumbel": "two_level",
        "fib_heap": "group_argmax", "argmax": "group_argmax",
    },
    # Alg 1 has no queue; queue names map onto its `selection` rule.
    "selection": {
        "argmax": "argmax", "fib_heap": "argmax", "group_argmax": "argmax",
        "noisy_max": "noisy_max",
        "gumbel": "gumbel", "bsls": "gumbel", "two_level": "gumbel",
    },
    # The sharded engine realizes the same two rules as collectives:
    # shard-then-member Gumbel-max (exact EM law) and exact argmax.  No
    # noisy_max port — report-noisy-max would need a D-wide Laplace draw,
    # exactly the O(D) traffic the blocked schedule exists to avoid.
    "shard": {
        "argmax": "argmax", "fib_heap": "argmax", "group_argmax": "argmax",
        "gumbel": "gumbel", "bsls": "gumbel", "two_level": "gumbel",
    },
}


def register(name: str, *, data_format: str, queues: Mapping[str, str],
             default_queue: Optional[str], doc: str = "",
             supports_max_seconds: bool = True,
             supports_screening: bool = False,
             supports_path: bool = False) -> Callable:
    """Decorator: add ``fn(data, y, config) -> FWResult`` under ``name``."""

    def deco(fn: Callable) -> Callable:
        _REGISTRY[name] = Backend(name=name, fn=fn, data_format=data_format,
                                  queues=queues, default_queue=default_queue,
                                  doc=doc,
                                  supports_max_seconds=supports_max_seconds,
                                  supports_screening=supports_screening,
                                  supports_path=supports_path)
        return fn

    return deco


def _ensure_builtins() -> None:
    global _BUILTINS_LOADED
    if not _BUILTINS_LOADED:
        import repro.core.solvers.backends  # noqa: F401  (registers on import)
        _BUILTINS_LOADED = True


def available_backends() -> Tuple[str, ...]:
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


def get_backend(name: str) -> Backend:
    _ensure_builtins()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown solver backend {name!r}; available: "
            f"{', '.join(available_backends())}") from None


def backend_doc(name: str) -> str:
    return get_backend(name).doc


# ---------------------------------------------------------------------------
# data coercion
# ---------------------------------------------------------------------------


def _is_padded_pair(X) -> bool:
    """A ``(PaddedCSR, PaddedCSC | ChunkedCSC)`` pair, as ``as_padded``
    builds it (DESIGN.md §5)."""
    return (isinstance(X, tuple) and len(X) == 2
            and isinstance(X[0], PaddedCSR)
            and isinstance(X[1], (PaddedCSC, ChunkedCSC)))


def _as_store(X):
    """The ``DatasetStore`` behind ``X``, or None (lazy import, no cycle)."""
    from repro.data.store import DatasetStore
    return X if isinstance(X, DatasetStore) else None


def resolve_data(X, y=None):
    """Resolve a ``DatasetRef``/``DatasetStore`` ``X`` into (source, labels).

    Plain matrices pass through unchanged (``y`` then required).  A ref with
    ``split="all"`` resolves to its open ``DatasetStore`` so the coercion
    layer can reuse the store's cached padded layout and setup state;
    train/test refs materialize the row subset.  An explicitly passed ``y``
    always wins over the store's labels.
    """
    from repro.data.store import DatasetRef, DatasetStore
    if isinstance(X, DatasetRef):
        X, ref_y = X.resolve()
        y = ref_y if y is None else y
    elif isinstance(X, DatasetStore):
        y = X.labels() if y is None else y
    if y is None:
        raise TypeError(
            "y is required unless X is a DatasetRef or DatasetStore "
            "(which carry their own labels)")
    return X, y


def as_host_csr(X) -> HostCSR:
    if isinstance(X, HostCSR):
        return X
    store = _as_store(X)
    if store is not None:
        return store.to_host_csr()   # mmap-backed, zero-copy per shard
    if isinstance(X, PreparedDataset):
        X = X.pair
    if _is_padded_pair(X):
        # O(nnz) rebuild from the padded lanes — never materialize N×D.
        pcsr = X[0]
        idx = np.asarray(pcsr.indices)
        val = np.asarray(pcsr.values, np.float64)
        nnz = np.asarray(pcsr.nnz)
        lane = np.arange(idx.shape[1])[None, :]
        mask = lane < nnz[:, None]
        rows = np.broadcast_to(np.arange(idx.shape[0])[:, None], idx.shape)
        from repro.core.sparse.formats import coo_to_host
        return coo_to_host(rows[mask], idx[mask], val[mask], pcsr.shape)
    if isinstance(X, (np.ndarray, jnp.ndarray)) and np.ndim(X) == 2:
        return dense_to_host(np.asarray(X))
    raise TypeError("X must be a HostCSR, a 2-D matrix, or a (PaddedCSR, "
                    f"PaddedCSC) pair; got {type(X).__name__}")


def as_dense_jax(X) -> jnp.ndarray:
    store = _as_store(X)
    if store is not None:
        # same arrays the in-memory path sees → identical iterates
        X = store.to_host_csr()
    if isinstance(X, HostCSR):
        return jnp.asarray(X.to_dense(), jnp.float32)
    if _is_padded_pair(X):
        return X[0]  # fw_dense consumes PaddedCSR natively
    if isinstance(X, (PaddedCSR, PreparedDataset)):
        return X if isinstance(X, PaddedCSR) else X.pcsr
    if np.ndim(X) == 2:
        return jnp.asarray(X, jnp.float32)
    raise TypeError("X must be a HostCSR, a 2-D matrix, a (PaddedCSR, "
                    "PaddedCSC) pair, or a DatasetStore/DatasetRef; "
                    f"got {type(X).__name__}")


def as_padded(X):
    """→ ``(PaddedCSR, PaddedCSC)``, or a ``PreparedDataset`` for dataset
    stores (same pair plus the persisted fw_setup cache; every padded
    backend accepts either)."""
    if isinstance(X, PreparedDataset):
        return X
    store = _as_store(X)
    if store is not None:
        return store.prepared()
    if _is_padded_pair(X):
        return X
    if isinstance(X, HostCSR):
        return host_to_padded(X)
    if isinstance(X, (np.ndarray, jnp.ndarray)) and np.ndim(X) == 2:
        return host_to_padded(dense_to_host(np.asarray(X)))
    raise TypeError("X must be a HostCSR, a 2-D matrix, a (PaddedCSR, "
                    "PaddedCSC) pair, or a DatasetStore/DatasetRef; "
                    f"got {type(X).__name__}")


def as_shard_source(X):
    """→ ``repro.distributed.ingest.ShardSource`` — the ``jax_shard``
    backend's deferred block coercion (the (a × b) grid is on the config,
    not the data, so bucketing happens at solve time, memoized per grid;
    dataset stores keep their identity so the block-layout cache applies)."""
    from repro.distributed.ingest import ShardSource
    return ShardSource.from_any(X)


_COERCE = {"dense": as_dense_jax, "host": as_host_csr, "padded": as_padded,
           "blocks": as_shard_source}


# ---------------------------------------------------------------------------
# solve
# ---------------------------------------------------------------------------


def resolve_queue(backend: Backend, config: FWConfig) -> FWConfig:
    """Fill in / translate ``config.queue`` for ``backend`` (see QUEUE_ALIASES)."""
    if config.queue is None:
        return dataclasses.replace(config, queue=backend.default_queue)
    try:
        native = backend.queues[config.queue]
    except KeyError:
        raise ValueError(
            f"backend {backend.name!r} does not support queue "
            f"{config.queue!r}; accepted: {', '.join(sorted(backend.queues))}"
        ) from None
    return dataclasses.replace(config, queue=native)


def check_screening_support(backend: Backend, config: FWConfig) -> None:
    """Refuse ``screen_every`` on engines without a mutable-geometry chunk
    loop (§13) — loudly and up front, so the fit service rejects such
    configs before charging any DP budget."""
    if config.screen_every > 0 and not backend.supports_screening:
        raise ValueError(
            f"backend {backend.name!r} does not support chunk-boundary "
            "screening (screen_every > 0): it has no host-driven chunk loop "
            "with mutable problem geometry — use the dense or jax_sparse "
            "backend, or set screen_every=0")


def check_path_support(backend: Backend, config: FWConfig) -> None:
    """Refuse ``lambdas`` on engines without a re-enterable chunked driver
    (§14) — loudly and up front, so the fit service rejects such configs
    before charging any DP budget."""
    if getattr(config, "lambdas", None) is not None and not backend.supports_path:
        raise ValueError(
            f"backend {backend.name!r} does not support warm-started λ-path "
            "(homotopy) solving (lambdas=...): it has no re-enterable chunked "
            "driver that can carry the iterate across λ segments — use the "
            "dense or jax_sparse backend, or solve each λ separately")


def solve(X, y=None, config: Optional[FWConfig] = None,
          **overrides) -> FWResult:
    """Run the configured Frank-Wolfe backend on (X, y).

    ``X``: HostCSR, dense (N, D) numpy/JAX matrix, a pre-built
    ``(PaddedCSR, PaddedCSC)`` pair, or a ``DatasetStore``/``DatasetRef``
    (in which case ``y`` defaults to the store's labels).  ``y``: (N,)
    labels in {0, 1}.  Keyword overrides are applied on top of ``config``
    (``solve(X, y, backend="jax_sparse", steps=100)``).

    ``backend="auto"`` defers the engine choice to the cost-model planner
    (DESIGN.md §9): the problem's shape statistics pick between the Alg-1
    dense scan, the Alg-2 kernel pipeline, and (when ``mesh`` names a real
    grid) the sharded engine.
    """
    from repro import obs
    config = config or FWConfig()
    if overrides:
        config = dataclasses.replace(config, **overrides)
    if config.lambdas is not None:
        # a λ-path config is one warm-started homotopy solve (§14); the
        # path entry point owns validation/accounting and returns the
        # per-λ FWResult sequence as a PathResult
        from repro.core.solvers.path import solve_path
        return solve_path(X, y, config=config)
    with obs.span("solve", loss=config.loss, steps=config.steps) as sp:
        check_gap_certificate(config)   # non-smooth loss + gap_tol/unknown
        if config.screen_every:
            from repro.core.solvers.screening import check_screen_config
            check_screen_config(config)
        X, y = resolve_data(X, y)
        if config.backend == "auto":
            with obs.span("solve.plan"):
                from repro.core.solvers.planner import (choose_backend,
                                                        data_stats)
                config = dataclasses.replace(
                    config, backend=choose_backend(data_stats(X), config))
        backend = get_backend(config.backend)
        check_screening_support(backend, config)
        config = resolve_queue(backend, config)
        sp.set(backend=backend.name, queue=config.queue)
        obs.count("solve.calls", backend=backend.name)
        with obs.span("solve.coerce", layout=backend.data_format):
            data = _COERCE[backend.data_format](X)
        with obs.span("solve.run", backend=backend.name):
            return backend.fn(data, y, config)
