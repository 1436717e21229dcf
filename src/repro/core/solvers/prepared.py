"""Prepared device-resident dataset: padded layouts + cached solver setup.

``PreparedDataset`` is what the solver registry's padded coercion returns
for a ``repro.data.store.DatasetStore``: the ``(PaddedCSR, PaddedCSC |
ChunkedCSC)`` pair plus a memo of the config-independent Frank-Wolfe setup
state ``(v̄₀, q̄₀, α₀)`` per loss — the O(NS) spmv sweep
``jax_sparse.fw_setup`` would otherwise re-run on every solve.

Exactness contract: on a cache miss the setup is computed by the *same*
``fw_setup_jit`` the un-prepared ``jax_sparse`` path calls, then persisted
via the ``saver`` hook (the store writes it under ``<root>/cache/``).  A hit
therefore replays bit-identical arrays, which is why ``solve(store_ref)``
takes exactly the same iterates as ``solve(X_in_memory)`` — parity pinned in
``tests/test_solvers.py``.

The cached setup is keyed to the labels it was computed against: calling
``setup_for`` with different labels bypasses the cache and computes fresh
(never poisoning the persisted state).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.core.sparse.formats import ChunkedCSC, PaddedCSC, PaddedCSR

SetupState = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]  # (v̄₀, q̄₀, α₀)
SetupLoader = Callable[[str], Optional[SetupState]]
SetupSaver = Callable[[str, SetupState], None]
# (backend, loss, platform) -> persisted autotune.TuningRecord or None
TuningLoader = Callable[[str, str, str], Optional[object]]


@dataclasses.dataclass
class PreparedDataset:
    """Padded pair + per-loss setup cache, bound to one label vector."""

    pcsr: PaddedCSR
    pcsc: Union[PaddedCSC, ChunkedCSC]
    y: np.ndarray                         # labels the setup cache is bound to
    loader: Optional[SetupLoader] = None  # disk-cache read hook (store)
    saver: Optional[SetupSaver] = None    # disk-cache write hook (store)
    tuning_loader: Optional[TuningLoader] = None   # §11 autotune replay hook
    _setup: Dict[str, SetupState] = dataclasses.field(
        default_factory=dict)
    # (backend, loss, platform) -> TuningRecord | None (None memoizes a miss)
    _tuning: Dict[Tuple[str, str, str], Optional[object]] = dataclasses.field(
        default_factory=dict)
    _tuned_csc: Dict[int, object] = dataclasses.field(default_factory=dict)

    @property
    def shape(self):
        return self.pcsr.shape

    @property
    def pair(self) -> Tuple[PaddedCSR, Union[PaddedCSC, ChunkedCSC]]:
        return self.pcsr, self.pcsc

    def _bound_labels(self, y) -> bool:
        y = np.asarray(y, dtype=np.float64)
        return y.shape == self.y.shape and bool(np.array_equal(y, self.y))

    def setup_for(self, y, loss: str) -> SetupState:
        """(v̄₀, q̄₀, α₀) for this dataset — cached, disk-backed, exact."""
        from repro.core.solvers.jax_sparse import fw_setup_jit
        if not self._bound_labels(y):
            # foreign labels: correct answer, but never cached
            return fw_setup_jit(self.pcsr, jnp.asarray(y, jnp.float32),
                                loss=loss)
        if loss not in self._setup:
            state = self.loader(loss) if self.loader else None
            if state is None:
                state = fw_setup_jit(self.pcsr,
                                     jnp.asarray(self.y, jnp.float32),
                                     loss=loss)
                if self.saver is not None:
                    self.saver(loss, state)
            self._setup[loss] = tuple(jnp.asarray(s) for s in state)
        return self._setup[loss]

    # ------------------------------------------------- §11 autotuned layout
    def tuning_for(self, backend: str, loss: str,
                   platform: Optional[str] = None):
        """The dataset's persisted autotune winner for (backend, loss) on
        the live platform, or None.  Misses are memoized too — a dataset
        with no tuning record costs one loader call per key, ever."""
        if platform is None:
            import jax
            platform = jax.devices()[0].platform
        key = (backend, loss, platform)
        if key not in self._tuning:
            rec = (self.tuning_loader(backend, loss, platform)
                   if self.tuning_loader else None)
            self._tuning[key] = rec
        return self._tuning[key]

    def set_tuning(self, record) -> None:
        """Install a freshly-searched record in-memory (the tuner's hook, so
        the session that ran the search also benefits from it)."""
        self._tuning[(record.backend, record.loss, record.platform)] = record

    def tuned_pcsc(self, record):
        """The CSC layout ``record`` names: the §11 tiered split at its
        ``ell_width``, memoized per width; the flat pair when untuned."""
        if record is None or record.ell_width is None:
            return self.pcsc
        width = int(record.ell_width)
        if width not in self._tuned_csc:
            from repro.core.sparse.formats import tiered_from_padded
            self._tuned_csc[width] = tiered_from_padded(self.pcsc, width)
        return self._tuned_csc[width]
