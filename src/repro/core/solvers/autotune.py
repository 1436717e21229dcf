"""Per-dataset layout/chunk autotuner for the device hot path (DESIGN.md §11).

The paper's `O(NS + T√D log D + TS²)` iteration cost only materializes when
the padded ELL layout fits the dataset.  It usually doesn't: text-like
designs have power-law column popularity, so the flat ``PaddedCSC`` pad
width (the exact max column nnz) is ~8× the 99th-percentile column — the
``jax_sparse`` step pays a (K_c × K_r) tile that is >100× the true work
(the BENCH_shard ``block_waste: 119.9`` finding).  This module searches a
small, bounded candidate space per dataset **without ever changing the
arithmetic**:

  * **ELL tier width** — ``TieredCSC`` splits the flat CSC at width ``k``:
    a narrow (D, k) primary table plus a full-width table for the few
    columns wider than ``k``, dispatched per step by ``lax.cond``.  Every
    candidate must pass a **bitwise parity probe** (coords/w/gaps identical
    to the flat layout, private and non-private) before it is eligible —
    an exactness gate, not a tolerance: the DP selection distribution is
    untouched because the iterates are untouched.
  * **chunk_steps** — re-entry granularity of the §9 chunked driver
    (host dispatch overhead vs post-convergence waste).
  * **jax_shard block geometry (a, b)** — mesh grids measured per dataset
    (degenerate on 1-device containers, searched on real meshes).

Timings are steady-state: every candidate program is compiled and run once
before the timed repetitions.  Winners persist as a :class:`TuningRecord`
in the ``DatasetStore`` ``cache/`` next to the padded layout — keyed by
content hash + platform + backend + loss — and are replayed on warm opens
(``store.prepared()`` wires the loader; no re-search).  Measured per-iter
times also feed ``solvers.planner`` as high-priority warmed observations,
so ``backend="auto"`` and vmap-vs-sequential choices see real numbers.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core.solvers.config import FWConfig

TUNE_VERSION = 1
# bounded search: at most this many tier-width candidates per dataset
MAX_WIDTH_CANDIDATES = 4
# chunk lengths the chunked-driver search tries (plus the planner default)
CHUNK_CANDIDATES = (16, 32, 64)


@dataclasses.dataclass(frozen=True)
class TuningRecord:
    """One dataset's tuning winner for (platform, backend, loss).

    ``ell_width`` of None means the flat layout won (or no candidate passed
    the parity probe); ``mesh`` is only set by the jax_shard search.  The
    record stores both per-iter timings so consumers (benches, the perf
    gate) can recompute the speedup it claims.
    """

    content_hash: str
    platform: str
    backend: str
    loss: str
    ell_width: Optional[int] = None
    chunk_steps: Optional[int] = None
    mesh: Optional[Tuple[int, int]] = None
    per_iter_default_ms: float = 0.0
    per_iter_tuned_ms: float = 0.0
    pass_parity: bool = True
    version: int = TUNE_VERSION

    @property
    def speedup(self) -> float:
        return self.per_iter_default_ms / max(self.per_iter_tuned_ms, 1e-12)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        if self.mesh is not None:
            d["mesh"] = list(self.mesh)
        return d

    @classmethod
    def from_json(cls, d: dict) -> Optional["TuningRecord"]:
        if not isinstance(d, dict) or d.get("version") != TUNE_VERSION:
            return None
        d = dict(d)
        if d.get("mesh") is not None:
            d["mesh"] = tuple(int(v) for v in d["mesh"])
        try:
            return cls(**d)
        except TypeError:
            return None


def candidate_widths(pcsc, max_candidates: int = MAX_WIDTH_CANDIDATES
                     ) -> List[int]:
    """Tier widths worth probing: whole-lane widths (``LANES`` times a power
    of two) from the first at or above the 90th-percentile column nnz up to
    (exclusive) the flat pad width.  Lane-aligned like the flat table, so
    the chip keeps both tiers row-major.  Bounded, and empty when the layout
    has no tail to split."""
    from repro.core.sparse.formats import LANES
    full = int(pcsc.indices.shape[1])
    cn = np.asarray(pcsc.nnz)
    if full <= LANES or cn.size == 0:
        return []
    lo = int(np.percentile(cn, 90))
    cands = []
    w = LANES
    while w < full and len(cands) < max_candidates:
        if w >= lo:
            cands.append(w)
        w *= 2
    return cands


def _bitwise_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.tobytes() == b.tobytes()


def _scan_once(pcsr, csc, setup, y_scan, *, steps, loss, lam, em_scale,
               private, seed=0):
    import jax

    from repro.core.solvers.jax_sparse import fw_scan_jit
    out = fw_scan_jit(pcsr, csc, *setup, lam, em_scale,
                      jax.random.PRNGKey(seed), 0.0, y_scan,
                      steps=steps, loss=loss, private=private)
    jax.block_until_ready(out[0])
    return out[:3]                       # (w, gaps, coords)


def _chunked_once(pcsr, csc, setup, y_scan, *, steps, loss, lam, em_scale,
                  private, chunk, seed=0):
    """(w, gaps, coords) of the §9 chunked driver: the masked chunk program
    re-entered every ``chunk`` steps, as ``jax_sparse._chunked_fw`` runs
    it."""
    import jax
    import jax.numpy as jnp

    from repro.core.solvers.jax_sparse import (fw_carry_init_jit,
                                               fw_scan_chunk_jit)
    carry = fw_carry_init_jit(pcsr.shape[1], pcsr.values.dtype, *setup,
                              em_scale, jax.random.PRNGKey(seed),
                              private=private)
    gaps, coords = [], []
    t0 = 0
    while t0 < steps:
        c = min(chunk, steps - t0)
        carry, (g, j) = fw_scan_chunk_jit(
            pcsr, csc, carry, lam, em_scale, 0.0, t0, y_scan,
            steps=c, loss=loss, private=private, early_stop=True)
        gaps.append(g)
        coords.append(j)
        t0 += c
    jax.block_until_ready(carry.w)
    return carry.w * carry.w_m, jnp.concatenate(gaps), jnp.concatenate(coords)


def probe_parity(pcsr, pcsc_default, csc_candidate, y, *, loss: str,
                 steps: int = 32, lam: float = 20.0, setup=None) -> bool:
    """The exactness gate: candidate layout must reproduce the flat layout's
    (w, gaps, coords) **bitwise**, on a private and a non-private run,
    through both drivers — the whole-run scan and the chunked driver.  The
    two compile the step in different contexts, so a candidate can agree
    in one and drift by an ulp in the other."""
    import jax.numpy as jnp

    from repro.core.losses import get_loss
    from repro.core.solvers.jax_sparse import em_scale_for, fw_setup_jit
    from repro.core.solvers.planner import default_chunk
    y32 = jnp.asarray(y, jnp.float32)
    if setup is None:
        setup = fw_setup_jit(pcsr, y32, loss=loss)
    y_scan = None if get_loss(loss).separable else y32
    for private in (False, True):
        cfg = FWConfig(steps=steps, epsilon=1.0, delta=1e-6,
                       queue="two_level" if private else "group_argmax")
        em = em_scale_for(cfg, pcsr.shape[0])
        kw = dict(steps=steps, loss=loss, lam=lam, em_scale=em,
                  private=private)
        for run in (_scan_once, functools.partial(
                _chunked_once, chunk=default_chunk(steps))):
            ref = run(pcsr, pcsc_default, setup, y_scan, **kw)
            got = run(pcsr, csc_candidate, setup, y_scan, **kw)
            if not all(_bitwise_equal(r, g) for r, g in zip(ref, got)):
                return False
    return True


def _time_per_iter_ms(fn, steps: int, repeats: int = 3) -> float:
    """Best-of-N steady-state per-iteration time; ``fn`` must block."""
    fn()                                 # warm: compile excluded
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best / steps * 1e3


def _time_layout(pcsr, csc, setup, y_scan, *, steps, loss, lam, em_scale,
                 private) -> float:
    kw = dict(steps=steps, loss=loss, lam=lam, em_scale=em_scale,
              private=private)
    return _time_per_iter_ms(
        lambda: _scan_once(pcsr, csc, setup, y_scan, **kw), steps)


def _tune_chunk(pcsr, csc, setup, y_scan, *, steps, loss, lam, em_scale,
                private) -> Optional[int]:
    """Pick the chunked-driver re-entry length: time a short chunked run at
    each candidate and keep the fastest (None = planner default wins)."""
    from repro.core.solvers.planner import default_chunk
    kw = dict(steps=steps, loss=loss, lam=lam, em_scale=em_scale,
              private=private)
    base = default_chunk(steps)
    cands = sorted({min(c, steps) for c in (base,) + CHUNK_CANDIDATES})
    timed = {c: _time_per_iter_ms(
        lambda c=c: _chunked_once(pcsr, csc, setup, y_scan, chunk=c, **kw),
        steps) for c in cands}
    best = min(timed, key=timed.get)
    return None if best == base else int(best)


def _feed_planner(backend: str, stats, per_iter_ms: float, *, loss: str,
                  platform: str, modes: Sequence[str] = ("sequential",)
                  ) -> None:
    from repro.core.solvers.planner import record_measured
    for mode in modes:
        record_measured(backend, mode, platform, stats, per_iter_ms / 1e3,
                        loss=loss)


def tune_jax_sparse(pcsr, pcsc, y, *, loss: str = "logistic", steps: int = 24,
                    probe_steps: int = 32, lam: float = 20.0,
                    content_hash: str = "", platform: Optional[str] = None,
                    setup=None, tune_chunk: bool = True) -> TuningRecord:
    """Search tier widths (+ chunk length) for the kernel pipeline.

    Candidates that fail the bitwise parity probe are discarded before any
    timing; the flat layout always remains eligible, so the tuner can only
    return a layout that is both exact and at least as fast as measured.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.losses import get_loss
    from repro.core.solvers.jax_sparse import em_scale_for, fw_setup_jit
    from repro.core.solvers.planner import data_stats
    from repro.core.sparse.formats import refuse_chunked, tiered_from_padded
    refuse_chunked(pcsc, "the tier search (autotune)")
    plat = platform or jax.devices()[0].platform
    y32 = jnp.asarray(y, jnp.float32)
    if setup is None:
        setup = fw_setup_jit(pcsr, y32, loss=loss)
    y_scan = None if get_loss(loss).separable else y32
    cfg = FWConfig(steps=steps, epsilon=1.0, delta=1e-6, queue="two_level")
    em_private = em_scale_for(cfg, pcsr.shape[0])
    kw = dict(steps=steps, loss=loss, lam=lam)

    def per_iter(csc) -> float:
        # both selection rules, worst case kept: the tuned layout must not
        # regress either the private or the non-private hot path
        return max(
            _time_layout(pcsr, csc, setup, y_scan, em_scale=1.0,
                         private=False, **kw),
            _time_layout(pcsr, csc, setup, y_scan, em_scale=em_private,
                         private=True, **kw))

    default_ms = per_iter(pcsc)
    obs.event("autotune.candidate", backend="jax_sparse", loss=loss,
              candidate="flat", per_iter_ms=default_ms, parity=True)
    best_width, best_ms = None, default_ms
    for width in candidate_widths(pcsc):
        cand = tiered_from_padded(pcsc, width)
        if not probe_parity(pcsr, pcsc, cand, y32, loss=loss,
                            steps=probe_steps, lam=lam, setup=setup):
            obs.event("autotune.candidate", backend="jax_sparse", loss=loss,
                      candidate=f"tiered-{width}", parity=False)
            continue                      # exactness gate: never eligible
        ms = per_iter(cand)
        obs.event("autotune.candidate", backend="jax_sparse", loss=loss,
                  candidate=f"tiered-{width}", per_iter_ms=ms, parity=True)
        if ms < best_ms:
            best_width, best_ms = width, ms
    winner = (tiered_from_padded(pcsc, best_width) if best_width is not None
              else pcsc)
    chunk = (_tune_chunk(pcsr, winner, setup, y_scan, em_scale=em_private,
                         private=True, **kw) if tune_chunk else None)
    stats = data_stats((pcsr, pcsc))
    _feed_planner("jax_sparse", stats, best_ms, loss=loss, platform=plat)
    obs.event("autotune.winner", backend="jax_sparse", loss=loss,
              ell_width=best_width, chunk_steps=chunk,
              per_iter_ms=best_ms,
              speedup=default_ms / max(best_ms, 1e-12))
    return TuningRecord(
        content_hash=content_hash, platform=plat, backend="jax_sparse",
        loss=loss, ell_width=best_width, chunk_steps=chunk, mesh=None,
        per_iter_default_ms=default_ms, per_iter_tuned_ms=best_ms,
        pass_parity=True)


def tune_jax_shard(src, y, *, loss: str = "logistic", steps: int = 24,
                   lam: float = 20.0, content_hash: str = "",
                   platform: Optional[str] = None) -> TuningRecord:
    """Search (a, b) block geometries for the sharded engine.

    Candidates are the factorizations of every device count ≤ the local
    device count — degenerate (just 1×1) on single-device containers, a
    real search on meshes.  Results are exact for every candidate (the
    collective schedule is parity-pinned per geometry), so only time
    decides; the winner also feeds the planner's cost book under the
    ``jax_shard`` key (the book the §9 mode choice reads for this backend).
    """
    import jax

    from repro.core.solvers.jax_shard import (make_shard_mesh, shard_em_scale,
                                              shard_program)
    from repro.core.solvers.planner import data_stats
    plat = platform or jax.devices()[0].platform
    n_dev = jax.device_count()
    cands = sorted({(a, b) for total in range(1, n_dev + 1)
                    for a in range(1, total + 1) if total % a == 0
                    for b in (total // a,)})
    cfg = FWConfig(steps=steps, lam=lam, queue="gumbel", epsilon=1.0,
                   delta=1e-6)
    em = shard_em_scale(cfg, src.shape[0])
    timings = {}
    for a, b in cands:
        mesh = make_shard_mesh(a, b)
        blocks = src.blocks(a, b)
        prog = shard_program(blocks, mesh, steps=steps, loss=loss,
                             selection="gumbel")
        import jax.numpy as jnp

        from repro.core.solvers.jax_shard import _pad_labels

        def run(mesh=mesh, blocks=blocks, prog=prog):
            with mesh:
                ypad = _pad_labels(y, blocks.padded[0])
                setup = prog.setup(blocks, ypad)
                out = prog.scan(blocks, ypad, *setup, jnp.float32(lam),
                                jnp.float32(em), jnp.float32(0.0),
                                jax.random.PRNGKey(0))
            jax.block_until_ready(out[0])

        timings[(a, b)] = _time_per_iter_ms(run, steps)
        obs.event("autotune.candidate", backend="jax_shard", loss=loss,
                  candidate=f"{a}x{b}", per_iter_ms=timings[(a, b)],
                  parity=True)
    best = min(timings, key=timings.get)
    obs.event("autotune.winner", backend="jax_shard", loss=loss,
              candidate=f"{best[0]}x{best[1]}",
              per_iter_ms=timings[best],
              speedup=timings[(1, 1)] / max(timings[best], 1e-12))
    default_ms = timings[(1, 1)]
    stats = data_stats(src.csr) if src.csr is not None else \
        data_stats(src.store)
    _feed_planner("jax_shard", stats, timings[best], loss=loss, platform=plat,
                  modes=("sequential", "vmap"))
    return TuningRecord(
        content_hash=content_hash, platform=plat, backend="jax_shard",
        loss=loss, ell_width=None, chunk_steps=None,
        mesh=best if best != (1, 1) else None,
        per_iter_default_ms=default_ms, per_iter_tuned_ms=timings[best],
        pass_parity=True)


def autotune(data, y=None, *, backend: str = "jax_sparse",
             loss: str = "logistic", steps: int = 24, probe_steps: int = 32, lam: float = 20.0,
             force: bool = False) -> TuningRecord:
    """Tune ``backend`` for one dataset; persist + replay through its store.

    ``data`` may be anything ``solve`` accepts.  For a ``DatasetStore``/
    ``DatasetRef`` the winner lands in ``cache/autotune-*.json`` (guarded by
    the content hash) and warm calls — this function *and* every consumer
    that resolves tuning through ``PreparedDataset`` — replay it without
    re-searching; ``force=True`` re-runs the search and overwrites.
    """
    import jax

    from repro.core.solvers.prepared import PreparedDataset
    from repro.core.solvers.registry import as_padded, as_shard_source, \
        resolve_data
    plat = jax.devices()[0].platform
    data, y = resolve_data(data, y)
    store = data if hasattr(data, "autotune_load") else None
    if store is not None and not force:
        rec = store.autotune_load(backend, loss, plat)
        if rec is not None:
            obs.count("autotune.replayed", backend=backend)
            return rec
    if backend == "jax_sparse":
        prepared = as_padded(data)
        if isinstance(prepared, PreparedDataset):
            pcsr, pcsc = prepared.pair
            setup = prepared.setup_for(y, loss)
        else:
            pcsr, pcsc = prepared
            setup = None
        rec = tune_jax_sparse(
            pcsr, pcsc, y, loss=loss, steps=steps,
            probe_steps=probe_steps, lam=lam,
            content_hash=getattr(store, "content_hash", ""), platform=plat,
            setup=setup)
        if isinstance(prepared, PreparedDataset):
            prepared.set_tuning(rec)
    elif backend == "jax_shard":
        src = as_shard_source(data)
        rec = tune_jax_shard(
            src, y, loss=loss, steps=steps, lam=lam,
            content_hash=getattr(store, "content_hash", ""), platform=plat)
    else:
        raise ValueError(
            f"autotune supports jax_sparse/jax_shard, got {backend!r}")
    if store is not None:
        store.autotune_save(rec)
    return rec
