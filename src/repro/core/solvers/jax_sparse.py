"""``jax_sparse`` backend — Algorithm 2 as one device-resident program.

This is the paper's fast iteration wired end-to-end through ``kernels/``
(DESIGN.md §5):

  * setup           — ``kernels/spmv`` ELL rmatvec builds ȳ and α₀ from the
                      padded CSR (one O(nnz) sweep each);
  * line 15 select  — ``kernels/bsls_draw`` two-level exponential-mechanism
                      draw (big step over √D group masses in XLA, little step
                      as the scalar-prefetch Pallas kernel that DMAs only the
                      winning group's tile), or the lazy group-argmax for the
                      non-private queue;
  * lines 22-28     — ``kernels/coord_update``: one coordinate's v̄, q̄, α and
                      g̃ increment from the selected column's tile;
  * line 29 refresh — once per step, a dense rebuild of the queue from the
                      final α (``tl_rebuild`` / ``ga_rebuild``).

The T-iteration loop is a single ``lax.scan``, so the whole optimization
lowers to one XLA while-loop with the kernels inlined — jit/pjit-compilable
and droppable onto the production mesh.  The Pallas kernel is compiled when
the program is lowered for a TPU and interpreted on any other platform
(``kernels.platform_kernel``); nothing in the config chooses it.

State representation (w_m-rescaling) is identical to ``fw_sparse``/``fw_jax``
— see DESIGN.md §2 — so the non-private path takes the *same steps* as both,
which the cross-backend parity test asserts.

The module is factored for batched sweeps (DESIGN.md §6) and dataset stores
(§7): ``fw_setup`` builds the config-independent state (ȳ, v̄₀, q̄₀, α₀ —
one O(nnz) pass shared by every (λ, ε) problem on the same design matrix)
and ``fw_scan`` runs the T-step loop with λ, the EM scale and the PRNG key
as *traced* scalars.  The two stages are jitted **separately**
(``fw_setup_jit`` / ``fw_scan_jit``): ``solvers.batched`` vmaps ``fw_scan``
over stacked per-config scalars, and a ``repro.data.store.DatasetStore``
persists ``fw_setup_jit``'s output so warm solves skip the setup sweep and
replay bit-identical state — both reuse paths are exact because they feed
the very arrays this module would have computed.

Gap-adaptive scheduling (DESIGN.md §9) splits the scan once more:
``fw_carry_init`` builds the full loop carry and ``fw_scan_chunk`` advances
it ``steps`` iterations starting at a *traced* global offset ``t0`` — so one
compiled chunk program is re-entered until the run converges (the FW gap
certificate g_t ≤ ``gap_tol``), times out, or exhausts T.  Early stopping is
a **masked scan**: once a chunk step observes the certificate the carry
freezes (``jnp.where`` selects the old state bit-for-bit, the PRNG key stops
splitting so DP noise draws after the stop are never consumed) and the
outputs emit (gap=0, coord=-1) sentinels.  Chunk boundaries never change the
arithmetic — iterates are bit-identical to the single whole-run scan at every
prefix, which is what the early-stopping parity tests pin.
"""
from __future__ import annotations

import time
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dp.accountant import em_log_weight_scale
from repro.core.losses import get_loss
from repro.core.samplers.bsls_jax import tl_init, tl_rebuild
from repro.core.samplers.group_argmax import ga_get_next, ga_init, ga_rebuild
from repro.core.solvers.config import STOP_MAX_STEPS, FWConfig, FWResult
from repro.core.solvers.stopping import (assemble_outputs, drive_chunks,
                                         resolve_chunk)
from repro.core.sparse.formats import (ChunkedCSC, PaddedCSR, TieredCSC,
                                       col_layout, refuse_chunked)
from repro.kernels.bsls_draw.ops import two_level_draw
from repro.kernels.coord_update.ops import coord_update
from repro.kernels.spmv.ops import ell_rmatvec


# Rows of column j's tile per coordinate-update chunk: one lane-width.
TILE_ROWS = 128


def step_chunks(col_nnz, coords) -> np.ndarray:
    """Row chunks the coordinate update ran at each step of a fixed-T scan's
    ``coords`` (any shape, e.g. (lanes, T)): ⌈nnz_j / TILE_ROWS⌉ for the
    step's coordinate j.  ``col_nnz`` is the layout's true per-column
    counts (``nnz`` of every column layout).  Host-side telemetry: it
    copies both arrays to the host."""
    return -(-np.asarray(col_nnz)[np.asarray(coords)] // TILE_ROWS)


def _scan_counts(col_nnz, coords) -> dict:
    """``solve.scan``'s deferred work counts: ``chunks`` (``step_chunks``
    summed) and ``col_nnz``, the sum over steps of nnz_j for the step's
    column — the column slots of those chunks that hold a real entry."""
    col_nnz, coords = np.asarray(col_nnz), np.asarray(coords)
    return {"chunks": int(step_chunks(col_nnz, coords).sum()),
            "col_nnz": int(col_nnz[coords].astype(np.int64).sum())}


def _pad_rows(a: jnp.ndarray) -> jnp.ndarray:
    """A (K,) column-tile vector padded with inert zeros / False to a whole
    number of ``TILE_ROWS`` chunks."""
    return jnp.pad(a, (0, -a.shape[0] % TILE_ROWS))


def fw_setup(
    pcsr: PaddedCSR, y: jnp.ndarray, *, loss: str
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Config-independent solve state: (v̄₀, q̄₀, α₀) via the spmv sweep.

    Depends only on (X, y, loss) — a λ/ε sweep over one design matrix
    computes this once and shares it across every problem in the batch.

    Separable objectives use the paper's ȳ decomposition; label-coupled ones
    carry the full row gradient in q̄ (α = Xᵀq̄/N with no ȳ term).
    """
    n = pcsr.shape[0]
    dtype = pcsr.values.dtype
    obj = get_loss(loss)
    with jax.named_scope("fw.setup"):
        vbar0 = jnp.zeros(n, dtype)
        if obj.separable:
            h = obj.split_grad
            ybar = ell_rmatvec(pcsr, y) / n
            qbar0 = h(vbar0)
            alpha0 = ell_rmatvec(pcsr, qbar0) / n - ybar
        else:
            qbar0 = obj.grad(vbar0, y)
            alpha0 = ell_rmatvec(pcsr, qbar0) / n
    return vbar0, qbar0, alpha0


class FWCarry(NamedTuple):
    """Full loop state of one Frank-Wolfe run, chunk-resumable.

    ``done``/``stop_at`` are the masked-scan early-stopping flags: once
    ``done`` flips, every later step is a frozen no-op and ``stop_at`` holds
    the number of iterations actually applied.
    """

    w: jnp.ndarray
    w_m: jnp.ndarray
    g_tilde: jnp.ndarray
    vbar: jnp.ndarray
    qbar: jnp.ndarray
    alpha: jnp.ndarray
    sampler: object
    key: jax.Array
    done: jnp.ndarray       # bool scalar
    stop_at: jnp.ndarray    # int32 scalar; valid when done


def fw_carry_init(
    d: int, dtype, vbar0, qbar0, alpha0, em_scale, key: jax.Array,
    *, private: bool,
) -> FWCarry:
    """Loop carry at t = 0 (``em_scale``/``key`` may be traced — vmappable)."""
    em_scale = jnp.asarray(em_scale, dtype)
    if private:
        sampler0 = tl_init(jnp.abs(alpha0) * em_scale)
    else:
        sampler0 = ga_init(jnp.abs(alpha0))
    return FWCarry(
        w=jnp.zeros(d, dtype), w_m=jnp.asarray(1.0, dtype),
        g_tilde=jnp.asarray(0.0, dtype), vbar=vbar0, qbar=qbar0, alpha=alpha0,
        sampler=sampler0, key=key, done=jnp.asarray(False),
        stop_at=jnp.asarray(0, jnp.int32))


def fw_scan_chunk(
    pcsr: PaddedCSR, pcsc, carry: FWCarry,
    lam, em_scale, gap_tol, t0, y=None,
    *, steps: int, loss: str, private: bool, early_stop: bool = False,
) -> Tuple[FWCarry, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Advance the carry by ``steps`` iterations starting after global step
    ``t0``; returns (carry, (gaps, coords)) for this chunk.

    ``lam`` (L1 radius), ``em_scale`` (exponential-mechanism log-weight
    scale; 1.0 when non-private), ``gap_tol`` and ``t0`` may be traced
    scalars — the first two are the vmap axis of ``solvers.batched``, the
    offset is what lets one compiled chunk be re-entered across a run.
    Everything shape- or branch-affecting (``steps``/``private``/
    ``early_stop``) is static, which is exactly what makes a sweep group
    batchable.

    With ``early_stop`` the scan is masked: the iteration that observes
    g_t ≤ gap_tol is still applied (the certificate speaks for the iterate it
    was computed from; applying one more FW step from a converged point stays
    inside the ball), after which the carry — PRNG key included, so no DP
    noise draw is ever consumed past the stop — freezes bit-for-bit and the
    outputs emit (0.0, -1).  ``gap_tol <= 0`` never triggers, so mixed
    cohorts are safe.

    ``y`` is the label vector, required (traced) for label-coupled
    objectives; separable objectives pass ``None`` so their compiled
    programs are unchanged.
    """
    n, d = pcsr.shape
    obj = get_loss(loss)
    if not obj.separable and y is None:
        raise ValueError(f"loss {loss!r} is label-coupled; pass y")
    dtype = pcsr.values.dtype
    inv_n = 1.0 / n
    lam = jnp.asarray(lam, dtype)
    em_scale = jnp.asarray(em_scale, dtype)
    gap_tol = jnp.asarray(gap_tol, dtype)
    t0 = jnp.asarray(t0, jnp.int32)

    def step(carry: FWCarry, i):
        (w, w_m, g_tilde, vbar, qbar, alpha, sampler, key,
         done, stop_at) = carry
        t = (t0 + i).astype(dtype)
        # ---- line 15: select coordinate -------------------------------------
        with jax.named_scope("fw.select"):
            key_next, sel_key = jax.random.split(key)
            if private:
                j = two_level_draw(sampler.c, sampler.v, sel_key)
            else:
                j, sampler = ga_get_next(sampler)
            j = jnp.minimum(j, d - 1)
        # ---- lines 16-21 -----------------------------------------------------
        with jax.named_scope("fw.step"):
            a_j = alpha[j]
            d_tilde = -lam * jnp.sign(a_j)
            d_tilde = jnp.where(a_j == 0, lam, d_tilde)
            gap = g_tilde - d_tilde * a_j
            eta = 2.0 / (t + 2.0)
            w_m = w_m * (1.0 - eta)
            w = w.at[j].add(eta * d_tilde / w_m)
            g_tilde = g_tilde * (1.0 - eta) + eta * d_tilde * a_j
        # ---- lines 22-28: the coordinate update over column j's tile -------
        def apply_tile(n_chunks, fetch):
            """Lines 22-28 on column j's tile, ``TILE_ROWS`` rows at a
            time: ``fetch(c)`` gives chunk c's (rows, values, mask).  Only
            the chunks holding j's nnz rows run, so a step moves
            O(nnz_j·Kr) lanes rather than the padded width's — the densest
            column sets the layout's width, not every step's cost.  Rows
            are distinct, so the chunks' v̄/q̄ updates commute.  The queue
            is refreshed after the loop, once, from the final α."""
            def chunk(c, state):
                vbar, qbar, alpha, g_tilde = state
                with jax.named_scope("fw.coord_update"):
                    r, x, m = fetch(c)
                    row_idx = pcsr.indices[r]       # (C, Kr)
                    row_val = pcsr.values[r]        # (C, Kr) — 0 at padding
                    y_col = None if obj.separable else y[r]
                    vbar, qbar, alpha, g_c = coord_update(
                        vbar, qbar, alpha, w, r, x, m, row_idx, row_val,
                        eta=eta, d_tilde=d_tilde, w_m=w_m, inv_n=inv_n,
                        loss=loss, y_col=y_col)
                return vbar, qbar, alpha, g_tilde + g_c

            return jax.lax.fori_loop(
                0, n_chunks, chunk, (vbar, qbar, alpha, g_tilde))

        def padded_tile(col):
            """``apply_tile`` on a padded column: the whole (K,) row of
            the table, sliced into chunks."""
            with jax.named_scope("fw.coord_update"):
                rows, xvals, mask = (_pad_rows(a) for a in col())   # (K,)
                n_chunks = (jnp.sum(mask) + TILE_ROWS - 1) // TILE_ROWS
            return apply_tile(n_chunks, lambda c: tuple(
                jax.lax.dynamic_slice_in_dim(a, c * TILE_ROWS, TILE_ROWS)
                for a in (rows, xvals, mask)))

        if isinstance(pcsc, TieredCSC):
            # §11 tiered layout: the few heavy columns run the full-width
            # tile, everything else the narrow one — same sums, fewer lanes
            vbar, qbar, alpha, g_tilde = jax.lax.cond(
                pcsc.is_heavy(j),
                lambda: padded_tile(lambda: pcsc.col_heavy(j)),
                lambda: padded_tile(lambda: pcsc.col_light(j)))
        elif isinstance(pcsc, ChunkedCSC):
            # §5 nnz-bounded layout: each chunk is read straight from the
            # flat entry arrays
            with jax.named_scope("fw.coord_update"):
                n_chunks = (jnp.take(pcsc.nnz, j) + TILE_ROWS - 1) // TILE_ROWS
            vbar, qbar, alpha, g_tilde = apply_tile(
                n_chunks, lambda c: pcsc.chunk(j, c, TILE_ROWS))
        else:
            vbar, qbar, alpha, g_tilde = padded_tile(lambda: pcsc.col(j))
        # ---- line 29: refresh the queue from the step's final α ------------
        # one dense O(D) pass; untouched coordinates rewrite the value they
        # hold, so the queue equals a scatter of every touched coordinate
        with jax.named_scope("fw.queue_refresh"):
            if private:
                sampler = tl_rebuild(sampler, jnp.abs(alpha) * em_scale)
            else:
                sampler = ga_rebuild(sampler, jnp.abs(alpha))
        new = FWCarry(w, w_m, g_tilde, vbar, qbar, alpha, sampler, key_next,
                      done, stop_at)
        if not early_stop:
            return new, (gap, j.astype(jnp.int32))
        # ---- §9 masked stopping: freeze frames once the certificate lands ---
        with jax.named_scope("fw.stop_mask"):
            newly = jnp.logical_and(~done, jnp.logical_and(gap_tol > 0,
                                                           gap <= gap_tol))
            frozen = carry._replace(
                done=jnp.logical_or(done, newly),
                stop_at=jnp.where(newly, t0 + i, stop_at))
            merged = jax.tree_util.tree_map(
                lambda old, fresh_leaf: jnp.where(done, old, fresh_leaf),
                frozen,
                new._replace(done=frozen.done, stop_at=frozen.stop_at))
            out_gap = jnp.where(done, jnp.asarray(0.0, dtype), gap)
            out_j = jnp.where(done, -1, j.astype(jnp.int32))
        return merged, (out_gap, out_j)

    ts = jnp.arange(1, steps + 1, dtype=jnp.int32)
    return jax.lax.scan(step, carry, ts)


def fw_scan(
    pcsr: PaddedCSR, pcsc,
    vbar0: jnp.ndarray, qbar0: jnp.ndarray, alpha0: jnp.ndarray,
    lam, em_scale, key: jax.Array, gap_tol=0.0, y=None,
    *, steps: int, loss: str, private: bool, early_stop: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Whole run as one scan; returns (w, gaps, coords, stop_step).

    ``stop_step`` is the number of iterations actually applied — ``steps``
    unless ``early_stop`` observed the gap certificate earlier.
    """
    dtype = pcsr.values.dtype
    carry0 = fw_carry_init(pcsr.shape[1], dtype, vbar0, qbar0, alpha0,
                           em_scale, key, private=private)
    carry, (gaps, coords) = fw_scan_chunk(
        pcsr, pcsc, carry0, lam, em_scale, gap_tol, 0, y,
        steps=steps, loss=loss, private=private, early_stop=early_stop)
    stop_step = jnp.where(carry.done, carry.stop_at,
                          jnp.asarray(steps, jnp.int32))
    return carry.w * carry.w_m, gaps, coords, stop_step


fw_setup_jit = jax.jit(fw_setup, static_argnames=("loss",))
fw_scan_jit = jax.jit(
    fw_scan, static_argnames=("steps", "loss", "private", "early_stop"))
fw_scan_chunk_jit = jax.jit(
    fw_scan_chunk, static_argnames=("steps", "loss", "private", "early_stop"))
fw_carry_init_jit = jax.jit(fw_carry_init, static_argnames=("d", "dtype",
                                                            "private"))


def em_scale_for(config: FWConfig, n_rows: int) -> float:
    """EM log-weight scale ε'·N/(2L) when the (native) queue is the DP
    two-level sampler; 1.0 otherwise (priorities are then raw |α|).

    A screened run's selection mechanism only gets the solve share of the
    budget — ``ε·(1 − screen_eps_frac)`` when rounds are planned (§13) —
    so the scale shrinks accordingly; the screening queries spend the rest.
    """
    if config.queue != "two_level":
        return 1.0
    epsilon = config.epsilon
    if config.screen_every > 0:
        from repro.core.solvers.screening import solve_epsilon
        epsilon = solve_epsilon(config)
    return em_log_weight_scale(
        epsilon=epsilon, delta=config.delta, steps=config.steps,
        n_rows=n_rows, lipschitz=config.loss_fn().lipschitz)


def _chunked_fw(pcsr, pcsc, setup, config: FWConfig, em_scale: float,
                private: bool, y=None) -> FWResult:
    """Host-driven chunk loop: re-enter one compiled ``fw_scan_chunk`` until
    the gap certificate lands, ``max_seconds`` expires, or T is spent
    (shared driver/assembly contract: ``solvers.stopping``)."""
    dtype = pcsr.values.dtype
    carry0 = fw_carry_init_jit(pcsr.shape[1], dtype, *setup, em_scale,
                               jax.random.PRNGKey(config.seed),
                               private=private)

    def advance(carry, t0, c):
        return fw_scan_chunk_jit(
            pcsr, pcsc, carry, config.lam, em_scale, config.gap_tol, t0, y,
            steps=c, loss=config.loss, private=private, early_stop=True)

    carry, outs, stop_step, stop_reason = drive_chunks(
        advance, carry0, steps=config.steps, chunk=resolve_chunk(config),
        max_seconds=config.max_seconds, done_of=lambda cy: cy.done,
        stop_at_of=lambda cy: cy.stop_at)
    gaps, coords = assemble_outputs(outs, config.steps, (0.0, -1))
    return FWResult(w=carry.w * carry.w_m, gaps=gaps, coords=coords,
                    losses=jnp.zeros_like(gaps), stop_step=stop_step,
                    stop_reason=stop_reason)


def _screened_chunked_fw(pcsr, pcsc, setup, config: FWConfig,
                         em_scale: float, private: bool,
                         y=None) -> FWResult:
    """§13 screened chunk loop: the §9 driver with mutable problem geometry.

    The padded pair lives in a :class:`stopping.ChunkGeometry` cell that the
    ``advance`` closure reads per entry; at every ``screen_every``-th chunk
    boundary the ``respec`` hook runs the privatized screening query over
    the live |α|, repacks the pair/carry to the survivors and swaps the
    cell — the next chunk compiles once for the smaller D and every term
    that scales with the padded width (masked-scan freezes, w/α scatter,
    √D selection, the (G, M) sampler state) shrinks with it.  Outputs are
    translated to original feature ids per chunk (``out_map``), before the
    boundary's repack changes what current-space ids mean; the final w is
    scattered back to the full D₀.  Per-chunk times are fed to the planner
    cost book against the *current* geometry's stats, so the model sees the
    shrinking D, not the admission-time one.
    """
    from repro.core.solvers.planner import data_stats, record_cost
    from repro.core.solvers.screening import (Screener, repack_carry,
                                              repack_pair)
    from repro.core.solvers.stopping import ChunkGeometry

    refuse_chunked(pcsc, "screening (FWConfig.screen_every)")
    dtype = pcsr.values.dtype
    pad_col = (pcsc.full_width if isinstance(pcsc, TieredCSC)
               else int(pcsc.indices.shape[1]))
    geom = ChunkGeometry(operands=(pcsr, pcsc), d=pcsr.shape[1],
                         pad_row=int(pcsr.indices.shape[1]), pad_col=pad_col)
    scr = Screener(config, d=pcsr.shape[1], n_rows=pcsr.shape[0],
                   row_width=int(pcsr.indices.shape[1]), em_scale=em_scale,
                   private=private)
    carry0 = fw_carry_init_jit(pcsr.shape[1], dtype, *setup, em_scale,
                               jax.random.PRNGKey(config.seed),
                               private=private)
    platform = jax.devices()[0].platform
    stats_cache = {}

    def cur_stats():
        if geom.version not in stats_cache:
            stats_cache[geom.version] = data_stats(geom.operands)
        return stats_cache[geom.version]

    def advance(carry, t0, c):
        p, q = geom.operands
        tw = time.perf_counter()
        carry, out = fw_scan_chunk_jit(
            p, q, carry, config.lam, em_scale, config.gap_tol, t0, y,
            steps=c, loss=config.loss, private=private, early_stop=True)
        jax.block_until_ready(out[0])
        record_cost("jax_sparse", "sequential", platform, cur_stats(),
                    (time.perf_counter() - tw) / c, loss=config.loss)
        return carry, out

    def out_map(out, t0):
        gaps, coords = out
        return gaps, scr.map_coords(coords)

    def respec(carry, t0, n_chunks):
        if not scr.due(n_chunks):
            return None
        keep = scr.screen(np.abs(np.asarray(carry.alpha)),
                          np.asarray(carry.w) != 0)
        if keep is None:
            return None
        tw = time.perf_counter()
        p2, q2 = repack_pair(*geom.operands, keep)
        carry2 = repack_carry(carry, keep, em_scale, private)
        pad2 = (q2.full_width if isinstance(q2, TieredCSC)
                else int(q2.indices.shape[1]))
        geom.swap((p2, q2), p2.shape[1],
                  pad_row=int(p2.indices.shape[1]), pad_col=pad2)
        info = scr.commit(keep, repack_seconds=time.perf_counter() - tw)
        return carry2, info

    carry, outs, stop_step, stop_reason = drive_chunks(
        advance, carry0, steps=config.steps, chunk=resolve_chunk(config),
        max_seconds=config.max_seconds, done_of=lambda cy: cy.done,
        stop_at_of=lambda cy: cy.stop_at, respec=respec, out_map=out_map)
    gaps, coords = assemble_outputs(outs, config.steps, (0.0, -1))
    return FWResult(w=scr.expand(carry.w * carry.w_m), gaps=gaps,
                    coords=coords, losses=jnp.zeros_like(gaps),
                    stop_step=stop_step, stop_reason=stop_reason)


def jax_sparse_fw(
    pcsr: PaddedCSR, pcsc, y: jnp.ndarray, config: FWConfig,
    setup: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray] = None,
) -> FWResult:
    """One solve through the kernel pipeline (both stages jitted).

    ``setup`` injects a precomputed ``fw_setup`` state — the dataset-store
    warm path; it must be the (v̄₀, q̄₀, α₀) this function would have
    computed (``PreparedDataset`` guarantees that by construction).

    Fixed-T configs run the single whole-run scan exactly as before;
    early-stopping configs (``gap_tol``/``max_seconds``) go through the
    chunked driver — same arithmetic per step, so iterates are bit-identical
    at every prefix.
    """
    n, _ = pcsr.shape
    private = config.queue == "two_level"
    em_scale = em_scale_for(config, n)
    y_scan = None if config.loss_fn().separable else jnp.asarray(y)

    from repro import obs
    if setup is None:
        with obs.span("solve.setup", loss=config.loss):
            setup = fw_setup_jit(pcsr, y, loss=config.loss)
    if config.screen_every > 0:
        # §13: mutable-geometry chunked driver (subsumes early stopping)
        return _screened_chunked_fw(pcsr, pcsc, setup, config, em_scale,
                                    private, y=y_scan)
    if config.early_stopping:
        return _chunked_fw(pcsr, pcsc, setup, config, em_scale, private,
                           y=y_scan)
    vbar0, qbar0, alpha0 = setup
    with obs.span("solve.scan", steps=config.steps, private=private,
                  col_layout=col_layout(pcsc)) as sp:
        t0 = time.perf_counter()
        w, gaps, coords, stop_step = fw_scan_jit(
            pcsr, pcsc, vbar0, qbar0, alpha0,
            config.lam, em_scale, jax.random.PRNGKey(config.seed), 0.0,
            y_scan, steps=config.steps, loss=config.loss, private=private)
        enqueue_s = time.perf_counter() - t0
        # queued behind the scan before any wait, so the device never idles
        # for its dispatch
        losses = jnp.zeros_like(gaps)
        if obs.enabled():
            # with a collector only: the span ends with the device run;
            # the row chunks the fit's coordinates ran, and the real
            # entries in them, are counted when the collector settles, off
            # the measured region
            sp.set(enqueue_s=enqueue_s, tile_rows=TILE_ROWS)
            jax.block_until_ready((w, gaps, coords, losses))
            col_nnz = pcsc.nnz
            sp.defer(lambda: _scan_counts(col_nnz, coords))
    return FWResult(w=w, gaps=gaps, coords=coords, losses=losses,
                    stop_step=config.steps, stop_reason=STOP_MAX_STEPS)
