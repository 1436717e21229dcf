"""Distributed DP Frank-Wolfe via shard_map — the paper's mechanism at pod scale.

Layout (DESIGN.md §8): rows → ("pod","data"), features → "model".  Every
device (a, b) holds one BlockSparse block plus:

  state        sharding                size/device
  w, α         P("model")  (replicated over rows)   D/B
  v̄, q̄         P(rows)     (replicated over model)  N/A
  w_m, g̃, key  replicated  scalars

The coordinate selection is the paper's Big-Step-Little-Step **promoted to a
collective schedule**: each feature shard's log-sum-exp mass is the "big
step" table (now one scalar *per device column*), the winning shard is drawn
by Gumbel-max over the B gathered masses, and only the winner runs its
in-shard ("little step") draw.  Per-iteration communication:

  selection   all_gather of B scalars over "model"       (paper's √D groups)
  dv/γ lanes  psum of 3 (Kc,) lanes over "model"
  α delta     psum of D/B floats over rows — or, with ``compress_topk`` > 0,
              an all_gather of 2k floats (error-feedback top-k, the gradient
              compression hook; the residual stays on-device and is re-added
              next iteration, so nothing is lost, only delayed)
  g̃ dot      1 scalar psum over both axes

versus the O(D) gradient gather a dense DP-FW would need.  The exponential
mechanism's DP guarantee is a statement about the *law* of the selected
index; shard-then-member Gumbel-max samples exactly softmax(all logits)
(law of total probability), so the accounting in core/dp applies unchanged.
With top-k compression the selection scores lag by the residuals — the same
stale-but-bounded regime as the paper's Alg-3 queue (documented §Perf).

Like the single-device ``jax_sparse`` engine, the program is split into

  ``setup``   the first-iteration dense pass (Alg 2 lines 8-14): one local
              scatter + one α psum over the row axes — depends only on
              (X, y, loss), shared by every (λ, ε) problem;
  ``scan``    T iterations as one lax.scan with **λ, the EM log-weight scale
              and the PRNG key as traced scalars** — a λ/ε grid re-enters the
              same compiled executable, and ``solvers.batched`` can vmap the
              whole sweep where the mesh allows.

``build_dist_fw`` returns both stages (plus their jitted composition) for a
given abstract block layout; everything is jit-able and dry-runnable — the
16×16 and 2×16×16 production lowerings are exercised through the registered
``jax_shard`` backend by launch/dryrun.py --arch paper-lasso.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.dp.accountant import em_log_weight_scale
from repro.core.losses import get_loss
from repro.distributed.block_sparse import BlockSparse


@dataclasses.dataclass(frozen=True)
class DistFWConfig:
    """Native config of the distributed engine (the registry's ``jax_shard``
    backend builds the same program from an ``FWConfig`` instead).

    Private selection draws the exponential mechanism at the per-step budget
    ``per_step_epsilon(ε, δ, T)`` — the same ``core.dp.accountant`` semantics
    every other backend uses (equivalence pinned in tests/test_jax_shard.py).
    """

    lam: float = 50.0
    steps: int = 1000
    loss: str = "logistic"
    selection: str = "gumbel"     # gumbel (DP exponential mech) | argmax
    epsilon: float = 1.0
    delta: float = 1e-6
    seed: int = 0
    compress_topk: int = 0        # 0 = dense α-delta psum; k = EF-top-k exchange
    gap_tol: float = 0.0          # §9: freeze the scan once g_t ≤ gap_tol

    def em_scale(self, n_rows: int) -> float:
        if self.selection != "gumbel":
            return 1.0
        return em_log_weight_scale(
            epsilon=self.epsilon, delta=self.delta, steps=self.steps,
            n_rows=n_rows, lipschitz=get_loss(self.loss).lipschitz)


class DistFW(NamedTuple):
    """The two jitted stages of one distributed FW program + composition.

    ``setup(blocks, y_pad) -> (v̄₀, q̄₀, α₀)`` — sharded P(rows)/P(rows)/
    P("model"); ``scan(blocks, y_pad, v̄₀, q̄₀, α₀, lam, em_scale, gap_tol,
    key) -> (w, gaps, coords, stop_step)``; ``whole`` is ``scan ∘ setup`` in one jit
    (what the dry-run lowers so setup's psum is in the collective audit too).
    """

    setup: Any
    scan: Any
    whole: Any


def _row_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def build_dist_fw(blocks_abs, mesh: Mesh, *, steps: int,
                  loss: str = "logistic", selection: str = "gumbel",
                  compress_topk: int = 0, early_stop: bool = False) -> DistFW:
    """Build the (setup, scan, whole) program for one abstract block layout.

    λ, the EM scale, the gap tolerance and the PRNG key are *traced*
    arguments of ``scan`` — the whole (λ, ε)-grid shares one compile.
    Shapes, ``steps``, ``selection``, ``compress_topk`` and ``early_stop``
    are baked in.  With ``early_stop`` the scan is masked (DESIGN.md §9):
    the gap is a replicated scalar, so every device freezes its carry —
    local w/v̄/q̄/α shards, the EF-top-k residual and the PRNG key — on the
    same step, bit-for-bit, and the frozen steps' collectives exchange
    discarded values; ``gap_tol <= 0`` never triggers.
    """
    rows = _row_axes(mesh)
    b_sz = blocks_abs.csc_rows.shape[1]
    n, d = blocks_abs.shape
    n_pad, d_pad = blocks_abs.padded
    a_sz = blocks_abs.csc_rows.shape[0]
    n_loc, d_loc = n_pad // a_sz, d_pad // b_sz
    loss_fn = get_loss(loss)

    block_spec = P(rows, "model", None, None)
    blocks_spec = BlockSparse(csc_rows=block_spec, csc_vals=block_spec,
                              csr_cols=block_spec, csr_vals=block_spec,
                              shape=blocks_abs.shape, padded=blocks_abs.padded)

    # ---- setup: first-iteration dense pass (Alg 2 lines 8-14) -------------
    # Separable objectives fold the label into the residual (q̄ − y);
    # label-coupled ones carry the full row gradient in q̄ directly.
    def setup_body(blocks: BlockSparse, y_loc: jnp.ndarray):
        csr_c = blocks.csr_cols.reshape(n_loc, -1)     # (N_loc, Kr)
        csr_v = blocks.csr_vals.reshape(n_loc, -1)
        vbar0 = jnp.zeros((n_loc,), jnp.float32)
        if loss_fn.separable:
            qbar0 = loss_fn.split_grad(vbar0)
            resid_q = (qbar0 - y_loc) / n              # (N_loc,)
        else:
            qbar0 = loss_fn.grad(vbar0, y_loc)
            resid_q = qbar0 / n
        alpha_part = jnp.zeros((d_loc,), jnp.float32).at[csr_c.reshape(-1)].add(
            (resid_q[:, None] * csr_v).reshape(-1))
        alpha0 = jax.lax.psum(alpha_part, rows)
        return vbar0, qbar0, alpha0

    setup_sm = jax.shard_map(
        setup_body, mesh=mesh, in_specs=(blocks_spec, P(rows)),
        out_specs=(P(rows), P(rows), P("model")), check_vma=False)

    # ---- scan: T iterations, (λ, em_scale, gap_tol, key) traced -----------
    # ``y_loc`` is the local row shard's labels — read only by label-coupled
    # objectives (dead for separable ones, whose programs are unchanged).
    def scan_body(blocks: BlockSparse, y_loc, vbar0, qbar0, alpha0,
                  lam, em_scale, gap_tol, key):
        csc_r = blocks.csc_rows.reshape(d_loc, -1)     # (D_loc, Kc)
        csc_v = blocks.csc_vals.reshape(d_loc, -1)
        csr_c = blocks.csr_cols.reshape(n_loc, -1)     # (N_loc, Kr)
        csr_v = blocks.csr_vals.reshape(n_loc, -1)
        my_b = jax.lax.axis_index("model")
        col_valid = (my_b * d_loc + jnp.arange(d_loc)) < d
        lam = jnp.asarray(lam, jnp.float32)
        em_scale = jnp.asarray(em_scale, jnp.float32)
        gap_tol = jnp.asarray(gap_tol, jnp.float32)

        def selection_fn(alpha, key_t):
            logits = jnp.where(col_valid, em_scale * jnp.abs(alpha), -jnp.inf)
            if selection == "gumbel":
                c_me = jax.scipy.special.logsumexp(logits)
                c_all = jax.lax.all_gather(c_me, "model", tiled=False)  # (B,)
                kg, km = jax.random.split(key_t)
                bw = jnp.argmax(c_all + jax.random.gumbel(kg, (b_sz,)))
                km = jax.random.fold_in(km, my_b)
                j_self = jnp.argmax(logits + jax.random.gumbel(km, (d_loc,)))
            else:
                c_me = jnp.max(logits)
                c_all = jax.lax.all_gather(c_me, "model", tiled=False)
                bw = jnp.argmax(c_all)
                j_self = jnp.argmax(logits)
            mine = (my_b == bw)
            j_loc = jax.lax.psum(jnp.where(mine, j_self, 0), "model")
            alpha_j = jax.lax.psum(jnp.where(mine, alpha[j_self], 0.0), "model")
            return mine, j_loc, alpha_j

        def iteration(carry, t_int):
            (w_loc, w_m, g_t, vbar, qbar, alpha, resid, key,
             done, stop_at) = carry
            t = t_int.astype(jnp.float32)
            old = (w_loc, w_m, g_t, vbar, qbar, alpha, resid, key)
            key_next, key_t = jax.random.split(key)
            mine, j_loc, alpha_j = selection_fn(alpha, key_t)

            # ---- Alg 2 lines 16-21 (replicated scalar math)
            d_tilde = jnp.where(alpha_j == 0, lam, -lam * jnp.sign(alpha_j))
            gap = g_t - d_tilde * alpha_j
            eta = 2.0 / (t + 2.0)
            w_m = w_m * (1.0 - eta)
            w_loc = jnp.where(
                mine, w_loc.at[j_loc].add(eta * d_tilde / w_m), w_loc)
            g_t = g_t * (1.0 - eta) + eta * d_tilde * alpha_j

            # ---- winner broadcasts its column's lanes over "model"
            rows_j = jnp.where(mine, csc_r[j_loc], 0)
            val_j = jnp.where(mine, csc_v[j_loc], 0.0)
            rows_j = jax.lax.psum(rows_j, "model")              # (Kc,)
            val_j = jax.lax.psum(val_j, "model")
            lane_ok = val_j != 0.0

            # ---- v̄/q̄ updates (replicated over model within each row shard)
            dv = jnp.where(lane_ok, eta * d_tilde * val_j / w_m, 0.0)
            vbar = vbar.at[rows_j].add(dv)
            margins = w_m * vbar[rows_j]
            hm = (loss_fn.split_grad(margins) if loss_fn.separable
                  else loss_fn.grad(margins, y_loc[rows_j]))
            gamma = jnp.where(lane_ok, hm - qbar[rows_j], 0.0)
            qbar = qbar.at[rows_j].add(gamma)

            # ---- α-shard delta from the touched rows' local columns
            gsc = gamma / n
            cols = csr_c[rows_j]                                # (Kc, Kr)
            vals = jnp.where(lane_ok[:, None], csr_v[rows_j], 0.0)
            delta = jnp.zeros((d_loc,), jnp.float32).at[cols.reshape(-1)].add(
                (gsc[:, None] * vals).reshape(-1))
            if compress_topk:
                resid = resid + delta
                k = compress_topk
                topv, topi = jax.lax.top_k(jnp.abs(resid), k)
                sent = resid[topi]
                resid = resid.at[topi].set(0.0)
                gi = jax.lax.all_gather(topi, rows, tiled=False)   # (R, k)
                gv = jax.lax.all_gather(sent, rows, tiled=False)
                delta_sum = jnp.zeros((d_loc,), jnp.float32).at[
                    gi.reshape(-1)].add(gv.reshape(-1))
            else:
                delta_sum = jax.lax.psum(delta, rows)
            alpha = alpha + delta_sum

            # ---- g̃ line 27: partial dots reduced over both axes
            dots = jnp.sum(vals * w_loc[cols], axis=1)          # (Kc,)
            g_dot = jax.lax.psum(jnp.sum(gsc * dots),
                                 rows + ("model",)) * w_m
            g_t = g_t + g_dot

            j_global = jax.lax.psum(
                jnp.where(mine, my_b * d_loc + j_loc, 0), "model")
            j_global = j_global.astype(jnp.int32)
            new = (w_loc, w_m, g_t, vbar, qbar, alpha, resid, key_next)
            if not early_stop:
                return new + (done, stop_at), (gap, j_global)
            # ---- §9 masked stopping: gap is replicated, so all devices
            # freeze the same step and the frozen lanes stay bit-identical.
            newly = jnp.logical_and(~done, jnp.logical_and(gap_tol > 0,
                                                           gap <= gap_tol))
            kept = jax.tree_util.tree_map(
                lambda o, fresh: jnp.where(done, o, fresh), old, new)
            out = (jnp.where(done, jnp.float32(0.0), gap),
                   jnp.where(done, -1, j_global))
            return kept + (jnp.logical_or(done, newly),
                           jnp.where(newly, t_int, stop_at)), out

        carry0 = (
            jnp.zeros((d_loc,), jnp.float32), jnp.float32(1.0),
            jnp.float32(0.0), vbar0, qbar0, alpha0,
            jnp.zeros((d_loc,), jnp.float32), key,
            jnp.asarray(False), jnp.asarray(0, jnp.int32),
        )
        ts = jnp.arange(1, steps + 1, dtype=jnp.int32)
        ((w_loc, w_m, *rest), (gaps, coords)) = jax.lax.scan(
            iteration, carry0, ts)
        done, stop_at = rest[-2], rest[-1]
        stop_step = jnp.where(done, stop_at, jnp.asarray(steps, jnp.int32))
        return w_loc * w_m, gaps, coords, stop_step

    scalar = P()
    scan_sm = jax.shard_map(
        scan_body, mesh=mesh,
        in_specs=(blocks_spec, P(rows), P(rows), P(rows), P("model"),
                  scalar, scalar, scalar, scalar),
        out_specs=(P("model"), P(), P(), P()), check_vma=False)

    def whole(blocks, y_pad, lam, em_scale, gap_tol, key):
        return scan_sm(blocks, y_pad, *setup_sm(blocks, y_pad), lam, em_scale,
                       gap_tol, key)

    return DistFW(setup=jax.jit(setup_sm), scan=jax.jit(scan_sm),
                  whole=jax.jit(whole))


def distributed_fw(blocks: BlockSparse, y: jnp.ndarray, cfg: DistFWConfig,
                   mesh: Mesh):
    """Run T distributed FW iterations. y: (N_pad,) f32 padded with zeros.

    Returns (w, gaps, coords, stop_step) with w sharded over "model".
    """
    prog = build_dist_fw(blocks, mesh, steps=cfg.steps, loss=cfg.loss,
                         selection=cfg.selection,
                         compress_topk=cfg.compress_topk,
                         early_stop=cfg.gap_tol > 0)
    n = blocks.shape[0]
    return prog.whole(blocks, y, jnp.float32(cfg.lam),
                      jnp.float32(cfg.em_scale(n)),
                      jnp.float32(cfg.gap_tol),
                      jax.random.PRNGKey(cfg.seed))


def dist_fw_shardings(blocks_abs, mesh: Mesh):
    """NamedShardings matching build_dist_fw's block/label in_specs (dry-run)."""
    rows = _row_axes(mesh)
    bs = NamedSharding(mesh, P(rows, "model", None, None))
    return (
        BlockSparse(csc_rows=bs, csc_vals=bs, csr_cols=bs, csr_vals=bs,
                    shape=blocks_abs.shape, padded=blocks_abs.padded),
        NamedSharding(mesh, P(rows)),
    )
