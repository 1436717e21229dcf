"""Batched multi-problem solving — vmapped λ/ε sweeps (DESIGN.md §6).

Real deployments never fit one (λ, ε) problem: they sweep regularization ×
privacy grids over the *same* design matrix.  Run sequentially, every problem
re-pays the O(NS) setup (data coercion, ȳ/α₀ spmv sweeps) and its own chain
of kernel launches.  ``solve_many`` amortizes all of it:

    from repro.core.solvers import FWConfig, grid, solve_many
    configs = grid(FWConfig(backend="jax_sparse", steps=500, queue="bsls"),
                   lam=(10.0, 30.0, 50.0), epsilon=(0.1, 1.0))
    results = solve_many(X, y, configs)        # list[FWResult], input order

Mechanics:

  * configs are bucketed into **sweep groups** — same backend / steps /
    resolved queue / loss / mesh (everything that shapes
    the compiled program); λ, ε, δ and seed may vary freely inside a group;
  * ``X`` is coerced **once per data layout**, not once per config;
  * a ``jax_sparse`` group shares the config-independent ``fw_setup`` state
    and one compiled scan through the spmv / coord_update / bsls_draw
    kernels — run as a single jitted ``vmap`` over stacked (λ, EM-scale,
    PRNG-key) triples, or as sequential re-entries of the width-free chunk
    program, whichever the §9 planner says is faster on this platform;
  * a ``jax_shard`` group shares one block build + setup and re-enters one
    compiled scan (vmapped over the stacked scalars on a 1×1 mesh, where
    the whole stack fits one device program; sequential re-entries on real
    grids — λ/ε/key are traced either way, so never a recompile);
  * every other backend (and singleton groups) drains through the normal
    per-config adapter on the pre-coerced data — same results, no compile
    blow-up for host loops that would not benefit.

Parity is structural, not approximate: the batched path calls the *same*
``fw_scan`` the sequential backend closes over, with the per-config scalars
traced instead of constant — tests assert step-for-step identical coordinate
sequences on the same keys.

Gap-adaptive scheduling (DESIGN.md §9) adds the **cohort** execution mode:
when a group's configs carry ``gap_tol``/``max_seconds``, the grid runs in
chunks of the shared compiled ``fw_scan_chunk`` and configs that converge
are *retired* between chunks, so the sweep stops paying for its slowest
member.  Which mode a group uses — one vmapped program vs sequential
re-entries of the width-free chunk program — is decided per problem by
``solvers.planner`` (measured per-iteration costs beat the model beat the
platform default); pass ``plan=`` to override.  Every mode runs the same
state machine on the same keys, so gap-certified results are independent of
the plan.  The one necessarily schedule-dependent knob is ``max_seconds``:
a wall-clock budget counts from when the config's execution starts — its
own ``solve()`` in sequential mode, the group's first chunk in cohort mode
(the lanes really do run concurrently) — so where a timeout lands depends
on how the grid was scheduled, as any wall-clock limit must.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.solvers.config import (STOP_GAP_TOL, STOP_MAX_SECONDS,
                                       STOP_MAX_STEPS, FWConfig, FWResult,
                                       check_gap_certificate)
from repro.core.solvers.planner import SolvePlan, record_cost
from repro.core.solvers.registry import (check_path_support,
                                         check_screening_support, get_backend,
                                         resolve_data, resolve_queue)

# FWConfig fields that must agree within one vmapped sweep group: they are
# jit-static (shape the compiled scan) or flip a Python-level branch.  The
# complementary set — lam / epsilon / delta / seed / gap_tol / max_seconds —
# is what a group stacks (the stopping knobs ride as traced scalars or
# host-side checks, so they never split a group).  The §13 screening knobs
# are group fields because a fired screen changes the problem *shape*: two
# screened members diverge to different widths (DP noise makes survivor sets
# seed-dependent), so a screened group can never be lane-stacked and must
# not mix with unscreened members.  ``lambdas`` (§14) is a group field
# because a λ-path is a different *control flow* — sequential-in-λ segments
# through shared global step slots — and only identical paths can share the
# fused-across-tenants schedule.
GROUP_FIELDS = ("backend", "steps", "queue", "loss", "selection", "mesh",
                "chunk_steps", "screen_every", "screen_eps_frac", "lambdas")


def grid(base: FWConfig | None = None, **axes) -> Tuple[FWConfig, ...]:
    """Cartesian product of FWConfig axes, for ``solve_many``.

    Each keyword is an FWConfig field; iterable values become sweep axes
    (crossed in the order given, last axis fastest), scalars are applied to
    every point::

        grid(lam=(10, 30), epsilon=(0.1, 1.0), backend="jax_sparse",
             queue="bsls", steps=200)   # -> 4 configs

    Strings are scalars, never axes.
    """
    base = base or FWConfig()

    def _scalar(k, v):
        if isinstance(v, str) or not isinstance(v, Iterable):
            return True
        # one mesh spec (a tuple of ints) / one λ-path (a sequence of
        # numbers) is a value, not a sweep axis; a sequence of tuples
        # sweeps meshes/paths
        if k == "mesh":
            return bool(v) and all(isinstance(x, int) for x in v)
        if k == "lambdas":
            return bool(v) and all(isinstance(x, (int, float)) for x in v)
        return False

    # mesh/lambdas specs normalize to tuples (both FWConfig fields must stay
    # hashable for solve_many/FitService grouping even when the caller wrote
    # a list)
    fixed = {k: tuple(v) if k in ("mesh", "lambdas") and _scalar(k, v)
             and v is not None else v
             for k, v in axes.items() if _scalar(k, v)}
    sweep = {k: tuple(tuple(x) if k in ("mesh", "lambdas") else x for x in v)
             for k, v in axes.items() if k not in fixed}
    unknown = set(axes) - {f.name for f in dataclasses.fields(FWConfig)}
    if unknown:
        raise ValueError(f"unknown FWConfig field(s): {', '.join(sorted(unknown))}")
    base = dataclasses.replace(base, **fixed)
    if not sweep:
        return (base,)
    names = tuple(sweep)
    return tuple(
        dataclasses.replace(base, **dict(zip(names, point)))
        for point in itertools.product(*(sweep[k] for k in names)))


def group_key(config: FWConfig) -> Tuple:
    """Sweep-group bucket of a config (queue already resolved to native)."""
    return tuple(getattr(config, f) for f in GROUP_FIELDS)


# ---------------------------------------------------------------------------
# the vmapped jax_sparse sweep
# ---------------------------------------------------------------------------


def _sweep_scan(pcsr, pcsc, vbar0, qbar0, alpha0, lams, em_scales, keys,
                y=None, *, steps, loss, private):
    """One compiled program for a whole sweep group: the vmapped T-step scan
    over shared setup state.  ``lams``/``em_scales``/``keys`` are stacked
    per-config; (v̄₀, q̄₀, α₀) come from ``fw_setup_jit`` — computed once per
    group, or replayed from a dataset store's persisted cache.  ``y`` is the
    shared label vector, broadcast across lanes (label-coupled objectives
    only; separable ones pass None)."""
    from repro.core.solvers.jax_sparse import fw_scan

    def one(lam, em_scale, key):
        w, gaps, coords, _ = fw_scan(
            pcsr, pcsc, vbar0, qbar0, alpha0, lam, em_scale, key, 0.0, y,
            steps=steps, loss=loss, private=private)
        return w, gaps, coords

    return jax.vmap(one)(lams, em_scales, keys)


_sweep_scan_jit = jax.jit(
    _sweep_scan, static_argnames=("steps", "loss", "private"))


def _cohort_chunk(pcsr, pcsc, carry, lams, em_scales, gap_tols, t0,
                  y=None, *, steps, loss, private):
    """One vmapped chunk of the cohort scheduler: every lane advances
    ``steps`` masked iterations from offset ``t0`` (lanes that already hold
    their certificate stay frozen, bit-for-bit)."""
    from repro.core.solvers.jax_sparse import fw_scan_chunk

    def one(carry_i, lam, em_scale, gap_tol):
        return fw_scan_chunk(pcsr, pcsc, carry_i, lam, em_scale, gap_tol, t0,
                             y, steps=steps, loss=loss, private=private,
                             early_stop=True)

    return jax.vmap(one, in_axes=(0, 0, 0, 0))(carry, lams, em_scales,
                                               gap_tols)


_cohort_chunk_jit = jax.jit(
    _cohort_chunk, static_argnames=("steps", "loss", "private"))


def _group_context(data, y, configs: Sequence[FWConfig]):
    """Shared (pcsr, pcsc, setup, scalars) of one jax_sparse sweep group."""
    from repro.core.solvers.jax_sparse import em_scale_for, fw_setup_jit
    from repro.core.solvers.prepared import PreparedDataset
    c0 = configs[0]
    if isinstance(data, PreparedDataset):
        pcsr, pcsc = data.pair
        # §11: replay the store's autotuned layout — parity-gated at tuning
        # time, so the whole group's iterates are bit-identical either way
        rec = data.tuning_for("jax_sparse", c0.loss)
        if rec is not None and rec.ell_width is not None:
            pcsc = data.tuned_pcsc(rec)
        setup = data.setup_for(y, c0.loss)
    else:
        pcsr, pcsc = data
        setup = fw_setup_jit(pcsr, jnp.asarray(y, jnp.float32),
                             loss=c0.loss)
    n = pcsr.shape[0]
    dtype = pcsr.values.dtype
    scalars = {
        "lams": jnp.asarray([c.lam for c in configs], dtype),
        "em_scales": jnp.asarray([em_scale_for(c, n) for c in configs],
                                 dtype),
        "gap_tols": jnp.asarray([c.gap_tol for c in configs], dtype),
        "keys": jnp.stack([jax.random.PRNGKey(c.seed) for c in configs]),
    }
    return pcsr, pcsc, setup, scalars


def _group_labels(c0: FWConfig, y):
    """Label operand for the group's scan: None for separable objectives
    (their compiled programs never read labels), the shared f32 vector for
    label-coupled ones."""
    if c0.loss_fn().separable:
        return None
    return jnp.asarray(y, jnp.float32)


def _group_stats(pcsr, pcsc):
    # planner.data_stats knows every pair layout (flat and §11 tiered)
    from repro.core.solvers.planner import data_stats
    return data_stats((pcsr, pcsc))


def _solve_jax_sparse_group(
    data, y, configs: Sequence[FWConfig], sp
) -> List[FWResult]:
    """Run a compatible fixed-T config group as one vmap-over-configs scan.

    With a collector active the ``group.vmap`` span ``sp`` records the row
    chunks of the lanes' coordinate updates, counted when the collector
    settles: ``lane_chunks`` summed over lanes and steps (the useful work),
    and ``run_chunks`` summed over steps of the lanes' maximum (the batched
    loop runs until its last lane is done, the others masked)."""
    c0 = configs[0]
    pcsr, pcsc, setup, sc = _group_context(data, y, configs)
    private = c0.queue == "two_level"
    t0 = time.perf_counter()
    w, gaps, coords = _sweep_scan_jit(
        pcsr, pcsc, *setup, sc["lams"], sc["em_scales"], sc["keys"],
        _group_labels(c0, y),
        steps=c0.steps, loss=c0.loss, private=private)
    jax.block_until_ready(w)
    record_cost(c0.backend, "vmap", jax.devices()[0].platform,
                _group_stats(pcsr, pcsc),
                (time.perf_counter() - t0) / (c0.steps * len(configs)),
                loss=c0.loss)
    if obs.enabled():
        from repro.core.solvers.jax_sparse import TILE_ROWS, step_chunks
        col_nnz = pcsc.nnz

        def counts():
            per = step_chunks(col_nnz, coords)           # (lanes, T)
            return {"lane_chunks": int(per.sum()),
                    "run_chunks": int(per.max(axis=0).sum())}
        sp.set(tile_rows=TILE_ROWS)
        sp.defer(counts)
    return [FWResult(w=w[i], gaps=gaps[i], coords=coords[i],
                     losses=jnp.zeros_like(gaps[i]), stop_step=c0.steps,
                     stop_reason=STOP_MAX_STEPS)
            for i in range(len(configs))]


def _solve_jax_sparse_group_sequential(
    data, y, configs: Sequence[FWConfig]
) -> List[FWResult]:
    """Planner mode "sequential": per-config solves sharing one coerced
    layout + one setup + one compiled (width-free) scan program.  Each config
    stops exactly when its own certificate/timeout lands — no lane padding,
    no cohort granularity."""
    from repro.core.solvers.jax_sparse import jax_sparse_fw
    pcsr, pcsc, setup, _ = _group_context(data, y, configs)
    stats = _group_stats(pcsr, pcsc)
    platform = jax.devices()[0].platform
    y32 = jnp.asarray(y, jnp.float32)
    out = []
    for cfg in configs:
        t0 = time.perf_counter()
        res = jax_sparse_fw(pcsr, pcsc, y32, cfg, setup=setup)
        jax.block_until_ready(res.w)
        if cfg.screen_every == 0:
            # screened solves record per-chunk inside the §13 driver with
            # the geometry each chunk actually ran at; a whole-solve average
            # over shrinking D would poison the cost book
            ran = max(res.stop_step_or(cfg.steps), 1)
            record_cost(cfg.backend, "sequential", platform, stats,
                        (time.perf_counter() - t0) / ran, loss=cfg.loss)
        out.append(res)
    return out


def _solve_jax_sparse_group_cohort(
    data, y, configs: Sequence[FWConfig]
) -> List[FWResult]:
    """Gap-adaptive cohort scheduling (DESIGN.md §9): the group advances in
    chunks of one compiled vmapped ``fw_scan_chunk``; configs whose gap
    certificate (or wall-clock budget) lands are retired between chunks, so
    the grid stops paying for its slowest member.  Iterates are bit-identical
    to the sequential early-stopping path — same state machine, same keys —
    which the bench asserts at every config's stop step."""
    from repro.core.solvers.jax_sparse import fw_carry_init
    from repro.core.solvers.planner import cohort_widths
    from repro.core.solvers.stopping import resolve_chunk
    c0 = configs[0]
    pcsr, pcsc, setup, sc = _group_context(data, y, configs)
    stats = _group_stats(pcsr, pcsc)
    platform = jax.devices()[0].platform
    private = c0.queue == "two_level"
    y_scan = _group_labels(c0, y)
    n_cfg = len(configs)
    steps = c0.steps
    chunk = resolve_chunk(c0)
    d = pcsr.shape[1]
    dtype = pcsr.values.dtype

    init = jax.jit(jax.vmap(
        lambda s, k: fw_carry_init(d, dtype, *setup, s, k, private=private)))
    cur = init(sc["em_scales"], sc["keys"])          # stacked FWCarry

    gaps_buf = np.zeros((n_cfg, steps), np.asarray(sc["lams"]).dtype)
    coords_buf = np.full((n_cfg, steps), -1, np.int32)
    final: List[Optional[FWResult]] = [None] * n_cfg
    active = list(range(n_cfg))                      # config ids, lane order
    t0 = 0
    t_start = time.perf_counter()

    def retire(lane_carry, cfg_id: int, ran: int, reason_if_full: str):
        done = bool(lane_carry.done)
        stop = int(lane_carry.stop_at) if done else ran
        reason = STOP_GAP_TOL if done else reason_if_full
        w = np.asarray(lane_carry.w * lane_carry.w_m)
        final[cfg_id] = FWResult(
            w=jnp.asarray(w), gaps=jnp.asarray(gaps_buf[cfg_id]),
            coords=jnp.asarray(coords_buf[cfg_id]),
            losses=jnp.zeros((steps,), w.dtype), stop_step=stop,
            stop_reason=reason)
        obs.event("cohort.retire", config=cfg_id, stop_step=stop,
                  stop_reason=reason, survivors=len(active) - 1)
        obs.count("cohort.retired", reason=reason)

    widths = cohort_widths(n_cfg)        # pow-2 bucket schedule, full → 1
    while active and t0 < steps:
        c = min(chunk, steps - t0)
        width = min(w for w in widths if w >= len(active))
        # pad the cohort to a bucket width by repeating lane 0 (its copies'
        # outputs are discarded) — the grid re-enters ≤ log2(B) compiled
        # widths instead of one program per survivor count
        lane_sel = list(range(len(active))) + [0] * (width - len(active))
        cfg_sel = jnp.asarray([active[lane] for lane in lane_sel])
        padded = jax.tree_util.tree_map(
            lambda a: a[jnp.asarray(lane_sel)], cur)
        tw = time.perf_counter()
        padded, (g, j) = _cohort_chunk_jit(
            pcsr, pcsc, padded, sc["lams"][cfg_sel], sc["em_scales"][cfg_sel],
            sc["gap_tols"][cfg_sel], t0, y_scan,
            steps=c, loss=c0.loss, private=private)
        jax.block_until_ready(g)
        dt = time.perf_counter() - tw
        record_cost(c0.backend, "vmap", platform, stats,
                    dt / (c * width), loss=c0.loss)
        obs.observe("cohort.chunk.seconds", dt)
        obs.count("cohort.chunk.steps", c * len(active))
        cur = jax.tree_util.tree_map(lambda a: a[: len(active)], padded)
        g_np, j_np = np.asarray(g), np.asarray(j)
        for lane, cfg_id in enumerate(active):
            gaps_buf[cfg_id, t0:t0 + c] = g_np[lane]
            coords_buf[cfg_id, t0:t0 + c] = j_np[lane]
        t0 += c
        elapsed = time.perf_counter() - t_start
        dones = np.asarray(cur.done)
        keep = []
        for lane, cfg_id in enumerate(active):
            timed_out = (configs[cfg_id].max_seconds is not None
                         and elapsed >= configs[cfg_id].max_seconds)
            if bool(dones[lane]) or timed_out or t0 >= steps:
                retire(jax.tree_util.tree_map(lambda a: a[lane], cur),
                       cfg_id, t0,
                       STOP_MAX_SECONDS
                       if (timed_out and not bool(dones[lane]))
                       else STOP_MAX_STEPS)
            else:
                keep.append(lane)
        if keep and keep != list(range(len(active))):
            cur = jax.tree_util.tree_map(lambda a: a[jnp.asarray(keep)], cur)
        active = [active[lane] for lane in keep]
    return final  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# λ-path groups (§14): sequential-in-λ, fused-across-tenants
# ---------------------------------------------------------------------------


def _solve_jax_sparse_path_group_sequential(
    data, y, configs: Sequence[FWConfig]
) -> List:
    """Per-config warm-started path drivers over one shared coercion +
    setup (each re-enters the same compiled chunk program anyway)."""
    from repro.core.solvers.path import jax_sparse_path
    pcsr, pcsc, setup, _ = _group_context(data, y, configs)
    y32 = jnp.asarray(y, jnp.float32)
    return [jax_sparse_path(pcsr, pcsc, y32, cfg, setup=setup)
            for cfg in configs]


def _solve_jax_sparse_path_group_fused(
    data, y, configs: Sequence[FWConfig]
) -> List:
    """Fused-across-tenants λ-path: every lane advances through the *same*
    fixed global step slots (segment k occupies [S_{k-1}, S_k) whether or
    not its certificate landed early — frozen lanes are bit-frozen no-ops),
    so one vmapped chunk program drives the whole group and the per-lane
    trajectories are bit-identical to the sequential path driver's.

    λ-paths are a GROUP_FIELDS member, so every lane shares lambdas /
    steps / budgets; ε (hence the EM scale), seed, and gap_tol stack.
    """
    from repro.core.solvers.jax_sparse import fw_carry_init
    from repro.core.solvers.path import PathResult, path_em_scale, path_plan
    from repro.core.solvers.stopping import resolve_chunk
    c0 = configs[0]
    pcsr, pcsc, setup, sc = _group_context(data, y, configs)
    stats = _group_stats(pcsr, pcsc)
    platform = jax.devices()[0].platform
    private = c0.queue == "two_level"
    y_scan = _group_labels(c0, y)
    n_cfg = len(configs)
    n, d = pcsr.shape
    dtype = pcsr.values.dtype
    plans = [path_plan(c, private=private) for c in configs]
    plan0 = plans[0]   # lambdas/steps are group fields → same budgets/offsets
    em_scales = jnp.asarray(
        [path_em_scale(c, p, n) for c, p in zip(configs, plans)], dtype)

    init = jax.jit(jax.vmap(
        lambda s, k: fw_carry_init(d, dtype, *setup, s, k, private=private)))
    cur = init(em_scales, sc["keys"])                # stacked FWCarry
    buf_dtype = np.asarray(sc["lams"]).dtype
    per_cfg: List[List[FWResult]] = [[] for _ in configs]

    for k, lam_k in enumerate(plan0.lambdas):
        budget, seg_off = plan0.budgets[k], plan0.offsets[k]
        if k:
            # warm restart per lane: un-freeze stopping flags, keep the rest
            cur = cur._replace(done=jnp.zeros(n_cfg, bool),
                               stop_at=jnp.zeros(n_cfg, jnp.int32))
        lams = jnp.full((n_cfg,), lam_k, dtype)
        chunk = resolve_chunk(dataclasses.replace(c0, steps=budget))
        gaps_buf = np.zeros((n_cfg, budget), buf_dtype)
        coords_buf = np.full((n_cfg, budget), -1, np.int32)
        t0 = 0
        while t0 < budget:
            c = min(chunk, budget - t0)
            tw = time.perf_counter()
            cur, (g, j) = _cohort_chunk_jit(
                pcsr, pcsc, cur, lams, em_scales, sc["gap_tols"],
                seg_off + t0, y_scan, steps=c, loss=c0.loss, private=private)
            jax.block_until_ready(g)
            record_cost(c0.backend, "vmap", platform, stats,
                        (time.perf_counter() - tw) / (c * n_cfg),
                        loss=c0.loss)
            gaps_buf[:, t0:t0 + c] = np.asarray(g)
            coords_buf[:, t0:t0 + c] = np.asarray(j)
            t0 += c
            if bool(np.asarray(cur.done).all()):
                break    # remaining slots stay sentinel-padded, as the
                         # sequential driver's assemble_outputs would
        dones, stops = np.asarray(cur.done), np.asarray(cur.stop_at)
        for i in range(n_cfg):
            done_i = bool(dones[i])
            stop = int(stops[i]) - seg_off if done_i else budget
            w = cur.w[i] * cur.w_m[i]
            per_cfg[i].append(FWResult(
                w=w, gaps=jnp.asarray(gaps_buf[i]),
                coords=jnp.asarray(coords_buf[i]),
                losses=jnp.zeros((budget,), w.dtype), stop_step=stop,
                stop_reason=STOP_GAP_TOL if done_i else STOP_MAX_STEPS))
        if obs.enabled():
            obs.event("path.lambda", index=k, lam=float(lam_k),
                      budget=budget, offset=seg_off, lanes=n_cfg,
                      converged=int(dones.sum()))
    return [PathResult(plans[i].lambdas, per_cfg[i], plans[i])
            for i in range(n_cfg)]


def _run_path_group(backend, data, y, member_cfgs: Sequence[FWConfig],
                    plan: SolvePlan) -> List:
    """Dispatch one λ-path sweep group (§14).

    A path is sequential-in-λ by construction; across tenants it runs fused
    (one vmapped chunk program through shared global step slots) or
    sequential, per the same §9 mode machinery as plain sweep groups.
    """
    from repro.core.solvers.path import run_path
    if backend.name == "jax_sparse" and len(member_cfgs) > 1:
        mode = plan.mode
        if mode == "auto":
            from repro.core.solvers.planner import group_mode
            pcsr = (data.pcsr if hasattr(data, "pcsr") else data[0])
            pcsc = (data.pcsc if hasattr(data, "pcsc") else data[1])
            mode = group_mode(_group_stats(pcsr, pcsc), len(member_cfgs),
                              loss=member_cfgs[0].loss,
                              backend=member_cfgs[0].backend)
        if mode == "vmap":
            with obs.span("group.path", size=len(member_cfgs), mode="fused"):
                return _solve_jax_sparse_path_group_fused(data, y,
                                                          member_cfgs)
        with obs.span("group.path", size=len(member_cfgs),
                      mode="sequential"):
            return _solve_jax_sparse_path_group_sequential(data, y,
                                                           member_cfgs)
    with obs.span("group.path", size=len(member_cfgs), mode="sequential"):
        return [run_path(backend, data, y, cfg) for cfg in member_cfgs]


# ---------------------------------------------------------------------------
# solve_many
# ---------------------------------------------------------------------------


def _as_plan(plan: Union[None, str, SolvePlan]) -> SolvePlan:
    if plan is None or plan == "auto":
        return SolvePlan(mode="auto")
    if isinstance(plan, str):
        if plan not in ("vmap", "sequential"):
            raise ValueError(
                f"plan must be 'auto'/'vmap'/'sequential' or a SolvePlan; "
                f"got {plan!r}")
        return SolvePlan(mode=plan)
    return plan


def _run_jax_sparse_group(data, y, member_cfgs: Sequence[FWConfig],
                          plan: SolvePlan) -> List[FWResult]:
    """Dispatch one jax_sparse sweep group per the §9 plan."""
    if plan.chunk_steps is None and hasattr(data, "tuning_for"):
        # §11: the store's autotuned chunk length is the plan default
        rec = data.tuning_for("jax_sparse", member_cfgs[0].loss)
        if rec is not None and rec.chunk_steps is not None:
            plan = dataclasses.replace(plan, chunk_steps=rec.chunk_steps)
    if plan.chunk_steps is not None:
        # the plan's chunk is a default, not an override: a per-config pin
        # (which is a GROUP_FIELDS member, so uniform here) still wins
        member_cfgs = [c if c.chunk_steps is not None
                       else dataclasses.replace(c,
                                                chunk_steps=plan.chunk_steps)
                       for c in member_cfgs]
    if member_cfgs[0].screen_every > 0:
        # §13: once a screen fires, per-member geometry diverges (DP noise
        # makes survivor sets seed-dependent), so lanes can never be stacked
        # — screened groups always run the sequential mutable-geometry
        # driver, whatever the plan says.
        with obs.span("group.screened", size=len(member_cfgs)):
            return _solve_jax_sparse_group_sequential(data, y, member_cfgs)
    early = any(c.early_stopping for c in member_cfgs)
    mode = plan.mode
    if mode == "auto":
        from repro.core.solvers.planner import group_mode
        pcsr = (data.pcsr if hasattr(data, "pcsr") else data[0])
        pcsc = (data.pcsc if hasattr(data, "pcsc") else data[1])
        mode = group_mode(_group_stats(pcsr, pcsc), len(member_cfgs),
                          loss=member_cfgs[0].loss,
                          backend=member_cfgs[0].backend)
    if mode == "sequential":
        with obs.span("group.sequential", size=len(member_cfgs)):
            return _solve_jax_sparse_group_sequential(data, y, member_cfgs)
    if early:
        with obs.span("group.cohort", size=len(member_cfgs)):
            return _solve_jax_sparse_group_cohort(data, y, member_cfgs)
    with obs.span("group.vmap", size=len(member_cfgs)) as sp:
        return _solve_jax_sparse_group(data, y, member_cfgs, sp)


def solve_many(X, y=None, configs: Sequence[FWConfig] = (), *,
               prepared: Optional[Dict[str, object]] = None,
               plan: Union[None, str, SolvePlan] = None) -> List[FWResult]:
    """Solve many FW problems over one (X, y); results in input order.

    ``X`` may be a ``DatasetStore``/``DatasetRef`` (labels then default to
    the store's own — the whole sweep reads one on-disk artifact).  Configs
    are grouped by ``GROUP_FIELDS`` (after queue resolution); each
    ``jax_sparse`` group of ≥ 2 runs on one shared coercion + setup +
    compiled scan, scheduled per the §9 execution plan — ``plan=None`` lets
    ``solvers.planner`` choose between the vmapped program (cohort-chunked
    with retirement when the group carries ``gap_tol``/``max_seconds``) and
    sequential re-entries; pass "vmap"/"sequential" or a ``SolvePlan`` to
    override.  A ``jax_shard`` group shares one setup + compiled scan per
    mesh (vmapped on a 1×1 mesh), and other groups fall back to the
    sequential per-config backend — in every case the data coercion is
    hoisted and shared across the whole call, and results are identical
    under every plan (same state machine, same keys).

    ``prepared`` is an optional caller-owned ``{data_format: coerced X}``
    cache: pass the same dict across calls (the fit service does, per
    drain) and each layout is coerced exactly once per service lifetime.

    Configs with ``lambdas`` set (§14 λ-paths) yield a ``PathResult`` at
    their position instead of an ``FWResult``; identical paths group and
    run fused across tenants where the planner allows.
    """
    configs = list(configs)
    if not configs:
        return []
    with obs.span("solve_many", configs=len(configs)) as sp:
        plan = _as_plan(plan)
        X, y = resolve_data(X, y)
        resolved = []
        auto_stats = None             # derived once, only if any config asks
        for c in configs:
            if c.backend == "auto":
                from repro.core.solvers.planner import (choose_backend,
                                                        data_stats)
                if auto_stats is None:
                    auto_stats = data_stats(X)
                c = dataclasses.replace(c,
                                        backend=choose_backend(auto_stats, c))
            check_gap_certificate(c)
            if c.screen_every:
                from repro.core.solvers.screening import check_screen_config
                check_screen_config(c)
            if c.lambdas is not None:
                from repro.core.solvers.path import check_path_config
                check_path_config(c)
            backend = get_backend(c.backend)
            check_screening_support(backend, c)
            check_path_support(backend, c)
            resolved.append((backend, resolve_queue(backend, c)))

        if prepared is None:
            prepared = {}             # data layout -> coerced X (once each)
        for backend, _ in resolved:
            if backend.data_format not in prepared:
                with obs.span("solve_many.coerce",
                              layout=backend.data_format):
                    prepared[backend.data_format] = backend.prepare(X)

        groups: Dict[Tuple, List[int]] = {}
        for i, (_, cfg) in enumerate(resolved):
            groups.setdefault(group_key(cfg), []).append(i)
        sp.set(groups=len(groups))

        results: List[FWResult | None] = [None] * len(configs)
        for members in groups.values():
            backend, _ = resolved[members[0]]
            data = prepared[backend.data_format]
            member_cfgs = [resolved[i][1] for i in members]
            with obs.span("solve_many.group", backend=backend.name,
                          size=len(members)):
                if member_cfgs[0].lambdas is not None:
                    # §14: λ-path groups get their own sequential-in-λ /
                    # fused-across-tenants schedule (and return PathResults)
                    out = _run_path_group(backend, data, y, member_cfgs,
                                          plan)
                elif backend.name == "jax_sparse" and len(members) > 1:
                    out = _run_jax_sparse_group(data, y, member_cfgs, plan)
                elif backend.name == "jax_shard" and len(members) > 1:
                    from repro.core.solvers.jax_shard import solve_shard_group
                    out = solve_shard_group(data, y, member_cfgs)
                else:
                    out = [backend.fn(data, y, cfg) for cfg in member_cfgs]
            for i, res in zip(members, out):
                results[i] = res
    return results  # type: ignore[return-value]
