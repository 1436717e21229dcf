"""``jax_shard`` backend — Algorithm 2 under feature sharding (DESIGN.md §8).

The registered face of ``repro.distributed``: one ``FWConfig`` whose
``mesh=(a, b)`` names the device grid (rows × features) runs the paper's
iteration as the shard_map collective schedule of
``distributed.fw_shard`` — shard-then-member Gumbel-max selection, lane
psums, α-delta reduction — over ``BlockSparse`` blocks built by
``distributed.ingest`` (store shards map straight onto blocks, with a
content-hash-guarded layout cache).

Program structure mirrors ``jax_sparse``: a config-independent ``setup``
pass plus a T-step ``scan`` whose (λ, EM scale, PRNG key) are traced — one
compile serves a whole (λ, ε) grid, and ``solvers.batched`` vmaps the sweep
where the mesh allows.  Compiled programs and meshes are memoized per
(grid, block shapes, static config) so repeated solves re-enter hot
executables.

On a 1×1 mesh every collective degenerates to the identity and the solve
reproduces the single-device oracle exactly (coords bit-identical) — pinned
in tests/test_jax_shard.py, which is what makes the backend testable on CPU
containers while the same code lowers onto the 16×16 / 2×16×16 production
meshes (``shard_lowering``, used by launch/dryrun.py and
benchmarks/perf_lasso.py).
"""
from __future__ import annotations

import functools
import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.dp.accountant import em_log_weight_scale
from repro.core.solvers.config import FWConfig, FWResult
from repro.distributed.fw_shard import (DistFW, build_dist_fw,
                                        dist_fw_shardings)
from repro.distributed.ingest import ShardSource

PRIVATE_SELECTION = "gumbel"


@functools.lru_cache(maxsize=None)
def make_shard_mesh(a: int, b: int):
    """(a × b) ("data", "model") mesh over the first a·b local devices."""
    if a < 1 or b < 1:
        raise ValueError(f"mesh must be positive, got ({a}, {b})")
    if a * b > jax.device_count():
        raise ValueError(
            f"FWConfig.mesh=({a}, {b}) needs {a * b} devices but only "
            f"{jax.device_count()} are visible")
    return jax.make_mesh((a, b), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def mesh_grid(config: FWConfig, src: ShardSource = None) -> Tuple[int, int]:
    """The (a × b) grid for one solve: the config's pin, else the dataset's
    §11 autotuned geometry (when ``src`` is store-backed and a record
    exists), else 1×1."""
    if config.mesh is not None:
        return tuple(int(v) for v in config.mesh)
    store = getattr(src, "store", None)
    if store is not None and hasattr(store, "autotune_load"):
        rec = store.autotune_load("jax_shard", config.loss,
                                  jax.devices()[0].platform)
        if rec is not None and rec.mesh is not None:
            return tuple(int(v) for v in rec.mesh)
    return (1, 1)


def _record_shard_cost(src: ShardSource, mode: str, seconds_per_step_lane:
                       float, *, loss: str) -> None:
    """Feed the group timing to the planner under the **jax_shard** key (the
    mis-keying this module used to dodge by not recording at all)."""
    from repro.core.solvers.planner import data_stats, record_cost
    source = src.csr if src.csr is not None else src.store
    if source is None:
        return
    record_cost("jax_shard", mode, jax.devices()[0].platform,
                data_stats(source), seconds_per_step_lane, loss=loss)


def shard_em_scale(config: FWConfig, n_rows: int) -> float:
    """EM log-weight scale for the (native) ``gumbel`` selection — the same
    ``core.dp.accountant`` formula ``jax_sparse.em_scale_for`` uses, so the
    two engines' (ε, δ, T) semantics cannot drift."""
    if config.queue != PRIVATE_SELECTION:
        return 1.0
    return em_log_weight_scale(
        epsilon=config.epsilon, delta=config.delta, steps=config.steps,
        n_rows=n_rows, lipschitz=config.loss_fn().lipschitz)


# program memo: building shard_map + jit per call would recompile every
# solve.  Keyed on everything that shapes the lowered executable.
_PROGRAMS: Dict[tuple, DistFW] = {}
_VMAPPED: Dict[tuple, object] = {}


def _program_key(blocks_abs, mesh, steps, loss, selection, compress_topk,
                 early_stop):
    return (blocks_abs.csc_rows.shape, blocks_abs.csr_cols.shape,
            blocks_abs.shape, blocks_abs.padded, mesh.axis_names,
            mesh.devices.shape, steps, loss, selection, compress_topk,
            early_stop)


def shard_program(blocks_abs, mesh, *, steps: int, loss: str, selection: str,
                  compress_topk: int = 0, early_stop: bool = False) -> DistFW:
    """Memoized (setup, scan, whole) program for one block layout + mesh."""
    key = _program_key(blocks_abs, mesh, steps, loss, selection,
                       compress_topk, early_stop)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = build_dist_fw(
            blocks_abs, mesh, steps=steps, loss=loss, selection=selection,
            compress_topk=compress_topk, early_stop=early_stop)
    return _PROGRAMS[key]


def vmapped_scan(blocks_abs, mesh, *, steps: int, loss: str, selection: str,
                 early_stop: bool = False):
    """jit(vmap(scan)) over stacked (λ, em_scale, gap_tol, key) — the batched
    sweep path on meshes where the whole stack fits one device program (1×1)."""
    key = _program_key(blocks_abs, mesh, steps, loss, selection, 0, early_stop)
    if key not in _VMAPPED:
        prog = shard_program(blocks_abs, mesh, steps=steps, loss=loss,
                             selection=selection, early_stop=early_stop)
        _VMAPPED[key] = jax.jit(jax.vmap(
            prog.scan, in_axes=(None, None, None, None, None, 0, 0, 0, 0)))
    return _VMAPPED[key]


def _pad_labels(y, n_pad: int) -> jnp.ndarray:
    y = jnp.asarray(y, jnp.float32)
    return jnp.zeros((n_pad,), jnp.float32).at[: y.shape[0]].set(y)


def _shard_result(w, gaps, coords, stop_step, d: int, steps: int) -> FWResult:
    stop = int(stop_step)
    return FWResult(w=w[:d], gaps=gaps, coords=coords,
                    losses=jnp.zeros_like(gaps), stop_step=stop,
                    stop_reason="gap_tol" if stop < steps else "max_steps")


def _reject_max_seconds(config: FWConfig) -> None:
    if config.max_seconds is not None:
        raise ValueError(
            "jax_shard runs as one compiled collective scan and cannot "
            "watch a wall clock; use gap_tol, or a host backend for "
            "max_seconds")


def shard_fw(src: ShardSource, y, config: FWConfig) -> FWResult:
    """One solve through the sharded collective schedule."""
    _reject_max_seconds(config)
    a, b = mesh_grid(config, src)
    mesh = make_shard_mesh(a, b)
    blocks = src.blocks(a, b)
    n, d = src.shape
    prog = shard_program(blocks, mesh, steps=config.steps, loss=config.loss,
                         selection=config.queue,
                         early_stop=config.gap_tol > 0)
    t0 = time.perf_counter()
    with mesh:
        ypad = _pad_labels(y, blocks.padded[0])
        with obs.span("shard.setup", mesh=f"{a}x{b}"):
            setup = prog.setup(blocks, ypad)
        with obs.span("shard.scan", mesh=f"{a}x{b}", steps=config.steps):
            w, gaps, coords, stop_step = prog.scan(
                blocks, ypad, *setup, jnp.float32(config.lam),
                jnp.float32(shard_em_scale(config, n)),
                jnp.float32(config.gap_tol),
                jax.random.PRNGKey(config.seed))
            jax.block_until_ready(w)
    _record_shard_cost(src, "sequential",
                       (time.perf_counter() - t0) / max(config.steps, 1),
                       loss=config.loss)
    return _shard_result(w, gaps, coords, stop_step, d, config.steps)


def solve_shard_group(src: ShardSource, y, configs) -> list:
    """A compatible config group on one shared setup: vmapped on a 1×1 mesh,
    sequential re-entries of the one compiled scan otherwise (λ/ε/gap_tol/key
    are traced either way, so the grid never recompiles)."""
    c0 = configs[0]
    for c in configs:
        _reject_max_seconds(c)
    a, b = mesh_grid(c0, src)
    mesh = make_shard_mesh(a, b)
    blocks = src.blocks(a, b)
    n, d = src.shape
    early = any(c.gap_tol > 0 for c in configs)
    prog = shard_program(blocks, mesh, steps=c0.steps, loss=c0.loss,
                         selection=c0.queue, early_stop=early)
    lams = jnp.asarray([c.lam for c in configs], jnp.float32)
    scales = jnp.asarray([shard_em_scale(c, n) for c in configs], jnp.float32)
    tols = jnp.asarray([c.gap_tol for c in configs], jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(c.seed) for c in configs])
    t0 = time.perf_counter()
    with mesh:
        ypad = _pad_labels(y, blocks.padded[0])
        with obs.span("shard.setup", mesh=f"{a}x{b}", size=len(configs)):
            setup = prog.setup(blocks, ypad)
        if a * b == 1:
            vscan = vmapped_scan(blocks, mesh, steps=c0.steps, loss=c0.loss,
                                 selection=c0.queue, early_stop=early)
            w, gaps, coords, stops = vscan(blocks, ypad, *setup, lams, scales,
                                           tols, keys)
            jax.block_until_ready(w)
            outs = [(w[i], gaps[i], coords[i], stops[i])
                    for i in range(len(configs))]
            mode = "vmap"
        else:
            outs = [prog.scan(blocks, ypad, *setup, lams[i], scales[i],
                              tols[i], keys[i])
                    for i in range(len(configs))]
            jax.block_until_ready(outs[-1][0])
            mode = "sequential"
    _record_shard_cost(
        src, mode,
        (time.perf_counter() - t0) / max(c0.steps * len(configs), 1),
        loss=c0.loss)
    return [_shard_result(w, g, c, s, d, c0.steps) for (w, g, c, s) in outs]


def shard_lowering(n: int, d: int, mesh, *, steps: int, kc: int, kr: int,
                   selection: str = "gumbel", compress_topk: int = 0,
                   loss: str = "logistic"):
    """(jitted whole-run fn, abstract args) for dry-run lowering.

    Builds ShapeDtypeStruct block specs for an (N × D) design on ``mesh``
    (rows over "pod"/"data", features over "model") and returns the
    registry backend's program ready for ``.lower(*args).compile()`` — what
    ``launch/dryrun.py --arch paper-lasso`` and ``benchmarks/perf_lasso.py``
    lower instead of any ad-hoc builder.  λ, the EM scale and the key are
    abstract traced scalars, matching the serving path.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.block_sparse import block_specs
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    a = 1
    for ax in ("pod", "data"):
        a *= sizes.get(ax, 1)
    b = sizes["model"]
    blocks_abs = block_specs(n, d, a, b, kc, kr)
    prog = shard_program(blocks_abs, mesh, steps=steps, loss=loss,
                         selection=selection, compress_topk=compress_topk)
    b_shd, y_shd = dist_fw_shardings(blocks_abs, mesh)
    repl = NamedSharding(mesh, P())
    jitted = jax.jit(prog.whole,
                     in_shardings=(b_shd, y_shd, repl, repl, repl, repl))
    f32 = jax.ShapeDtypeStruct
    key_abs = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    args = (blocks_abs, f32((blocks_abs.padded[0],), jnp.float32),
            f32((), jnp.float32), f32((), jnp.float32), f32((), jnp.float32),
            key_abs)
    return jitted, args
