#!/usr/bin/env python3
"""Smoke run of the DP-LASSO Frank-Wolfe solver on TPU.

    python chip_smoke.py [--seed S]             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4 [--seed S]   # four chips: the sharded solve

Data is the paper's rcv1 at its published shape (20,242 × 47,236, ~73 nnz
per row), generated from ``--seed``; the settings are the paper's speed run
(λ = 50, T = 4000, δ = 1/N²).  On one chip, through ``jax_sparse``:

  (a) a non-private ``solve``; its first 100 coordinates must equal those of
      the float64 host reference (``host_sparse``, run on the CPU);
  (b) a private ``solve`` at ε = 1: w finite and inside the L1 ball, all T
      steps taken, and the compiled scan holds the Pallas selection kernel;
  (c) a 2 × 2 λ × ε ``solve_many`` grid;
  (d) a ``FitService`` draining requests from two tenants.

With ``--chips 4`` only the non-private ``jax_shard`` solve on a 2 × 2 mesh
runs, for ``SHARD_STEPS`` steps, against the same host reference, and the
bytes of the design matrix each device holds are printed.

The device holds one copy of the padded layout for every phase.  Times
printed are smoke timings of this run, not benchmark results.  The last line
of standard output is one JSON object naming the device; without a TPU the
script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

LAM, STEPS, EPS = 50.0, 4000, 1.0
HOST_STEPS = 100           # coordinates compared with the host reference
# The four-chip solve is checked on its first HOST_STEPS coordinates only;
# a shorter run keeps the four-chip call short.
SHARD_STEPS = 400
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def require_tpu(count: int):
    """The first ``count`` devices, which must be TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        raise SystemExit(f"chip_smoke needs {count} TPU device(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:count]


def dataset():
    from repro.configs.paper_lasso import DATASETS
    return DATASETS["rcv1"]


class CompileClock:
    """Seconds XLA spent compiling, split off each phase's wall time."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, *args, **kwargs):
        if event == COMPILE_EVENT:
            self.seconds += duration

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, t0 = self.seconds, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        comp = self.seconds - c0
        print(f"smoke timing (not a benchmark) phase={name} "
              f"wall_s={wall:.3f} compile_s={comp:.3f} "
              f"run_s={wall - comp:.3f}", flush=True)


def make_data(seed: int):
    from repro.data.synthetic import make_sparse_classification
    ds = dataset()
    X, y, _ = make_sparse_classification(ds.n, ds.d, ds.nnz_per_row,
                                         ds.informative, seed=seed)
    print(f"data {ds.name}: N={X.shape[0]} D={X.shape[1]} nnz={X.nnz}",
          flush=True)
    return X, y


def host_coords(X, y):
    """First HOST_STEPS coordinates of the float64 host reference."""
    import numpy as np

    from repro.core.solvers import FWConfig, solve
    t0 = time.perf_counter()
    res = solve(X, y, FWConfig(backend="host_sparse", lam=LAM,
                               steps=HOST_STEPS))
    print(f"host_sparse reference: {HOST_STEPS} steps in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    return np.asarray(res.coords)


def alpha_margin(X, y, steps: int) -> float:
    """Gap between the two largest |α| after ``steps`` host steps — how
    close the selection at step ``steps + 1`` was to a tie."""
    import numpy as np

    from repro.core.fw_sparse import sparse_fw
    w = sparse_fw(X, np.asarray(y, np.float64), lam=LAM, steps=steps).w
    n = X.shape[0]
    p = 1.0 / (1.0 + np.exp(-X.matvec(w)))
    top = np.sort(np.abs(X.rmatvec(p - y) / n))[-2:]
    return float(top[1] - top[0])


def check_coords(name: str, got, want, X, y) -> None:
    import numpy as np
    got = np.asarray(got)[:HOST_STEPS]
    diff = np.flatnonzero(got != want)
    if diff.size:
        t = int(diff[0])
        margin = alpha_margin(X, y, t)
        print(f"{name}: coordinates part from the host reference at step "
              f"{t + 1} ({got[t]} vs {want[t]}); the two largest |alpha| "
              f"there differ by {margin:.3e}", flush=True)
        raise AssertionError(f"{name} coordinates differ from host_sparse")
    print(f"{name}: first {HOST_STEPS} coordinates equal host_sparse's",
          flush=True)


def check_fit(name: str, res, lam: float, steps: int = STEPS) -> None:
    import numpy as np
    w = np.asarray(res.w, np.float64)
    l1 = float(np.abs(w).sum())
    if not np.isfinite(w).all():
        raise AssertionError(f"{name}: w is not finite")
    if l1 > lam * (1 + 1e-5):
        raise AssertionError(f"{name}: ||w||_1 = {l1} > lambda = {lam}")
    if res.stop_step_or(steps) != steps:
        raise AssertionError(f"{name}: stopped at {res.stop_step}, not T")
    print(f"{name}: ||w||_1={l1:.6f} nnz={int((w != 0).sum())} "
          f"last gap={float(res.gaps[-1]):.6e}", flush=True)


def one_chip(seed: int, clock: CompileClock, dev) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.dp.accountant import PrivacyAccountant
    from repro.core.solvers import FWConfig, grid, solve, solve_many
    from repro.core.solvers.jax_sparse import (em_scale_for, fw_scan_jit,
                                               fw_setup_jit)
    from repro.core.solvers.registry import as_padded
    from repro.serve import FitRequest, FitService, FitServiceConfig

    X, y = make_data(seed)
    n = X.shape[0]
    delta = 1.0 / n ** 2
    want = host_coords(X, y)
    with clock.phase("setup: padded layout to device"):
        pair = as_padded(X)                  # the one resident copy
        jax.block_until_ready(pair)
    pcsr, pcsc = pair
    print(f"padded layout: csr {tuple(pcsr.indices.shape)} "
          f"csc {tuple(pcsc.indices.shape)}", flush=True)

    with clock.phase("a: non-private solve"):
        res = solve(pair, y, FWConfig(backend="jax_sparse", lam=LAM,
                                      steps=STEPS))
        jax.block_until_ready(res.w)
    check_coords("a", res.coords, want, X, y)
    check_fit("a", res, LAM)

    private = FWConfig(backend="jax_sparse", lam=LAM, steps=STEPS,
                       queue="two_level", epsilon=EPS, delta=delta)
    with clock.phase("b: private solve"):
        res = solve(pair, y, private)
        jax.block_until_ready(res.w)
    check_fit("b", res, LAM)
    y32 = jnp.asarray(y, jnp.float32)
    scan = fw_scan_jit.lower(
        pcsr, pcsc, *fw_setup_jit(pcsr, y32, loss=private.loss), LAM,
        em_scale_for(private, n), jax.random.PRNGKey(private.seed), 0.0,
        None, steps=STEPS, loss=private.loss, private=True).compile()
    if "tpu_custom_call" not in scan.as_text():
        raise AssertionError("b: the private scan holds no Pallas kernel")
    print("b: compiled scan holds the bsls_draw kernel (tpu_custom_call)",
          flush=True)

    configs = grid(private, lam=(25.0, LAM), epsilon=(EPS, 0.1))
    with clock.phase(f"c: solve_many grid of {len(configs)}"):
        results = solve_many(pair, y, configs)
        jax.block_until_ready([r.w for r in results])
    for cfg, r in zip(configs, results):
        check_fit(f"c lam={cfg.lam} eps={cfg.epsilon}", r, cfg.lam)

    budget = dict(epsilon=2.0, delta=delta, total_steps=STEPS)
    svc = FitService(pair, y, accountants={
        "tenant_a": PrivacyAccountant(**budget),
        "tenant_b": PrivacyAccountant(**budget)},
        config=FitServiceConfig(slots=4))
    requests = [("tenant_a", configs[0]), ("tenant_b", configs[1]),
                ("tenant_a", configs[2]), ("tenant_b", configs[3]),
                ("tenant_b", FWConfig(backend="jax_sparse", lam=LAM,
                                      steps=STEPS))]
    for uid, (tenant, cfg) in enumerate(requests):
        svc.submit(FitRequest(uid=uid, tenant=tenant, config=cfg))
    with clock.phase(f"d: FitService, {len(requests)} requests"):
        done = svc.run()
    for req in done:
        if req.status != "done":
            raise AssertionError(f"d: request {req.uid} {req.status}: "
                                 f"{req.reason}")
        check_fit(f"d request {req.uid} ({req.tenant})", req.result,
                  req.config.lam)
    svc.verify_ledger()
    stats = svc.stats()
    print(f"d: {stats['done']} done in {stats['batches']} batches "
          f"{stats['batch_sizes']}; ledger verified", flush=True)


def four_chips(seed: int, clock: CompileClock, devs) -> None:
    import jax

    from repro.core.solvers import FWConfig, solve
    from repro.core.solvers.registry import as_shard_source

    X, y = make_data(seed)
    want = host_coords(X, y)
    src = as_shard_source(X)
    with clock.phase("shard: jax_shard solve on a 2x2 mesh"):
        res = solve(src, y, FWConfig(backend="jax_shard", mesh=(2, 2),
                                     lam=LAM, steps=SHARD_STEPS))
        jax.block_until_ready(res.w)
    held = collections.Counter()
    for leaf in jax.tree_util.tree_leaves(src.blocks(2, 2)):
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    for d in devs:
        print(f"device {d.id}: holds {held[d.id]} bytes of blocks",
              flush=True)
    if set(held) != {d.id for d in devs} or min(held.values()) == 0:
        raise AssertionError(f"blocks are not spread over {len(devs)} "
                             f"devices: {dict(held)}")
    check_coords("shard", res.coords, want, X, y)
    check_fit("shard", res, LAM, SHARD_STEPS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    dev = devs[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}",
          flush=True)
    clock = CompileClock()
    if args.chips == 4:
        four_chips(args.seed, clock, devs)
    else:
        one_chip(args.seed, clock, dev)
    for d in devs:
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"smoke peak device memory (not a benchmark) device={d.id} "
              f"peak_bytes_in_use={peak}", flush=True)
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
