#!/usr/bin/env python3
"""The control of a cell: the plain reference put in the program's place,
one precision below the configuration's, at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

For each seed it makes the run's data, answers the requests a run checks
(their lam and ``FWConfig.seed`` as the run's first cycle orders them) with
the reference in the lower precision, and judges those answers exactly as
``run.py`` judges the program's.  A sound limit has the control come out
not correct on every seed; the numbers it prints are the upper readings of
``bench/limits/<cell>.json``.  ``--fault altered`` reads a planted fault
instead: the reference in the configuration's own precision with the
selection of one step (the middle one) of every fit altered where it is
produced, to the next member of the chosen group.  ``--program`` reads the
program's own numbers instead, through the harness with a window of one
cycle (this needs the chip).  ``--fresh-data`` gives each seed a dataset
and a request pool of its own (the configuration's ``dataset.seed`` and the
mix's ``pool_seed`` drawn from the seed), so that readings cover distinct
datasets and trajectories, where the timed runs share one of each.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# the precision one step below each precision a configuration may state
LOWER = {"float64": "float32", "float32": "bfloat16"}


def fresh(cell, seed: int):
    """``cell`` with a dataset and a request pool drawn from ``seed``."""
    import copy

    import numpy as np
    rng = np.random.default_rng([seed % 2 ** 64, 4])
    cell = copy.deepcopy(cell)
    cell.config["dataset"]["seed"] = int(rng.integers(2 ** 31))
    cell.traffic["pool_seed"] = int(rng.integers(2 ** 31))
    return cell


def program_readings(cell, seed: int, compile_cache: bool) -> dict:
    """The program's numbers and verdict for one seed: one cycle of the
    cell's traffic through the harness."""
    from bench import harness
    res = harness.run_cell(cell, seed, 0.0, False,
                           t_start=time.perf_counter(),
                           compile_cache=compile_cache)
    return {"seed": seed, "correct": res["correct"],
            **{k: v["value"] for k, v in res["checks"].items()}}


def control_requests(cell, seed: int, k: int):
    """``k`` requests of the first cycle of a run of ``cell`` at ``seed``."""
    from bench import driver
    drv = driver.make_driver(cell.config, cell.traffic, seed,
                             cell.bench_dir)
    return drv.cycle(drv.pool, 0)[:k]


def control_readings(cell, seed: int, fault: str = "none") -> dict:
    """The control's (or a planted fault's) numbers and verdict for one
    seed."""
    from bench import correct, reference
    from bench.datagen import run_data
    cfg = cell.config
    ds = cfg["dataset"]
    twin = run_data(ds, seed)
    prob = reference.Problem.from_arrays(twin.indptr, twin.indices,
                                         twin.data, twin.y, twin.shape)
    private = cfg["queue"] == "two_level"
    fits = []
    for req in control_requests(cell, seed, int(cell.limits["checked_fits"])):
        fit = reference.free_run(
            prob, lam=req.lam, steps=cfg["steps"],
            scale=correct.private_scale(cfg) if private else None,
            noise=(reference.gumbel_stream(req.seed, cfg["steps"], ds["d"],
                                           cfg["draw"])
                   if private else None),
            dtype=(LOWER[cfg["precision"]] if fault == "none"
                   else cfg["precision"]))
        if fault == "altered":
            coords = fit.coords.copy()
            t = coords.shape[0] // 2
            _, m = reference.group_shape(ds["d"], cfg["draw"])
            coords[t] = min(coords[t] - coords[t] % m
                            + (coords[t] + 1) % m, ds["d"] - 1)
            fit = reference.Fit(w=fit.w, gaps=fit.gaps, coords=coords)
        fits.append((fit, req.lam, req.seed))
    ok, shown = correct.check(prob, cfg, fits, cell.limits, {})
    return {"seed": seed, "correct": ok,
            **{k: v["value"] for k, v in shown.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--fault", choices=("none", "altered"), default="none")
    ap.add_argument("--program", action="store_true",
                    help="read the program's numbers (needs the chip)")
    ap.add_argument("--fresh-data", action="store_true",
                    help="a dataset and a request pool for every seed")
    args = ap.parse_args(argv)
    from bench import harness
    base = harness.load_cell(args.workload)
    side = "program" if args.program else args.fault
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        cell = fresh(base, seed) if args.fresh_data else base
        t0 = time.perf_counter()
        row = (program_readings(cell, seed, compile_cache=k == 0)
               if args.program else control_readings(cell, seed, args.fault))
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps({"workload": cell.name, "side": side,
                          "dataset_seed": cell.config["dataset"]["seed"],
                          "pool_seed": cell.traffic["pool_seed"], **row}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
