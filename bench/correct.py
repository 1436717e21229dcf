"""The comparison that decides ``correct``.

After the window has closed, a sample of the requests that finished (drawn
from the run's seed) is replayed by the plain reference (``reference.py``)
on the benchmark's own copy of the data; every number is the worst over the
sample and is held to its limit in ``bench/limits/<cell>.json``.  Requests
that did not finish, and a service ledger that does not replay, are exact
comparisons with limit 0.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

from bench import reference
from bench.driver import Request, delta_of

REPLAYED = ("sel_gap", "w_err", "gap_err")


def pick(requests: List[Request], k: int, run_seed: int) -> List[Request]:
    """``k`` of the finished requests, drawn from the run's seed."""
    done = [r for r in requests if r.status == "done"]
    rng = np.random.default_rng([run_seed % 2 ** 64, 2])
    idx = sorted(rng.choice(len(done), size=min(k, len(done)),
                            replace=False)) if done else []
    return [done[i] for i in idx]


def private_scale(config: dict) -> float:
    return reference.em_log_weight_scale(
        config["epsilon"], delta_of(config), config["steps"],
        config["dataset"]["n"])


def host_fit(result) -> reference.Fit:
    return reference.Fit(w=np.asarray(result.w), gaps=np.asarray(result.gaps),
                         coords=np.asarray(result.coords))


def replay_numbers(prob: reference.Problem, config: dict, fit: reference.Fit,
                   lam: float, seed: int) -> Dict[str, float]:
    """The reference's numbers for one fit of ``config`` at (lam, seed)."""
    steps = config["steps"]
    if config["queue"] == "two_level":
        return reference.replay(prob, fit, lam=lam, steps=steps,
                                scale=private_scale(config),
                                noise=reference.gumbel_stream(
                                    seed, steps, prob.shape[1],
                                    config["draw"]))
    return reference.replay(prob, fit, lam=lam, steps=steps)


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """Each number beside its limit; correct when every one is within."""
    shown = {name: {"value": numbers[name], "limit": limits[name]}
             for name in limits if name in numbers}
    ok = (set(limits) <= set(numbers)
          and all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
                  for v in shown.values()))
    return ok, shown


def check(prob: reference.Problem, config: dict, fits: List[tuple],
          limits: Dict[str, float], exact: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """``fits`` is a list of (reference.Fit, lam, seed); ``exact`` holds the
    counts compared with limit 0 (requests not done, ledger drift)."""
    numbers = {name: (math.inf if not fits else 0.0) for name in REPLAYED}
    for fit, lam, seed in fits:
        got = replay_numbers(prob, config, fit, lam, seed)
        for name in REPLAYED:
            numbers[name] = max(numbers[name], got[name])
    numbers.update(exact)
    return judge(numbers, {**{k: limits[k] for k in REPLAYED},
                           **{k: 0.0 for k in exact}})
