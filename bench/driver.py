"""The general traffic driver: one reader of every traffic file.

A traffic file (``bench/traffic/<name>.json``) names an ``entry`` and its
parameters; nothing else about a mix lives in code.  The entry is the way
requests reach the program, and it too is found by name: the class
``Entry`` of ``bench/entries/<entry>.py``, a subclass of ``Driver``.  A new
way in is a new file there; no table lists them.

Every mix works through a fixed pool of requests, a list of (lam,
``FWConfig.seed``) pairs derived from the file's ``pool_seed``, so that every
run does the same work; the run's seed draws the order.  (A private fit's
work depends on its draws: the columns it picks set how many row chunks
each step runs.)

The window runs whole cycles until ``seconds`` have elapsed: the first
cycle always, a later one only while the window is open, and the window
closes when its last request has returned with its device work done.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
import time
from typing import List

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent


def fit_seed(pool_seed: int, index: int) -> int:
    """``FWConfig.seed`` of member ``index`` of a pool (fits in int32)."""
    rng = np.random.default_rng([pool_seed % 2 ** 64, index % 2 ** 64])
    return int(rng.integers(0, 2 ** 31 - 1))


def order_stream(run_seed: int):
    """The generator every cycle's order is drawn from."""
    return np.random.default_rng([run_seed % 2 ** 64, 1])


@dataclasses.dataclass
class Request:
    """One request as the benchmark issued it, and what came back."""

    index: int
    lam: float
    seed: int
    tenant: str = ""
    status: str = "queued"
    result: object = None      # the program's FWResult (device arrays)
    seconds: float = 0.0       # host clock, issue to return


@dataclasses.dataclass
class Window:
    requests: List[Request]
    elapsed_s: float
    compiles: int = 0

    @property
    def done(self) -> List[Request]:
        return [r for r in self.requests if r.status == "done"]

    @property
    def failed(self) -> int:
        return len(self.requests) - len(self.done)


def fw_config(config: dict, *, lam: float, seed: int):
    """The program's ``FWConfig`` for one request of ``config``."""
    from repro.core.solvers import FWConfig
    private = config["queue"] == "two_level"
    extra = dict(epsilon=config["epsilon"], delta=delta_of(config)) \
        if private else {}
    return FWConfig(backend=config["backend"], lam=lam, steps=config["steps"],
                    loss=config["loss"], queue=config["queue"], seed=seed,
                    **extra)


def delta_of(config: dict) -> float:
    rule = config["delta"]
    if rule == "1/n^2":
        return 1.0 / config["dataset"]["n"] ** 2
    return float(rule)


def empty_columns(pair):
    """The resident layout with every column marked empty: the same shapes
    and the same compiled programs, but each step's coordinate update runs
    no chunk, so a warm-up fit costs a fraction of a real one.  None when
    the layout is not a padded pair of that kind."""
    try:
        pcsr, pcsc = pair
        import jax.numpy as jnp
        return pcsr, dataclasses.replace(pcsc, nnz=jnp.zeros_like(pcsc.nnz))
    except (TypeError, ValueError, AttributeError):
        return None


class CompileCounter:
    """Counts XLA backend compilations inside a ``with`` block."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _on(self, event, duration, *args, **kwargs):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> bool:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


class Driver:
    """Base of every entry: the request pool, the order of each cycle and
    the timed window.  An entry adds ``coerce(X_host, y)`` (the program's
    layout, counted as set-up), ``warm()``, ``run_cycle(first)`` (one
    cycle of the pool through the program, returning its ``Request``s) and
    ``close()`` (drop the program's state); it may add ``verify_ledger()``
    (0 when sound), which is then compared exactly."""

    def __init__(self, config: dict, traffic: dict, run_seed: int):
        self.config = config
        self.traffic = traffic
        self.pool = [(lam, fit_seed(traffic["pool_seed"], i))
                     for i, lam in enumerate(self.pool_lams())]
        self.order = order_stream(run_seed)
        # warm-up requests use seeds outside the pool
        self.warm_pool = [(lam, fit_seed(traffic["pool_seed"], -1 - i))
                          for i, (lam, _) in enumerate(self.pool)]

    def pool_lams(self) -> List[float]:
        """lam of each pool member: the configuration's, ``pool`` times."""
        return [float(self.config["lam"])] * int(self.traffic["pool"])

    def cycle(self, pool: List[tuple], first: int) -> List[Request]:
        """One cycle of ``pool``'s requests in an order drawn for the run."""
        return [Request(index=first + k, lam=pool[i][0], seed=pool[i][1])
                for k, i in enumerate(self.order.permutation(len(pool)))]

    def run_cycle(self, first: int) -> List[Request]:
        raise NotImplementedError

    def window(self, seconds: float) -> Window:
        """Whole cycles: the first always, then while ``seconds`` last."""
        reqs: List[Request] = []
        with CompileCounter() as compiles:
            t0 = time.perf_counter()
            while not reqs or time.perf_counter() - t0 < seconds:
                reqs.extend(self.run_cycle(len(reqs)))
            elapsed = time.perf_counter() - t0
        return Window(reqs, elapsed, compiles.count)


def entry_class(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """``Entry`` of ``bench/entries/<name>.py``."""
    path = bench_dir / "entries" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no traffic entry {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        "bench_entry_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Entry


def make_driver(config: dict, traffic: dict, run_seed: int,
                bench_dir: pathlib.Path = BENCH_DIR) -> Driver:
    return entry_class(traffic["entry"], bench_dir)(config, traffic,
                                                    run_seed)
