"""``"entry": "solve"``: one caller issuing back-to-back ``solve`` calls on
the resident layout, in cycles of the traffic file's ``pool`` requests (lam
from the configuration), each cycle in an order drawn from the run's seed."""
from __future__ import annotations

import time
from typing import List

from bench.driver import Driver, Request, empty_columns, fw_config


class Entry(Driver):

    def coerce(self, X_host, y):
        import jax

        from repro.core.solvers.registry import as_padded
        self.y = y
        self.data = as_padded(X_host)
        jax.block_until_ready(self.data)

    def _solve(self, data, req: Request):
        import jax

        from repro.core.solvers import solve
        with jax.profiler.TraceAnnotation("bench.fit"):
            res = solve(data, self.y, fw_config(self.config, lam=req.lam,
                                                seed=req.seed))
            jax.block_until_ready(res)
        return res

    def warm(self):
        cheap = empty_columns(self.data)
        self._solve(cheap if cheap is not None else self.data,
                    self.cycle(self.warm_pool[:1], -1)[0])

    def run_cycle(self, first: int) -> List[Request]:
        reqs = self.cycle(self.pool, first)
        for req in reqs:
            t_req = time.perf_counter()
            req.result = self._solve(self.data, req)
            req.seconds = time.perf_counter() - t_req
            req.status = "done"
        return reqs

    def close(self):
        self.data = None
