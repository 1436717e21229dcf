"""``"entry": "service"``: one ``FitService`` over the host matrix (the
service pays the layout coercion itself, with its default ``slots``) and
``clients`` closed-loop clients spread over ``tenants`` tenants by a Zipf
law of exponent ``zipf_s``.  A round is one request from every client; its
pool pairs ``lam_choices``, repeated to the number of clients, with seeds,
and each round hands the pairs to the clients in an order drawn from the
run's seed.  Every tenant's accountant is sized for ``requests_per_tenant``
requests at the configuration's (epsilon, delta, T)."""
from __future__ import annotations

import math
import time
from typing import List

import numpy as np

from bench.driver import Driver, Request, delta_of, empty_columns, fw_config


def zipf_clients(clients: int, tenants: int, s: float) -> List[int]:
    """Tenant of each client: counts proportional to 1/rank^s, rounded by
    largest remainder (8 clients over 4 tenants at s=1 give 4/2/1/1)."""
    share = 1.0 / np.arange(1, tenants + 1) ** s
    share = clients * share / share.sum()
    counts = np.floor(share).astype(int)
    for t in np.argsort(-(share - counts), kind="stable")[: clients
                                                           - counts.sum()]:
        counts[t] += 1
    return [t for t in range(tenants) for _ in range(counts[t])]


class Entry(Driver):

    def pool_lams(self) -> List[float]:
        return np.resize(np.asarray(self.traffic["lam_choices"], float),
                         int(self.traffic["clients"])).tolist()

    def _accountants(self):
        from repro.core.dp.accountant import PrivacyAccountant
        k = int(self.traffic["requests_per_tenant"])
        return {self._tenant(t): PrivacyAccountant(
            epsilon=self.config["epsilon"] * math.sqrt(k),
            delta=delta_of(self.config),
            total_steps=self.config["steps"] * k)
            for t in range(int(self.traffic["tenants"]))}

    @staticmethod
    def _tenant(t: int) -> str:
        return f"tenant{t}"

    def coerce(self, X_host, y):
        import jax

        from repro.serve import FitService
        self.y = y
        self.svc = FitService(X_host, y, accountants=self._accountants())
        jax.block_until_ready(getattr(self.svc, "X", None))
        self.tenant_of = zipf_clients(int(self.traffic["clients"]),
                                      int(self.traffic["tenants"]),
                                      float(self.traffic["zipf_s"]))

    def _round(self, svc, pool: List[tuple], first: int) -> List[Request]:
        import jax

        from repro.serve import FitRequest
        reqs = self.cycle(pool, first)
        for req, tenant in zip(reqs, self.tenant_of):
            req.tenant = self._tenant(tenant)
            svc.submit(FitRequest(uid=req.index, tenant=req.tenant,
                                  config=fw_config(self.config, lam=req.lam,
                                                   seed=req.seed)))
        t_round = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.round"):
            out = {r.uid: r for r in svc.run()}
        t_round = time.perf_counter() - t_round
        for req in reqs:
            req.seconds = t_round
            got = out.get(req.index)
            req.status = got.status if got is not None else "lost"
            req.result = got.result if got is not None else None
        return reqs

    def warm(self):
        from repro.serve import FitService
        cheap = empty_columns(getattr(self.svc, "X", None))
        first = -len(self.warm_pool) - 1
        if cheap is None:
            self._round(self.svc, self.warm_pool, first)
            return
        warm_svc = FitService(cheap, self.y, accountants=self._accountants())
        self._round(warm_svc, self.warm_pool, first)

    def run_cycle(self, first: int) -> List[Request]:
        return self._round(self.svc, self.pool, first)

    def verify_ledger(self) -> int:
        """0 when the service's epsilon ledger replays to its accountants."""
        try:
            self.svc.verify_ledger()
        except Exception as e:  # noqa: BLE001 — any drift is the reading
            print(f"ledger verification failed: {e!r}", flush=True)
            return 1
        return 0

    def close(self):
        self.svc = None
