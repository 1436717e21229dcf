"""DP-LASSO fit service: slot-based request/response engine over solve_many.

The LM side of the repo serves tokens through ``serve/engine.py``; this is
the same lifecycle — **submit → admit → batch → drain** — applied to the
paper's workload: multi-tenant DP-LASSO fit requests against one resident
design matrix (the hyperparameter-sweep traffic pattern of Khanna et al.).

  * **submit** queues a ``FitRequest`` (tenant + FWConfig);
  * **admit** resolves the request's queue, and for private queues charges
    the tenant's ``PrivacyAccountant`` *before* any compute — a request
    whose tenant budget (or tenant) is missing/exhausted is refused, never
    run, and never charged.  The charge is denominated in the accountant's
    own step currency: a request running T_req selections at its own
    (ε_req, δ) consumes ``ceil(T_req · (ε'_req/ε'_acct)²)`` tenant steps
    (= ``T_acct · (ε_req/ε_acct)²`` at matching δ), so under advanced
    composition the pool bounds the tenant's *actual* ε loss no matter what
    per-request (ε, T) mix arrives; requests with a weaker δ than the
    accountant's are refused outright;
  * **batch** packs admitted requests into sweep groups (``batched.group_key``)
    and chops each group to at most ``slots`` configs — the compiled-batch
    width, directly analogous to the serving engine's decode-slot count;
  * **drain** runs each slot-batch through ``solve_many`` (one shared setup
    + compiled scan per ``jax_sparse`` batch, scheduled by the §9 planner —
    cohort-chunked with retirement when requests carry ``gap_tol``;
    ``jax_shard`` batches share one setup + compiled scan on their mesh).
    Each backend's data layout is coerced once per service lifetime — the
    service owns the ``prepared`` cache ``solve_many`` fills — so
    per-request ``backend=`` selection (e.g. a ``jax_shard`` scale-out fit
    next to ``jax_sparse`` traffic) costs no repeated conversions and
    changes nothing about ε-accounting: admission charges by the *resolved*
    queue name, whatever engine realizes it.

Per-request planning (DESIGN.md §9): a request may submit
``backend="auto"`` — admission resolves it through the cost-model planner
against the resident dataset's shape statistics *before* queue resolution,
so grouping, slot packing and ε-charging all see a concrete backend.
Early-stopping requests (``gap_tol``/``max_seconds``) are admitted and
charged exactly like fixed-T ones: DP budget is charged up-front for the
requested T (stopping early never refunds — the noise draws past the stop
are simply never consumed, which only *under*-uses the charged budget).

Everything is synchronous single-controller, like ``ServingEngine``: the
host loop is the scheduler, each drained batch is one XLA program.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Mapping, Optional

import jax

from repro import obs
from repro.core.dp.accountant import PrivacyAccountant, per_step_epsilon
from repro.core.solvers.batched import group_key, solve_many
from repro.core.solvers.config import (FWConfig, FWResult,
                                       check_gap_certificate)
from repro.core.solvers.registry import (check_path_support,
                                         check_screening_support, get_backend,
                                         resolve_queue)
from repro.obs.ledger import AuditLedger
from repro.obs.metrics import quantile

# Native queue/selection names that consume privacy budget (the DP
# exponential mechanism and report-noisy-max realizations, per backend).
PRIVATE_QUEUES = frozenset({"bsls", "two_level", "gumbel", "noisy_max"})


@dataclasses.dataclass
class FitRequest:
    uid: int
    tenant: str
    config: FWConfig
    # filled by the service
    status: str = "queued"            # queued | done | rejected | failed
    reason: str = ""                  # set when rejected/failed
    result: Optional[FWResult] = None
    submitted_at: float = 0.0
    finished_at: float = 0.0

    @property
    def latency_s(self) -> float:
        return max(self.finished_at - self.submitted_at, 0.0)


@dataclasses.dataclass(frozen=True)
class FitServiceConfig:
    slots: int = 8                    # max configs per compiled batch
    # ε-spend audit trail (DESIGN.md §12): None keeps the ledger in-memory
    # only; a path appends every charge/refusal as JSONL (and a restarted
    # service continues the same file)
    ledger_path: Optional[str] = None


class FitService:
    """Multi-tenant DP-LASSO fitting over one resident (X, y) dataset."""

    def __init__(self, X, y=None,
                 accountants: Optional[Mapping[str, PrivacyAccountant]] = None,
                 config: FitServiceConfig = FitServiceConfig()):
        if config.slots < 1:
            raise ValueError("slots must be >= 1")
        # Resolve the data source once and coerce each backend layout once
        # per service lifetime: ``self._coerced`` is the caller-owned cache
        # ``solve_many`` fills lazily (padded is pre-warmed here — the common
        # case), so no request ever re-pays a conversion.  Keeping the
        # *resolved source* (not just one coerced layout) is what lets a
        # per-request ``backend=`` choose its own layout — a jax_shard
        # request against a DatasetStore maps shards onto BlockSparse blocks
        # through the store's content-hash-guarded block cache, while
        # jax_sparse requests keep the PreparedDataset padded/setup caches
        # (both persist across service restarts via the store's cache/ dir).
        from repro.core.solvers.registry import as_padded, resolve_data
        X, y = resolve_data(X, y)
        self._source = X
        self._coerced: Dict[str, object] = {"padded": as_padded(X)}
        self.X = self._coerced["padded"]   # kept for introspection/back-compat
        self.y = y
        self._stats = None                 # planner ProblemStats, lazy (§9)
        self.accountants: Dict[str, PrivacyAccountant] = dict(accountants or {})
        self.cfg = config
        self.queue: List[FitRequest] = []
        self.finished: List[FitRequest] = []
        self.batches_run = 0
        self.batch_sizes: List[int] = []
        self.serving_s = 0.0              # wall-clock actually spent draining
        # the ε-spend audit trail: every accountant's attach state is the
        # base of its replay chain (pre-spent budgets audit cleanly)
        self.ledger = AuditLedger(config.ledger_path)
        for tenant, acct in sorted(self.accountants.items()):
            self.ledger.open_tenant(tenant, acct)

    # ------------------------------------------------------------------ public
    def submit(self, req: FitRequest) -> None:
        req.submitted_at = time.time()
        req.status = "queued"
        self.queue.append(req)
        obs.count("service.submitted", tenant=req.tenant)

    def run(self) -> List[FitRequest]:
        """Drain the queue; returns every request (done/rejected/failed)."""
        with obs.span("service.run", queued=len(self.queue)):
            admitted = [r for r in self.queue if self._admit(r)]
            rejected = [r for r in self.queue if r.status == "rejected"]
            self.queue = []
            for batch in self._pack(admitted):
                self._drain(batch)
        done = sorted(admitted + rejected, key=lambda r: r.uid)
        for r in done:
            obs.count("service.finished", status=r.status)
            if r.status == "done":
                obs.observe("service.latency_s", r.latency_s)
        self.finished.extend(done)
        return done

    def stats(self) -> dict:
        """Per-request latency + throughput + per-tenant accountant state."""
        done = [r for r in self.finished if r.status == "done"]
        lat = [r.latency_s for r in done]
        return {
            "requests": len(self.finished),
            "done": len(done),
            "rejected": sum(r.status == "rejected" for r in self.finished),
            "failed": sum(r.status == "failed" for r in self.finished),
            "batches": self.batches_run,
            "batch_sizes": list(self.batch_sizes),
            "queue_depth": len(self.queue),
            # interpolated order statistics (shared obs helper) — the old
            # lat[len(lat)//2] midpoint was not a p50 on even-length samples
            "latency_s": {
                "p50": quantile(lat, 0.50),
                "p90": quantile(lat, 0.90),
                "p99": quantile(lat, 0.99),
                "max": max(lat) if lat else 0.0,
            },
            # over drain time only — idle wall-clock between run() calls is
            # not serving time
            "throughput_fits_per_s": (
                len(done) / self.serving_s if self.serving_s > 0 else 0.0),
            "tenants": {
                t: {"spent_steps": a.spent_steps,
                    "remaining_steps": a.remaining_steps,
                    "spent_epsilon": a.spent_epsilon()}
                for t, a in self.accountants.items()},
        }

    def verify_ledger(self) -> Dict[str, dict]:
        """Audit the ε-spend ledger against the live accountants (exact —
        raises on any drift; see ``AuditLedger.verify``)."""
        return self.ledger.verify(self.accountants)

    def checkpoint_accountants(self, directory: str) -> str:
        """Snapshot accountant state via ``repro.checkpoint`` so a restart
        resumes from audited spend (pair with ``config.ledger_path``)."""
        return self.ledger.checkpoint(directory, self.accountants)

    # --------------------------------------------------------------- internals
    def _planned_backend(self, cfg: FWConfig) -> str:
        """Cost-model backend choice against the resident dataset.

        Stats come from the resolved *source* — for a ``DatasetStore`` that
        is O(1) manifest metadata (cached per content hash by the planner),
        so admissions never re-derive shape facts from the coerced padded
        pair, let alone materialize anything."""
        from repro.core.solvers.planner import choose_backend, data_stats
        if self._stats is None:
            self._stats = data_stats(self._source)
        return choose_backend(self._stats, cfg)

    def _admit(self, req: FitRequest) -> bool:
        """Validate the config, resolve the queue, and charge the tenant for
        private fits.  Refusals leave the accountant untouched (spend is
        atomic — it raises before mutating), and a request is only charged
        once it can no longer fail validation."""
        try:
            cfg = req.config
            if cfg.backend == "auto":                # §9 per-request planning
                cfg = dataclasses.replace(
                    cfg, backend=self._planned_backend(cfg))
            backend = get_backend(cfg.backend)
            if (cfg.max_seconds is not None
                    and not backend.supports_max_seconds):
                # the backend adapter would raise this at drain time — after
                # the charge, and failing its whole batch; refuse here,
                # charge-free, instead
                raise ValueError(
                    f"backend {backend.name!r} runs as one compiled scan "
                    "and cannot enforce max_seconds; use gap_tol or a "
                    "chunked backend")
            # §13: bad screening knobs and engines without a mutable-geometry
            # chunk loop are refused here, charge-free, not at drain time
            if cfg.screen_every:
                from repro.core.solvers.screening import check_screen_config
                check_screen_config(cfg)
            check_screening_support(backend, cfg)
            # §14: malformed λ-paths and engines without a re-enterable
            # chunked driver — same contract: refuse before any charge
            if cfg.lambdas is not None:
                from repro.core.solvers.path import check_path_config
                check_path_config(cfg)
            check_path_support(backend, cfg)
            resolved = resolve_queue(backend, cfg)
            # unknown loss -> KeyError; gap_tol on a non-smooth objective ->
            # ValueError — both refused here, before any budget is charged
            check_gap_certificate(resolved)
        except (ValueError, KeyError) as e:
            return self._reject(req, str(e))
        req.config = resolved
        # effective selection rule: the dense adapter runs `queue` when one
        # was given, falling back to `selection` only for queue=None
        if resolved.queue is not None:
            effective = resolved.queue
        elif backend.name == "dense":
            effective = resolved.selection
        else:
            effective = None
        if effective in PRIVATE_QUEUES:
            acct = self.accountants.get(req.tenant)
            if acct is None:
                return self._reject(
                    req, f"tenant {req.tenant!r} has no privacy budget")
            try:
                # bad (ε, δ, T) raise here, BEFORE the budget is touched —
                # a config the solver would choke on must never be charged
                steps = self._charged_steps(acct, resolved)
                before = AuditLedger.state_of(acct)
                acct.spend(steps)
            except (RuntimeError, ValueError) as e:
                return self._reject(req, str(e))
            self.ledger.charge(
                tenant=req.tenant, uid=req.uid, steps=steps, before=before,
                acct=acct, request=self._request_facts(resolved))
        obs.count("service.admitted", tenant=req.tenant)
        return True

    @staticmethod
    def _request_facts(cfg: FWConfig) -> dict:
        """The request facts a later audit needs to interpret a charge.

        Must never raise, even on invalid configs — the refusal path records
        these same facts — so screening contributes only its raw knobs, never
        derived ``screen_plan`` quantities (whose math refuses bad fracs).
        """
        facts = {"epsilon": cfg.epsilon, "delta": cfg.delta,
                 "steps": cfg.steps, "queue": cfg.queue,
                 "backend": cfg.backend, "loss": cfg.loss}
        if cfg.screen_every:
            facts["screen_every"] = cfg.screen_every
            facts["screen_eps_frac"] = cfg.screen_eps_frac
        if cfg.lambdas is not None:
            # raw λ-sequence only — the derived PathPlan refuses malformed
            # paths, and refusals must record facts without raising
            facts["lambdas"] = [float(l) for l in cfg.lambdas]
        return facts

    @staticmethod
    def _charged_steps(acct: PrivacyAccountant, cfg: FWConfig) -> int:
        """Tenant steps consumed by a fit running T_req selections at its own
        per-step rate ε'_req = ε_req/√(8·T_req·log(1/δ)).

        The accountant's pool is T_acct steps at rate ε'_acct; under advanced
        composition ε grows as ε'·√k, so equal-ε-budget accounting charges
        ``T_req · (ε'_req/ε'_acct)²`` pool steps (the 1e-9 absorbs float slop
        before ceil).  A request with δ weaker than the accountant's is not
        expressible in its currency and is refused.

        §13 screening splits the request ε: the T EM selections run at the
        solve share ε·(1 − screen_eps_frac), and each of the R planned
        screening rounds is one extra advanced-composition query at
        ε_round = ε·frac/√(8R·log(1/δ)) — both priced in the same pool-step
        currency and charged up-front at admission (a screen that never
        fires, like a stop before T, under-uses the charge; never refunds).
        """
        if cfg.delta > acct.delta * (1.0 + 1e-12):
            raise ValueError(
                f"request δ={cfg.delta:g} is weaker than the tenant "
                f"accountant's δ={acct.delta:g}")
        if cfg.lambdas is not None:
            # §14: a path runs T_total = Σ budgets selections at the single
            # uniform rate ε' = ε/√(8·T_total·log(1/δ)).  T·ε'² is T-free,
            # so this prices identically to a plain solve at the same ε —
            # kept explicit so the charge derives from the plan the drivers
            # execute, not from a coincidence of algebra.  Screening is
            # refused with paths at admission, so there is no rounds term.
            from repro.core.solvers.path import path_plan
            pplan = path_plan(cfg, private=True)
            ratio = pplan.eps_per_step / acct.per_step
            return max(1, math.ceil(pplan.total_steps * ratio * ratio - 1e-9))
        from repro.core.solvers.screening import screen_plan
        plan = screen_plan(cfg, private=True)
        eps_req_step = per_step_epsilon(plan.eps_solve, cfg.delta, cfg.steps)
        ratio = eps_req_step / acct.per_step
        charged = max(1, math.ceil(cfg.steps * ratio * ratio - 1e-9))
        if plan.rounds:
            sratio = plan.eps_round / acct.per_step
            charged += max(1, math.ceil(plan.rounds * sratio * sratio - 1e-9))
        return charged

    def _reject(self, req: FitRequest, reason: str) -> bool:
        req.status, req.reason = "rejected", reason
        req.finished_at = time.time()
        # every refusal is a ledger fact: charge-free, with the tenant's
        # (unchanged) accountant state attested when one exists
        self.ledger.refusal(tenant=req.tenant, uid=req.uid, reason=reason,
                            acct=self.accountants.get(req.tenant),
                            request=self._request_facts(req.config))
        obs.count("service.rejected", tenant=req.tenant)
        return False

    def _pack(self, admitted: List[FitRequest]) -> List[List[FitRequest]]:
        """Group compatible configs, then chop each group to ``slots``."""
        groups: Dict[tuple, List[FitRequest]] = {}
        for r in admitted:
            groups.setdefault(group_key(r.config), []).append(r)
        batches = []
        for members in groups.values():
            for i in range(0, len(members), self.cfg.slots):
                batches.append(members[i:i + self.cfg.slots])
        return batches

    def _drain(self, batch: List[FitRequest]) -> None:
        t0 = time.time()
        for req in batch:
            obs.observe("service.wait_s", t0 - req.submitted_at,
                        tenant=req.tenant)
        try:
            with obs.span("service.batch", size=len(batch),
                          backend=batch[0].config.backend):
                results = solve_many(self._source, self.y,
                                     [r.config for r in batch],
                                     prepared=self._coerced)
                # device work is dispatched asynchronously: a request is
                # done (and its latency stamped) when its result exists, and
                # a device failure must land in this batch's handler
                jax.block_until_ready(
                    [getattr(r, "results", r) for r in results])
        except Exception as e:  # noqa: BLE001 — one bad batch must not
            # strand the rest of the queue.  The charged budget is NOT
            # refunded: admission cannot prove how far the mechanism got
            # before failing, and DP accounting must stay conservative.
            now = time.time()
            obs.count("service.batch_failures")
            for req in batch:
                req.status = "failed"
                req.reason = f"solver error: {e}"
                req.finished_at = now
            self.serving_s += now - t0
            return
        now = time.time()
        for req, res in zip(batch, results):
            req.result = res
            req.status = "done"
            req.finished_at = now
        self.serving_s += now - t0
        self.batches_run += 1
        self.batch_sizes.append(len(batch))
