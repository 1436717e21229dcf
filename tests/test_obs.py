"""Telemetry subsystem (DESIGN.md §12): tracing, metrics, audit ledger.

The two contracts that make observability safe to leave on:

  * **No perturbation** — telemetry on vs off yields bit-identical iterates
    on every backend, private and non-private (instrumentation is host-side
    only; it never enters traced code).
  * **True no-op when disabled** — the disabled path is one global read per
    call site; a solve with the collector off must not be measurably slower
    than one with the module never touched.

Plus the DP audit ledger's exactness contract: replaying the JSONL trail
recomputes every tenant's ε through ``PrivacyAccountant`` itself and must
match the live accountant bit-for-bit.
"""
import json
import math
import time

import numpy as np
import pytest

from repro import obs
from repro.core.dp.accountant import PrivacyAccountant
from repro.core.solvers import FWConfig, grid, solve, solve_many
from repro.obs.ledger import AuditLedger
from repro.obs.metrics import MetricsRegistry, quantile

FIVE_BACKENDS = ("dense", "host_sparse", "jax_dense", "jax_sparse",
                 "jax_shard")


@pytest.fixture(scope="module")
def problem():
    from repro.data.synthetic import make_sparse_classification
    X, y, _ = make_sparse_classification(
        n=80, d=300, nnz_per_row=8, informative=10, seed=7)
    return X, y


def _cfg(backend: str, **kw) -> FWConfig:
    if backend == "jax_shard":
        kw.setdefault("mesh", (1, 1))
    return FWConfig(backend=backend, **kw)


def _assert_bit_identical(a, b, msg=""):
    for field in ("coords", "w", "gaps"):
        x, y = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        assert x.shape == y.shape and x.tobytes() == y.tobytes(), \
            f"{msg}: {field} perturbed by telemetry"


# ---------------------------------------------------------------------------
# tentpole guard: telemetry must never perturb iterates
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("private", (False, True),
                         ids=("nonprivate", "private"))
@pytest.mark.parametrize("backend", FIVE_BACKENDS)
def test_telemetry_no_perturbation(problem, backend, private):
    """Tier-1: telemetry on vs off is bit-identical on every backend."""
    X, y = problem
    kw = dict(lam=6.0, steps=12)
    if private:
        kw.update(queue="bsls", epsilon=1.0, delta=1e-6)
    off = solve(X, y, _cfg(backend, **kw))
    with obs.session():
        on = solve(X, y, _cfg(backend, **kw))
    assert not obs.enabled()
    _assert_bit_identical(on, off, f"{backend}/private={private}")


def test_telemetry_no_perturbation_chunked_and_cohort(problem):
    """The chunked early-stop driver and the cohort scheduler emit per-chunk
    events — and still replay the exact same state machine."""
    X, y = problem
    cfg = FWConfig(backend="jax_sparse", lam=6.0, steps=24, gap_tol=1e-6)
    off = solve(X, y, cfg)
    cfgs = grid(cfg, lam=(4.0, 8.0, 16.0))
    off_many = solve_many(X, y, cfgs, plan="vmap")
    with obs.session() as tel:
        on = solve(X, y, cfg)
        on_many = solve_many(X, y, cfgs, plan="vmap")
    _assert_bit_identical(on, off, "chunked")
    for a, b in zip(on_many, off_many):
        _assert_bit_identical(a, b, "cohort")
    assert on.stop_step == off.stop_step
    assert on.stop_reason == off.stop_reason
    # the instrumented run actually recorded its chunk lifecycle
    names = [e["name"] for e in tel.events if e["ev"] == "event"]
    assert "chunks.stop" in names


def test_disabled_path_overhead_bounded(problem):
    """Disabled primitives are a handful of ns each, and a warmed solve with
    the collector off is not slower than one with it on."""
    t0 = time.perf_counter()
    for _ in range(50_000):
        obs.count("x")
        with obs.span("y"):
            pass
    assert time.perf_counter() - t0 < 1.0     # ~100 sec/call budget of 10 µs

    X, y = problem
    cfg = FWConfig(backend="jax_sparse", lam=6.0, steps=10)
    solve(X, y, cfg)                          # warm the compile cache

    def best_of(n, fn):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_off = best_of(3, lambda: solve(X, y, cfg))
    with obs.session():
        t_on = best_of(3, lambda: solve(X, y, cfg))
    # generous band: CI wobble, but "off" must never cost more than "on"
    # plus noise — that would mean the disabled path does real work
    assert t_off <= t_on * 1.5 + 0.05, (t_off, t_on)


# ---------------------------------------------------------------------------
# metrics: interpolated quantiles, registry, exporters
# ---------------------------------------------------------------------------


def test_quantile_is_interpolated():
    vals = [1.0, 2.0, 3.0, 4.0]
    assert quantile(vals, 0.0) == 1.0
    assert quantile(vals, 1.0) == 4.0
    assert quantile(vals, 0.5) == 2.5          # NOT vals[len//2] == 3.0
    assert quantile(vals, 0.25) == 1.75
    assert quantile([5.0], 0.9) == 5.0
    assert quantile([], 0.5) == 0.0
    assert quantile([3.0, 1.0, 2.0], 0.5) == 2.0    # unsorted input ok
    with pytest.raises(ValueError):
        quantile(vals, 1.5)


def test_metrics_registry_and_labels():
    reg = MetricsRegistry()
    reg.counter("hits", cache="padded").inc()
    reg.counter("hits", cache="padded").inc(2)
    reg.counter("hits", cache="setup").inc()
    reg.gauge("depth").set(7)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.histogram("lat").observe(v)
    snap = {(m["name"], tuple(sorted(m["labels"].items()))): m
            for m in reg.snapshot()}
    assert snap[("hits", (("cache", "padded"),))]["value"] == 3
    assert snap[("hits", (("cache", "setup"),))]["value"] == 1
    assert snap[("depth", ())]["value"] == 7
    h = snap[("lat", ())]
    assert h["count"] == 4 and h["p50"] == 2.5 and h["max"] == 4.0


def test_session_jsonl_round_trip_and_prometheus(tmp_path):
    from repro.obs.exporters import prometheus_text, read_jsonl
    path = tmp_path / "ev.jsonl"
    with obs.session(jsonl_path=str(path), meta={"suite": "t"}) as tel:
        with obs.span("outer", k=1):
            with obs.span("inner"):
                obs.count("c", lbl="a")
                obs.observe("h", 0.25)
                obs.gauge("g", 3.5)
        obs.event("e", detail="x")
        text = prometheus_text(tel)
    records = read_jsonl(str(path))
    assert records[0]["ev"] == "meta" and records[0]["suite"] == "t"
    spans = {r["name"]: r for r in records if r["ev"] == "span"}
    assert spans["inner"]["parent"] == spans["outer"]["id"]
    assert spans["outer"]["attrs"]["k"] == 1
    kinds = {r["ev"] for r in records}
    assert {"span", "event", "metric"} <= kinds
    assert 'repro_c_total{lbl="a"} 1' in text
    assert 'repro_h{quantile="0.5"}' in text and "repro_h_count 1" in text
    assert "repro_g 3.5" in text


def test_session_restores_previous_collector():
    with obs.session() as outer:
        with obs.session() as inner:
            assert obs.get() is inner
        assert obs.get() is outer
    assert obs.get() is None


def test_span_defer_settles_on_disable_session_and_export():
    """Deferred attributes join their span's record when the collector
    settles, never before; the no-op span drops them."""
    from repro.obs.exporters import to_records
    calls = []

    def later(n):
        def fn():
            calls.append(n)
            return {"n": n}
        return fn

    tel = obs.enable()
    with obs.span("a") as sp:
        sp.defer(later(1))
    (rec,) = tel.events
    assert "n" not in rec["attrs"] and calls == []
    assert obs.disable() is tel
    assert rec["attrs"]["n"] == 1 and calls == [1]
    with obs.session() as tel:
        with obs.span("b") as sp:
            sp.defer(later(2))
        exported = [r for r in to_records(tel) if r.get("name") == "b"]
        assert exported[0]["attrs"]["n"] == 2
    with obs.session() as tel:
        with obs.span("c") as sp:
            sp.defer(later(3))
    assert tel.events[0]["attrs"]["n"] == 3
    with obs.span("d") as sp:                       # collector off
        sp.defer(later(4))
    assert calls == [1, 2, 3]


def test_cache_counters_from_store(tmp_path, problem):
    """DatasetStore cache layers report hit/miss through obs."""
    from repro.data.store import DatasetStore
    X, y = problem
    store = DatasetStore.from_arrays(str(tmp_path / "ds"), X, y)
    cfg = FWConfig(backend="jax_sparse", lam=8.0, steps=5)
    with obs.session() as tel:
        solve(store, config=cfg)       # cold: padded + setup misses
        warm = DatasetStore.open(store.root)
        solve(warm, config=cfg)        # warm: both replayed from cache/
        counts = {(m["name"], m["labels"].get("cache"),
                   m["labels"].get("outcome")): m["value"]
                  for m in tel.metrics.snapshot() if m["type"] == "counter"}
    assert counts[("store.cache", "padded", "miss")] >= 1
    assert counts[("store.cache", "padded", "hit")] >= 1
    assert counts[("store.cache", "setup", "miss")] >= 1
    assert counts[("store.cache", "setup", "hit")] >= 1


# ---------------------------------------------------------------------------
# the ε-spend audit ledger
# ---------------------------------------------------------------------------


def _spend(ledger, tenant, acct, uid, steps):
    before = AuditLedger.state_of(acct)
    acct.spend(steps)
    ledger.charge(tenant=tenant, uid=uid, steps=steps, before=before,
                  acct=acct, request={"epsilon": 1.0})


def test_ledger_replay_exact_and_persistent(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    acct = PrivacyAccountant(epsilon=2.0, delta=1e-6, total_steps=64)
    led = AuditLedger(path)
    led.open_tenant("acme", acct)
    _spend(led, "acme", acct, uid=0, steps=10)
    _spend(led, "acme", acct, uid=1, steps=6)
    led.refusal(tenant="acme", uid=2, reason="budget exhausted", acct=acct)
    totals = led.totals()["acme"]
    assert totals["spent_steps"] == 16 and totals["refusals"] == 1
    # ε recomputed through the accountant's own formula: bitwise equal
    assert totals["spent_epsilon"] == acct.spent_epsilon()
    report = led.verify({"acme": acct})
    assert report["acme"]["exact"] is True

    # the file alone carries the whole chain — and a reopened ledger
    # continues it instead of truncating
    assert AuditLedger.replay(AuditLedger.load(path))["acme"][
        "spent_steps"] == 16
    led2 = AuditLedger(path)
    assert len(led2.entries) == len(led.entries)
    _spend(led2, "acme", acct, uid=3, steps=4)
    assert AuditLedger.replay(AuditLedger.load(path))["acme"][
        "spent_steps"] == 20


def test_ledger_detects_tampering():
    acct = PrivacyAccountant(epsilon=2.0, delta=1e-6, total_steps=64)
    led = AuditLedger()
    led.open_tenant("t", acct)
    _spend(led, "t", acct, uid=0, steps=8)
    # forged charge amount: after != before + steps
    bad = [dict(e) for e in led.entries]
    bad[1] = dict(bad[1], steps=4)
    with pytest.raises(ValueError, match="charge of 4 steps"):
        AuditLedger.replay(bad)
    # skipped entry: chain gap
    acct2 = PrivacyAccountant(epsilon=2.0, delta=1e-6, total_steps=64)
    led2 = AuditLedger()
    led2.open_tenant("t", acct2)
    _spend(led2, "t", acct2, uid=0, steps=8)
    _spend(led2, "t", acct2, uid=1, steps=8)
    with pytest.raises(ValueError, match="last known spend"):
        AuditLedger.replay([led2.entries[0], led2.entries[2]])
    # live accountant drifted from the trail
    acct.spend(1)
    with pytest.raises(ValueError, match="spent steps"):
        led.verify({"t": acct})


def test_ledger_checkpoint_restore_round_trip(tmp_path):
    accts = {
        "a": PrivacyAccountant(epsilon=2.0, delta=1e-6, total_steps=64),
        "b": PrivacyAccountant(epsilon=1.0, delta=1e-5, total_steps=32),
    }
    accts["a"].spend(12)
    led = AuditLedger()
    path = led.checkpoint(str(tmp_path), accts)
    back = AuditLedger.restore_accountants(path)
    assert set(back) == {"a", "b"}
    for t in accts:
        assert back[t].spent_steps == accts[t].spent_steps
        assert back[t].spent_epsilon() == accts[t].spent_epsilon()
        assert (back[t].epsilon, back[t].delta, back[t].total_steps) == \
            (accts[t].epsilon, accts[t].delta, accts[t].total_steps)


# ---------------------------------------------------------------------------
# FitService acceptance: drain under telemetry, audited end to end
# ---------------------------------------------------------------------------


def _service(X, y, **cfg_kw):
    from repro.serve import FitService, FitServiceConfig
    return FitService(X, y, accountants={
        "acme": PrivacyAccountant(epsilon=6.0, delta=1e-6, total_steps=144),
        "globex": PrivacyAccountant(epsilon=1.0, delta=1e-6, total_steps=45),
    }, config=FitServiceConfig(slots=4, **cfg_kw))


def _submit_mixed(svc):
    from repro.serve import FitRequest
    uid = 0
    for cfg in grid(FWConfig(backend="jax_sparse", steps=10, queue="bsls",
                             delta=1e-6), lam=(4.0, 8.0), epsilon=(0.5, 2.0)):
        svc.submit(FitRequest(uid=uid, tenant="acme", config=cfg))
        uid += 1
    for cfg in grid(FWConfig(backend="jax_sparse", steps=10, queue="bsls",
                             delta=1e-6, epsilon=0.5),
                    lam=(4.0, 8.0, 16.0, 32.0)):
        svc.submit(FitRequest(uid=uid, tenant="globex", config=cfg))
        uid += 1
    for lam in (4.0, 8.0):
        svc.submit(FitRequest(uid=uid, tenant="globex",
                              config=FWConfig(backend="jax_sparse",
                                              steps=10, lam=lam)))
        uid += 1


def test_fit_service_telemetry_acceptance(problem, tmp_path, monkeypatch):
    """ISSUE-8 acceptance: a full drain with telemetry enabled is (a) bit-
    identical to telemetry-off, (b) leaves a replayable ledger whose ε
    totals exactly match the accountants, (c) serves latency percentiles
    and queue depth through stats(), and queue wait through both
    exporters."""
    from repro.core.solvers import planner
    from repro.obs.exporters import prometheus_text
    # pin the group execution mode: the §9 planner picks vmap vs sequential
    # from its *measured* cost book, and the off-drain's own timings can
    # flip the choice for the on-drain — scheduling nondeterminism this
    # test must hold fixed to isolate the telemetry-perturbation contract
    # (vmap and sequential lowerings differ in float LSBs)
    monkeypatch.setattr(planner, "group_mode",
                        lambda *a, **k: "vmap")
    X, y = problem

    svc_off = _service(X, y)
    _submit_mixed(svc_off)
    done_off = svc_off.run()

    ledger_path = str(tmp_path / "ledger.jsonl")
    events_path = str(tmp_path / "events.jsonl")
    svc_on = _service(X, y, ledger_path=ledger_path)
    with obs.session(jsonl_path=events_path) as tel:
        _submit_mixed(svc_on)
        done_on = svc_on.run()
        prom = prometheus_text(tel)

    # (a) bit-identical responses, request by request
    assert [r.status for r in done_on] == [r.status for r in done_off]
    for a, b in zip(done_on, done_off):
        if a.status == "done":
            _assert_bit_identical(a.result, b.result, f"uid={a.uid}")

    # (b) the on-disk trail alone replays to the live accountants' ε,
    # bitwise (verify raises on any drift)
    report = svc_on.verify_ledger()
    for tenant, rec in report.items():
        assert rec["exact"] is True
        assert rec["spent_epsilon"] == \
            svc_on.accountants[tenant].spent_epsilon()
    disk = AuditLedger.replay(AuditLedger.load(ledger_path))
    for tenant, rec in disk.items():
        assert rec["spent_epsilon"] == \
            svc_on.accountants[tenant].spent_epsilon()
    # exactly one refusal (globex's 4th DP fit), attested in the trail
    assert disk["globex"]["refusals"] == 1

    # (c) percentiles, queue depth via stats(), queue wait via both
    # exporters
    stats = svc_on.stats()
    lat = stats["latency_s"]
    assert lat["p50"] <= lat["p90"] <= lat["p99"] <= lat["max"]
    assert lat["p50"] > 0 and stats["queue_depth"] == 0
    assert "repro_service_latency_s" in prom
    assert "repro_service_wait_s" in prom
    with open(events_path) as f:
        records = [json.loads(line) for line in f]
    metric_names = {r["name"] for r in records if r["ev"] == "metric"}
    assert "service.latency_s" in metric_names
    assert "service.wait_s" in metric_names

    # and the report CLI renders it all without error
    from repro.obs.report import render_path
    out = render_path(events_path, ledger_path)
    assert "service.run" in out and "tenant ε-spend ledger" in out


def test_fit_service_stats_percentiles_interpolated(problem):
    """The p50 is an order statistic of the latency sample, not an index."""
    svc = _service(*problem)
    from repro.serve import FitRequest
    for i, lam in enumerate((4.0, 8.0)):
        svc.submit(FitRequest(uid=i, tenant="acme", config=FWConfig(
            backend="jax_sparse", steps=5, lam=lam)))
    svc.run()
    lat = sorted(r.latency_s for r in svc.finished)
    got = svc.stats()["latency_s"]
    assert got["p50"] == pytest.approx(quantile(lat, 0.5))
    assert got["p50"] <= got["max"]   # even-length sample: mean of the two


# ---------------------------------------------------------------------------
# trainer: telemetry rides along, history and log sink unchanged
# ---------------------------------------------------------------------------


def test_trainer_fit_obs_and_log_sink():
    import jax
    import jax.numpy as jnp

    from repro.train.trainer import (TrainConfig, make_train_state,
                                     make_train_step)
    from repro.train.optimizer import get_optimizer
    from repro.train import trainer

    tc = TrainConfig(total_steps=8, warmup=1, peak_lr=1e-2)
    loss_fn = lambda p, batch, remat=True: jnp.sum((p["w"] - batch["x"]) ** 2)
    step_fn = make_train_step(loss_fn, tc)
    opt = get_optimizer(tc.optimizer)
    state0 = make_train_state(
        lambda k: {"w": jnp.zeros((4,), jnp.float32)}, opt,
        jax.random.PRNGKey(0))

    def batches():
        while True:
            yield {"x": jnp.ones((4,), jnp.float32)}

    lines = []
    with obs.session() as tel:
        state, history = trainer.fit(
            state0, step_fn, batches(), steps=8, log_every=2,
            log=lines.append)
    assert len(history) == 5                  # steps 0,2,4,6 + final
    assert all("loss=" in ln for ln in lines)  # sink got the text
    span_names = {e["name"] for e in tel.events if e["ev"] == "span"}
    assert "train.fit" in span_names
    hist = [m for m in tel.metrics.snapshot()
            if m["name"] == "train.step_seconds"]
    assert hist and hist[0]["count"] == 8
