"""Host-side telemetry: solve-lifecycle tracing, metrics, DP audit ledger.

One rule governs everything in this package (DESIGN.md §12): telemetry is
**host-side only and a true no-op when disabled**.  Instrumentation never
touches a PRNG key and never changes a control-flow decision; traced code
carries only ``jax.named_scope`` names, metadata that changes no
computation — solver iterates are bit-identical with telemetry on or off,
which tier-1 tests pin on all five backends, private and non-private.
While a collector is active its spans are mirrored into the JAX profiler,
and ``solve.scan`` waits for the device it times; host copies for work
counts are deferred until the collector settles (``Span.defer``).

Call sites use the module-level helpers, which cost one global read plus a
``None`` check when no collector is active::

    from repro import obs

    with obs.session(jsonl_path="run-events.jsonl"):
        res = solve(X, y, config)          # spans/counters recorded
    # disabled again here: the same call records nothing

    obs.count("my.counter", 3, kind="demo")
    with obs.span("my.phase", size=n):
        ...
    obs.observe("my.latency_s", dt)        # histogram w/ interpolated p50/90/99

Exporters (``repro.obs.exporters``) render a run as a JSONL event log or
Prometheus-style text exposition; ``python -m repro.obs.report`` pretty-
prints the span tree, hot counters, and the per-tenant ε ledger.
"""
from repro.obs.core import (Telemetry, count, disable, enable,  # noqa: F401
                            enabled, event, gauge, get, observe, session,
                            span)
from repro.obs.exporters import prometheus_text, write_jsonl  # noqa: F401
from repro.obs.ledger import AuditLedger  # noqa: F401
from repro.obs.metrics import quantile  # noqa: F401
