"""Readings of the program's own ``repro.obs`` spans, shared by the
per-layer readers (``bench/metrics/*.py``).

The program records a span when it closes, as ``{"ev": "span", "name",
"id", "parent", "ts", "dur_s", "attrs"}`` with ``ts`` in seconds on the
collector's clock.  With a collector active, ``solve.scan`` carries
``steps``, ``chunks`` (row chunks its coordinate updates ran),
``tile_rows`` (the rows of one chunk) and ``enqueue_s`` (seconds until
the scan's dispatch returned), and ``group.vmap`` carries ``size``,
``tile_rows``, ``lane_chunks`` and ``run_chunks``.  A program that lacks an
attribute gives no reading: each helper returns None there, and never
raises.
"""
from __future__ import annotations

from typing import List, Optional

# The benchmark's unit of coordinate-update work: a chunk of 128 rows of
# the chosen column.  The program states the width it counted in
# (``tile_rows``); counts in any other width give no reading, so a change
# of the program's tile width is a change of the benchmark.
TILE_ROWS = 128


def named(run, name: str) -> List[dict]:
    """The run's spans called ``name``, in the order they closed."""
    return [ev for ev in run.spans
            if ev.get("ev") == "span" and ev.get("name") == name]


def attr_sum(spans: List[dict], key: str) -> Optional[float]:
    """Sum of attribute ``key`` over ``spans``; None if one lacks it."""
    vals = [ev.get("attrs", {}).get(key) for ev in spans]
    if not vals or any(v is None for v in vals):
        return None
    return sum(vals)


def chunk_sum(spans: List[dict], key: str) -> Optional[float]:
    """Sum of the chunk count ``key`` over ``spans``; None unless each span
    counted chunks of the benchmark's ``TILE_ROWS`` rows."""
    if any(ev.get("attrs", {}).get("tile_rows") != TILE_ROWS for ev in spans):
        return None
    return attr_sum(spans, key)


def whole_fits(run) -> Optional[List[dict]]:
    """The window's ``solve.scan`` spans, or None unless there is one per
    fit of the window (a window that caught part of a fit gives no
    number)."""
    scans = named(run, "solve.scan")
    return scans if scans and len(scans) == run.work["fits"] else None


def whole_lanes(run) -> Optional[List[dict]]:
    """The window's ``group.vmap`` spans, or None unless their lanes are
    the window's fits, one each."""
    groups = named(run, "group.vmap")
    if not groups or attr_sum(groups, "size") != run.work["lanes"]:
        return None
    return groups


def lane_runs(groups: List[dict]) -> Optional[int]:
    """Sum over ``group.vmap`` spans of lanes x the row chunks the batched
    loop ran: the chunk slots the device worked through, masked or not."""
    if chunk_sum(groups, "run_chunks") is None or attr_sum(
            groups, "size") is None:
        return None
    return sum(ev["attrs"]["size"] * ev["attrs"]["run_chunks"]
               for ev in groups)


def ancestor(spans_by_id: dict, ev: dict, name: str) -> Optional[dict]:
    """The nearest enclosing span of ``ev`` called ``name``."""
    ev = spans_by_id.get(ev.get("parent"))
    while ev is not None and ev.get("name") != name:
        ev = spans_by_id.get(ev.get("parent"))
    return ev
