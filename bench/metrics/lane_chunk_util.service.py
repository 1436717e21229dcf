"""Percent of the vmapped sweep's chunk slots that did useful work: over
the ``group.vmap`` spans, the lanes' own row chunks (``lane_chunks``) over
lanes x the chunks the batched loop ran (``size`` x ``run_chunks``; every
step runs as many chunks as its busiest lane, the other lanes masked)."""
from bench.spans import chunk_sum, lane_runs, whole_lanes


def read(run):
    groups = whole_lanes(run)
    if groups is None:
        return None
    useful, slots = chunk_sum(groups, "lane_chunks"), lane_runs(groups)
    return 100.0 * useful / slots if useful is not None and slots else None
