"""Device microseconds of the service's vmapped sweep program
(``batched._sweep_scan``) per lane-chunk slot: its runs in the trace over
the ``group.vmap`` spans' lanes x row chunks the batched loop ran
(``size`` x ``run_chunks``)."""
from bench.spans import lane_runs, whole_lanes
from bench.tracefile import device_time_per


def read(run):
    groups = whole_lanes(run)
    slots = lane_runs(groups) if groups is not None else None
    if not slots:
        return None
    return device_time_per(run, r"^jit__sweep_scan\(", "modules", slots, 1e6)
