"""Device microseconds per lane-step of the service's vmapped sweep program
(``batched._sweep_scan``): its runs in the trace over lanes x steps."""
from bench.tracefile import device_time_per


def read(run):
    return device_time_per(run, r"^jit__sweep_scan\(", "modules",
                           run.work["lanes"] * run.work["steps_per_fit"], 1e6)
