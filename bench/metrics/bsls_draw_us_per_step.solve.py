"""Device microseconds per step of the selection kernel
(``kernels/bsls_draw``, the Pallas little step): the duration of its events
in the trace over fits x steps."""
from bench.tracefile import device_time_per


def read(run):
    return device_time_per(run, r"^little_step_pallas$", "ops",
                           run.work["fits"] * run.work["steps_per_fit"], 1e6)
