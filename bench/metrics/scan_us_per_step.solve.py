"""Device microseconds per Frank-Wolfe step of the single-fit scan program
(``jax_sparse.fw_scan``): its runs in the trace over fits x steps."""
from bench.tracefile import device_time_per


def read(run):
    return device_time_per(run, r"^jit_fw_scan\(", "modules",
                           run.work["fits"] * run.work["steps_per_fit"], 1e6)
