"""Device milliseconds of the setup sweep (``jax_sparse.fw_setup``) per fit:
the runs of its program in the trace over the fits of the window."""
from bench.tracefile import device_time_per


def read(run):
    return device_time_per(run, r"^jit_fw_setup\(", "modules",
                           run.work["fits"], 1e3)
