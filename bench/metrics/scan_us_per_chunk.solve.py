"""Device microseconds of the single-fit scan program
(``jax_sparse.fw_scan``) per row chunk its coordinate updates ran: the
program's runs in the trace over the ``chunks`` of the window's
``solve.scan`` spans.  The cost of one unit of work, with the columns the
data picked factored out."""
from bench.spans import chunk_sum, whole_fits
from bench.tracefile import device_time_per


def read(run):
    scans = whole_fits(run)
    chunks = chunk_sum(scans, "chunks") if scans is not None else None
    if not chunks:
        return None
    return device_time_per(run, r"^jit_fw_scan\(", "modules", chunks, 1e6)
