"""Share of its roofline that the selection kernel (``kernels/bsls_draw``)
reaches: the larger of its bytes and operations over the chip's peaks
(``bench/kernels.bsls_draw_cost``, from its block shapes), for every call in
the trace, over the kernel's device time."""
from bench.kernels import bsls_draw_cost, roofline_share
from bench.tracefile import device_events


def read(run):
    got = device_events(run, r"^little_step_pallas$", "ops")
    if got is None or not run.peaks or got.total_s() <= 0:
        return None
    cfg = run.cell.config
    flops, bytes_ = bsls_draw_cost(cfg["dataset"]["d"], cfg["draw"])
    return roofline_share(flops * len(got), bytes_ * len(got),
                          got.total_s(), run.peaks)
