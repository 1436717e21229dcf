"""Share of the coordinate update's column slots that hold a real entry, in
the single fits: 100 x the ``col_nnz`` of every ``solve.scan`` span (the
sum over steps of nnz_j for the step's column j) over ``bench.spans.TILE_ROWS``
x their ``chunks``.  A step of column j runs ceil(nnz_j / 128) chunks of 128
slots, so the rest of those slots is padding the update moves for nothing.
Like ``chunks_per_step.solve`` it describes the work the fits pick."""
from bench.spans import TILE_ROWS, attr_sum, chunk_sum, whole_fits


def read(run):
    scans = whole_fits(run)
    if scans is None:
        return None
    chunks, nnz = chunk_sum(scans, "chunks"), attr_sum(scans, "col_nnz")
    if not chunks or nnz is None:
        return None
    return 100.0 * nnz / (TILE_ROWS * chunks)
