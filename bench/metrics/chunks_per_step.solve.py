"""Row chunks of ``bench.spans.TILE_ROWS`` (128) rows that the coordinate
update ran per Frank-Wolfe step of the single fits: the ``chunks`` of every
``solve.scan`` span over their ``steps`` (the program counts
ceil(nnz_j / 128) for each step's column j).  With the width fixed it moves
only with the columns the fits pick: it describes the work, and a change
in it is a change of workload, not of the cost of a chunk."""
from bench.spans import attr_sum, chunk_sum, whole_fits


def read(run):
    scans = whole_fits(run)
    if scans is None:
        return None
    chunks, steps = chunk_sum(scans, "chunks"), attr_sum(scans, "steps")
    return chunks / steps if chunks is not None and steps else None
