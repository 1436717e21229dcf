"""The ``col_fill.solve`` reader (``bench/metrics/col_fill.solve.py``) on
hand-made runs: the share of the coordinate update's column slots that hold
a real entry, and no number where the spans cannot give one."""
import pytest

from bench.tests import tiny
from bench.tests.test_bench_program_spans import (STEPS, _fits, _other_width,
                                                  _read)

CELL, METRIC = "news20-dp.solve", "col_fill.solve"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("col_fill"), steps=STEPS)


def _filled(col_nnz=(640, 1280, 960), **kw):
    """``_fits`` with the ``col_nnz`` a program of nnz-counting spans adds."""
    events = _fits(**kw)
    scans = [ev for ev in events if ev["name"] == "solve.scan"]
    for ev, n in zip(scans, col_nnz):
        if "chunks" in ev["attrs"]:
            ev["attrs"].update(col_nnz=n, col_layout="chunked")
    return events


def test_col_fill_reads_real_entries_over_chunk_slots(root):
    got = _read(root, CELL, METRIC, _filled())
    assert got == pytest.approx(100.0 * 2880 / (128 * 180))


@pytest.mark.parametrize("case", ("no_spans", "partial_window", "no_col_nnz",
                                  "no_chunks", "zero_chunks", "other_width"))
def test_col_fill_gives_none(root, case):
    spans = {"no_spans": [],
             "partial_window": _filled()[3:],
             "no_col_nnz": _fits(),            # a program before col_nnz
             "no_chunks": _filled(drop=("chunks",)),
             "zero_chunks": _filled(chunks=(0, 0, 0)),
             "other_width": _other_width(_filled()),
             }[case]
    assert _read(root, CELL, METRIC, spans) is None
