"""The readers of the program's own spans (``bench/spans.py`` and the
per-layer metrics that use it), on hand-made runs and on the fixture trace,
and the program's spans in a profiler trace on the CPU."""
import pathlib

import numpy as np
import pytest

from bench import harness, tracefile
from bench.tests import tiny

FIXTURE = (pathlib.Path(__file__).resolve().parents[1] / "fixtures"
           / "dp_solve_tiny.xplane.pb.gz")
FITS, STEPS = 3, 40
# the fixture's jit_fw_scan device time per step (test_bench_trace.py)
SCAN_US_PER_STEP = 121.65420833333334
SOLVE_READERS = ("chunks_per_step.solve", "scan_us_per_chunk.solve",
                 "enqueue_ms_per_fit.solve")
SERVICE_READERS = ("lane_chunk_util.service",
                   "scan_us_per_lane_chunk.service")


@pytest.fixture(scope="module")
def fixture_trace():
    return tracefile.load(str(FIXTURE))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("spans"), steps=STEPS)


def _read(root, cell_name, name, spans, trace=None, fits=FITS, lanes=None):
    cell = harness.load_cell(cell_name, root)
    run = harness.Run(cell=cell, host={}, spans=spans, trace=trace,
                      work={"fits": fits, "steps_per_fit": STEPS,
                            "lanes": fits if lanes is None else lanes},
                      chips=[0], peaks={})
    return harness.metric_reader(cell.bench_dir, name)(run)


def _span(id_, name, parent, ts, dur, **attrs):
    return {"ev": "span", "name": name, "id": id_, "parent": parent,
            "ts": ts, "dur_s": dur, "attrs": attrs}


def _fits(chunks=(50, 70, 60), enqueue=(0.002, 0.003, 0.004), drop=()):
    """Three ``solve`` calls, each solve -> solve.run -> solve.scan, closed
    children first as the program records them; ``drop`` leaves attributes
    out (a program that lacks them)."""
    events = []
    for k, (c, e) in enumerate(zip(chunks, enqueue)):
        base, ts = 10 * k + 1, 1.0 * k
        attrs = {"steps": STEPS, "private": True, "chunks": c,
                 "tile_rows": 128, "enqueue_s": e}
        for key in drop:
            attrs.pop(key)
        events += [_span(base + 2, "solve.scan", base + 1, ts + 0.010, 0.5,
                         **attrs),
                   _span(base + 1, "solve.run", base, ts + 0.001, 0.52),
                   _span(base, "solve", 0, ts, 0.53, loss="logistic")]
    return events


def test_solve_readers_on_the_fixture(root, fixture_trace):
    spans = _fits()
    got = {n: _read(root, "rcv1-dp.solve", n, spans, fixture_trace)
           for n in SOLVE_READERS}
    assert got["chunks_per_step.solve"] == pytest.approx(180 / (FITS * STEPS))
    assert got["scan_us_per_chunk.solve"] == pytest.approx(
        SCAN_US_PER_STEP * FITS * STEPS / 180, rel=1e-12)
    # scan start + enqueue - solve start: 12, 13, 14 ms
    assert got["enqueue_ms_per_fit.solve"] == pytest.approx(13.0)


def test_service_readers_on_hand_made_trace(root):
    groups = [_span(1, "group.vmap", 0, 0.0, 2.0, size=8, tile_rows=128,
                    lane_chunks=960, run_chunks=200),
              _span(2, "group.vmap", 0, 3.0, 2.0, size=8, tile_rows=128,
                    lane_chunks=640, run_chunks=100)]
    sweep = tracefile.Events.of(["jit__sweep_scan(7)", "jit__sweep_scan(7)",
                                 "jit_fw_setup(3)"],
                                [1_000, 5_000, 9_000], [3_000, 2_400, 500])
    trace = tracefile.Trace(ops={}, modules={0: sweep},
                            host=tracefile.Events.of([], [], []),
                            window=(0, 10_000))
    got = {n: _read(root, "rcv1-dp.service", n, groups, trace, fits=16)
           for n in SERVICE_READERS}
    assert got["lane_chunk_util.service"] == pytest.approx(
        100.0 * 1600 / (8 * 300))
    assert got["scan_us_per_lane_chunk.service"] == pytest.approx(
        5_400e-9 * 1e6 / (8 * 300))


def _other_width(events):
    """``events`` as a program counting chunks of 256 rows would give."""
    return [dict(ev, attrs={**ev["attrs"], "tile_rows": 256})
            if "tile_rows" in ev["attrs"] else ev for ev in events]


@pytest.mark.parametrize("case", ("no_spans", "partial_window",
                                  "no_chunks", "no_enqueue", "no_parent",
                                  "other_width"))
def test_solve_readers_give_none(root, fixture_trace, case):
    spans = {"no_spans": [],
             "partial_window": _fits()[3:],
             "no_chunks": _fits(drop=("chunks",)),
             "no_enqueue": _fits(drop=("enqueue_s",)),
             "no_parent": [e for e in _fits() if e["name"] != "solve"],
             "other_width": _other_width(_fits()),
             }[case]
    want_none = {"no_spans": SOLVE_READERS, "partial_window": SOLVE_READERS,
                 "no_chunks": SOLVE_READERS[:2],
                 "no_enqueue": SOLVE_READERS[2:],
                 "no_parent": SOLVE_READERS[2:],
                 "other_width": SOLVE_READERS[:2]}[case]
    for name in SOLVE_READERS:
        got = _read(root, "rcv1-dp.solve", name, spans, fixture_trace)
        assert (got is None) == (name in want_none), (case, name, got)


@pytest.mark.parametrize("case", ("no_spans", "lanes_differ", "no_counts",
                                  "no_trace", "other_width"))
def test_service_readers_give_none(root, case):
    full = [_span(1, "group.vmap", 0, 0.0, 2.0, size=8, tile_rows=128,
                  lane_chunks=960, run_chunks=200)]
    spans = {"no_spans": [], "lanes_differ": full, "no_trace": full,
             "no_counts": [_span(1, "group.vmap", 0, 0.0, 2.0, size=8)],
             "other_width": _other_width(full)}[case]
    lanes = 16 if case == "lanes_differ" else 8
    for name in SERVICE_READERS:
        got = _read(root, "rcv1-dp.service", name, spans, None, fits=8,
                    lanes=lanes)
        if case == "no_trace" and name == "lane_chunk_util.service":
            assert got == pytest.approx(100.0 * 960 / 1600)
        else:
            assert got is None, (case, name)


def test_program_spans_share_the_profiler_clock(tmp_path):
    """With a collector active the program's spans land in the profiler's
    host plane, nested as the program opened them."""
    import jax

    from repro import obs
    from repro.core.solvers import FWConfig, solve
    from repro.data.synthetic import make_sparse_classification
    X, y, _ = make_sparse_classification(n=60, d=40, nnz_per_row=5,
                                         informative=4, seed=2)
    cfg = FWConfig(backend="jax_sparse", lam=4.0, steps=6)
    solve(X, y, cfg)                                  # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.session():
            solve(X, y, cfg)
        solve(X, y, cfg)                              # collector off
    finally:
        jax.profiler.stop_trace()
    tr = tracefile.load(tracefile.latest_xplane(str(tmp_path)))
    names = np.asarray(tr.host.names)
    for name in ("solve", "solve.setup", "solve.scan"):
        assert (names == name).sum() == 1, name
    outer, = np.flatnonzero(names == "solve")
    inner, = np.flatnonzero(names == "solve.scan")
    assert tr.host.start[outer] <= tr.host.start[inner]
    assert (tr.host.start[inner] + tr.host.dur[inner]
            <= tr.host.start[outer] + tr.host.dur[outer])
