"""The trace reduction, on a small trace recorded on a TPU v5e and on
hand-made intervals.

The fixture is a traced run of ``rcv1-dp.solve`` at the tiny size of
``tiny.py`` (T = 40) that fitted 3 times in its window; the numbers below
are what that run printed."""
import pathlib

import numpy as np
import pytest

from bench import harness, tracefile
from bench.peaks import peaks
from bench.tests import tiny

FIXTURE = (pathlib.Path(__file__).resolve().parents[1] / "fixtures"
           / "dp_solve_tiny.xplane.pb.gz")
FITS, STEPS = 3, 40
RECORDED = {"fw_setup_ms.solve": 0.14529833333333333,
            "scan_us_per_step.solve": 121.65420833333334,
            "bsls_draw_us_per_step.solve": 0.7304416666666668,
            "bsls_draw_roofline.solve": 0.7716073566643425,
            "idle_share.solve": 45.17901967996774}


@pytest.fixture(scope="module")
def trace():
    return tracefile.load(str(FIXTURE))


def test_fixture_structure(trace):
    assert list(trace.ops) == [0] and list(trace.modules) == [0]
    runs = trace.modules[0].within(*trace.window)
    assert len(runs.matching(r"^jit_fw_scan\(")) == FITS
    assert len(runs.matching(r"^jit_fw_setup\(")) == FITS
    kernel = trace.ops[0].within(*trace.window).matching(
        r"^little_step_pallas$")
    assert len(kernel) == FITS * STEPS
    assert 0 < trace.busy_s(0) < trace.window_s
    assert trace.window_s == pytest.approx(0.02598988)
    assert trace.busy_s(0) == pytest.approx(0.014247907)


def test_readers_on_the_fixture(tmp_path, trace):
    root = tiny.tiny_root(tmp_path, steps=STEPS)
    cell = harness.load_cell("rcv1-dp.solve", root)
    run = harness.Run(cell=cell, host={"layout_s": 0.5},
                      work={"fits": FITS, "steps_per_fit": STEPS,
                            "lanes": FITS},
                      spans=[], trace=trace, chips=[0],
                      peaks=peaks("TPU v5 lite"))
    got = {m["name"]: harness.metric_reader(cell.bench_dir, m["name"])(run)
           for m in cell.per_layer}
    assert got["layout_s"] == 0.5
    for name, value in RECORDED.items():
        assert got[name] == pytest.approx(value, rel=1e-12), name
    assert 0 < got["bsls_draw_roofline.solve"] < 100
    out = harness.breakdown(trace, 0)
    assert len(out["device_ops"]) == 10 and out["idle_gaps"]


def test_union_self_time_and_gaps_by_hand():
    # a while op [0, 100) holding two leaves, a third leaf after a gap
    ops = tracefile.Events.of(["while", "fusion f32[8]", "little_step_pallas",
                               "copy"], [0, 10, 50, 150], [100, 20, 30, 10])
    host = tracefile.Events.of(["bench.window", "bench.fit", "dispatch"],
                               [0, 0, 110], [200, 200, 20])
    tr = tracefile.Trace(ops={0: ops}, modules={}, host=host,
                         window=(0, 200))
    assert ops.self_ns.tolist() == [50, 20, 30, 10]
    assert tr.union(0).tolist() == [[10, 30], [50, 80], [150, 160]]
    assert tr.busy_s(0) == pytest.approx(60e-9)
    gaps = tr.idle_gaps(0, top=2)
    assert gaps[0] == ("dispatch", pytest.approx(70e-9))
    assert gaps[1] == ("bench.fit", pytest.approx(40e-9))


def test_op_names():
    assert tracefile.op_name(
        "%little_step_pallas.3 = s32[1]{0:T(128)} custom-call(s32[1] %g)"
    ) == "little_step_pallas"
    assert tracefile.op_name(
        "%fusion.7 = f32[4000]{0:T(1024)S(1)} fusion(s32[130000] %a), "
        "kind=kCustom") == "fusion f32[4000]"
    assert tracefile.op_name("%while.34 = (s32[], f32[8]) while(%t)") \
        == "while"


def test_empty_window_reads_nothing():
    tr = tracefile.Trace(ops={}, modules={}, host=tracefile.Events.of(
        [], np.zeros(0), np.zeros(0)), window=(0, 10))
    assert tr.busy_s(0) == 0.0
