"""``correct`` holds a sound run and refuses a broken one.

Each test drives a whole run at the tiny size with the chip check off: a
sound run is correct; the control (the reference one precision below the
configuration's, put in the program's place) is not; and neither is a run
whose timed path is broken underneath: a step that leaves the state
unchanged, a service batch with half its lanes left out, an answer altered
where it is produced.  (One chip: no exchange between chips to leave out.)
"""
import dataclasses

import jax
import numpy as np
import pytest

from bench import control, harness
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("faults"))


@pytest.fixture(scope="module")
def long_root(tmp_path_factory):
    """T=400: bfloat16's drift needs some hundreds of steps to show at the
    tiny size (the cell's own T=4000 reads 1e4-1e7 times the program's)."""
    return tiny.tiny_root(tmp_path_factory.mktemp("control"), steps=400)


@pytest.fixture
def fresh_programs():
    """Compiled programs hold the code they were traced from: drop them
    before and after planting a fault."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("workload", ["rcv1-dp.solve",
                                      "rcv1-nonprivate.solve",
                                      "rcv1-dp.service"])
def test_sound_run_is_correct(root, workload):
    res = tiny.run(root, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", ["rcv1-dp.solve",
                                      "rcv1-nonprivate.solve",
                                      "rcv1-dp.service"])
def test_control_is_not_correct(long_root, workload):
    cell = harness.load_cell(workload, long_root)
    for seed in (2 ** 31 + 11, 2 ** 31 + 12, 2 ** 31 + 13):
        row = control.control_readings(cell, seed)
        assert not row["correct"], row


def _unchanged_state(vbar, qbar, alpha, w, *args, **kwargs):
    return vbar, qbar, alpha, jax.numpy.zeros((), alpha.dtype)


@pytest.mark.parametrize("workload", ["rcv1-dp.solve",
                                      "rcv1-nonprivate.solve"])
def test_step_that_leaves_state_unchanged(root, workload, monkeypatch,
                                          fresh_programs):
    from repro.core.solvers import jax_sparse
    monkeypatch.setattr(jax_sparse, "coord_update", _unchanged_state)
    assert not tiny.run(root, workload)["correct"]


def test_service_batch_with_half_its_lanes_left_out(root, monkeypatch):
    from repro.serve import fit_service
    real = fit_service.solve_many

    def half(X, y, configs, **kw):
        kept = list(configs)[: max(1, len(configs) // 2)]
        out = real(X, y, kept, **kw)
        return [out[i % len(out)] for i in range(len(configs))]

    monkeypatch.setattr(fit_service, "solve_many", half)
    assert not tiny.run(root, "rcv1-dp.service")["correct"]


@pytest.mark.parametrize("workload", ["rcv1-dp.solve",
                                      "rcv1-nonprivate.solve"])
def test_answer_altered_where_produced(root, workload, monkeypatch):
    import repro.core.solvers as solvers
    real = solvers.solve

    def altered(*args, **kwargs):
        res = real(*args, **kwargs)
        coords = np.asarray(res.coords).copy()
        t = coords.shape[0] // 2
        coords[t] = (coords[t] + 1) % tiny.TINY["d"]
        return dataclasses.replace(res, coords=jax.numpy.asarray(coords))

    monkeypatch.setattr(solvers, "solve", altered)
    assert not tiny.run(root, workload)["correct"]


def test_fresh_data_gives_each_seed_a_dataset_and_a_pool(root):
    cell = harness.load_cell("rcv1-dp.solve", root)
    a, b = control.fresh(cell, 2 ** 31 + 5), control.fresh(cell, 2 ** 31 + 6)
    assert a.config["dataset"]["seed"] != b.config["dataset"]["seed"]
    assert a.traffic["pool_seed"] != b.traffic["pool_seed"]
    assert control.fresh(cell, 2 ** 31 + 5).config == a.config
    assert cell.config["dataset"]["seed"] == 1
    assert control.control_readings(a, 2 ** 31 + 5)["correct"] is not None


def test_stated_draw_is_the_programs_and_no_other_is_replayed(root):
    from bench import reference
    draw = dict(harness.load_cell("rcv1-dp.solve", root).config["draw"])
    from repro.core.samplers.bsls_jax import group_shape
    for d in (1200, 20242, 47236):
        assert reference.group_shape(d, draw) == group_shape(d)
    draw["key_split"] = "per step: (sel, key_next) = split(key)"
    with pytest.raises(ValueError):
        reference.gumbel_stream(1, 4, 1200, draw)
