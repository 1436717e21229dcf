"""A new configuration, traffic mix, traffic entry and per-layer metric need
only new files under bench/ and new entries in BENCHMARK.json: no harness
code changes."""
import json

import pytest

from bench import driver
from bench.tests import tiny

NEW_METRIC = '''"""Requests per fit window second, from the host clock."""


def read(run):
    return float(run.work["fits"])
'''

# a way in that no file of the benchmark knows: every cycle is one
# ``solve_many`` call over the whole pool
NEW_ENTRY = '''"""``"entry": "grid"``: one caller, one ``solve_many`` over the pool."""
import time

from bench.driver import Driver, fw_config


class Entry(Driver):

    def coerce(self, X_host, y):
        import jax
        from repro.core.solvers.registry import as_padded
        self.y, self.data = y, as_padded(X_host)
        jax.block_until_ready(self.data)

    def warm(self):
        self.run_cycle(-len(self.pool))

    def run_cycle(self, first):
        import jax
        from repro.core.solvers.batched import solve_many
        reqs = self.cycle(self.pool, first)
        t0 = time.perf_counter()
        out = solve_many(self.data, self.y, [
            fw_config(self.config, lam=r.lam, seed=r.seed) for r in reqs])
        jax.block_until_ready(out)
        for req, res in zip(reqs, out):
            req.result, req.status = res, "done"
            req.seconds = time.perf_counter() - t0
        return reqs

    def close(self):
        self.data = None
'''


def _add_cell(root, spec, config, traffic, moves):
    name = f"{config}.{traffic}"
    spec["workloads"].append({
        "name": name, "config": config, "traffic": traffic, "chips": 1,
        "why": "added by files alone"})
    for m in spec["end_to_end"]:
        if m["name"] == moves:
            m["workloads"].append(name)
    return name


def test_new_config_mix_and_metric_by_name(tmp_path):
    root = tiny.tiny_root(tmp_path)
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "rcv1-dp-tiny.json").read_text())
    cfg.update(name="tiny-dp-eps01", epsilon=0.1)
    (bench / "configs" / "tiny-dp-eps01.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "service-2.json").write_text(json.dumps({
        "entry": "service", "clients": 2, "tenants": 2, "zipf_s": 1.0,
        "lam_choices": [50.0], "pool_seed": 5, "requests_per_tenant": 64}))
    (bench / "metrics" / "fits_in_window.tiny.py").write_text(NEW_METRIC)
    limits = json.loads((bench / "limits" / "rcv1-dp.service.json")
                        .read_text())
    (bench / "limits" / "tiny-dp-eps01.service-2.json").write_text(
        json.dumps(limits))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-dp-eps01", "source": "test fixture",
        "file": "bench/configs/tiny-dp-eps01.json", "reduced": [],
        "why": "a configuration added by files alone"})
    cell = _add_cell(root, spec, "tiny-dp-eps01", "service-2", "fits_per_s")
    spec["per_layer"].append({
        "name": "fits_in_window.tiny", "unit": "fits", "better": "higher",
        "source": "host_clock", "layer": "service and batching",
        "moves": "fits_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    res = tiny.run(root, cell, trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["fits_in_window.tiny"]["value"] >= 2
    plain = tiny.run(root, cell)
    assert set(plain["metrics"]) == {"setup_s", "fits_per_s",
                                     "peak_hbm_bytes"}


def test_new_traffic_entry_by_name(tmp_path):
    root = tiny.tiny_root(tmp_path)
    bench = root / "bench"
    (bench / "entries" / "grid.py").write_text(NEW_ENTRY)
    (bench / "traffic" / "grid-3.json").write_text(json.dumps({
        "entry": "grid", "pool": 3, "pool_seed": 9}))
    limits = json.loads((bench / "limits" / "rcv1-dp.solve.json")
                        .read_text())
    (bench / "limits" / "rcv1-dp.grid-3.json").write_text(json.dumps(limits))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = _add_cell(root, spec, "rcv1-dp", "grid-3", "fit_s")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    res = tiny.run(root, cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "fit_s", "peak_hbm_bytes"}


def test_unknown_entry_is_refused(tmp_path):
    with pytest.raises(KeyError):
        driver.entry_class("no-such-entry", tmp_path)
