"""One benchmark run of one cell, driven by the data in ``BENCHMARK.json``.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric is found by name:

* configuration ``<c>`` -> the ``file`` its entry names (sizes, run
  setting, guarantee);
* traffic ``<t>`` -> ``bench/traffic/<t>.json``, read by ``driver.py``,
  whose ``entry`` names ``bench/entries/<entry>.py``;
* cell ``<w>`` -> its limits in ``bench/limits/<w>.json``;
* per-layer metric ``<m>`` -> ``bench/metrics/<m>.py``, whose ``read(run)``
  returns the number or None when the run holds nothing to read.

A run: generate the data from the seed, coerce it with the program's own
layout path, warm every program the cell uses, measure whole requests for
``seconds``, read the device's peak memory, free the program's state, and
replay a sample of the answers with the plain reference.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from typing import Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# A traced run traces whole requests for this long at most (at least one):
# the trace holds every operation of every loop iteration, about 70 per
# private step, and its size and reading time grow with the window.
TRACE_SECONDS = 8.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: pathlib.Path


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench_dir = root / "bench"
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((bench_dir / "limits" / f"{name}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer, bench_dir=bench_dir)


def metric_reader(bench_dir: pathlib.Path, name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a per-layer reader may look at."""

    cell: Cell
    host: Dict[str, float]        # host-clock readings, seconds
    work: Dict[str, int]          # fits, steps per fit, lanes
    spans: List[dict]             # the program's repro.obs events
    trace: object                 # tracefile.Trace, or None
    chips: List[int]              # chip ids the cell used
    peaks: dict


def _device(require_tpu: bool, chips: int):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def _use_compile_cache():
    import jax

    from repro.launch.compile_cache import use_compile_cache
    where = use_compile_cache()
    # keep every program, small ones included, so a second run compiles
    # nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             compile_cache: bool = True,
             out_dir: Optional[pathlib.Path] = None) -> dict:
    """One run; returns the result object (the last line's content)."""
    import jax

    from bench import correct
    devs = _device(require_tpu, cell.chips)
    dev = devs[0]
    if compile_cache:
        print(f"compile cache: {_use_compile_cache()}", flush=True)
    from repro import obs

    twin, drv, host = _setup(cell, seed, t_start)
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    print("set-up " + " ".join(f"{k}={v:.3f}" for k, v in host.items())
          + f"; window of {seconds} s", flush=True)

    tel = obs.enable() if trace else None
    logdir = str((out_dir or BENCH_DIR / "out") / "trace"
                 / f"{cell.name}-{seed}")
    if trace:
        jax.profiler.start_trace(logdir, profiler_options=_trace_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        win = drv.window(seconds)
    if trace:
        jax.profiler.stop_trace()
        obs.disable()
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    took = sorted(r.seconds for r in win.requests)
    print(f"window: {len(win.requests)} requests, {len(win.done)} done in "
          f"{win.elapsed_s:.3f} s; request seconds min {took[0]:.4f} median "
          f"{took[len(took) // 2]:.4f} max {took[-1]:.4f}; compilations "
          f"inside the window: {win.compiles}", flush=True)
    print("request seconds in order: "
          + " ".join(f"{r.seconds:.4f}" for r in win.requests), flush=True)

    # the answers go to the host and the program's state is freed before
    # the reference runs
    sample = correct.pick(win.requests, int(cell.limits["checked_fits"]),
                          seed)
    fits = [(correct.host_fit(r.result), r.lam, r.seed) for r in sample]
    exact = {"requests_not_done": float(win.failed)}
    if hasattr(drv, "verify_ledger"):
        exact["ledger_drift"] = float(drv.verify_ledger())
    for r in win.requests:
        r.result = None
    drv.close()
    del drv
    ok, shown = _judge(cell, twin, fits, exact)

    result = {"correct": bool(ok), "attempted": len(win.requests),
              "failed": win.failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    work = {"fits": len(win.done), "steps_per_fit": int(cell.config["steps"]),
            "lanes": len(win.done)}
    if trace:
        metrics = _per_layer(cell, host, work, tel.events, logdir, devs,
                             device, result, require_tpu, keep=out_dir)
    else:
        values = {"setup_s": host["setup_s"],
                  "fit_s": win.elapsed_s / max(len(win.done), 1),
                  "fits_per_s": len(win.done) / win.elapsed_s,
                  "peak_hbm_bytes": float(peak)}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update(metrics=metrics, device=device,
                  compiles_in_window=win.compiles, checks=shown)
    return result


def _setup(cell: Cell, seed: int, t_start: float):
    """Data from the seed, the program's coercion, and the warm-up."""
    from bench import driver
    from bench.datagen import run_data
    from repro.core.sparse.formats import HostCSR
    host: Dict[str, float] = {"start_s": time.perf_counter() - t_start}
    t0 = time.perf_counter()
    twin = run_data(cell.config["dataset"], seed)
    x_host = HostCSR(twin.indptr, twin.indices, twin.data, twin.shape)
    drv = driver.make_driver(cell.config, cell.traffic, seed,
                              cell.bench_dir)
    host["data_s"] = time.perf_counter() - t0
    with driver.CompileCounter() as compiles:
        t0 = time.perf_counter()
        drv.coerce(x_host, twin.y)
        host["layout_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        drv.warm()
        host["warm_s"] = time.perf_counter() - t0
    host["setup_s"] = time.perf_counter() - t_start
    host["setup_compiles"] = compiles.count
    host["setup_compile_s"] = compiles.seconds
    return twin, drv, host


def _judge(cell: Cell, twin, fits, exact):
    from bench import correct, reference
    t0 = time.perf_counter()
    prob = reference.Problem.from_arrays(twin.indptr, twin.indices,
                                         twin.data, twin.y, twin.shape)
    ok, shown = correct.check(prob, cell.config, fits, cell.limits, exact)
    print(f"reference replay of {len(fits)} fit(s): "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    return ok, shown


def _per_layer(cell: Cell, host, work, spans, logdir, devs, device, result,
               require_tpu, keep) -> dict:
    """Read the cell's per-layer metrics from the trace and the spans; add
    ``busy_s``/``window_s`` to ``device`` and the ``breakdown``."""
    import numpy as np

    from bench import tracefile
    from bench.peaks import peaks
    path = tracefile.latest_xplane(logdir)
    tr = tracefile.load(path) if path else None
    if keep is None:
        shutil.rmtree(logdir, ignore_errors=True)
    chips = [d.id for d in devs]
    run = Run(cell=cell, host=host, work=work, spans=list(spans), trace=tr,
              chips=chips,
              peaks=peaks(devs[0].device_kind) if require_tpu else {})
    metrics = {}
    for m in cell.per_layer:
        value = metric_reader(cell.bench_dir, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if tr is not None:
        device["busy_s"] = float(np.mean([tr.busy_s(c) for c in chips]))
        device["window_s"] = tr.window_s
        result["breakdown"] = breakdown(tr, chips[0])
    return metrics


def _trace_options():
    from jax.profiler import ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def breakdown(tr, chip: int) -> dict:
    """The ten device operations that took most time (self time, summed
    by name), and the ten longest idle gaps named by what the host was
    doing."""
    ops = tr.ops.get(chip)
    top = sorted(ops.within(*tr.window).self_by_name_s().items(),
                 key=lambda kv: -kv[1])[:10] if ops is not None else []
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[n, s] for n, s in tr.idle_gaps(chip, 10)]}


def report(result: dict) -> str:
    """The last stdout line; the compared numbers also go to stderr."""
    for name, v in result["checks"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return json.dumps(result)
