"""A copy of the benchmark's data files at a size the CPU runs in seconds.

Every configuration keeps its run setting and guarantee; only the dataset
shrinks (300 x 1,200, 15 nnz per row) and T drops to ``steps``.  Runs go
through the real harness with the chip check off, so everything but the
chip is exercised.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import time

from bench import harness

TINY = dict(n=300, d=1200, nnz_per_row=15.0, informative=25)


def tiny_root(tmp: pathlib.Path, steps: int = 40) -> pathlib.Path:
    """``tmp`` laid out as a checkout holding a tiny copy of the benchmark."""
    root = tmp / "checkout"
    shutil.copytree(harness.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("out", "tests",
                                                  "__pycache__"))
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for entry in spec["configs"]:
        cfg = json.loads((harness.ROOT / entry["file"]).read_text())
        cfg["dataset"].update(TINY)
        cfg["steps"] = steps
        entry["file"] = entry["file"].replace(".json", "-tiny.json")
        (root / entry["file"]).write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run(root: pathlib.Path, workload: str, *, seed: int = 2 ** 31 + 5,
        seconds: float = 0.3, trace: bool = False) -> dict:
    cell = harness.load_cell(workload, root)
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            compile_cache=False, out_dir=root / "out")
