"""Distributed Frank-Wolfe (shard_map, 2×2 mesh in a subprocess — jax device
count is locked at first init, so multi-device runs get their own process).

The non-private solve goes through the *registry* (``backend="jax_shard"``,
``mesh=(2, 2)``) so the full production path — ShardSource coercion → block
build → setup/scan programs — is exercised on a genuinely sharded mesh, not
just the 1×1 parity harness of test_jax_shard.py."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np, jax, jax.numpy as jnp
from repro.data.synthetic import make_sparse_classification
from repro.core.fw_sparse import sparse_fw
from repro.distributed.block_sparse import build_block_sparse
from repro.distributed.fw_shard import DistFWConfig, distributed_fw

X, y, _ = make_sparse_classification(n=120, d=400, nnz_per_row=10,
                                     informative=15, seed=5)
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
blocks = build_block_sparse(X, 2, 2)
y_pad = jnp.zeros(blocks.padded[0], jnp.float32).at[:len(y)].set(
    jnp.asarray(y, jnp.float32))

out = {}
from repro.core.solvers import FWConfig, solve
res = solve(X, y, FWConfig(backend="jax_shard", mesh=(2, 2), lam=8.0,
                           steps=80))
host = sparse_fw(X, y, lam=8.0, steps=80, queue="fib_heap")
out["coords_match"] = bool(
    (np.asarray(res.coords) == np.asarray(host.coords)).all())
out["w_maxdiff"] = float(np.abs(np.asarray(res.w) - np.asarray(host.w)).max())
out["gap_dist"] = float(res.gaps[-1])
out["gap_host"] = float(host.gaps[-1])

with mesh:
    wg, gg, cg, sg = distributed_fw(
        blocks, y_pad,
        DistFWConfig(lam=8.0, steps=60, selection="gumbel", epsilon=1.0), mesh)
out["dp_finite"] = bool(np.isfinite(np.asarray(wg)).all())
out["dp_unique_coords"] = len(set(np.asarray(cg).tolist()))
out["dp_stop_step"] = int(sg)

with mesh:
    wc, gc, _, _ = distributed_fw(
        blocks, y_pad,
        DistFWConfig(lam=8.0, steps=80, selection="argmax", compress_topk=8),
        mesh)
out["topk_gap"] = float(gc[-1])
out["topk_l1"] = float(np.abs(np.asarray(wc)).sum())
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist_result():
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                          text=True, timeout=900, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_distributed_matches_host_oracle(dist_result):
    """Sharded FW takes the same steps as the faithful host Alg 2."""
    assert dist_result["coords_match"]
    assert dist_result["w_maxdiff"] < 1e-5


def test_distributed_gap_matches(dist_result):
    assert dist_result["gap_dist"] == pytest.approx(
        dist_result["gap_host"], rel=1e-3, abs=1e-5)


def test_distributed_dp_runs(dist_result):
    assert dist_result["dp_finite"]
    assert dist_result["dp_unique_coords"] > 10   # EM explores
    assert dist_result["dp_stop_step"] == 60      # no gap_tol → full T


def test_topk_compression_converges(dist_result):
    """Error-feedback top-k must stay close to the dense exchange and respect
    the L1 ball."""
    assert dist_result["topk_gap"] < 0.1
    assert dist_result["topk_l1"] <= 8.0 * (1 + 1e-5)
