"""Compile the main path for a TPU v5e at rcv1's published widths.

Nothing runs: the programs are compiled for a v5e that is described, not
attached, so the chip's compiler refuses here what it would refuse on the
chip — a block that is not tile-aligned, a primitive Mosaic cannot lower,
a program that does not fit the 16 GiB of HBM.  The topology is described
inside a module fixture, never while a module is imported.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_lasso import DATASETS
from repro.core.sparse.formats import PaddedCSC, PaddedCSR, lane_padded

RCV1 = DATASETS["rcv1"]
N, D = RCV1.n, RCV1.d
KR = 110            # max row nnz of the generated rcv1 matrix
KC = lane_padded(N)  # its most popular column appears in every row
HBM_BYTES = 16 * 2**30
HEADROOM = 0.75     # programs may claim at most this share of HBM


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abs(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def pair(one_chip):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pcsr = PaddedCSR(s((N, KR), jnp.int32), s((N, KR), jnp.float32),
                     s((N,), jnp.int32), (N, D))
    pcsc = PaddedCSC(s((D, KC), jnp.int32), s((D, KC), jnp.float32),
                     s((D,), jnp.int32), (N, D))
    return pcsr, pcsc


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert used < HEADROOM * HBM_BYTES, (m.argument_size_in_bytes,
                                         m.temp_size_in_bytes)
    return m.temp_size_in_bytes


def test_bsls_draw_compiles_to_a_tpu_kernel(one_chip):
    from repro.core.samplers.bsls_jax import group_shape
    from repro.kernels.bsls_draw.ops import two_level_draw
    g, m = group_shape(D)
    assert g % 8 == 0 and m % 128 == 0
    args = _abs((jax.ShapeDtypeStruct((g,), jnp.float32),
                 jax.ShapeDtypeStruct((g, m), jnp.float32),
                 jax.eval_shape(lambda: jax.random.PRNGKey(0))), one_chip)
    text = two_level_draw.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_fw_setup_compiles(pair, one_chip):
    from repro.core.solvers.jax_sparse import fw_setup_jit
    pcsr, _ = pair
    y = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=one_chip)
    _fits(fw_setup_jit.lower(pcsr, y, loss="logistic").compile())


@pytest.mark.parametrize("private", [False, True])
def test_scan_chunk_compiles_and_fits(pair, one_chip, private):
    """The chunked scan at the paper's speed-run chunk: the (D, Kc) column
    table stays resident once — no relayout copy of it in temporaries."""
    from repro.core.solvers.jax_sparse import fw_carry_init, fw_scan_chunk_jit
    from repro.core.solvers.planner import default_chunk
    pcsr, pcsc = pair
    f32 = jax.ShapeDtypeStruct((), jnp.float32)
    carry = _abs(jax.eval_shape(
        lambda v, q, a, k: fw_carry_init(D, jnp.float32, v, q, a, 1.0, k,
                                         private=private),
        jax.ShapeDtypeStruct((N,), jnp.float32),
        jax.ShapeDtypeStruct((N,), jnp.float32),
        jax.ShapeDtypeStruct((D,), jnp.float32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))), one_chip)
    scal = _abs(f32, one_chip)
    t0 = _abs(jax.ShapeDtypeStruct((), jnp.int32), one_chip)
    compiled = fw_scan_chunk_jit.lower(
        pcsr, pcsc, carry, scal, scal, scal, t0, None,
        steps=default_chunk(4000), loss="logistic", private=private,
        early_stop=True).compile()
    temp = _fits(compiled)
    assert temp < KC * D * 8 // 4      # no second copy of the column table
    assert ("tpu_custom_call" in compiled.as_text()) == private


@pytest.mark.parametrize("private", [False, True])
def test_news20_chunked_scan_compiles_and_fits(one_chip, private):
    """news20 at its published shape on the nnz-bounded column layout
    (DESIGN.md §5): the whole fixed-T scan fits one chip in well under a
    GB, where the flat column table alone would take 218 GB."""
    from repro.core.solvers.jax_sparse import fw_scan_jit
    from repro.core.sparse.formats import ChunkedCSC
    news20 = DATASETS["news20"]
    n, d = news20.n, news20.d
    kr, nnz = 548, 9_095_385     # the generated twin's widest row, its nnz
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pcsr = PaddedCSR(s((n, kr), jnp.int32), s((n, kr), jnp.float32),
                     s((n,), jnp.int32), (n, d))
    pcsc = ChunkedCSC(s((d + 1,), jnp.int32), s((nnz + 128,), jnp.int32),
                      s((nnz + 128,), jnp.float32), s((d,), jnp.int32),
                      (n, d))
    f32 = s((), jnp.float32)
    key = _abs(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
    compiled = fw_scan_jit.lower(
        pcsr, pcsc, s((n,), jnp.float32), s((n,), jnp.float32),
        s((d,), jnp.float32), f32, f32, key, f32, None, steps=4000,
        loss="logistic", private=private).compile()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 2**30
    assert ("tpu_custom_call" in compiled.as_text()) == private


def test_jax_shard_scan_compiles_on_2x2(topo):
    """The four-chip path: the sharded scan on a v5e 2×2 mesh, each device
    holding one (N/2 × D/2) block."""
    from jax.sharding import AxisType, Mesh

    from repro.core.solvers.jax_shard import shard_lowering
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    jitted, args = shard_lowering(N, D, mesh, steps=4000, kc=lane_padded(N // 2),
                                  kr=KR, selection="argmax")
    compiled = jitted.lower(*args).compile()
    _fits(compiled)
    assert "all-reduce" in compiled.as_text()
