"""The queue refresh of the ``jax_sparse`` scan (Algorithm 2, line 29).

The scan refreshes the selection queue once per step, after the chunk loop,
with a dense rebuild from the final α (``tl_rebuild`` / ``ga_rebuild``).
These tests hold it to the per-chunk scatter refresh it replaced, kept here
as ``_scatter_step``: every 128-row chunk scattered |α| of the coordinates
its rows touch into the table (``tl_update`` / ``ga_update``).

* the table (v / p) is bit-identical after every step; the two-level
  sampler's ``c`` is bit-identical for every group whose row changed and
  unchanged elsewhere;
* the argmax bounds equal a scatter-max of each touched coordinate's final
  priority.  A step of one chunk gives the per-chunk bounds bit for bit; a
  step of several chunks may give lower ones, since the per-chunk ratchet
  also kept priorities α held between chunks.  Both stay upper bounds and
  the next selection is the same;
* flat ``PaddedCSC`` and ``TieredCSC``, single and vmapped lanes, steps of
  one chunk and of three or more;
* fixed-T fits take the same coordinates as a scan with the scatter rule;
* the chunk loop of the compiled step scatters only into v̄, q̄ and α.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.samplers.bsls_jax import (tl_init, tl_rebuild, tl_update)
from repro.core.samplers.group_argmax import (ga_get_next, ga_init,
                                              ga_rebuild, ga_update)
from repro.core.solvers import jax_sparse
from repro.core.solvers.jax_sparse import TILE_ROWS
from repro.core.sparse.formats import TieredCSC, tiered_from_padded
from repro.kernels.bsls_draw.ops import two_level_draw
from repro.kernels.coord_update.ops import coord_update

N, D, LAM, STEPS = 900, 80, 5.0, 40
# a large EM scale makes the private draws near-argmax, so they reach the
# popular multi-chunk columns as well as the rare one-chunk ones
EM_SCALE = 400.0


@pytest.fixture(scope="module")
def problem():
    """900 rows: most columns fit one 128-row chunk, the popular ones 3-7."""
    from repro.data.synthetic import make_sparse_classification
    X, y, _ = make_sparse_classification(
        n=N, d=D, nnz_per_row=8, informative=8, seed=3)
    return X, y


@pytest.fixture(scope="module", params=("flat", "tiered"))
def layout(request, problem):
    from repro.core.solvers.registry import as_padded
    X, y = problem
    pcsr, pcsc = as_padded(X)
    if request.param == "tiered":
        pcsc = tiered_from_padded(pcsc, TILE_ROWS)
        assert isinstance(pcsc, TieredCSC)
    setup = jax_sparse.fw_setup_jit(pcsr, jnp.asarray(y, jnp.float32),
                                    loss="logistic")
    return pcsr, pcsc, setup


def _col_chunks(X) -> np.ndarray:
    return -(-np.bincount(X.indices, minlength=X.shape[1]) // TILE_ROWS)


def _scatter_step(pcsr, pcsc, carry, lam, em_scale, t, *, private):
    """One fixed-T step with the per-chunk scatter refresh (the rule the
    scan used before the dense rebuild).  Returns the carry, (gap, j), and
    the queue a single scatter of the step's touched coordinates with
    their final priorities would give."""
    n, d = pcsr.shape
    (w, w_m, g_tilde, vbar, qbar, alpha, sampler, key, done,
     stop_at) = carry
    dtype = pcsr.values.dtype
    em_scale = jnp.asarray(em_scale, dtype)
    t = jnp.asarray(t, jnp.int32).astype(dtype)
    key_next, sel_key = jax.random.split(key)
    if private:
        j = two_level_draw(sampler.c, sampler.v, sel_key)
    else:
        j, sampler = ga_get_next(sampler)
    j = jnp.minimum(j, d - 1)
    a_j = alpha[j]
    d_tilde = -lam * jnp.sign(a_j)
    d_tilde = jnp.where(a_j == 0, lam, d_tilde)
    gap = g_tilde - d_tilde * a_j
    eta = 2.0 / (t + 2.0)
    w_m = w_m * (1.0 - eta)
    w = w.at[j].add(eta * d_tilde / w_m)
    g_tilde = g_tilde * (1.0 - eta) + eta * d_tilde * a_j
    refresh = tl_update if private else ga_update
    scale = em_scale if private else 1.0

    def tile(col):
        rows, xvals, mask = (jnp.pad(a, (0, -a.shape[0] % TILE_ROWS))
                             for a in col())
        n_chunks = (jnp.sum(mask) + TILE_ROWS - 1) // TILE_ROWS

        def chunk(c, state):
            vbar, qbar, alpha, g_tilde, queue = state
            r, x, m = (jax.lax.dynamic_slice_in_dim(a, c * TILE_ROWS,
                                                    TILE_ROWS)
                       for a in (rows, xvals, mask))
            row_idx = pcsr.indices[r]
            row_val = pcsr.values[r]
            vbar, qbar, alpha, g_c = coord_update(
                vbar, qbar, alpha, w, r, x, m, row_idx, row_val,
                eta=eta, d_tilde=d_tilde, w_m=w_m, inv_n=1.0 / n,
                loss="logistic")
            flat = row_idx.reshape(-1)
            queue = refresh(queue, flat, jnp.abs(alpha[flat]) * scale)
            return vbar, qbar, alpha, g_tilde + g_c, queue

        vbar_, qbar_, alpha_, g_, queue = jax.lax.fori_loop(
            0, n_chunks, chunk, (vbar, qbar, alpha, g_tilde, sampler))
        ran = jnp.arange(rows.shape[0]) < n_chunks * TILE_ROWS
        # rows past the chunks that ran count as padding lanes (index 0,
        # as in the layout), never as ids >= d: ``*_update`` rewrites slot 0
        # with its old value for those, which races a live slot-0 write
        touched = jnp.where(ran[:, None], pcsr.indices[rows], 0).reshape(-1)
        once = refresh(sampler, touched, jnp.abs(alpha_[touched]) * scale)
        return vbar_, qbar_, alpha_, g_, queue, once

    if isinstance(pcsc, TieredCSC):
        out = jax.lax.cond(pcsc.is_heavy(j),
                           lambda: tile(lambda: pcsc.col_heavy(j)),
                           lambda: tile(lambda: pcsc.col_light(j)))
    else:
        out = tile(lambda: pcsc.col(j))
    vbar, qbar, alpha, g_tilde, queue, once = out
    new = jax_sparse.FWCarry(w, w_m, g_tilde, vbar, qbar, alpha, queue,
                             key_next, done, stop_at)
    return new, (gap, j.astype(jnp.int32)), once


def _dense_step(pcsr, pcsc, carry, lam, em_scale, t, *, private):
    """One step of the scan under test, from global offset ``t``."""
    carry, (gaps, coords) = jax_sparse.fw_scan_chunk(
        pcsr, pcsc, carry, lam, em_scale, 0.0, t, steps=1, loss="logistic",
        private=private)
    return carry, (gaps[0], coords[0])


_next = jax.jit(lambda q: ga_get_next(q)[0])


def _queue_arrays(q, private):
    return (q.v, q.c) if private else (q.p, q.bound)


def _assert_same_refresh(prev, dense, scatter, once, *, private, chunks):
    """The step's queue under the dense rule against the scatter rule."""
    for name in ("w", "w_m", "g_tilde", "vbar", "qbar", "alpha", "key"):
        np.testing.assert_array_equal(getattr(dense, name),
                                      getattr(scatter, name), err_msg=name)
    table, group = (np.asarray(a) for a in
                    _queue_arrays(dense.sampler, private))
    s_table, s_group = (np.asarray(a) for a in
                        _queue_arrays(scatter.sampler, private))
    o_table, o_group = (np.asarray(a) for a in _queue_arrays(once, private))
    np.testing.assert_array_equal(table, s_table)
    np.testing.assert_array_equal(table, o_table)
    if private:
        p_table, p_c = (np.asarray(a) for a in
                        _queue_arrays(prev.sampler, private))
        changed = (table != p_table).any(axis=1)
        np.testing.assert_array_equal(group[changed], s_group[changed])
        np.testing.assert_array_equal(group[~changed], p_c[~changed])
        return
    np.testing.assert_array_equal(group, o_group)
    if chunks == 1:
        np.testing.assert_array_equal(group, s_group)
    assert (group <= s_group).all()
    assert (group >= table.max(axis=1)).all()
    assert int(_next(dense.sampler)) == int(_next(scatter.sampler))


@pytest.mark.parametrize("private", (False, True),
                         ids=("nonprivate", "private"))
def test_dense_refresh_equals_the_scatter_refresh(problem, layout, private):
    X, _ = problem
    pcsr, pcsc, setup = layout
    em = EM_SCALE if private else 1.0
    carry = jax_sparse.fw_carry_init(D, jnp.float32, *setup, em,
                                     jax.random.PRNGKey(7), private=private)
    dense = jax.jit(lambda cy, t: _dense_step(pcsr, pcsc, cy, LAM, em, t,
                                              private=private))
    scatter = jax.jit(lambda cy, t: _scatter_step(
        pcsr, pcsc, cy, LAM, em, t + 1, private=private))
    col_chunks = _col_chunks(X)
    seen = set()
    for t in range(STEPS):
        new, (gap, j) = dense(carry, t)
        old, (s_gap, s_j), once = scatter(carry, t)
        assert int(j) == int(s_j) and float(gap) == float(s_gap)
        chunks = int(col_chunks[int(j)])
        _assert_same_refresh(carry, new, old, once, private=private,
                             chunks=chunks)
        seen.add(chunks)
        carry = new
    assert 1 in seen and max(seen) >= 3, seen


@pytest.mark.parametrize("private", (False, True),
                         ids=("nonprivate", "private"))
def test_dense_refresh_equals_the_scatter_refresh_vmapped(problem, layout,
                                                          private):
    X, _ = problem
    pcsr, pcsc, setup = layout
    lams = jnp.asarray([LAM, 2.0 * LAM], jnp.float32)
    ems = jnp.asarray([EM_SCALE, EM_SCALE / 4] if private else [1.0, 1.0],
                      jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(3), jax.random.PRNGKey(4)])
    carry = jax.vmap(lambda s, k: jax_sparse.fw_carry_init(
        D, jnp.float32, *setup, s, k, private=private))(ems, keys)
    dense = jax.jit(jax.vmap(
        lambda cy, lam, em, t: _dense_step(pcsr, pcsc, cy, lam, em, t,
                                           private=private),
        in_axes=(0, 0, 0, None)))
    scatter = jax.jit(jax.vmap(
        lambda cy, lam, em, t: _scatter_step(pcsr, pcsc, cy, lam, em, t + 1,
                                             private=private),
        in_axes=(0, 0, 0, None)))
    col_chunks = _col_chunks(X)
    seen = set()
    lane = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
    for t in range(STEPS // 2):
        new, (gap, j) = dense(carry, lams, ems, t)
        old, (s_gap, s_j), once = scatter(carry, lams, ems, t)
        np.testing.assert_array_equal(j, s_j)
        np.testing.assert_array_equal(gap, s_gap)
        for i in range(2):
            chunks = int(col_chunks[int(j[i])])
            _assert_same_refresh(lane(carry, i), lane(new, i), lane(old, i),
                                 lane(once, i), private=private,
                                 chunks=chunks)
            seen.add(chunks)
        carry = new
    assert 1 in seen and max(seen) >= 3, seen


@pytest.mark.parametrize("private", (False, True),
                         ids=("nonprivate", "private"))
def test_fixed_t_fit_takes_the_scatter_rule_coordinates(layout, private):
    pcsr, pcsc, setup = layout
    em = EM_SCALE if private else 1.0
    key = jax.random.PRNGKey(11)
    w, gaps, coords, _ = jax_sparse.fw_scan_jit(
        pcsr, pcsc, *setup, LAM, em, key, 0.0, None, steps=STEPS,
        loss="logistic", private=private)

    @jax.jit
    def scatter_scan():
        carry = jax_sparse.fw_carry_init(D, jnp.float32, *setup, em, key,
                                         private=private)

        def step(cy, t):
            cy, out, _ = _scatter_step(pcsr, pcsc, cy, LAM, em, t,
                                       private=private)
            return cy, out

        cy, (g, j) = jax.lax.scan(step, carry,
                                  jnp.arange(1, STEPS + 1, dtype=jnp.int32))
        return cy.w * cy.w_m, g, j

    s_w, s_gaps, s_coords = scatter_scan()
    np.testing.assert_array_equal(coords, s_coords)
    np.testing.assert_array_equal(gaps, s_gaps)
    np.testing.assert_array_equal(w, s_w)


@pytest.mark.parametrize("private", (False, True),
                         ids=("nonprivate", "private"))
def test_rebuild_equals_update_on_a_random_table(private):
    """Sampler level: a scatter of distinct and repeated indices, padding
    lanes (idx >= d) included, against the dense rebuild."""
    rng = np.random.default_rng(5)
    d = 1000
    base = jnp.asarray(rng.random(d), jnp.float32)
    state = tl_init(base) if private else ga_init(base)
    # repeated ids in the first groups and padding lanes (idx >= d); slot 0
    # stays live-free, since ``*_update`` rewrites it with its old value for
    # each dropped lane
    idx = np.concatenate([rng.integers(1, 300, size=60),
                          rng.integers(d, d + 50, size=20)])
    new = np.asarray(base).copy()
    live = idx[idx < d]
    new[live] = rng.random(live.size).astype(np.float32)
    new = jnp.asarray(new)
    idx = jnp.asarray(idx, jnp.int32)
    vals = new[jnp.minimum(idx, d - 1)]
    if private:
        old = tl_update(state, idx, vals)
        fresh = tl_rebuild(state, new)
        np.testing.assert_array_equal(fresh.v, old.v)
        changed = np.asarray((fresh.v != state.v).any(axis=1))
        assert changed.any() and not changed.all()
        np.testing.assert_array_equal(np.asarray(fresh.c)[changed],
                                      np.asarray(old.c)[changed])
        np.testing.assert_array_equal(np.asarray(fresh.c)[~changed],
                                      np.asarray(state.c)[~changed])
    else:
        old = ga_update(state, idx, vals)
        fresh = ga_rebuild(state, new)
        np.testing.assert_array_equal(fresh.p, old.p)
        np.testing.assert_array_equal(fresh.bound, old.bound)


# ---------------------------------------------------------------------------
# structure: what the compiled chunk loop scatters into
# ---------------------------------------------------------------------------

_CALLS = re.compile(r"(?:body|condition|to_apply|calls|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_SCATTER = re.compile(r"=\s*([a-z]+\d*\[[\d,]*\])\S*\s+scatter\(")
_WHILE = re.compile(r"\swhile\(.*body=%?([\w.\-]+)")


def _computations(hlo: str) -> dict:
    """name -> instruction lines of an HLO module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split()
            name = (head[1] if head[0] == "ENTRY" else head[0]).lstrip("%")
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _reach(comps: dict, root: str) -> set:
    """Computations ``root`` calls, transitively, itself included."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += _CALLS.findall(line)
            for group in _BRANCHES.findall(line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def _scatters(comps: dict, roots: set) -> list:
    return [m for c in roots for line in comps[c]
            for m in _SCATTER.findall(line)]


def _chunk_loop_scatters(hlo: str, n: int) -> list:
    """Scatter result shapes in the body of the innermost while loops that
    scatter into a row-space (N,) vector — the coordinate update's chunk
    loop."""
    comps = _computations(hlo)
    bodies = {b for lines in comps.values() for line in lines
              for b in _WHILE.findall(line)}
    row_space = f"f32[{n}]"
    with_update = {b for b in bodies
                   if row_space in _scatters(comps, _reach(comps, b))}
    inner = [b for b in with_update
             if not (_reach(comps, b) - {b}) & with_update]
    assert inner, "no chunk loop found"
    return [s for b in inner for s in _scatters(comps, _reach(comps, b))]


@pytest.mark.parametrize("private", (False, True),
                         ids=("nonprivate", "private"))
def test_chunk_loop_scatters_only_the_coordinate_update(layout, private):
    pcsr, pcsc, setup = layout
    carry = jax_sparse.fw_carry_init(D, jnp.float32, *setup, 1.0,
                                     jax.random.PRNGKey(0), private=private)
    hlo = jax_sparse.fw_scan_chunk_jit.lower(
        pcsr, pcsc, carry, LAM, 1.0, 0.0, 0, None, steps=2,
        loss="logistic", private=private).as_text(dialect="hlo")
    table = (carry.sampler.v if private else carry.sampler.p).shape
    queue_shapes = {f"f32[{table[0] * table[1]}]",
                    f"f32[{table[0]},{table[1]}]",
                    f"f32[{table[0]}]", f"pred[{table[0]}]"}
    assert not queue_shapes & {f"f32[{N}]", f"f32[{D}]"}
    scatters = _chunk_loop_scatters(hlo, N)
    # v̄ and q̄ (row space), α (feature space)
    assert sorted(set(scatters)) == sorted({f"f32[{N}]", f"f32[{D}]"})
    assert not queue_shapes & set(scatters)
