"""Statistical law tests for the exponential mechanism across every sampler.

The paper's privacy proof assumes each coordinate selection is *exactly* the
exponential mechanism P(j) ∝ exp(ε'·u(j)/(2Δu)).  Four implementations claim
that law — Gumbel-max (dense Alg 1), the host BSLS reservoir walk (Alg 4),
its vectorized two-level form, and the device two-level sampler behind the
bsls_draw Pallas kernel.  Here each one's empirical selection frequencies
over many seeded draws are chi-square-tested against the analytic softmax
computed by ``exponential_mechanism_probs`` — the same oracle the privacy
accounting is calibrated to.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.stats import chi2

from repro.core.dp.mechanisms import (em_logits, exponential_mechanism_probs,
                                      gumbel_argmax)
from repro.core.samplers.bsls import BSLSSampler
from repro.core.samplers.bsls_jax import tl_init, tl_sample
from repro.kernels.bsls_draw.ops import two_level_draw

D = 24
EPS_STEP, SENS = 0.9, 0.06
N_DRAWS = 20_000
# chance that a correct sampler fails the chi-square test at a given seed
FALSE_ALARM = 1e-3


@pytest.fixture(scope="module")
def em_problem():
    """Scores + the analytic law every sampler must match."""
    scores = np.random.default_rng(5).uniform(0.0, 1.0, D)
    logits = np.asarray(em_logits(jnp.asarray(scores, jnp.float32),
                                  EPS_STEP, SENS))
    probs = np.asarray(exponential_mechanism_probs(
        jnp.asarray(scores, jnp.float32), EPS_STEP, SENS))
    return scores, logits, probs


def _chi2_ratio(draws: np.ndarray, probs: np.ndarray):
    """(χ²/dof, bound): the bound is the χ² quantile at ``FALSE_ALARM`` for
    the test's degrees of freedom, over dof — a fixed ratio bound would
    reject a correct sampler at a rate that depends on D."""
    counts = np.bincount(draws, minlength=probs.shape[0])[: probs.shape[0]]
    e = probs * len(draws)
    m = e >= 5
    dof = max(int(m.sum()) - 1, 1)
    ratio = float(((counts[m] - e[m]) ** 2 / e[m]).sum() / dof)
    return ratio, float(chi2.ppf(1.0 - FALSE_ALARM, dof) / dof)


def _draw_gumbel(logits, n):
    keys = jax.random.split(jax.random.PRNGKey(101), n)
    lg = jnp.asarray(logits, jnp.float32)
    return np.asarray(jax.vmap(lambda k: gumbel_argmax(k, lg))(keys))


def _draw_two_level(logits, n):
    state = tl_init(jnp.asarray(logits, jnp.float32))
    keys = jax.random.split(jax.random.PRNGKey(102), n)
    return np.asarray(jax.vmap(lambda k: tl_sample(state, k))(keys))


def _draw_two_level_kernel(logits, n):
    """The jax_sparse selection path: big step in XLA + bsls_draw kernel."""
    state = tl_init(jnp.asarray(logits, jnp.float32))
    keys = jax.random.split(jax.random.PRNGKey(103), n)
    return np.asarray(jax.vmap(
        lambda k: two_level_draw(state.c, state.v, k))(keys))


def _draw_bsls_walk(logits, n):
    s = BSLSSampler(logits, seed=104)
    return np.asarray([s.sample() for _ in range(n)])


def _draw_bsls_fast(logits, n):
    s = BSLSSampler(logits, seed=105)
    return np.asarray([s.sample_fast() for _ in range(n)])


SAMPLERS = {
    "gumbel": _draw_gumbel,
    "two_level": _draw_two_level,
    "two_level_kernel": _draw_two_level_kernel,
    "bsls_walk": _draw_bsls_walk,
    "bsls_fast": _draw_bsls_fast,
}


@pytest.mark.parametrize("name", sorted(SAMPLERS))
def test_sampler_matches_analytic_em_law(em_problem, name):
    """Empirical selection frequencies agree with the analytic softmax."""
    _, logits, probs = em_problem
    draws = SAMPLERS[name](logits, N_DRAWS)
    assert draws.min() >= 0 and draws.max() < D, name
    ratio, bound = _chi2_ratio(draws, probs)
    assert ratio < bound, name
    # total-variation backstop: catches a sampler that passes chi-square on
    # the high-mass coordinates but starves the tail
    freq = np.bincount(draws, minlength=D) / len(draws)
    assert 0.5 * np.abs(freq - probs).sum() < 0.02, name


@pytest.mark.parametrize("name", sorted(SAMPLERS))
def test_sampler_concentrates_with_budget(em_problem, name):
    """More per-step budget ⇒ the top-scored coordinate wins more often —
    the qualitative privacy/utility dial every sampler must share."""
    scores, _, _ = em_problem
    top = int(np.argmax(scores))
    hits = {}
    for eps_step in (0.2, 5.0):
        logits = np.asarray(em_logits(jnp.asarray(scores, jnp.float32),
                                      eps_step, SENS))
        draws = SAMPLERS[name](logits, 4_000)
        hits[eps_step] = float((draws == top).mean())
    probs_tight = np.asarray(exponential_mechanism_probs(
        jnp.asarray(scores, jnp.float32), 5.0, SENS))
    assert hits[5.0] > hits[0.2] + 0.1, name
    assert hits[5.0] == pytest.approx(float(probs_tight[top]), abs=0.05), name


# ---------------------------------------------------------------------------
# per-loss sensitivity flow (DESIGN.md §10): every engine scores coordinate j
# with scale·|α_j| where scale = ε'·N/(2·L_loss) from
# ``accountant.em_log_weight_scale``.  That realizes the analytic mechanism
# P(j) ∝ exp(ε'·u/(2Δu)) with u = λ|α_j| and per-loss sensitivity
# Δu = λ·L_loss/N — pinned here empirically for each registered objective's
# Lipschitz constant, plus exact drift pins on the formula itself.
# ---------------------------------------------------------------------------

import dataclasses
import math

from repro.core.dp.accountant import em_log_weight_scale, per_step_epsilon
from repro.core.losses import OBJECTIVES
from repro.core.solvers.config import FWConfig

EPS_RUN, DELTA_RUN, T_RUN, N_ROWS, LAM = 1.0, 1e-6, 50, 400, 8.0


@pytest.fixture(scope="module")
def alpha_scores():
    """A fixed |α| surrogate; per-loss scales change its EM concentration."""
    return np.random.default_rng(9).uniform(0.0, 1.1, D)


@pytest.mark.parametrize("loss", sorted(OBJECTIVES))
def test_em_draws_match_per_loss_sensitivity_law(alpha_scores, loss):
    """Empirical two-level draws under scale·|α| agree (chi-square + TVD)
    with the analytic EM at utility λ|α| and sensitivity λ·L_loss/N."""
    lip = OBJECTIVES[loss].lipschitz
    scale = em_log_weight_scale(epsilon=EPS_RUN, delta=DELTA_RUN,
                                steps=T_RUN, n_rows=N_ROWS, lipschitz=lip)
    eps_step = per_step_epsilon(EPS_RUN, DELTA_RUN, T_RUN)
    probs = np.asarray(exponential_mechanism_probs(
        jnp.asarray(LAM * alpha_scores, jnp.float32), eps_step,
        LAM * lip / N_ROWS))
    state = tl_init(jnp.asarray(scale * alpha_scores, jnp.float32))
    keys = jax.random.split(jax.random.PRNGKey(106), N_DRAWS)
    draws = np.asarray(jax.vmap(lambda k: tl_sample(state, k))(keys))
    ratio, bound = _chi2_ratio(draws, probs)
    assert ratio < bound, loss
    freq = np.bincount(draws, minlength=D) / len(draws)
    assert 0.5 * np.abs(freq - probs).sum() < 0.02, loss


def test_logistic_em_scale_drift_pin():
    """Bit-identical to the formula the seed shipped: ε'·N/(2L), L = 1."""
    got = em_log_weight_scale(epsilon=1.3, delta=1e-5, steps=77,
                              n_rows=1234, lipschitz=1.0)
    expect = (1.3 / math.sqrt(8.0 * 77 * math.log(1.0 / 1e-5))) \
        * 1234 / (2.0 * 1.0)
    assert got == expect


def test_huber_em_scale_doubles_logistic():
    """L_huber = 0.5 halves the sensitivity, so the scale exactly doubles —
    the per-loss path is live, not a constant."""
    kw = dict(epsilon=0.9, delta=1e-6, steps=40, n_rows=500)
    s_log = em_log_weight_scale(lipschitz=OBJECTIVES["logistic"].lipschitz,
                                **kw)
    s_hub = em_log_weight_scale(lipschitz=OBJECTIVES["huber"].lipschitz,
                                **kw)
    assert s_hub == 2.0 * s_log


def test_engine_scales_agree_per_loss():
    """jax_sparse and jax_shard derive their EM scales from the same
    accountant formula — per loss, bit-identically."""
    from repro.core.solvers.jax_shard import shard_em_scale
    from repro.core.solvers.jax_sparse import em_scale_for
    for loss in sorted(OBJECTIVES):
        cfg = FWConfig(loss=loss, epsilon=1.0, delta=1e-6, steps=50,
                       queue="two_level")
        expect = em_log_weight_scale(
            epsilon=1.0, delta=1e-6, steps=50, n_rows=N_ROWS,
            lipschitz=OBJECTIVES[loss].lipschitz)
        assert em_scale_for(cfg, N_ROWS) == expect, loss
        shard_cfg = dataclasses.replace(cfg, queue="gumbel")
        assert shard_em_scale(shard_cfg, N_ROWS) == expect, loss
