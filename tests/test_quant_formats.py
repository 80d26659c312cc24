"""Quantizer properties — the premises of the paper's Proposition 1:
unbiasedness E[q(x)|x] = x and scale-invariance q(lambda x) = lambda q(x)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.quant.formats import (int4_uniform, luq_fp4, fp8_e4m3, fp8_e5m2,
                                 make_quantizer, LUQ_EXP_LEVELS)


@pytest.mark.parametrize("quant,step_frac", [(luq_fp4, 0.5),
                                             (int4_uniform, 1.0 / 7.0)])
def test_unbiasedness(quant, step_frac):
    """E[q(x) | x] = x, tested per coordinate with a distribution-free
    Hoeffding bound: each draw deviates from x by at most one grid step, so
    |mean - x| <= step * sqrt(ln(2 d / delta) / (2 n)) w.p. 1 - delta.
    (A per-coordinate z-test is fragile for rare-event coords whose
    rounding probability is ~0 or ~1.)"""
    key = jax.random.PRNGKey(0)
    d, n_draws = 512, 2000
    x = jax.random.normal(key, (d,), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(1), n_draws)
    qs = jax.vmap(lambda k: quant(x, k))(keys)
    mean = np.asarray(qs.mean(axis=0))
    xs = np.asarray(x)
    step = float(np.abs(xs).max()) * step_frac      # largest grid gap
    tol = step * np.sqrt(np.log(2 * d / 1e-3) / (2 * n_draws))
    dev = np.abs(mean - xs)
    assert dev.max() < tol, (dev.max(), tol)
    # ... and the mean deviation must be an order tighter than the bound
    assert dev.mean() < tol / 4


@pytest.mark.parametrize("quant", [luq_fp4, int4_uniform])
def test_scale_invariance(quant):
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (256,), jnp.float32)
    for lam in (0.5, 3.0, 1e-3, 1e3):
        q1 = quant(x * lam, jax.random.PRNGKey(7))
        q2 = quant(x, jax.random.PRNGKey(7)) * lam
        np.testing.assert_allclose(np.asarray(q1), np.asarray(q2),
                                   rtol=1e-5, atol=1e-30)


def test_luq_grid_membership():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2048,), jnp.float32)
    q = luq_fp4(x, jax.random.PRNGKey(4))
    alpha = float(jnp.max(jnp.abs(x)))
    grid = {0.0} | {alpha * 2.0 ** (-k) for k in range(LUQ_EXP_LEVELS)}
    for v in np.unique(np.abs(np.asarray(q))):
        assert any(abs(v - g) <= 1e-5 * alpha for g in grid), v


def test_luq_variance_scales_with_linf():
    """Prop. 1: Var(q(x)) = Theta(||x||_inf^2)."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (256,), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(6), 500)

    def var_of(v):
        qs = jax.vmap(lambda k: luq_fp4(v, k))(keys)
        return float(jnp.var(qs - v[None]).mean())

    v1 = var_of(x)
    v100 = var_of(x * 100.0)
    ratio = v100 / max(v1, 1e-20)
    assert 0.5 * 100 ** 2 < ratio < 2.0 * 100 ** 2, ratio


def test_int4_levels():
    x = jnp.linspace(-1, 1, 1001)
    q = int4_uniform(x, jax.random.PRNGKey(0))
    levels = np.unique(np.asarray(q))
    assert len(levels) <= 15


@pytest.mark.parametrize("fmt", ["fp8_e4m3", "fp8_e5m2", "bf16", "none"])
def test_cast_formats_idempotent(fmt):
    q = make_quantizer(fmt)
    x = jax.random.normal(jax.random.PRNGKey(1), (64,), jnp.float32)
    q1 = q(x, None)
    q2 = q(q1, None)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=1, max_value=300),
       st.floats(min_value=1e-3, max_value=1e3))
def test_luq_bounded_by_max(n, scale):
    x = jax.random.normal(jax.random.PRNGKey(n), (n,), jnp.float32) * scale
    q = luq_fp4(x, jax.random.PRNGKey(n + 1))
    assert float(jnp.max(jnp.abs(q))) <= float(jnp.max(jnp.abs(x))) * (1 + 1e-5)


def test_all_zero_input():
    z = jnp.zeros((32,), jnp.float32)
    for fmt in ("luq_fp4", "int4"):
        q = make_quantizer(fmt)(z, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(q), 0.0)
