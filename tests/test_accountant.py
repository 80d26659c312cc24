"""RDP accountant vs a numerical-integration oracle + properties."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.dp.accountant import (DEFAULT_ORDERS, RDPAccountant,
                                 compute_rdp_sgm, rdp_to_eps)


def rdp_oracle(q, sigma, alpha, n=800_001, span=40.0):
    x = np.linspace(-span, span, n)
    log_mu0 = -x ** 2 / (2 * sigma ** 2) - math.log(sigma * math.sqrt(2 * math.pi))
    log_mu1 = -(x - 1) ** 2 / (2 * sigma ** 2) - math.log(
        sigma * math.sqrt(2 * math.pi))
    log_mix = np.logaddexp(math.log1p(-q) + log_mu0, math.log(q) + log_mu1)
    integrand = np.exp(log_mu0 + alpha * (log_mix - log_mu0))
    return math.log(np.trapezoid(integrand, x)) / (alpha - 1)


@pytest.mark.parametrize("q,sigma,alpha", [
    (0.01, 1.0, 2.0), (0.01, 1.0, 8.0), (0.01, 1.0, 2.5),
    (0.05, 0.8, 3.5), (0.1, 1.5, 1.25), (0.02, 0.5, 4.0),
    (0.001, 2.0, 32.0), (0.5, 1.0, 6.0), (0.2, 0.7, 10.5),
])
def test_rdp_matches_numerical_oracle(q, sigma, alpha):
    got = compute_rdp_sgm(q, sigma, alpha)
    want = rdp_oracle(q, sigma, alpha)
    assert abs(got - want) / max(abs(want), 1e-12) < 1e-4


def test_q1_reduces_to_gaussian_mechanism():
    for sigma in (0.5, 1.0, 4.0):
        for alpha in (2.0, 8.0, 64.0):
            assert abs(compute_rdp_sgm(1.0, sigma, alpha)
                       - alpha / (2 * sigma ** 2)) < 1e-12


@settings(max_examples=30, deadline=None)
@given(st.floats(min_value=0.001, max_value=0.3),
       st.floats(min_value=0.5, max_value=4.0))
def test_eps_monotone_in_steps(q, sigma):
    a = RDPAccountant()
    a.step(noise_multiplier=sigma, sample_rate=q, steps=10)
    e1, _ = a.get_epsilon(1e-5)
    a.step(noise_multiplier=sigma, sample_rate=q, steps=90)
    e2, _ = a.get_epsilon(1e-5)
    assert e2 >= e1 >= 0


def test_eps_decreasing_in_sigma():
    eps = []
    for sigma in (0.6, 1.0, 2.0, 4.0):
        a = RDPAccountant()
        a.step(noise_multiplier=sigma, sample_rate=0.01, steps=1000)
        eps.append(a.get_epsilon(1e-5)[0])
    assert all(e1 > e2 for e1, e2 in zip(eps, eps[1:])), eps


def test_mnist_reference_point():
    """sigma=1.1, q=256/60000, 30 epochs — classic DP-SGD tutorial setting;
    eps should land near ~1.8 (TF-privacy reports ~1.79 at delta=1e-5)."""
    a = RDPAccountant()
    a.step(noise_multiplier=1.1, sample_rate=256 / 60_000,
           steps=int(60_000 / 256 * 30))
    eps, _ = a.get_epsilon(1e-5)
    assert 1.5 < eps < 2.2, eps


def test_analysis_composition_and_fraction():
    a = RDPAccountant()
    a.step(noise_multiplier=1.0, sample_rate=0.02, steps=2000, label="train")
    e_train, _ = a.get_epsilon(1e-5)
    a.step(noise_multiplier=0.5, sample_rate=0.02, steps=10, label="analysis")
    e_both, _ = a.get_epsilon(1e-5)
    assert e_both > e_train
    frac = a.analysis_fraction(1e-5)
    assert 0.0 < frac < 1.0


def test_state_roundtrip():
    a = RDPAccountant()
    a.step(noise_multiplier=1.2, sample_rate=0.01, steps=55)
    a.step(noise_multiplier=0.5, sample_rate=0.03, steps=2, label="analysis")
    b = RDPAccountant.from_state_dict(a.state_dict())
    assert a.get_epsilon(1e-5) == b.get_epsilon(1e-5)


def test_invalid_inputs():
    a = RDPAccountant()
    with pytest.raises(ValueError):
        a.step(noise_multiplier=1.0, sample_rate=1.5)
    with pytest.raises(ValueError):
        a.step(noise_multiplier=-1.0, sample_rate=0.5)
