"""The plain privacy reference against hand counts: the quadrature of one
SGM step at an integer order against the binomial sum written out, and
the steps a window spends."""
import math

import pytest

from bench.reference import accountant as ra


def _binomial_rdp(q, sigma, alpha):
    a = sum(math.comb(alpha, k) * (1 - q) ** (alpha - k) * q ** k
            * math.exp(k * (k - 1) / (2 * sigma ** 2))
            for k in range(alpha + 1))
    return math.log(a) / (alpha - 1)


@pytest.mark.parametrize("q,sigma,alpha", [(256 / 39209, 1.0, 2),
                                           (256 / 39209, 1.0, 17),
                                           (32 / 39209, 0.5, 8),
                                           (0.25, 2.0, 5)])
def test_sgm_rdp_integer_orders(q, sigma, alpha):
    want = _binomial_rdp(q, sigma, alpha)
    assert ra.sgm_rdp(q, sigma, alpha) == pytest.approx(want, rel=1e-9)


def test_window_mechanisms_counts_analyses():
    t = {"dataset_size": 39209, "batch": 256,
         "dp": {"noise_multiplier": 1.0, "analysis_interval": 2,
                "analysis_batch_size": 32, "analysis_noise": 0.5}}
    # 153 steps an epoch: epochs 0, 1, 2 begun, analyses at 0 and 2
    (tq, ts, tn), (aq, asig, an) = ra.window_mechanisms(t, 2 * 153 + 9)
    assert (tq, ts, tn) == (256 / 39209, 1.0, 315)
    assert (aq, asig, an) == (32 / 39209, 0.5, 2)
    assert ra.window_mechanisms(t, 153)[1][2] == 1


def test_epsilon_grows_with_steps():
    mech = [(256 / 39209, 1.0, 300), (32 / 39209, 0.5, 1)]
    fewer = [(256 / 39209, 1.0, 291), (32 / 39209, 0.5, 1)]
    assert ra.epsilon(fewer, 1e-5) < ra.epsilon(mech, 1e-5)
