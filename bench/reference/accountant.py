"""Plain reference for the privacy a DP-SGD window spends.

Each step of DP-SGD with Poisson sampling at rate ``q`` and Gaussian noise
of ``sigma`` times the clipping norm is a Sampled Gaussian Mechanism. Its
Renyi divergence at order ``alpha`` (Mironov, Talwar and Zhang 2019,
arXiv:1908.10530) is ``log A / (alpha - 1)`` with

    A = E_{z ~ N(0, sigma^2)} [((1 - q) + q exp((2z - 1) / (2 sigma^2)))^alpha],

taken here by direct quadrature on a fine grid in log space (no series, no
closed form), for integer and fractional orders alike. Steps compose by
adding their divergences order by order; the total converts to
``(eps, delta)`` by ``eps = rdp + log(1 - 1/alpha) - (log delta +
log alpha) / (alpha - 1)`` (Balle et al. 2020, arXiv:1905.09982), the
least over the orders.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import numpy as np

# The orders the configuration's accountant evaluates (the repo's RDP
# accountant default): eps is a least over these, so the reference takes
# the same set.
ORDERS: Tuple[float, ...] = tuple(
    [1.25, 1.5, 1.75, 2.0, 2.25, 2.5, 3.0, 3.5, 4.0, 4.5]
    + [float(a) for a in range(5, 64)]
    + [80.0, 96.0, 128.0, 192.0, 256.0, 384.0, 512.0])

POINTS_PER_SIGMA = 400


def sgm_rdp(q: float, sigma: float, alpha: float) -> float:
    """Renyi divergence (nats) of one SGM step at order ``alpha``."""
    if q == 0.0:
        return 0.0
    if q == 1.0:
        return alpha / (2.0 * sigma ** 2)
    # the integrand peaks near z = alpha and is Gaussian-thin around it
    lo, hi = -40.0 * sigma, alpha + 40.0 * sigma
    n = int(math.ceil((hi - lo) / sigma * POINTS_PER_SIGMA)) + 1
    z = np.linspace(lo, hi, n)
    dz = z[1] - z[0]
    log_mu0 = -z * z / (2 * sigma ** 2) - 0.5 * math.log(2 * math.pi
                                                        * sigma ** 2)
    log_ratio = np.logaddexp(math.log1p(-q), math.log(q)
                             + (2 * z - 1) / (2 * sigma ** 2))
    terms = log_mu0 + alpha * log_ratio
    top = float(np.max(terms))
    log_a = top + math.log(float(np.sum(np.exp(terms - top))) * dz)
    return log_a / (alpha - 1.0)


def epsilon(mechanisms: Sequence[Tuple[float, float, int]], delta: float,
            orders: Sequence[float] = ORDERS) -> float:
    """``eps`` at ``delta`` of ``(q, sigma, steps)`` mechanisms composed."""
    best = math.inf
    for a in orders:
        rdp = sum(steps * sgm_rdp(q, sigma, a)
                  for q, sigma, steps in mechanisms if steps)
        eps = rdp + math.log1p(-1.0 / a) - (math.log(delta)
                                           + math.log(a)) / (a - 1.0)
        best = min(best, eps)
    return max(best, 0.0)


def window_mechanisms(t: Dict, train_steps: int
                      ) -> Sequence[Tuple[float, float, int]]:
    """The SGM steps a window of ``train_steps`` DP-SGD steps spends, from
    the cell's stated traffic: every step at ``batch / dataset_size`` and
    the noise multiplier, and one DPQuant analysis step at the start of
    every ``analysis_interval``-th epoch the window begins (the analysis
    samples ``analysis_batch_size`` rows and adds ``analysis_noise``)."""
    dp, n = t["dp"], t["dataset_size"]
    per_epoch = n // t["batch"]
    epochs_begun = -(-train_steps // per_epoch)
    analyses = len(range(0, epochs_begun, dp["analysis_interval"]))
    return [(t["batch"] / n, dp["noise_multiplier"], train_steps),
            (dp["analysis_batch_size"] / n, dp["analysis_noise"], analyses)]
