"""Seeded traffic: training data and serving request mixes.

Every generator is a function of the cell's parameters and the seed alone.
Serving sizes and arrival gaps are stratified quantiles of the stated
distributions in one fixed order, and the seed fills the prompts: every
seed offers the same work at the same times, so runs on different seeds
measure the system, not the draw (with some twenty requests in a window,
the order alone moved tokens per second by a quarter).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


def seed_words(seed: int, n: int = 4) -> List[int]:
    """``n`` 31-bit integers derived from any non-negative seed."""
    ss = np.random.SeedSequence(int(seed))
    return [int(w) & 0x7FFFFFFF for w in ss.generate_state(n)]


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


# ---------------------------------------------------------------------- #
# training: class-conditional images, made on the device
# ---------------------------------------------------------------------- #
def make_images(key, n: int, num_classes: int, image_size: int,
                channels: int, noise: float):
    """``(images (n, s * s * c) f32, labels (n,) int32)``: a Gaussian
    prototype per class plus per-image Gaussian noise, in one jitted call;
    each row is one image, flattened in NHWC order."""
    import jax
    import jax.numpy as jnp

    d = image_size * image_size * channels

    @jax.jit
    def build(key):
        kp, kl, kn = jax.random.split(key, 3)
        protos = jax.random.normal(kp, (num_classes, d), jnp.float32)
        labels = jax.random.randint(kl, (n,), 0, num_classes, jnp.int32)
        x = protos[labels] + noise * jax.random.normal(kn, (n, d),
                                                      jnp.float32)
        return x, labels

    return build(key)


def distinct_rows(seed: int, n: int, count: int) -> np.ndarray:
    """``count`` distinct row indices of ``[0, n)``."""
    if count > n:
        raise ValueError(f"{count} distinct rows asked of {n}")
    return rng(seed, 1).permutation(n)[:count]


# ---------------------------------------------------------------------- #
# serving: open-loop request mixes
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class ServedRequest:
    arrival: float          # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


# One fixed shuffle of the stratified lengths and gaps, the same for every
# seed.
ORDER_SEED = 20260101


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def _exponential_gaps(n: int, rate: float) -> np.ndarray:
    return np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)]) / rate


def open_loop(traffic: dict, seed: int, seconds: float, vocab: int
              ) -> List[ServedRequest]:
    """Poisson arrivals at ``traffic["rate"]`` requests/s for ``seconds``,
    with lognormal prompt and output lengths, clipped.

    ``n = round(rate * seconds)`` requests; the gaps are the ``n``
    stratified quantiles of the exponential distribution, so their sum is
    ``seconds`` up to rounding.  Lengths and gaps are shuffled by the fixed
    ``ORDER_SEED``; the prompts' tokens come from ``seed``.
    """
    n = max(1, int(round(traffic["rate"] * seconds)))
    p, o = traffic["prompt"], traffic["output"]
    plens = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                 p["max"])
    olens = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                 o["max"])
    gaps = _exponential_gaps(n, traffic["rate"])
    order = np.random.default_rng(ORDER_SEED)
    plens, olens, gaps = (order.permutation(plens), order.permutation(olens),
                          order.permutation(gaps))
    arrivals = np.cumsum(gaps) - gaps[0]
    tok = rng(seed, 3)
    return [ServedRequest(float(a), tok.integers(0, vocab, int(pl),
                                                 dtype=np.int32), int(ol))
            for a, pl, ol in zip(arrivals, plens, olens)]
