"""DPQuant scheduler: Algorithm 1 + 2 semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import DPConfig
from repro.core.loss_impact import compute_loss_impact
from repro.core.policy import (QuantPolicy, full_policy, random_policy,
                               singleton_policies, union_policy)
from repro.core.scheduler import DPQuantScheduler
from repro.core.selection import (sample_without_replacement, select_targets,
                                  selection_probs)
from repro.dp.accountant import RDPAccountant


def test_policy_flags():
    p = QuantPolicy((0, 2), 4)
    np.testing.assert_array_equal(np.asarray(p.flags()), [1, 0, 1, 0])
    assert len(full_policy(5)) == 5
    u = union_policy([QuantPolicy((0,), 3), QuantPolicy((2,), 3)], 3)
    assert u.layers == (0, 2)


def test_selection_probs_prefer_low_impact():
    scores = np.array([0.0, 1.0, 0.5])
    p = selection_probs(scores, beta=5.0)
    assert p[0] > p[2] > p[1]
    np.testing.assert_allclose(p.sum(), 1.0)


def test_beta_limits():
    scores = np.array([0.0, 1.0, 0.2, 0.8])
    p0 = selection_probs(scores, beta=0.0)
    np.testing.assert_allclose(p0, 0.25)             # PLS limit
    ph = selection_probs(scores, beta=1e4)
    assert ph[0] > 0.99                               # deterministic limit


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=2, max_value=12), st.integers(min_value=1, max_value=12))
def test_sampling_without_replacement(n, m):
    rng = np.random.RandomState(0)
    probs = rng.rand(n)
    probs /= probs.sum()
    idx = sample_without_replacement(probs, m, rng)
    assert len(idx) == min(m, n)
    assert len(set(idx)) == len(idx)                  # no repeats


def test_select_targets_respects_budget():
    rng = np.random.RandomState(0)
    pols = singleton_policies(10)
    scores = np.zeros(10)
    pol = select_targets(scores, pols, beta=1.0, m=7, rng=rng, n_layers=10)
    assert len(pol) == 7


def test_scheduler_modes():
    dp = DPConfig(quant_fraction=0.5)
    for mode in ("static", "pls", "dpquant"):
        s = DPQuantScheduler(n_layers=8, dp=dp, mode=mode, seed=1)
        p1 = s.select(0)
        p2 = s.select(1)
        assert len(p1) == 4 and len(p2) == 4
        if mode == "static":
            assert p1.layers == p2.layers             # fixed subset
    # pls rotates with overwhelming probability across several epochs
    s = DPQuantScheduler(n_layers=8, dp=dp, mode="pls", seed=2)
    seen = {s.select(e).layers for e in range(6)}
    assert len(seen) > 1


def test_loss_impact_identifies_sensitive_layer():
    """Toy probe: quantizing layer 1 hurts the loss, layer 0 doesn't.
    The estimator must rank layer 1 as higher impact."""
    def probe_step(params, opt, batch, seed, flags):
        loss = 1.0 + 5.0 * flags[1] + 0.01 * flags[0]
        return params, opt, {"loss": jnp.float32(loss)}

    pols = singleton_policies(2)
    scores = compute_loss_impact(
        probe_step=probe_step, params={}, opt_state=(), policies=pols,
        batches=[{}, {}], reps=2, seed=0, measure_clip=10.0,
        measure_noise=0.01, sample_rate=0.01, accountant=None,
        ema_scores=None, ema_alpha=0.3)
    assert scores[1] > scores[0]


def test_loss_impact_charges_accountant():
    def probe_step(params, opt, batch, seed, flags):
        return params, opt, {"loss": jnp.float32(1.0)}

    acc = RDPAccountant()
    compute_loss_impact(
        probe_step=probe_step, params={}, opt_state=(), policies=singleton_policies(3),
        batches=[{}], reps=1, seed=0, measure_clip=0.01, measure_noise=0.5,
        sample_rate=0.05, accountant=acc, ema_scores=None, ema_alpha=0.3)
    assert len(acc.history) == 1
    assert acc.history[0].label == "analysis"
    assert acc.get_epsilon(1e-5)[0] > 0


def test_loss_impact_privatized():
    """With tiny clip + large noise the output is dominated by noise ->
    different seeds give different scores (the release is randomized)."""
    def probe_step(params, opt, batch, seed, flags):
        return params, opt, {"loss": jnp.float32(float(flags.sum()))}

    pols = singleton_policies(4)
    kw = dict(probe_step=probe_step, params={}, opt_state=(), policies=pols,
              batches=[{}], reps=1, measure_clip=0.01, measure_noise=0.5,
              sample_rate=0.01, accountant=None, ema_scores=None,
              ema_alpha=0.3)
    s1 = compute_loss_impact(seed=1, **kw)
    s2 = compute_loss_impact(seed=2, **kw)
    assert not np.allclose(s1, s2)
    # and clipped: |pre-noise release| <= C
    assert np.linalg.norm(s1) < 0.01 + 5 * 0.5 * 0.01 * np.sqrt(4)


def test_scheduler_state_roundtrip():
    dp = DPConfig(quant_fraction=0.75)
    s = DPQuantScheduler(n_layers=8, dp=dp, mode="dpquant", seed=3)
    s.scores = np.arange(8.0)
    s.select(0)
    state = s.state_dict()
    s2 = DPQuantScheduler(n_layers=8, dp=dp, mode="dpquant", seed=99)
    s2.load_state_dict(state)
    np.testing.assert_array_equal(s2.scores, s.scores)
    assert s2.current.layers == s.current.layers
    # same RNG continuation
    assert s.select(1).layers == s2.select(1).layers


def test_scheduler_roundtrip_resume_mid_training():
    """Checkpoint/restore mid-training: a restored scheduler must continue
    exactly — same EMA continuation (n_analyses survives the round-trip),
    same selections, same analysis cadence."""
    def probe_step(params, opt, batch, seed, flags):
        loss = 1.0 + float(np.sum(np.asarray(flags) * np.arange(1, 7)))
        return params, opt, {"loss": jnp.float32(loss)}

    def analyze(s, epoch, seed):
        return s.maybe_analyze(
            probe_step=probe_step, params={}, opt_state=(), batches=[{}],
            sample_rate=0.01, accountant=None, epoch=epoch, seed=seed)

    dp = DPConfig(quant_fraction=0.5, analysis_interval=2, analysis_reps=1)
    s = DPQuantScheduler(n_layers=6, dp=dp, mode="dpquant", seed=7)
    # epochs 0..2: two analyses (0, 2) and three selections
    for e in range(3):
        analyze(s, e, seed=100 + e)
        s.select(e)
    assert s.n_analyses == 2

    s2 = DPQuantScheduler(n_layers=6, dp=dp, mode="dpquant", seed=7)
    s2.load_state_dict(s.state_dict())
    assert s2.n_analyses == s.n_analyses
    np.testing.assert_array_equal(s2.scores, s.scores)
    # continue both for three more epochs (epoch 4 triggers an EMA update,
    # which only behaves identically if n_analyses was restored)
    for e in range(3, 6):
        ran1 = analyze(s, e, seed=100 + e)
        ran2 = analyze(s2, e, seed=100 + e)
        assert ran1 == ran2 == (e % 2 == 0)
        assert s.select(e).layers == s2.select(e).layers
    np.testing.assert_allclose(s2.scores, s.scores)
    assert s.n_analyses == s2.n_analyses == 3
