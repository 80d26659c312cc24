"""Quantizer-backend dispatch: registry/fallback/env, ref-vs-pallas
equivalence, fused-vs-ref clipping, and executor bit-equivalence on pallas."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import DPConfig, ModelConfig, OptimConfig, QuantConfig, RunConfig
from repro.quant import backend as qb
from repro.quant.formats import STOCHASTIC_FORMATS
from repro.quant.fake_quant import qeinsum

from hypothesis import given, settings, strategies as st


# --------------------------------------------------------------------------- #
# registry / resolution
# --------------------------------------------------------------------------- #
def test_capability_table_shape():
    table = qb.capability_table()
    assert set(table) == set(qb.OPS)
    # ref implements every format for quantize/matmul; pallas is LUQ-only
    for op in ("quantize", "matmul"):
        assert "luq_fp4" in table[op]["ref"]
        assert table[op]["pallas"] == ("luq_fp4",)
    # clip is format-agnostic on both backends
    assert table["clip_sum"]["ref"] == (qb.ANY_FORMAT,)
    assert table["clip_sum"]["pallas"] == (qb.ANY_FORMAT,)


def test_explicit_fallback_to_ref():
    _, be = qb.get_quantizer("luq_fp4", "pallas")
    assert be == "pallas"
    _, be = qb.get_quantizer("int4", "pallas")   # pallas lacks int4
    assert be == "ref"
    _, be = qb.get_matmul("fp8_e4m3", "pallas")
    assert be == "ref"
    _, be = qb.get_clip_sum("fused")             # DPConfig alias
    assert be == "pallas"


def test_resolve_backend_env_override(monkeypatch):
    monkeypatch.delenv(qb.ENV_VAR, raising=False)
    assert qb.resolve_backend(None) == "ref"
    assert qb.resolve_backend("pallas") == "pallas"
    monkeypatch.setenv(qb.ENV_VAR, "pallas")
    assert qb.resolve_backend(None) == "pallas"
    assert qb.resolve_backend("ref") == "pallas"   # env wins over config


def test_unknown_backend_raises(monkeypatch):
    monkeypatch.delenv(qb.ENV_VAR, raising=False)
    with pytest.raises(ValueError):
        qb.resolve_backend("cuda")
    monkeypatch.setenv(qb.ENV_VAR, "bogus")
    with pytest.raises(ValueError):
        qb.resolve_backend(None)


# --------------------------------------------------------------------------- #
# backend equivalence: quantizer properties
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("fmt", STOCHASTIC_FORMATS)
def test_stochastic_quantizer_unbiased(fmt, backend):
    """E[q(x)] ~ x for every stochastic format on both backends."""
    q, _ = qb.get_quantizer(fmt, backend)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 24), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(1), 96)
    draws = jax.vmap(lambda k: q(x, k))(keys)
    mean = np.asarray(draws, np.float32).mean(axis=0)
    resid = np.linalg.norm(mean - np.asarray(x))
    single = np.linalg.norm(np.asarray(draws[0], np.float32) - np.asarray(x))
    # the many-draw mean must contract toward x (unbiasedness); a biased
    # quantizer leaves a floor the averaging cannot remove
    assert resid < single / 3, (fmt, backend, resid, single)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("shape", [(7, 13, 5), (1, 1), (257,), (3, 130)])
def test_luq_odd_shapes_stay_on_grid(backend, shape):
    q, _ = qb.get_quantizer("luq_fp4", backend)
    x = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)
    out = q(x, jax.random.PRNGKey(3))
    assert out.shape == x.shape
    alpha = float(jnp.max(jnp.abs(x)))
    grid = {0.0} | {alpha * 2.0 ** (-k) for k in range(7)}
    for v in np.unique(np.abs(np.asarray(out, np.float32))):
        assert any(abs(v - g) <= 1e-5 * max(alpha, 1.0) for g in grid), \
            (backend, shape, v)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("fmt", STOCHASTIC_FORMATS)
def test_all_zero_tensor_quantizes_to_zero(fmt, backend):
    q, _ = qb.get_quantizer(fmt, backend)
    x = jnp.zeros((9, 33), jnp.float32)
    out = q(x, jax.random.PRNGKey(4))
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@settings(deadline=None, max_examples=8)
@given(st.integers(min_value=1, max_value=64),
       st.integers(min_value=1, max_value=64))
def test_pallas_matmul_unbiased_property(m, n):
    """Property: the fused pallas matmul's many-draw mean approaches the
    exact product for arbitrary (non-tile-multiple) shapes."""
    k = 32
    a = jax.random.normal(jax.random.PRNGKey(m), (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(n + 1000), (k, n), jnp.float32)
    mm, be = qb.get_matmul("luq_fp4", "pallas")
    assert be == "pallas"
    keys = jax.random.split(jax.random.PRNGKey(7), 24)
    draws = np.asarray(jax.vmap(lambda kk: mm(a, b, kk))(keys))
    exact = np.asarray(a @ b)
    rel = np.linalg.norm(draws.mean(0) - exact) / np.linalg.norm(exact)
    single = np.linalg.norm(draws[0] - exact) / np.linalg.norm(exact)
    assert rel < single / 2 + 1e-6, (m, n, rel, single)


def test_qeinsum_backend_value_close_to_ref_statistically():
    """qeinsum(pallas) and qeinsum(ref) draw different random bits but both
    are unbiased — their per-draw means must converge to the same GEMM."""
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 48))
    w = jax.random.normal(jax.random.PRNGKey(1), (48, 16))
    exact = np.asarray(x @ w)

    def mean_out(backend, n=24):
        outs = [np.asarray(qeinsum("ab,bc->ac", x, w, seed=jnp.uint32(i),
                                   flag=jnp.float32(1), backend=backend))
                for i in range(n)]
        return np.mean(outs, 0)

    rel_ref = np.linalg.norm(mean_out("ref") - exact) / np.linalg.norm(exact)
    rel_pal = np.linalg.norm(mean_out("pallas") - exact) / np.linalg.norm(exact)
    assert rel_ref < 0.15 and rel_pal < 0.15, (rel_ref, rel_pal)


# --------------------------------------------------------------------------- #
# fused clip vs ref clip
# --------------------------------------------------------------------------- #
def _quad_loss(params, ex, rng):
    del rng
    return (0.5 * jnp.sum((params["w"] * ex["x"] - ex["y"]) ** 2)
            + jnp.sum(params["b"] * ex["x"][:2]))


def test_fused_clip_matches_ref_grads_and_metrics():
    from repro.dp.clip import per_example_clipped_grad_sum
    key = jax.random.PRNGKey(0)
    batch = {"x": jax.random.normal(key, (8, 5)) * 2.0,
             "y": jax.random.normal(jax.random.fold_in(key, 1), (8, 5))}
    params = {"w": jnp.arange(1.0, 6.0), "b": jnp.ones((2,)) * 0.3}
    outs = {}
    for cb in ("ref", "fused"):
        outs[cb] = per_example_clipped_grad_sum(
            _quad_loss, params, batch, clip_norm=0.9, microbatch_size=4,
            rng=jax.random.PRNGKey(0), clip_backend=cb)
    g_ref, m_ref = outs["ref"]
    g_fused, m_fused = outs["fused"]
    for leaf_r, leaf_f in zip(jax.tree_util.tree_leaves(g_ref),
                              jax.tree_util.tree_leaves(g_fused)):
        np.testing.assert_allclose(np.asarray(leaf_r), np.asarray(leaf_f),
                                   rtol=1e-5, atol=1e-6)
    for k in ("loss", "grad_norm_mean", "grad_norm_max", "clip_fraction"):
        np.testing.assert_allclose(float(m_ref[k]), float(m_fused[k]),
                                   rtol=1e-5, err_msg=k)


def test_fused_clip_rejects_partial_accum():
    from repro.dp.clip import per_example_clipped_grad_sum
    batch = {"x": jnp.ones((4, 3)), "y": jnp.ones((4, 3))}
    params = {"w": jnp.ones((3,)), "b": jnp.ones((2,))}
    with pytest.raises(ValueError, match="partial"):
        per_example_clipped_grad_sum(
            _quad_loss, params, batch, clip_norm=1.0, microbatch_size=4,
            rng=jax.random.PRNGKey(0), clip_backend="fused",
            partial_accum_shards=2)


def test_clip_backend_validated():
    from repro.dp.clip import per_example_clipped_grad_sum
    with pytest.raises(ValueError, match="clip_backend"):
        per_example_clipped_grad_sum(
            _quad_loss, {"w": jnp.ones(3), "b": jnp.ones(2)},
            {"x": jnp.ones((2, 3)), "y": jnp.ones((2, 3))},
            clip_norm=1.0, microbatch_size=2, rng=jax.random.PRNGKey(0),
            clip_backend="pallas")   # DPConfig spelling is "fused"


# --------------------------------------------------------------------------- #
# full-train-step parity + executor bit-equivalence on pallas
# --------------------------------------------------------------------------- #
def _tiny_run(**kw):
    model = ModelConfig(name="resnet-tiny", family="resnet",
                        resnet_blocks=(1,), num_classes=4, image_size=8,
                        compute_dtype="float32")
    defaults = dict(
        model=model,
        quant=QuantConfig(fmt="luq_fp4"),
        dp=DPConfig(enabled=True, clip_norm=1.0, noise_multiplier=0.8,
                    microbatch_size=4, analysis_interval=100),
        optim=OptimConfig(name="sgd", lr=0.2),
        global_batch=4, steps_per_epoch=2, steps=8, seed=0)
    defaults.update(kw)
    return RunConfig(**defaults)


def _train_params(run, epochs=1):
    from repro.data.synthetic import ImageClassDataset
    from repro.train_loop import Trainer
    ds = ImageClassDataset(n=64, num_classes=4, image_size=8, noise=0.3,
                           seed=0)
    tr = Trainer(run, ds, mode="static")
    for e in range(epochs):
        tr.train_epoch(e)
    return tr.params, tr.history


def test_train_step_parity_fused_vs_ref_clip():
    """Identical seeds + quant draws; only the clip implementation differs —
    final params must agree to fp32 tolerance."""
    run_ref = _tiny_run()
    run_fused = _tiny_run(dp=dataclasses.replace(run_ref.dp,
                                                 clip_backend="fused"))
    p_ref, h_ref = _train_params(run_ref)
    p_fused, h_fused = _train_params(run_fused)
    for lr, lf in zip(jax.tree_util.tree_leaves(p_ref),
                      jax.tree_util.tree_leaves(p_fused)):
        np.testing.assert_allclose(np.asarray(lr), np.asarray(lf),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(h_ref[0].loss, h_fused[0].loss, rtol=1e-4)


def test_scan_loop_bit_equivalent_on_pallas_backend():
    """The scan and loop executors must stay bit-identical when every
    quantizer runs through the pallas kernels (interpret mode on CPU)."""
    runs = {ex: _tiny_run(quant=QuantConfig(fmt="luq_fp4",
                                            backend="pallas"),
                          epoch_executor=ex)
            for ex in ("scan", "loop")}
    p_scan, _ = _train_params(runs["scan"])
    p_loop, _ = _train_params(runs["loop"])
    for ls, ll in zip(jax.tree_util.tree_leaves(p_scan),
                      jax.tree_util.tree_leaves(p_loop)):
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(ll))


def test_trainer_rejects_bad_backend_knobs(monkeypatch):
    from repro.data.synthetic import ImageClassDataset
    from repro.train_loop import Trainer
    # the env override intentionally wins over config, so clear it to test
    # the config-validation path
    monkeypatch.delenv(qb.ENV_VAR, raising=False)
    ds = ImageClassDataset(n=16, num_classes=4, image_size=8, seed=0)
    with pytest.raises(ValueError):
        Trainer(_tiny_run(quant=QuantConfig(fmt="luq_fp4", backend="gpu")),
                ds, mode="static")
    bad_dp = dataclasses.replace(_tiny_run().dp, clip_backend="pallas")
    with pytest.raises(ValueError):
        Trainer(_tiny_run(dp=bad_dp), ds, mode="static")
