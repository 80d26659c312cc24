"""Sharded ghost engine: 8-fake-device parity (subprocess — the main test
process must keep the default 1-CPU-device view).

Acceptance contract (ISSUE 5 / docs/ARCHITECTURE.md): sharded ghost
(per-shard squared-norm taps + ONE psum of the clipped grad sums) on an
8-fake-device mesh matches single-device ghost to fp32 tolerance, under
BOTH epoch executors.
"""
import subprocess
import sys
import textwrap


def _run(code: str, timeout: int = 600):
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         cwd=".")
    assert res.returncode == 0, res.stderr + res.stdout
    assert "OK" in res.stdout, res.stdout


def test_sharded_ghost_step_matches_single_device():
    """One ghost DP step: driver-level parity of grads + metrics between
    the shard_map formulation on (8, 1) and single-device ghost, with the
    full GhostAux hook coverage and a microbatched pass 1."""
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import ModelConfig, QuantConfig
        from repro.dp.ghost import (ghost_clipped_grad_sum,
                                    sharded_ghost_clipped_grad_sum)
        from repro.models.registry import build_model
        from jax.sharding import AxisType

        cfg = ModelConfig(name="g", family="dense_lm", n_layers=2,
                          d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                          d_ff=64, vocab_size=128,
                          compute_dtype="float32", remat=True)
        model = build_model(cfg, QuantConfig(fmt="luq_fp4"))
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)}
        qflags = jnp.ones((cfg.policy_len(),), jnp.float32)

        def loss_one(p, ex, r):
            b1 = jax.tree_util.tree_map(lambda x: x[None], ex)
            return model.loss_fn(p, b1, r, qflags)

        def pel(p, b, r):
            return model.per_example_loss(p, b, r, qflags)

        rng = jax.random.PRNGKey(42)
        aux = model.ghost_aux(qflags)
        mesh = jax.make_mesh((8, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        gu, mu = jax.jit(lambda p, b: ghost_clipped_grad_sum(
            loss_one, pel, p, b, clip_norm=0.8, rng=rng,
            hooked_mask=model.ghost_mask(p), aux=aux))(params, batch)
        gs, ms = jax.jit(lambda p, b: sharded_ghost_clipped_grad_sum(
            loss_one, pel, p, b, clip_norm=0.8, rng=rng,
            hooked_mask=model.ghost_mask(p), aux=aux, mesh=mesh,
            ghost_microbatch=1))(params, batch)
        for (pa, x), (_, y) in zip(
                jax.tree_util.tree_leaves_with_path(gu),
                jax.tree_util.tree_leaves_with_path(gs)):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=2e-4, atol=2e-5,
                err_msg=jax.tree_util.keystr(pa))
        for k in mu:
            np.testing.assert_allclose(float(mu[k]), float(ms[k]),
                                       rtol=1e-4, atol=1e-6)
        print("OK")
    """)


def test_sharded_ghost_both_executors_match_single_device():
    """Full train-setup parity: ghost on the (8, 1) mesh (auto-sharded)
    under BOTH epoch executors ends at the same params as 1-device ghost
    (fp32 tolerance — Gram einsums fuse differently across programs)."""
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import (RunConfig, DPConfig, OptimConfig,
                                  QuantConfig, ModelConfig)
        from repro.launch.steps import build_train_setup, build_epoch_fn
        from repro.models.registry import build_model
        from jax.sharding import AxisType

        cfg = ModelConfig(name="g", family="dense_lm", n_layers=2,
                          d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                          d_ff=64, vocab_size=128,
                          compute_dtype="float32", remat=True)
        model = build_model(cfg, QuantConfig(fmt="luq_fp4"))
        B, S, STEPS = 8, 16, 2
        run = RunConfig(model=cfg, quant=QuantConfig(fmt="luq_fp4"),
                        dp=DPConfig(enabled=True, grad_mode="ghost",
                                    clip_norm=0.8, noise_multiplier=0.5),
                        optim=OptimConfig(name="sgd", lr=0.1),
                        global_batch=B, seq_len=S)
        params0 = model.init(jax.random.PRNGKey(0))
        batches = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (STEPS, B, S), 0, cfg.vocab_size)}
        seeds = jnp.arange(STEPS, dtype=jnp.uint32)
        lrs = jnp.full((STEPS,), 0.1, jnp.float32)
        qflags = jnp.ones((cfg.policy_len(),), jnp.float32)

        results = {}
        for shape in ((1, 1), (8, 1)):
            mesh = jax.make_mesh(shape, ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
            setup = build_train_setup(model, run, mesh)
            opt0 = setup.opt_init_fn(params0)
            # loop executor
            step = jax.jit(setup.step_fn, in_shardings=setup.in_shardings,
                           out_shardings=setup.out_shardings)
            p, o = params0, opt0
            for i in range(STEPS):
                b = {"tokens": batches["tokens"][i]}
                p, o, _ = step(p, o, b, seeds[i], qflags, lrs[i])
            results[(shape, "loop")] = p
            # scan executor (donates params/opt -> fresh copies)
            epoch_fn = build_epoch_fn(setup)
            p2, _, _ = epoch_fn(
                jax.tree_util.tree_map(jnp.copy, params0),
                jax.tree_util.tree_map(jnp.copy, opt0),
                batches, seeds, qflags, lrs)
            results[(shape, "scan")] = p2

        ref = results[((1, 1), "loop")]
        for key, got in results.items():
            for (pa, x), (_, y) in zip(
                    jax.tree_util.tree_leaves_with_path(ref),
                    jax.tree_util.tree_leaves_with_path(got)):
                np.testing.assert_allclose(
                    np.asarray(x), np.asarray(y), rtol=2e-4, atol=2e-4,
                    err_msg=f"{key} {jax.tree_util.keystr(pa)}")
        print("OK")
    """)
