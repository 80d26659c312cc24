"""Step builders: jit-ready train / prefill / decode functions + shardings.

``build_train_setup``/``build_serve_setup`` assemble, for a (model, mesh):
  * the pure step function (DP-SGD/DP-Adam or plain),
  * in/out NamedShardings derived from logical axes via the partitioner,
  * abstract (ShapeDtypeStruct) arguments for ``jit(...).lower()`` —
    the multi-pod dry-run and the roofline derive everything from these.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import RunConfig
from repro.dp.clip import per_example_clipped_grad_sum
from repro.dp.engine import validate_grad_mode
from repro.dp.ghost import (ghost_clipped_grad_sum,
                            sharded_ghost_clipped_grad_sum)
from repro.dp.noise import add_gaussian_noise
from repro.models.registry import Model
from repro.optim import make_optimizer, apply_updates
from repro.optim.optimizers import AdamState
from repro.parallel import partitioner as pt
from repro.parallel.axes import partitioning_context
from repro.runtime.tracing import scope


def _replicated(mesh):
    return NamedSharding(mesh, P())


def _opt_axes(opt_name: str, paxes):
    if opt_name in ("sgd",):
        return ()
    if opt_name == "momentum":
        return paxes
    return AdamState(paxes, paxes, None)


@dataclasses.dataclass
class TrainSetup:
    step_fn: Callable
    in_shardings: Tuple
    out_shardings: Tuple
    abstract_args: Tuple
    mesh: Mesh
    rules: dict
    init_fn: Callable           # sharding-annotated param init
    opt_init_fn: Callable


def _microbatch(run: RunConfig, mesh: Mesh) -> int:
    dp_axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_degree = 1
    for a in dp_axes:
        dp_degree *= sizes[a]
    if run.dp.microbatch_mode == "single":
        return 1
    mb = run.dp.microbatch_size * dp_degree
    return max(1, min(mb, run.global_batch))


def build_train_setup(model: Model, run: RunConfig, mesh: Mesh,
                      batch_size: Optional[int] = None,
                      seq_len: Optional[int] = None) -> TrainSetup:
    cfg = model.config
    if run.dp.enabled:
        validate_grad_mode(run.dp, model)
    rules = pt.merge_rules(pt.DEFAULT_RULES, cfg.sharding_overrides)
    resolver = pt.activation_resolver(mesh, rules)
    opt = make_optimizer(run.optim)
    B = batch_size or run.global_batch
    S = seq_len or run.seq_len
    mb = _microbatch(run, mesh)
    n_layers = cfg.policy_len()
    accum_dtype = jnp.dtype(run.dp.grad_accum_dtype)

    # ---- abstract shapes ----
    abstract_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    abstract_opt = jax.eval_shape(opt.init, abstract_params)
    abstract_batch = model.batch_spec(B, S)
    abstract_args = (
        abstract_params, abstract_opt, abstract_batch,
        jax.ShapeDtypeStruct((), jnp.uint32),       # seed
        jax.ShapeDtypeStruct((n_layers,), jnp.float32),  # qflags
        jax.ShapeDtypeStruct((), jnp.float32),      # lr
    )

    # ---- shardings ----
    paxes = model.param_axes()
    param_sh = pt.tree_shardings(paxes, abstract_params, mesh, rules)
    opt_sh = pt.tree_shardings(_opt_axes(opt.name, paxes), abstract_opt,
                               mesh, rules)
    batch_sh = pt.tree_shardings(model.batch_axes(), abstract_batch,
                                 mesh, rules)
    rep = _replicated(mesh)
    in_shardings = (param_sh, opt_sh, batch_sh, rep, rep, rep)
    out_shardings = (param_sh, opt_sh, None)

    def micro_constrain(micro):
        """Keep the microbatch example-dim data-sharded after the reshape."""
        def one(x, ax):
            logical = (None, "batch") + tuple(ax[1:])
            return jax.lax.with_sharding_constraint(
                x, pt.named_sharding(logical, x.shape, mesh, rules))
        return jax.tree_util.tree_map(one, micro, model.batch_axes())

    dp_shards = 1
    _sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for _a in ("pod", "data"):
        dp_shards *= _sizes.get(_a, 1)
    _axes_leaf = lambda x: x is None or (isinstance(x, tuple) and len(x) > 0
                                         and all(isinstance(e, (str, type(None)))
                                                 for e in x))

    def partial_constrain(tree):
        """Partial grad sums: leading shard dim over (pod, data); param dims
        keep their own sharding."""
        def one(ax, x):
            logical = ("batch",) + tuple(ax or [None] * (x.ndim - 1))
            if len(logical) != x.ndim:
                return x
            try:
                sh = pt.named_sharding(logical, x.shape, mesh, rules)
            except ValueError:
                return x
            return jax.lax.with_sharding_constraint(x, sh)
        return jax.tree_util.tree_map(one, paxes, tree, is_leaf=_axes_leaf)

    # ---- ghost-mode execution strategy (docs/ARCHITECTURE.md) ----
    # sharded: shard_map over the data axes (per-shard norm taps + one
    # psum) when the mesh actually data-parallelizes and params are not
    # model-sharded; otherwise the GSPMD driver with a sharding-constrained
    # pass-2 batch.  ghost_microbatch chunks pass 1 either way.
    model_degree = _sizes.get("model", 1)
    gs = run.dp.ghost_sharded
    ghost_is_on = run.dp.enabled and run.dp.grad_mode == "ghost"
    if ghost_is_on and gs == "on" and model_degree > 1:
        raise ValueError("dp.ghost_sharded='on' requires params replicated "
                         "over the data axes (model axis degree 1); use "
                         "'auto'/'off' on model-parallel meshes")
    if gs == "on":
        ghost_use_sharded = ghost_is_on   # divisibility checked in-driver
    else:
        ghost_use_sharded = (gs == "auto" and ghost_is_on and dp_shards > 1
                             and model_degree == 1
                             and B % dp_shards == 0)
    ghost_mb_local = run.dp.ghost_microbatch

    def ghost_batch_constrain(b):
        return jax.tree_util.tree_map(jax.lax.with_sharding_constraint,
                                      b, batch_sh)

    def add_noise(grad_sum, rng):
        return scope("dp_noise", lambda g, key: add_gaussian_noise(
            g, clip_norm=run.dp.clip_norm,
            noise_multiplier=run.dp.noise_multiplier,
            batch_size=B, rng=key))(grad_sum, rng)

    def update(grads, opt_state, params, lr):
        updates, new_opt = opt.update(grads, opt_state, params, lr)
        return apply_updates(params, updates), new_opt

    def train_step(params, opt_state, batch, seed, qflags, lr):
        with partitioning_context(resolver):
            rng = jax.random.PRNGKey(seed)
            clip_rng, noise_rng, loss_rng = jax.random.split(rng, 3)

            def loss_one(p, ex, r):
                b1 = jax.tree_util.tree_map(lambda x: x[None], ex)
                return model.loss_fn(p, b1, r, qflags)

            if run.dp.enabled and run.dp.grad_mode == "ghost":
                def pel(p, b, r):
                    return model.per_example_loss(p, b, r, qflags)

                aux = (model.ghost_aux(qflags)
                       if model.ghost_aux is not None else None)
                if ghost_use_sharded:
                    grad_sum, metrics = sharded_ghost_clipped_grad_sum(
                        loss_one, pel, params, batch,
                        clip_norm=run.dp.clip_norm, rng=clip_rng,
                        hooked_mask=model.ghost_mask(params),
                        mesh=mesh, data_axes=("pod", "data"),
                        accum_dtype=accum_dtype, aux=aux,
                        ghost_microbatch=ghost_mb_local)
                else:
                    grad_sum, metrics = ghost_clipped_grad_sum(
                        loss_one, pel, params, batch,
                        clip_norm=run.dp.clip_norm, rng=clip_rng,
                        hooked_mask=model.ghost_mask(params),
                        accum_dtype=accum_dtype, aux=aux,
                        ghost_microbatch=run.dp.ghost_microbatch,
                        constrain=ghost_batch_constrain)
                grads = add_noise(grad_sum, noise_rng)
            elif run.dp.enabled:
                grad_sum, metrics = per_example_clipped_grad_sum(
                    loss_one, params, batch,
                    clip_norm=run.dp.clip_norm, microbatch_size=mb,
                    rng=clip_rng, constrain=micro_constrain,
                    accum_dtype=accum_dtype,
                    partial_accum_shards=(dp_shards if run.dp.partial_accum
                                          else 0),
                    constrain_partial=partial_constrain,
                    clip_backend=run.dp.clip_backend)
                grads = add_noise(grad_sum, noise_rng)
            else:
                def mean_loss(p):
                    return model.loss_fn(p, batch, loss_rng, qflags)
                loss, grads = jax.value_and_grad(mean_loss)(params)
                metrics = {"loss": loss}

            new_params, new_opt = scope("opt_update", update)(
                grads, opt_state, params, lr)
            return new_params, new_opt, metrics

    def init_fn(key):
        return model.init(key)

    return TrainSetup(
        step_fn=train_step, in_shardings=in_shardings,
        out_shardings=out_shardings, abstract_args=abstract_args,
        mesh=mesh, rules=rules, init_fn=init_fn, opt_init_fn=opt.init)


def build_epoch_fn(setup: TrainSetup, *, unroll: int = 1):
    """Compile a whole epoch (or chunk of steps) into one scan program.

    Returns a jitted function

        ``epoch_fn(params, opt_state, batches, seeds, qflags, lrs)
            -> (params, opt_state, metrics)``

    where ``batches`` is the epoch's pre-drawn batch tree with a leading
    ``steps`` axis, ``seeds``/``lrs`` are per-step ``(steps,)`` arrays, and
    ``metrics`` holds every per-step metric stacked on device.  The body is
    exactly ``setup.step_fn`` — the same traced computation the per-step
    executor jits — scanned over the step axis, so the two executors are
    numerically interchangeable.  ``params``/``opt_state`` buffers are
    donated: the epoch program updates them in place instead of allocating
    a second copy of the model per step.

    ``unroll`` is forwarded to ``jax.lax.scan``: unrolling k step bodies per
    loop iteration removes while-loop overhead and lets XLA overlap the
    params-independent work of adjacent steps (batch dequant, PRNG,
    DP-noise generation); it trades compile time for throughput, so the
    default stays 1 and the benchmark/production configs opt in.

    The epoch program carries the same shardings as the per-step jit:
    params/opt keep ``setup``'s tree shardings and the stacked batches get
    the per-step batch sharding with a replicated leading step axis, so on
    a multi-device mesh the scan executor partitions exactly like the
    legacy loop instead of falling back to unannotated placement.
    """
    param_sh, opt_sh, batch_sh = setup.in_shardings[:3]
    stacked_batch_sh = jax.tree_util.tree_map(
        lambda sh: NamedSharding(sh.mesh, P(None, *sh.spec)), batch_sh)
    rep = _replicated(setup.mesh)

    def epoch_fn(params, opt_state, batches, seeds, qflags, lrs):
        def body(carry, xs):
            p, o = carry
            batch, seed, lr = xs
            p, o, metrics = setup.step_fn(p, o, batch, seed, qflags, lr)
            return (p, o), metrics

        (params, opt_state), metrics = jax.lax.scan(
            body, (params, opt_state), (batches, seeds, lrs),
            unroll=unroll)
        return params, opt_state, metrics

    return jax.jit(
        epoch_fn,
        in_shardings=(param_sh, opt_sh, stacked_batch_sh, rep, rep, rep),
        out_shardings=setup.out_shardings,
        donate_argnums=(0, 1))


@dataclasses.dataclass
class ServeSetup:
    prefill_fn: Callable
    decode_fn: Callable
    prefill_in_shardings: Tuple
    prefill_abstract: Tuple
    decode_in_shardings: Tuple
    decode_abstract: Tuple
    mesh: Mesh
    rules: dict


def build_serve_setup(model: Model, run: RunConfig, mesh: Mesh,
                      batch_size: int, seq_len: int,
                      kv_fmt: str = "none") -> ServeSetup:
    cfg = model.config
    rules = pt.merge_rules(pt.DEFAULT_RULES, cfg.sharding_overrides)
    resolver = pt.activation_resolver(mesh, rules)

    if kv_fmt not in model.kv_formats:
        raise ValueError(
            f"model family {cfg.family!r} does not support "
            f"kv_fmt={kv_fmt!r} (supported: {model.kv_formats})")
    # Only pass the kwarg for quantized formats so ("none",)-only families
    # keep their original zero-extra-arg serve hook signatures.
    kv_kw = {} if kv_fmt == "none" else {"kv_fmt": kv_fmt}

    abstract_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_sh = pt.tree_shardings(model.param_axes(), abstract_params,
                                 mesh, rules)
    abstract_batch = model.batch_spec(batch_size, seq_len)
    batch_sh = pt.tree_shardings(model.batch_axes(), abstract_batch,
                                 mesh, rules)
    abstract_cache = model.cache_spec(batch_size, seq_len, **kv_kw)
    cache_sh = pt.tree_shardings(model.cache_axes(**kv_kw), abstract_cache,
                                 mesh, rules)
    token_sh = pt.named_sharding(("batch",), (batch_size,), mesh, rules)

    def prefill_fn(params, batch):
        with partitioning_context(resolver):
            return model.prefill(params, batch, cache_len=seq_len, **kv_kw)

    def decode_fn(params, cache, token):
        with partitioning_context(resolver):
            return model.decode_step(params, cache, token, **kv_kw)

    return ServeSetup(
        prefill_fn=prefill_fn, decode_fn=decode_fn,
        prefill_in_shardings=(param_sh, batch_sh),
        prefill_abstract=(abstract_params, abstract_batch),
        decode_in_shardings=(param_sh, cache_sh, token_sh),
        decode_abstract=(abstract_params, abstract_cache,
                         jax.ShapeDtypeStruct((batch_size,), jnp.int32)),
        mesh=mesh, rules=rules)
