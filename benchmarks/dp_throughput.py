"""DP gradient-engine benchmark: vmap vs ghost clipped-grad throughput.

Times the full jitted DP-SGD train step (``launch.steps.build_train_setup``
step_fn: clipped grad sum + Gaussian noise + SGD update) for the two
per-example gradient engines (``DPConfig.grad_mode``) on one transformer
and one ResNet config, sweeping the batch size.  Per batch point the two
modes' steps are interleaved (``benchmarks/common.interleave_timed``) and
the median repetition is reported, cancelling machine drift/throttling.

What the sweep shows (committed JSON, docs/ARCHITECTURE.md "DP gradient
modes"):

* steps/sec — ghost overtakes vmap as the batch grows.  The vmap path's
  per-example weight grads are B skinny GEMMs per layer (and, for convs,
  XLA's slow grouped-conv wgrad path) plus an O(B x params)
  materialize/norm/clip-reduce pass; ghost replaces them with per-layer
  Gram norms and ONE reweighted batched backward.  At small batch the
  ghost two-pass overhead (second forward) dominates and vmap wins —
  the crossover is the point of the mode switch.
* per-example gradient state — ``repro.dp.ghost.per_example_state_bytes``:
  vmap materializes ``B x params_total`` floats per microbatch; ghost only
  materializes the non-hooked fallback leaves (norm scales, embeddings,
  heads), so its per-example state is an order of magnitude flatter in B.
  (Gram buffers are O(B x T^2) transients, excluded.)

    PYTHONPATH=src python benchmarks/dp_throughput.py
    PYTHONPATH=src python benchmarks/dp_throughput.py --smoke   # CI job
    # sharded-ghost smoke on a fake 8-device mesh, microbatched pass 1:
    PYTHONPATH=src python benchmarks/dp_throughput.py --smoke \
        --grad-mode ghost --mesh 8x1 --microbatch 1

Writes ``BENCH_dp_throughput.json`` (cwd) and prints ``dp_throughput,...``
CSV rows (see benchmarks/common.py).
"""
from __future__ import annotations

import os
import sys

# --mesh spawns fake host devices, which must be configured BEFORE the
# first jax import anywhere in the process (both "--mesh 8x1" and
# "--mesh=8x1" spellings)
def _peek_mesh_arg(argv):
    for i, tok in enumerate(argv):
        if tok == "--mesh" and i + 1 < len(argv):
            return argv[i + 1]
        if tok.startswith("--mesh="):
            return tok.split("=", 1)[1]
    return None


_mesh_arg = _peek_mesh_arg(sys.argv)
if _mesh_arg:
    _n = 1
    for _part in _mesh_arg.split("x"):
        _n *= int(_part)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count"
                               f"={_n}")

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from common import emit, interleave_timed, median_by, make_run
from repro.config import ModelConfig
from repro.dp.ghost import per_example_state_bytes
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_train_setup
from repro.models.registry import build_model

ALL_MODES = ("vmap", "ghost")


def lm_model(smoke: bool) -> ModelConfig:
    """Short-sequence LM sized so per-example wgrads are skinny GEMMs and
    B x params materialization is substantial — the regime DP large-batch
    training lives in (the paper's LM setting at CPU scale).  remat off:
    nothing at bench scale needs it, and rematerialization doubles the
    ghost engine's forward recompute (same choice as quant_backends)."""
    if smoke:
        return ModelConfig(name="dp-lm-bench", family="dense_lm",
                           n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                           head_dim=16, d_ff=128, vocab_size=128,
                           compute_dtype="float32", remat=False)
    return ModelConfig(name="dp-lm-bench", family="dense_lm",
                       n_layers=4, d_model=384, n_heads=8, n_kv_heads=8,
                       head_dim=48, d_ff=768, vocab_size=512,
                       compute_dtype="float32", remat=False)


def cnn_model(smoke: bool) -> ModelConfig:
    return ModelConfig(name="dp-cnn-bench", family="resnet",
                       resnet_blocks=(1, 1), num_classes=8,
                       image_size=8 if smoke else 16,
                       compute_dtype="float32")


def make_batch(cfg: ModelConfig, batch: int, seq_len: int):
    if cfg.family == "dense_lm":
        return {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (batch, seq_len), 0, cfg.vocab_size)}
    s = cfg.image_size
    return {"image": jax.random.normal(jax.random.PRNGKey(1),
                                       (batch, s, s, cfg.in_channels)),
            "label": jax.random.randint(jax.random.PRNGKey(2), (batch,),
                                        0, cfg.num_classes)}


def bench_point(cfg: ModelConfig, batch: int, seq_len: int, fmt: str,
                reps: int, modes=ALL_MODES, mesh_shape=None,
                ghost_microbatch: int = 0) -> dict:
    """One (model, batch) sweep point: median-rep step time per mode."""
    if mesh_shape:
        axes = ("data", "model")[:len(mesh_shape)]
        mesh = jax.make_mesh(mesh_shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
    else:
        mesh = make_host_mesh()
    data = make_batch(cfg, batch, seq_len)
    qflags = jnp.ones((cfg.policy_len(),), jnp.float32)
    steps = {}
    for mode in modes:
        run = make_run(cfg, fmt=fmt, dp=True, batch=batch, optimizer="sgd")
        run = dataclasses.replace(
            run, seq_len=seq_len,
            dp=dataclasses.replace(run.dp, grad_mode=mode,
                                   ghost_microbatch=ghost_microbatch))
        model = build_model(cfg, run.quant)
        setup = build_train_setup(model, run, mesh, batch_size=batch,
                                  seq_len=seq_len)
        params = model.init(jax.random.PRNGKey(0))
        opt_state = setup.opt_init_fn(params)
        fn = jax.jit(setup.step_fn)
        # warm call exists only to compile; the timed reps below re-feed
        # the same initial params/opt_state (no donation on this jit)
        jax.block_until_ready(
            fn(params, opt_state, data, jnp.uint32(0), qflags,
               jnp.float32(0.5)))
        steps[mode] = (fn, params, opt_state)
        last_model, last_params = model, params

    def timed(mode):
        fn, params, opt_state = steps[mode]

        def run_once() -> float:
            t0 = time.perf_counter()
            jax.block_until_ready(
                fn(params, opt_state, data, jnp.uint32(0), qflags,
                   jnp.float32(0.5)))
            return time.perf_counter() - t0

        return run_once

    results = interleave_timed({m: timed(m) for m in modes}, reps=reps)
    point = {"batch": batch}
    for mode in modes:
        wall = median_by(results[mode], lambda t: t)
        point[mode] = {"step_s_median": wall, "steps_per_sec": 1.0 / wall,
                       "step_s_reps": results[mode]}
    if "vmap" in point and "ghost" in point:
        point["speedup_ghost_over_vmap"] = (point["vmap"]["step_s_median"]
                                            / point["ghost"]["step_s_median"])
    # analytic per-example gradient state (the batch-scaling memory term),
    # counted from the params already initialized for the timed steps;
    # with the model's GhostAux hooks (dense_lm) ghost state is exactly 0
    aux = (last_model.ghost_aux(qflags)
           if last_model.ghost_aux is not None else None)
    point["per_example_state_bytes"] = per_example_state_bytes(
        last_params, last_model.ghost_mask(last_params), batch, aux=aux)
    return point


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes for the CI smoke job")
    ap.add_argument("--batches", type=int, nargs="*", default=None)
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--fmt", default="luq_fp4")
    ap.add_argument("--grad-mode", default="both",
                    choices=["both", "vmap", "ghost"],
                    help="restrict the timed modes (CI smokes the ghost "
                         "path alone on the fake-device mesh)")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="ghost_microbatch pass-1 chunk size for the "
                         "ghost rows (0 = whole batch)")
    ap.add_argument("--mesh", default=None,
                    help="AxB fake-device mesh shape, e.g. 8x1 — spawns "
                         "XLA host devices and exercises the sharded "
                         "ghost driver (must be first-parsed: sets "
                         "XLA_FLAGS before jax import)")
    ap.add_argument("--out", default="BENCH_dp_throughput.json")
    args = ap.parse_args(argv)
    modes = ALL_MODES if args.grad_mode == "both" else (args.grad_mode,)
    mesh_shape = (tuple(int(p) for p in args.mesh.split("x"))
                  if args.mesh else None)

    # odd rep counts keep median_by an actual median (with 2 reps the
    # upper-middle element is the worst run, not a median)
    reps = args.reps or (3 if args.smoke else 5)
    seq_len = 8 if args.smoke else 16

    models = {"transformer": lm_model(args.smoke),
              "resnet": cnn_model(args.smoke)}
    # the vmap->ghost crossover for the LM sits around B ~ 48-64 on this
    # host, so the transformer sweep extends to 128 where the gap is wide
    batches_by_model = {
        "transformer": args.batches or ((2, 4) if args.smoke
                                        else (8, 16, 32, 64, 128)),
        "resnet": args.batches or ((2, 4) if args.smoke
                                   else (8, 16, 32, 64)),
    }
    payload = {
        "benchmark": "dp_throughput",
        "note": ("full jitted DP-SGD step (clip+noise+SGD) per mode; "
                 "interleaved reps, median reported; "
                 "per_example_state_bytes is the analytic batch-scaling "
                 "memory term (vmap: B x all params; ghost: B x non-hooked "
                 "fallback leaves only)"),
        "config": {"fmt": args.fmt,
                   "batches": {k: list(v)
                               for k, v in batches_by_model.items()},
                   "reps": reps, "seq_len": seq_len, "smoke": args.smoke,
                   "modes": list(modes), "mesh": args.mesh,
                   "ghost_microbatch": args.microbatch},
        "models": {},
    }
    for name, cfg in models.items():
        sweep = []
        for batch in batches_by_model[name]:
            point = bench_point(cfg, batch, seq_len, args.fmt, reps,
                                modes=modes, mesh_shape=mesh_shape,
                                ghost_microbatch=args.microbatch)
            sweep.append(point)
            row = {"model": name, "batch": batch}
            for m in modes:
                row[f"{m}_sps"] = round(point[m]["steps_per_sec"], 3)
            if "speedup_ghost_over_vmap" in point:
                row["speedup"] = round(point["speedup_ghost_over_vmap"], 3)
            row["vmap_state_mb"] = round(
                point["per_example_state_bytes"]["vmap_bytes"] / 2**20, 1)
            row["ghost_state_mb"] = round(
                point["per_example_state_bytes"]["ghost_bytes"] / 2**20, 1)
            emit("dp_throughput", **row)
        payload["models"][name] = {
            "model_config": {"family": cfg.family,
                             "d_model": cfg.d_model,
                             "n_layers": cfg.n_layers,
                             "d_ff": cfg.d_ff, "vocab": cfg.vocab_size,
                             "resnet_blocks": list(cfg.resnet_blocks),
                             "image_size": cfg.image_size},
            "sweep": sweep,
        }

    lm_sweep = payload["models"]["transformer"]["sweep"]
    big = [p for p in lm_sweep
           if p["batch"] >= 32 and "speedup_ghost_over_vmap" in p]
    if big:
        payload["transformer_speedup_at_batch_ge_32"] = {
            str(p["batch"]): p["speedup_ghost_over_vmap"] for p in big}
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    head = (f" (transformer B>=32 ghost speedup: "
            f"{[round(p['speedup_ghost_over_vmap'], 2) for p in big]})"
            if big else "")
    print(f"wrote {args.out}{head}")


if __name__ == "__main__":
    main()
