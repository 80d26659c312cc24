import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, record memory/cost/collective analyses.

THE two lines above must run before any jax import (jax locks the device
count at first init); that's why this module sets XLA_FLAGS at the very top
and must be the process entry point:

    PYTHONPATH=src python -m repro.launch.dryrun --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k

Outputs one JSON per cell under --out (default results/dryrun/).
"""
import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402
from pathlib import Path  # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.config import (DPConfig, OptimConfig, QuantConfig, RunConfig,
                          SHAPES)  # noqa: E402
from repro.configs import ASSIGNED_ARCHS, get_config  # noqa: E402
from repro.launch import hlo_analysis, roofline  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import build_serve_setup, build_train_setup  # noqa: E402
from repro.models.registry import build_model  # noqa: E402

# The production meshes model v5e pods: placeholder CPU devices stand in for
# the chips, so the roofline peaks are named here, not read from a device.
TARGET_DEVICE_KIND = "TPU v5 lite"


def cell_skip_reason(cfg, shape) -> str:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("SKIP(full-attention): 500k dense-KV decode is assigned only "
                "to sub-quadratic (ssm/hybrid) archs")
    return ""


def _mem_dict(ma) -> dict:
    return {k: getattr(ma, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             fmt: str = "luq_fp4", extra_tag: str = "",
             overrides: dict = None, dp_overrides: dict = None) -> dict:
    import dataclasses as _dc
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "kind": shape.kind, "tag": extra_tag}
    reason = cell_skip_reason(cfg, shape)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    quant = QuantConfig(fmt=fmt)
    model = build_model(cfg, quant)
    dp_kwargs = dict(enabled=True, microbatch_size=1,
                     microbatch_mode=("single" if cfg.family == "moe_lm"
                                      else "data_parallel"),
                     grad_accum_dtype=("bfloat16" if cfg.family == "moe_lm"
                                       else "float32"))
    if dp_overrides:
        dp_kwargs.update(dp_overrides)
    run = RunConfig(
        model=cfg, quant=quant,
        dp=DPConfig(**dp_kwargs),
        optim=OptimConfig(name="sgd", lr=0.5),
        global_batch=shape.global_batch, seq_len=shape.seq_len)

    t0 = time.time()
    if shape.kind == "train":
        setup = build_train_setup(model, run, mesh)
        jitted = jax.jit(setup.step_fn, in_shardings=setup.in_shardings,
                         out_shardings=setup.out_shardings)
        lowered = jitted.lower(*setup.abstract_args)
    elif shape.kind == "prefill":
        setup = build_serve_setup(model, run, mesh,
                                  shape.global_batch, shape.seq_len)
        jitted = jax.jit(setup.prefill_fn,
                         in_shardings=setup.prefill_in_shardings)
        lowered = jitted.lower(*setup.prefill_abstract)
    else:  # decode
        setup = build_serve_setup(model, run, mesh,
                                  shape.global_batch, shape.seq_len)
        jitted = jax.jit(setup.decode_fn,
                         in_shardings=setup.decode_in_shardings)
        lowered = jitted.lower(*setup.decode_abstract)
    rec["lower_s"] = round(time.time() - t0, 2)

    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)

    ma = compiled.memory_analysis()
    print(f"[{arch} x {shape_name} x {rec['mesh']}] memory_analysis:", ma)
    ca = dict(compiled.cost_analysis())
    print(f"[{arch} x {shape_name} x {rec['mesh']}] xla cost_analysis "
          f"(per-iteration, loops counted once): "
          f"flops={ca.get('flops', 0):.3e} "
          f"bytes={ca.get('bytes accessed', 0):.3e}")
    hlo = compiled.as_text()
    analysis = hlo_analysis.analyze(hlo)

    n_dev = mesh.devices.size
    abstract_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mf = roofline.model_flops(cfg, abstract_params, shape.kind,
                              shape.global_batch, shape.seq_len, n_dev)
    terms = roofline.derive(ca, hlo, TARGET_DEVICE_KIND,
                            model_flops_per_device=mf,
                            hlo_analysis=analysis)

    rec.update({
        "status": "ok",
        "memory": _mem_dict(ma),
        "xla_cost": {k: float(v) for k, v in ca.items()
                     if isinstance(v, (int, float))},
        "collectives": analysis["collectives"],
        "hlo_warnings": analysis["warnings"],
        "roofline": terms.as_dict(),
        "n_params": roofline.count_params(abstract_params),
        "n_active_params": roofline.active_params(cfg, abstract_params),
        "n_devices": n_dev,
    })
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all' (the 10 assigned)")
    ap.add_argument("--shape", default="all",
                    help="train_4k|prefill_32k|decode_32k|long_500k|all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--fmt", default="luq_fp4")
    ap.add_argument("--tag", default="", help="variant tag for perf runs")
    ap.add_argument("--out", default="results/dryrun")
    # perf-variant overrides (hillclimb levers)
    ap.add_argument("--microbatch-size", type=int, default=None)
    ap.add_argument("--partial-accum", action="store_true")
    ap.add_argument("--ssm-chunk", type=int, default=None)
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--attn-chunk-q", type=int, default=None)
    args = ap.parse_args()

    overrides = {}
    if args.ssm_chunk is not None:
        overrides["ssm_chunk"] = args.ssm_chunk
    if args.capacity_factor is not None:
        overrides["moe_capacity_factor"] = args.capacity_factor
    if args.attn_chunk_q is not None:
        overrides["attn_chunk_q"] = args.attn_chunk_q
    dp_overrides = {}
    if args.microbatch_size is not None:
        dp_overrides["microbatch_size"] = args.microbatch_size
    if args.partial_accum:
        dp_overrides["partial_accum"] = True

    archs = ASSIGNED_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_tag = "multi" if mp else "single"
                name = f"{arch}__{shape}__{mesh_tag}"
                if args.tag:
                    name += f"__{args.tag}"
                path = outdir / f"{name}.json"
                try:
                    rec = run_cell(arch, shape, mp, fmt=args.fmt,
                                   extra_tag=args.tag, overrides=overrides,
                                   dp_overrides=dp_overrides)
                except Exception as e:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": str(e),
                           "traceback": traceback.format_exc()}
                    failures += 1
                    print(f"[{name}] ERROR: {e}")
                path.write_text(json.dumps(rec, indent=2, default=str))
                status = rec.get("status")
                if status == "ok":
                    r = rec["roofline"]
                    print(f"[{name}] OK compute={r['compute_s']:.3e}s "
                          f"memory={r['memory_s']:.3e}s "
                          f"collective={r['collective_s']:.3e}s "
                          f"dominant={r['dominant']}")
                elif status == "skipped":
                    print(f"[{name}] {rec['reason']}")
    print("dry-run complete; failures:", failures)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
