"""Quantizer-backend benchmark: ref (jnp) vs pallas (fused kernels) DP steps.

Times ``Trainer.train_epoch`` for the two quantizer backends
(``QuantConfig.backend``) on one ResNet and one transformer config — the
two families the serve/train hot paths quantize — re-using the interleaved
drift-cancelling protocol of ``benchmarks/epoch_executor.py``: both
backends' trainers are warmed (compile) first, then epochs alternate
ref/pallas so slow machine drift hits both equally.

On CPU the pallas kernels run in *interpret mode* (Pallas emulates the TPU
grid with XLA ops), so these numbers measure dispatch correctness and
interpret overhead, not kernel speed — on a TPU backend the same code
compiles the fused kernels, which are the production path there.  The JSON
keeps both readings honest: ``pallas_over_ref_step_ratio`` > 1 on CPU is
expected.

    PYTHONPATH=src python benchmarks/quant_backends.py
    PYTHONPATH=src python benchmarks/quant_backends.py --smoke   # CI job

Writes ``BENCH_quant_backends.json`` (cwd) and prints
``quant_backends,...`` CSV rows (see benchmarks/common.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import numpy as np

from common import bench_trainers, emit, make_run
from repro.config import ModelConfig
from repro.data.synthetic import ImageClassDataset, TokenDataset
from repro.train_loop import Trainer

BACKENDS = ("ref", "pallas")


def bench_backends(base_run, dataset, *, epochs: int,
                   warmup_epochs: int = 1) -> dict:
    """Time both backends via the shared interleaved protocol."""
    trainers = {
        backend: Trainer(dataclasses.replace(
            base_run, quant=dataclasses.replace(base_run.quant,
                                                backend=backend)),
            dataset, mode="static")
        for backend in BACKENDS}
    results = bench_trainers(trainers, epochs=epochs,
                             steps_per_epoch=base_run.steps_per_epoch,
                             warmup_epochs=warmup_epochs)
    return {backend: {"backend": backend, **r}
            for backend, r in results.items()}


def lm_model() -> ModelConfig:
    return ModelConfig(name="lm-bench", family="dense_lm",
                       n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                       head_dim=16, d_ff=128, vocab_size=256,
                       compute_dtype="float32", remat=False)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes for the CI smoke job")
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--steps-per-epoch", type=int, default=None)
    ap.add_argument("--out", default="BENCH_quant_backends.json")
    args = ap.parse_args(argv)

    epochs = args.epochs or (1 if args.smoke else 3)
    spe = args.steps_per_epoch or (2 if args.smoke else 8)
    batch = 2

    configs = {
        "resnet": {
            "model": ModelConfig(name="resnet-bench", family="resnet",
                                 resnet_blocks=(1,), num_classes=8,
                                 image_size=8 if args.smoke else 16,
                                 compute_dtype="float32"),
            "seq_len": None,
        },
        "transformer": {
            "model": lm_model(),
            "seq_len": 16 if args.smoke else 32,
        },
    }

    env_override = os.environ.get("REPRO_QUANT_BACKEND")
    if env_override:
        # resolve_backend lets the env var beat QuantConfig.backend, so
        # both legs would silently run the same backend
        print(f"warning: REPRO_QUANT_BACKEND={env_override!r} is set and "
              "overrides both legs; unset it for a real ref-vs-pallas "
              "comparison (the JSON records the override)")

    payload = {"benchmark": "quant_backends",
               "env_backend_override": env_override,
               "note": ("pallas runs in Pallas interpret mode on CPU "
                        "(grid emulated with XLA ops); ratios > 1 vs ref "
                        "are expected off-TPU"),
               "config": {"epochs": epochs, "steps_per_epoch": spe,
                          "batch": batch, "fmt": "luq_fp4", "dp": True,
                          "smoke": args.smoke},
               "models": {}}

    for name, cfg in configs.items():
        run = make_run(cfg["model"], fmt="luq_fp4", dp=True, batch=batch,
                       steps_per_epoch=spe, optimizer="sgd",
                       quant_fraction=1.0)
        if cfg["seq_len"]:
            run = dataclasses.replace(run, seq_len=cfg["seq_len"])
        if cfg["model"].family == "resnet":
            ds = ImageClassDataset(n=128, num_classes=8,
                                   image_size=cfg["model"].image_size,
                                   noise=0.4, seed=0)
        else:
            ds = TokenDataset(n=128, vocab=cfg["model"].vocab_size,
                              seq_len=cfg["seq_len"], seed=0)
        # materialize the shared example cache up front (both backends
        # read the same dataset; see benchmarks/epoch_executor.py)
        ds.get(np.arange(ds.n))

        results = bench_backends(run, ds, epochs=epochs)
        ratio = (results["pallas"]["ms_per_step"]
                 / results["ref"]["ms_per_step"])
        for r in results.values():
            emit("quant_backends", model=name, backend=r["backend"],
                 steps=r["steps"], wall_s=round(r["wall_s"], 4),
                 ms_per_step=round(r["ms_per_step"], 3))
        emit("quant_backends", model=name, backend="pallas/ref",
             steps="-", wall_s="-", ms_per_step=round(ratio, 3))
        payload["models"][name] = {
            "ref": results["ref"], "pallas": results["pallas"],
            "pallas_over_ref_step_ratio": ratio,
        }

    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
