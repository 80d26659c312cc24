"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train \
        --arch resnet18 --mode dpquant --epochs 10 --eps 8 \
        --quant-fraction 0.9 --fmt luq_fp4 --checkpoint-dir ckpt/

Any registered arch id works (use --smoke for the reduced config — the full
LM configs need the production mesh).  Restores from the latest valid
checkpoint automatically (fault-tolerant restart).
"""
from __future__ import annotations

import argparse

from repro.config import DPConfig, ModelConfig, OptimConfig, QuantConfig, RunConfig
from repro.configs import get_config, get_smoke_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.data.synthetic import ImageClassDataset, NLIDataset, TokenDataset
from repro.runtime.faults import FaultEvent, FaultPlan
from repro.runtime.preemption import Preempted, PreemptionHandler
from repro.train_loop import Trainer


def make_dataset(cfg: ModelConfig, n: int, seq_len: int, seed: int = 0):
    if cfg.family in ("resnet", "densenet"):
        return ImageClassDataset(n=n, num_classes=cfg.num_classes,
                                 image_size=cfg.image_size, seed=seed)
    if cfg.family == "bert":
        return NLIDataset(n=n, vocab=cfg.vocab_size, seq_len=seq_len,
                          num_classes=cfg.num_classes, seed=seed)
    return TokenDataset(n=n, vocab=cfg.vocab_size, seq_len=seq_len, seed=seed)


def main(argv=None):
    """Parse flags, train, and return the :class:`Trainer`."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-scale)")
    ap.add_argument("--mode", default="dpquant",
                    choices=["dpquant", "pls", "static"])
    ap.add_argument("--no-dp", action="store_true")
    ap.add_argument("--fmt", default="luq_fp4")
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"],
                    help="quantizer backend (repro.quant.backend dispatch); "
                         "REPRO_QUANT_BACKEND overrides")
    ap.add_argument("--clip-backend", default="ref",
                    choices=["ref", "fused"],
                    help="per-example clip path: jnp reference or the fused "
                         "Pallas clip+sum kernel")
    ap.add_argument("--grad-mode", default="vmap",
                    choices=["vmap", "ghost"],
                    help="per-example gradient engine: vmap(grad) "
                         "materialization or two-pass ghost-norm clipping "
                         "(docs/ARCHITECTURE.md 'DP gradient modes')")
    ap.add_argument("--ghost-microbatch", type=int, default=0,
                    help="ghost pass-1 chunk size (0 = whole batch): scans "
                         "the norm pass in chunks so activations alone "
                         "bound ghost memory")
    ap.add_argument("--ghost-sharded", default="auto",
                    choices=["auto", "on", "off"],
                    help="data-parallel ghost formulation: shard_map with "
                         "per-shard norm taps + one psum of the clipped "
                         "grad sums (auto = when the mesh data axes have "
                         "degree > 1)")
    ap.add_argument("--quant-fraction", type=float, default=0.9)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--steps-per-epoch", type=int, default=10)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--dataset-size", type=int, default=4096)
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "momentum", "adam", "adamw"])
    ap.add_argument("--clip-norm", type=float, default=1.0)
    ap.add_argument("--noise-multiplier", type=float, default=1.0)
    ap.add_argument("--eps", type=float, default=None,
                    help="stop when the privacy budget is reached")
    ap.add_argument("--microbatch", type=int, default=16)
    ap.add_argument("--executor", default="scan", choices=["scan", "loop"],
                    help="epoch executor: one compiled scan per epoch "
                         "(default) or the legacy per-step loop")
    ap.add_argument("--epoch-chunk", type=int, default=0,
                    help="scan chunk size in steps (0 = whole epoch)")
    ap.add_argument("--epoch-unroll", type=int, default=1,
                    help="lax.scan unroll factor for the scan executor")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--preempt-at", type=int, default=None,
                    help="inject a preemption at this global step: the "
                         "trainer writes a mid-epoch checkpoint and exits; "
                         "a rerun resumes bit-identically")
    ap.add_argument("--handle-signals", action="store_true",
                    help="checkpoint-and-exit on SIGTERM (scheduler "
                         "eviction notice) instead of dying mid-step")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    run = RunConfig(
        model=cfg,
        quant=QuantConfig(fmt=args.fmt, backend=args.backend),
        dp=DPConfig(enabled=not args.no_dp, clip_norm=args.clip_norm,
                    noise_multiplier=args.noise_multiplier,
                    microbatch_size=args.microbatch,
                    quant_fraction=args.quant_fraction,
                    clip_backend=args.clip_backend,
                    grad_mode=args.grad_mode,
                    ghost_microbatch=args.ghost_microbatch,
                    ghost_sharded=args.ghost_sharded),
        optim=OptimConfig(name=args.optimizer, lr=args.lr),
        global_batch=args.batch, seq_len=args.seq_len,
        steps_per_epoch=args.steps_per_epoch,
        steps=args.epochs * args.steps_per_epoch, seed=args.seed,
        epoch_executor=args.executor, epoch_chunk=args.epoch_chunk,
        epoch_unroll=args.epoch_unroll)

    ds = make_dataset(cfg, args.dataset_size, args.seq_len, args.seed)
    ev = make_dataset(cfg, 512, args.seq_len, args.seed + 1) \
        if cfg.family in ("resnet", "densenet", "bert") else None
    handler = None
    if args.preempt_at is not None or args.handle_signals:
        plan = (FaultPlan([FaultEvent(kind="preempt", at=args.preempt_at)],
                          seed=args.seed)
                if args.preempt_at is not None else None)
        handler = PreemptionHandler(faults=plan,
                                    handle_signals=args.handle_signals)
    tr = Trainer(run, ds, eval_dataset=ev, mode=args.mode,
                 checkpoint_dir=args.checkpoint_dir, preemption=handler)
    resumed = tr.restore_latest()
    if resumed is not None:
        print(f"resumed from checkpoint at epoch {resumed}"
              + (" (mid-epoch)" if tr._mid_epoch is not None else ""))
    # --epochs is the run's *total* epoch count: train whatever is left
    # past the epoch cursor (a finished run is a clean no-op restart)
    remaining = max(0, args.epochs - tr._next_epoch)
    try:
        tr.train(remaining, eps_budget=args.eps, verbose=True)
    except Preempted as p:
        if tr.ckpt:
            tr.ckpt.wait()
        print(f"preempted at step {p.step}; checkpoint written — rerun to "
              "resume")
        return tr
    if tr.ckpt:
        tr.ckpt.wait()
    final = tr.history[-1]
    print(f"final: loss={final.loss:.4f} eps={final.eps:.3f} "
          f"acc={final.accuracy}")
    return tr


if __name__ == "__main__":
    main()
