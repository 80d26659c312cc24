"""Trainer: the full DPQuant training loop (paper Fig. 2 pipeline).

Per epoch:
  1. (every ``analysis_interval`` epochs) COMPUTELOSSIMPACT on Poisson-
     sampled probe batches — charges one "analysis" SGM step;
  2. SELECTTARGETS -> this epoch's quantized-layer flags;
  3. ``steps_per_epoch`` DP-SGD/DP-Adam steps on Poisson-sampled batches —
     each charges one "train" SGM step;
  4. optional eval + checkpoint (params, opt, accountant, scheduler, sampler).

Two epoch executors (``RunConfig.epoch_executor``):

  * ``"scan"`` (default) — the epoch's Poisson batches are pre-drawn,
    stacked, and the whole epoch runs as ONE compiled ``jax.lax.scan``
    program with donated params/opt buffers.  Invariant: the host
    synchronizes with the device **once per epoch** (reading the stacked
    per-step metrics); the RDP accountant is charged once with
    ``steps=steps_per_epoch``.  The quantization flags are fixed for the
    epoch (paper Fig. 2), so they ride along as a broadcast operand.
  * ``"loop"`` — the legacy per-step python loop (one dispatch + one host
    sync + one accountant charge per step).  Kept as a fallback and as the
    reference for the scan/loop equivalence test.

Both executors draw identical sample indices, per-step seeds, and learning
rates from the same ``RunConfig.seed``, and the accountant merges
consecutive identical SGM events, so they produce identical params,
optimizer state, and epsilon on a fixed seed.

Also supports mode="pls" / mode="static" (ablations / baselines) and
dp.enabled=False (the non-private comparison in paper Fig. 1a).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import RunConfig
from repro.core.scheduler import DPQuantScheduler
from repro.checkpoint.manager import CheckpointManager
from repro.data.poisson import PoissonSampler
from repro.dp.accountant import RDPAccountant
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_epoch_fn, build_train_setup
from repro.models.registry import Model, build_model
from repro.optim.schedule import make_schedule
from repro.runtime.preemption import Preempted, PreemptionHandler
from repro.runtime.tracing import install_gc_span


@dataclasses.dataclass
class EpochStats:
    epoch: int
    loss: float
    eps: float
    analysis_eps_fraction: float
    quantized_layers: int
    accuracy: Optional[float] = None
    wall_s: float = 0.0


class Trainer:
    def __init__(self, run: RunConfig, dataset, *, mode: str = "dpquant",
                 eval_dataset=None, mesh=None, checkpoint_dir: str = None,
                 group_size: int = 1, eval_fn: Callable = None,
                 preemption: Optional[PreemptionHandler] = None):
        self.run = run
        self.dataset = dataset
        self.eval_dataset = eval_dataset
        self.eval_fn = eval_fn
        self.mode = mode
        # Fail fast on backend knobs: the dispatch happens at trace time
        # deep inside the jitted step, where a typo'd backend name would
        # surface as an opaque tracer error.  Both epoch executors run the
        # same step_fn, so scan/loop are interchangeable on any backend.
        from repro.quant.backend import resolve_backend
        resolve_backend(run.quant.backend)
        if run.dp.clip_backend not in ("ref", "fused"):
            raise ValueError(f"dp.clip_backend must be 'ref' or 'fused', "
                             f"got {run.dp.clip_backend!r}")
        self.model: Model = build_model(run.model, run.quant)
        # grad_mode validation (incl. ghost-hook support for the family)
        # happens in build_train_setup below, before any tracing
        self.mesh = mesh or make_host_mesh()
        self.setup = build_train_setup(self.model, run, self.mesh)
        self.step_fn = jax.jit(self.setup.step_fn,
                               in_shardings=self.setup.in_shardings,
                               out_shardings=self.setup.out_shardings)
        if run.epoch_executor not in ("scan", "loop"):
            raise ValueError(
                f"epoch_executor must be 'scan' or 'loop', "
                f"got {run.epoch_executor!r}")
        self.epoch_fn = (build_epoch_fn(self.setup, unroll=run.epoch_unroll)
                         if run.epoch_executor == "scan" else None)
        self.schedule = make_schedule(run.optim, run.steps)
        self.sampler = PoissonSampler(dataset.n, run.global_batch,
                                      seed=run.seed)
        self._probe_rng = np.random.RandomState(run.seed + 777)
        self.accountant = RDPAccountant()
        self.scheduler = DPQuantScheduler(
            n_layers=run.model.policy_len(), dp=run.dp, mode=mode,
            group_size=group_size, seed=run.seed)
        self._place(self.model.init(jax.random.PRNGKey(run.seed)), None)
        self.step = 0
        self.history: List[EpochStats] = []
        self.ckpt = (CheckpointManager(checkpoint_dir)
                     if checkpoint_dir else None)
        self.preemption = preemption
        # epoch cursor: train(n) runs n epochs starting here; restore sets
        # it past the checkpointed epoch (or *at* it for mid-epoch resume)
        self._next_epoch = 0
        self._logits_fn = None
        # mid-epoch resume record ({"epoch", "epoch_step", "epoch_losses"})
        # set by restore_latest when the checkpoint was a preemption save
        self._mid_epoch: Optional[dict] = None
        install_gc_span()

    def _place(self, params, opt_state) -> None:
        """Commit params and optimizer state (fresh from ``opt_init_fn``
        when ``None``) to the step's input shardings.  The compiled step
        and epoch programs return them so placed; placing them so from the
        start keeps the first call from compiling a second program."""
        param_sh, opt_sh = self.setup.in_shardings[:2]
        self.params = jax.device_put(params, param_sh)
        if opt_state is None:
            opt_state = self.setup.opt_init_fn(self.params)
        self.opt_state = jax.device_put(opt_state, opt_sh)

    # ------------------------------------------------------------------ #
    def _probe_step(self, params, opt_state, batch, seed, flags):
        lr = self.schedule(self.step)
        return self.step_fn(params, opt_state, batch, seed, flags,
                            jnp.float32(lr))

    # ------------------------------------------------------------------ #
    def train_epoch(self, epoch: int) -> EpochStats:
        with jax.profiler.TraceAnnotation("train.epoch", epoch=epoch):
            return self._train_epoch(epoch)

    def _train_epoch(self, epoch: int) -> EpochStats:
        t0 = time.time()
        run = self.run
        resume = None
        if self._mid_epoch is not None:
            if self._mid_epoch["epoch"] != epoch:
                raise RuntimeError(
                    f"mid-epoch checkpoint is for epoch "
                    f"{self._mid_epoch['epoch']}, cannot run epoch {epoch}")
            resume = self._mid_epoch
            self._mid_epoch = None
        if resume is None:
            # ---- Algorithm 1 (analysis) ----
            if self.mode == "dpquant":
                with jax.profiler.TraceAnnotation("train.analysis",
                                                  epoch=epoch):
                    nb = min(run.dp.analysis_batch_size, run.global_batch)
                    nb = max(run.dp.microbatch_size, nb)
                    probe_batches = [self.dataset.get(
                        self._probe_rng.randint(0, self.dataset.n, nb))
                        for _ in range(run.dp.analysis_reps)]
                    self.scheduler.maybe_analyze(
                        probe_step=self._probe_step, params=self.params,
                        opt_state=self.opt_state, batches=probe_batches,
                        sample_rate=min(1.0, nb / self.dataset.n),
                        accountant=self.accountant,
                        epoch=epoch, seed=run.seed * 1000 + epoch)
            # ---- Algorithm 2 (selection) ----
            with jax.profiler.TraceAnnotation("train.select") as span:
                policy = self.scheduler.select(epoch)
                span.set_metadata(quantized=len(policy))
        else:
            # mid-epoch resume: analysis + selection already ran before the
            # preemption and their RNG draws / accountant charges are in
            # the restored state — re-running either would double-consume
            # the probe and scheduler streams.  The restored scheduler
            # still holds this epoch's policy.
            policy = self.scheduler.current
        flags = policy.flags()

        # ---- DP-SGD steps ----
        start = resume["epoch_step"] if resume else 0
        prior = resume["epoch_losses"] if resume else []
        if run.epoch_executor == "scan":
            losses = self._train_steps_scan(flags, epoch, start, prior)
        else:
            losses = self._train_steps_loop(flags, epoch, start, prior)

        with jax.profiler.TraceAnnotation("train.epoch_end"):
            eps, _ = (self.accountant.get_epsilon(run.dp.delta)
                      if run.dp.enabled else (0.0, 0))
            frac = (self.accountant.analysis_fraction(run.dp.delta)
                    if run.dp.enabled and self.mode == "dpquant" else 0.0)
            acc = self.evaluate() if self.eval_dataset is not None else None
            stats = EpochStats(epoch=epoch, loss=float(np.mean(losses)),
                               eps=eps, analysis_eps_fraction=frac,
                               quantized_layers=len(policy), accuracy=acc,
                               wall_s=time.time() - t0)
            self.history.append(stats)
            if self.ckpt is not None:
                self.save(epoch)
        return stats

    def _maybe_preempt(self, epoch: int, epoch_step: int,
                       losses: List[float]) -> None:
        """Step-boundary preemption poll (both executors call this).

        When the handler fires, a *mid-epoch* checkpoint is written —
        params, opt state, accountant history, scheduler EMA/policy,
        sampler + probe RNG stream positions, and the epoch cursor — and
        :class:`Preempted` is raised.  The accountant is already exact at
        every step boundary (the loop executor charges per step; the scan
        executor charges per chunk, and consecutive identical SGM events
        merge), so the saved epsilon equals the uninterrupted run's at the
        same global step.
        """
        if self.preemption is None or not self.preemption.should_preempt(
                self.step):
            return
        if self.ckpt is not None:
            self.save(epoch, epoch_step=epoch_step, epoch_losses=losses,
                      mid_epoch=True)
            self.ckpt.wait()
        raise Preempted(self.step)

    def _train_steps_loop(self, flags, epoch: int, start: int = 0,
                          prior: List[float] = ()) -> List[float]:
        """Legacy executor: one dispatch + host sync + charge per step."""
        run = self.run
        losses = list(prior)
        for es in range(start, run.steps_per_epoch):
            with jax.profiler.TraceAnnotation("train.chunk", step=self.step,
                                              k=1):
                with jax.profiler.TraceAnnotation("train.sample"):
                    idx = self.sampler.sample()
                with jax.profiler.TraceAnnotation("train.gather"):
                    batch = self.dataset.get(idx)
                with jax.profiler.TraceAnnotation("train.feed"):
                    seed = jnp.uint32(self.step + run.seed)
                    lr = jnp.float32(self.schedule(self.step))
                with jax.profiler.TraceAnnotation("train.dispatch"):
                    self.params, self.opt_state, metrics = self.step_fn(
                        self.params, self.opt_state, batch, seed, flags, lr)
                with jax.profiler.TraceAnnotation("train.wait"):
                    losses.append(float(metrics["loss"]))
                if run.dp.enabled:
                    with jax.profiler.TraceAnnotation("train.account"):
                        self.accountant.step(
                            noise_multiplier=run.dp.noise_multiplier,
                            sample_rate=self.sampler.q, steps=1,
                            label="train")
                self.step += 1
                with jax.profiler.TraceAnnotation("train.poll"):
                    self._maybe_preempt(epoch, es + 1, losses)
        return losses

    def _train_steps_scan(self, flags, epoch: int, start: int = 0,
                          prior: List[float] = ()) -> List[float]:
        """Scan executor: the epoch (in chunks of ``epoch_chunk`` steps, or
        whole) runs as one compiled program; the host syncs once per chunk
        and the accountant is charged once per chunk — consecutive
        identical SGM events merge, so the history is identical to a
        single per-epoch charge while staying exact at every chunk
        boundary (where preemption may checkpoint)."""
        run = self.run
        steps = run.steps_per_epoch
        chunk = run.epoch_chunk if run.epoch_chunk > 0 else steps
        losses: List[float] = list(prior)
        done = start
        while done < steps:
            k = min(chunk, steps - done)
            with jax.profiler.TraceAnnotation("train.chunk", step=self.step,
                                              k=k):
                with jax.profiler.TraceAnnotation("train.sample"):
                    idx = self.sampler.sample_epoch(k)
                with jax.profiler.TraceAnnotation("train.gather"):
                    flat = self.dataset.get(idx.reshape(-1))
                    batches = jax.tree_util.tree_map(
                        lambda x: x.reshape((k, -1) + x.shape[1:]), flat)
                with jax.profiler.TraceAnnotation("train.feed"):
                    seeds = jnp.asarray(
                        np.arange(self.step, self.step + k) + run.seed,
                        jnp.uint32)
                    lrs = jnp.asarray([self.schedule(self.step + i)
                                       for i in range(k)], jnp.float32)
                # self.epoch_fn is looked up here, at call time: a wrapper
                # put on the instance runs inside this span
                with jax.profiler.TraceAnnotation("train.dispatch"):
                    self.params, self.opt_state, metrics = self.epoch_fn(
                        self.params, self.opt_state, batches, seeds, flags,
                        lrs)
                with jax.profiler.TraceAnnotation("train.wait"):
                    chunk_losses = np.asarray(metrics["loss"])
                losses.extend(float(v) for v in chunk_losses)
                self.step += k
                done += k
                if run.dp.enabled:
                    with jax.profiler.TraceAnnotation("train.account"):
                        self.accountant.step(
                            noise_multiplier=run.dp.noise_multiplier,
                            sample_rate=self.sampler.q, steps=k,
                            label="train")
                with jax.profiler.TraceAnnotation("train.poll"):
                    self._maybe_preempt(epoch, done, losses)
        return losses

    def train(self, epochs: int, *, eps_budget: Optional[float] = None,
              verbose: bool = False) -> List[EpochStats]:
        """Train ``epochs`` more epochs from the current epoch cursor.

        A fresh trainer starts at epoch 0; after ``restore_latest`` the
        cursor sits past the last completed epoch (or *at* the preempted
        epoch for a mid-epoch checkpoint, which is finished first).
        """
        start = self._next_epoch
        for e in range(start, start + epochs):
            stats = self.train_epoch(e)
            self._next_epoch = e + 1
            if verbose:
                print(f"epoch {e}: loss={stats.loss:.4f} eps={stats.eps:.3f} "
                      f"k={stats.quantized_layers} acc={stats.accuracy}")
            if eps_budget is not None and stats.eps >= eps_budget:
                break  # paper: truncate training at the privacy budget
        return self.history

    # ------------------------------------------------------------------ #
    def evaluate(self, n: int = 512) -> float:
        if self.eval_fn is not None:
            return self.eval_fn(self.params)
        idx = np.arange(min(n, self.eval_dataset.n))
        batch = self.eval_dataset.get(idx)
        if "label" not in batch:
            return float("nan")
        flags = jnp.zeros((self.run.model.policy_len(),), jnp.float32)
        preds = self._predict(batch, flags)
        return float((preds == np.asarray(batch["label"])).mean())

    def _predict(self, batch, flags):
        if self._logits_fn is None:
            # one compiled forward; run eagerly it would compile every
            # conv and flag branch separately
            self._logits_fn = jax.jit(self._logits)
        logits = self._logits_fn(self.params, batch, flags)
        return np.asarray(jnp.argmax(logits, -1))

    def _logits(self, params, batch, flags):
        from repro.models import resnet as rn, densenet as dn, bert as bt
        cfg, quant = self.run.model, self.run.quant
        if cfg.family == "resnet":
            return rn.forward(params, batch["image"], flags, cfg, quant)
        if cfg.family == "densenet":
            return dn.forward(params, batch["image"], flags, cfg, quant)
        if cfg.family == "bert":
            h = bt.forward(params, batch["tokens"], flags, cfg, quant)
            return (h[:, 0].astype(jnp.float32) @ params["cls_w"]
                    + params["cls_b"])
        raise ValueError(f"no predict for family {cfg.family}")

    # ------------------------------------------------------------------ #
    def save(self, epoch: int, *, epoch_step: int = 0,
             epoch_losses: List[float] = (), mid_epoch: bool = False) -> None:
        """Checkpoint everything a bit-identical resume needs.

        Besides params/opt, the aux payload carries the accountant
        history, scheduler EMA + current policy, sampler RNG cursor, the
        probe RNG stream position (analysis batch draws), and — for
        preemption saves (``mid_epoch``) — the epoch step index and the
        partial per-step losses so the finished epoch's stats match the
        uninterrupted run's.
        """
        aux = {
            "accountant": self.accountant.state_dict(),
            "scheduler": self.scheduler.state_dict(),
            "sampler": self.sampler.state_dict(),
            "probe_rng": self._probe_rng.get_state(),
            "history": [dataclasses.asdict(s) for s in self.history],
            "step": self.step,
            "epoch": epoch,
            "mid_epoch": bool(mid_epoch),
            "epoch_step": int(epoch_step),
            "epoch_losses": [float(x) for x in epoch_losses],
        }
        self.ckpt.save(self.step, {"params": self.params,
                                   "opt": self.opt_state}, aux)

    def restore_latest(self) -> Optional[int]:
        if self.ckpt is None:
            return None
        res = self.ckpt.restore_latest({"params": self.params,
                                        "opt": self.opt_state})
        if res is None:
            return None
        _, tree, aux = res
        self._place(tree["params"], tree["opt"])
        self.accountant = RDPAccountant.from_state_dict(aux["accountant"])
        self.scheduler.load_state_dict(aux["scheduler"])
        self.sampler.load_state_dict(aux["sampler"])
        if "probe_rng" in aux:
            self._probe_rng.set_state(aux["probe_rng"])
        # older checkpoints carry fields EpochStats no longer has (steps_s)
        known = {f.name for f in dataclasses.fields(EpochStats)}
        self.history = [EpochStats(**{k: v for k, v in d.items()
                                      if k in known})
                        for d in aux.get("history", [])]
        self.step = aux["step"]
        if aux.get("mid_epoch"):
            # preemption save: re-enter the interrupted epoch, skipping
            # analysis/selection and the already-run steps (train_epoch)
            self._mid_epoch = {"epoch": aux["epoch"],
                               "epoch_step": aux["epoch_step"],
                               "epoch_losses": list(aux["epoch_losses"])}
            self._next_epoch = aux["epoch"]
        else:
            self._mid_epoch = None
            self._next_epoch = aux["epoch"] + 1
        return aux["epoch"]
