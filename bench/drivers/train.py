"""Training cells: DP-SGD through ``repro.train_loop.Trainer``.

Set-up builds one Trainer (its compiled probe step and epoch-chunk
program), warms every shape the window uses by running the Trainer's own
epoch on throwaway state, then drives the first steps the check compares:
two calls of the epoch-chunk program from the seed's parameters on rows
that all differ, one stepping only its first step (the state after one
step) and one stepping its first three (the learning rate is an operand of
the program, so both are the window's own program and feed).  The
Trainer then carries the three-step state into the window.

The window is ``Trainer.train`` from epoch 0, so it holds epoch 0's
DPQuant analysis, selection, sampling, accounting and every chunk; it
ends at the first chunk boundary after ``seconds``, through the Trainer's
own preemption poll.  After the window the plain reference
(``bench/reference/resnet.py``) follows the same three steps, and the
privacy the Trainer's accountant reports for the window is compared with
the plain RDP composition (``bench/reference/accountant.py``) of the
steps the harness counted through the epoch-chunk program.
"""
from __future__ import annotations

import shutil
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, harness, peaks, traffic
from bench.trace import Trace

TRAINER_SEED = 1234


class DeviceImages:
    """The cell's training set, held on the device as flat rows; ``get``
    gathers rows and gives them the image shape."""

    def __init__(self, images, labels, shape):
        self.images, self.labels = images, labels
        self.n = int(images.shape[0])
        self._take = jax.jit(lambda a, b, i: (
            jnp.take(a, i, axis=0).reshape((i.shape[0],) + shape),
            jnp.take(b, i, axis=0)))

    def get(self, indices) -> dict:
        with jax.profiler.TraceAnnotation("bench.gather"):
            x, y = self._take(self.images, self.labels,
                              jnp.asarray(indices, jnp.int32))
        return {"image": x, "label": y}


def run_config(cell: dict, mc, run_seed: int, smoke: bool):
    from repro.config import DPConfig, OptimConfig, QuantConfig, RunConfig
    t = harness.traffic_params(cell, smoke)
    steps_per_epoch = t["dataset_size"] // t["batch"]
    return RunConfig(
        model=mc,
        quant=QuantConfig(**t["quant"]),
        dp=DPConfig(**t["dp"]),
        optim=OptimConfig(**t["optim"]),
        global_batch=t["batch"], seq_len=1,
        steps_per_epoch=steps_per_epoch,
        steps=steps_per_epoch * t["epochs_planned"], seed=run_seed,
        epoch_executor="scan", epoch_chunk=t["epoch_chunk"])


def check_flags(seed: int, n_layers: int, fraction: float) -> np.ndarray:
    """The policy of the checked steps: ``round(fraction * n_layers)`` of
    the layers quantized, which ones drawn from the seed."""
    k = int(round(fraction * n_layers))
    layers = traffic.rng(traffic.seed_words(seed)[2], 4).permutation(
        n_layers)[:k]
    flags = np.zeros((n_layers,), np.float32)
    flags[layers] = 1.0
    return flags


def reference_steps(numbers: dict, t: dict, flags, dtype=jnp.float32,
                    batch_rows=None):
    """The reference's step compiled for the checked policy."""
    from bench.reference import resnet as ref
    return ref.DPSteps(numbers, flags * (t["quant"]["fmt"] != "none"),
                       batch_rows or t["batch"], lr=float(t["optim"]["lr"]),
                       clip=float(t["dp"]["clip_norm"]),
                       noise=float(t["dp"]["noise_multiplier"]),
                       dtype=dtype)


class Session:
    """One Trainer with its compiled programs, reusable across seeds (the
    readings script drives many seeds through one session)."""

    def __init__(self, cell: dict, seed: int, smoke: bool, log):
        from repro.runtime.preemption import PreemptionHandler
        from repro.train_loop import Trainer

        self.cell, self.smoke, self.log = cell, smoke, log
        self.t = harness.traffic_params(cell, smoke)
        self.mc = harness.model_config(cell, smoke)
        self.numbers = harness.model_numbers(cell, smoke)
        words = traffic.seed_words(seed)
        # The Trainer's own randomness (Poisson sampling, DP noise, the
        # scheduler's draws) is the same for every seed, so that every
        # seed's window does the same work: DPQuant's choice of layers
        # changes the cost of a step.  The seed draws the data and the
        # checked steps.
        self.run = run_config(cell, self.mc, TRAINER_SEED, smoke)
        t = self.t
        images, labels = traffic.make_images(
            jax.random.PRNGKey(words[1]), t["dataset_size"],
            self.numbers["num_classes"], self.numbers["image_size"],
            self.numbers["in_channels"], t["image_noise"])
        size, ch = self.numbers["image_size"], self.numbers["in_channels"]
        self.data = DeviceImages(images, labels, (size, size, ch))
        self.handler = PreemptionHandler()
        self.deadline = None
        self.poll_hook = None
        handler = self.handler

        def should_preempt(step):
            if self.poll_hook is not None:
                self.poll_hook()
            return self.deadline is not None and \
                time.perf_counter() >= self.deadline
        handler.should_preempt = should_preempt
        self.trainer = Trainer(self.run, self.data, mode=t["mode"],
                               preemption=handler)
        self.spans = []
        self.chunk_steps = []
        self._wrap()
        self._init = jax.jit(self.trainer.model.init)
        self._warm()

    # ------------------------------------------------------------------ #
    def _wrap(self):
        """Benchmark spans around the Trainer's calls into each layer."""
        tr = self.trainer
        epoch_fn, train_epoch = tr.epoch_fn, tr.train_epoch
        analyze, select = tr.scheduler.maybe_analyze, tr.scheduler.select
        spans, chunk_steps = self.spans, self.chunk_steps

        def timed_epoch_fn(*a):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.epoch_program"):
                out = epoch_fn(*a)
                # the Trainer reads this chunk's losses next, which waits
                # for the same program: the span ends when the chunk does
                jax.block_until_ready(out)
            spans.append((t0, time.perf_counter()))
            chunk_steps.append(int(a[3].shape[0]))
            return out

        def spanned(name, fn):
            def inner(*a, **k):
                with jax.profiler.TraceAnnotation(name):
                    return fn(*a, **k)
            return inner

        tr.epoch_fn = timed_epoch_fn
        tr.train_epoch = spanned("bench.epoch_host", train_epoch)
        tr.sampler.sample_epoch = spanned("bench.sample",
                                          tr.sampler.sample_epoch)
        tr.scheduler.maybe_analyze = spanned("bench.analysis", analyze)
        tr.scheduler.select = spanned("bench.selection", select)

    def _fresh_state(self, key):
        p = self._init(key)
        self.trainer._place(p, None)
        return self.trainer.params, self.trainer.opt_state

    def _warm(self):
        """Run the Trainer's own epoch 0 on throwaway state until its first
        chunk boundary: every program and shape of the window compiles
        here.  Host state is put back afterwards."""
        from repro.dp.accountant import RDPAccountant
        from repro.runtime.preemption import Preempted
        tr = self.trainer
        sched = tr.scheduler.state_dict()
        sampler = tr.sampler.state_dict()
        probe = tr._probe_rng.get_state()
        self.deadline = 0.0
        try:
            tr.train(1)
        except Preempted:
            pass
        self.deadline = None
        tr.scheduler.load_state_dict(sched)
        tr.sampler.load_state_dict(sampler)
        tr._probe_rng.set_state(probe)
        tr.accountant = RDPAccountant()
        tr.step, tr.history, tr._next_epoch = 0, [], 0
        self.spans.clear()
        self.chunk_steps.clear()

    # ------------------------------------------------------------------ #
    def check_steps(self, seed: int) -> dict:
        """Drive the checked steps from ``seed`` through the epoch-chunk
        program, with the policy's share of layers quantized, and leave
        the three-step state in the Trainer for the window."""
        tr, t = self.trainer, self.t
        words = traffic.seed_words(seed)
        run_seed = words[0] % 4_000_000
        B, K = t["batch"], t["epoch_chunk"]
        rows = traffic.distinct_rows(words[2], self.data.n, K * B)
        flat = self.data.get(rows)
        batches = jax.tree_util.tree_map(
            lambda x: x.reshape((K, B) + x.shape[1:]), flat)
        seeds = np.arange(K, dtype=np.uint32) + np.uint32(run_seed)
        quant = check_flags(seed, self.mc.policy_len(),
                            t["dp"]["quant_fraction"])
        steps = t["check_steps"]
        out = {"seeds": seeds[:steps], "run_seed": run_seed,
               "images": np.asarray(batches["image"][:steps]),
               "labels": np.asarray(batches["label"][:steps])}
        out.update(self._steps_from_seed(batches, seeds, quant, run_seed))
        tr.step = K
        return out

    def _steps_from_seed(self, batches, seeds, flags, run_seed) -> dict:
        tr, t = self.trainer, self.t
        K, steps = t["epoch_chunk"], t["check_steps"]
        lr = float(t["optim"]["lr"])
        lrs_one = np.zeros((K,), np.float32)
        lrs_one[0] = lr
        lrs_three = np.zeros((K,), np.float32)
        lrs_three[:steps] = lr
        key = jax.random.PRNGKey(run_seed)
        args = (batches, jnp.asarray(seeds), jnp.asarray(flags))
        p0, _ = self._fresh_state(key)
        pa, oa = self._fresh_state(key)
        p1, _, _ = tr.epoch_fn(pa, oa, *args, jnp.asarray(lrs_one))
        g1 = harness.leaf_norms(jax.tree_util.tree_map(
            lambda a, b: (a - b) / lr, p0, p1))
        pb, ob = self._fresh_state(key)
        p3, o3, metrics = tr.epoch_fn(pb, ob, *args, jnp.asarray(lrs_three))
        change = harness.leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, p3, p0))
        tr.params, tr.opt_state = p3, o3
        return {"losses": [float(v) for v in
                           np.asarray(metrics["loss"])[:steps]],
                "grad1": g1, "change": change, "flags": flags}

    # ------------------------------------------------------------------ #
    def window(self, seconds: float, trace: bool) -> dict:
        """Measure ``Trainer.train`` for ``seconds`` (to the next chunk
        boundary); with ``trace`` profile a steady stretch of it."""
        from repro.runtime.preemption import Preempted
        tr, log = self.trainer, self.log
        prof = {"dir": None, "t0": None, "t1": None, "overhead": 0.0,
                "ann": None}
        lead, span = self.t["trace_lead_s"], self.t["trace_span_s"]

        def poll():
            now = time.perf_counter()
            if not trace:
                return
            if prof["dir"] is None and now - t_start >= lead:
                a = time.perf_counter()
                prof["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(prof["dir"])
                prof["ann"] = jax.profiler.TraceAnnotation("bench.window")
                prof["ann"].__enter__()
                prof["t0"] = time.perf_counter()
                prof["overhead"] += prof["t0"] - a
            elif prof["t0"] is not None and prof["t1"] is None \
                    and now - prof["t0"] >= span:
                prof["t1"] = time.perf_counter()
                prof["ann"].__exit__(None, None, None)
                jax.profiler.stop_trace()
                prof["overhead"] += time.perf_counter() - prof["t1"]

        self.spans.clear()
        self.chunk_steps.clear()
        self.poll_hook = poll
        tr.accountant.step = jax.profiler.annotate_function(
            tr.accountant.step, name="bench.account")
        step0 = tr.step
        snap = log.snapshot()
        t_start = time.perf_counter()
        self.deadline = t_start + seconds
        try:
            tr.train(10 ** 6)
        except Preempted:
            pass
        t_end = time.perf_counter()
        self.deadline, self.poll_hook = None, None
        compiled = log.since(snap)
        out = {"steps": tr.step - step0, "window_s": t_end - t_start,
               "counted_steps": sum(self.chunk_steps),
               "eps": tr.accountant.get_epsilon(self.t["dp"]["delta"])[0],
               "t_start": t_start, "spans": list(self.spans),
               "compiles_in_window": compiled["compiles"],
               "profiler_s": prof["overhead"], "trace": None}
        if trace and prof["dir"] is not None:
            if prof["t1"] is None:
                prof["ann"].__exit__(None, None, None)
                jax.profiler.stop_trace()
                prof["t1"] = time.perf_counter()
            out["trace"] = Trace.load(prof["dir"])
            out["trace_host"] = (prof["t0"], prof["t1"])
            shutil.rmtree(prof["dir"], ignore_errors=True)
        return out

    def free(self):
        tr = self.trainer
        tr.params = tr.opt_state = None
        self.data = None


def reference_readings(numbers: dict, t: dict, check: dict,
                       dtype=jnp.float32, batch_rows=None,
                       steps=None) -> dict:
    """The plain reference over the checked steps (see check_steps)."""
    images, labels = check["images"], check["labels"]
    if batch_rows is not None:
        images, labels = images[:, :batch_rows], labels[:, :batch_rows]
    steps = steps or reference_steps(numbers, t, check["flags"], dtype,
                                     batch_rows)
    return steps.run(check["run_seed"], images, labels, check["seeds"])


def compare(prog: dict, ref: dict) -> dict:
    """The worst relative loss gap over the steps, and the worst-leaf gaps
    of the first gradient's norm and of the change's (leaves whose
    reference gradient is under a thousandth of the median leaf's are
    left out of the change)."""
    med_g = sorted(ref["grad1"].values())[len(ref["grad1"]) // 2]
    moved = [n for n, v in ref["grad1"].items() if v >= 1e-3 * med_g]
    return {"loss": max(abs(x - y) / abs(y) for x, y in
                        zip(prog["losses"], ref["losses"])),
            "grad": harness.worst_leaf_gap(prog["grad1"], ref["grad1"]),
            "change": harness.worst_leaf_gap(prog["change"], ref["change"],
                                             keep=moved)}


def eps_gap(t: dict, eps: float, steps: int) -> float:
    """Relative gap between the accountant's ``eps`` for the window and
    the plain composition of the ``steps`` the harness counted."""
    from bench.reference import accountant as ra
    ref = ra.epsilon(ra.window_mechanisms(t, steps), t["dp"]["delta"])
    return abs(eps - ref) / ref


def checks_from(numbers: dict, limits: dict):
    """``(checks, correct)``: each compared number beside its limit."""
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in numbers.items()}
    compared = {k: c for k, c in checks.items() if c["limit"] is not None}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    return checks, correct


def run(cell: dict, *, seed: int, seconds: float, trace: bool, smoke: bool,
        devices, log) -> dict:
    t_setup = time.perf_counter()
    snap = log.snapshot()
    # The reference's step compiles in a thread while the system sets up;
    # whatever of it is left when set-up ends is waited for and kept out
    # of setup_s, and it is done before the window opens.
    t = harness.traffic_params(cell, smoke)
    numbers = harness.model_numbers(cell, smoke)
    flags = check_flags(seed, harness.model_config(cell, smoke).policy_len(),
                        t["dp"]["quant_fraction"])
    ref_steps = []
    compiler = threading.Thread(target=lambda: ref_steps.append(
        reference_steps(numbers, t, flags)))
    compiler.start()
    with harness.BackendRecord() as backends:
        ses = Session(cell, seed, smoke, log)
        check = ses.check_steps(seed)
    jax.block_until_ready(ses.trainer.params)
    t_wait = time.perf_counter()
    compiler.join()
    setup_s = t_wait - t_setup
    if not ref_steps:
        raise RuntimeError("the reference's step failed to compile")
    compile_setup = log.since(snap)

    win = ses.window(seconds, trace)
    peak = harness.memory_peak(devices)
    ses.free()

    t_ref = time.perf_counter()
    ref = reference_readings(numbers, t, check, steps=ref_steps[0])
    readings = compare(check, ref)
    readings["eps"] = eps_gap(t, win["eps"], win["counted_steps"])
    readings["window_steps"] = win["counted_steps"]
    readings["reference_s"] = time.perf_counter() - t_ref
    limits = {k: v for k, v in cell["check"]["limits"].items()}
    checks, correct = checks_from(
        {k: v for k, v in readings.items() if k in limits}, limits)
    if win["compiles_in_window"]:
        correct = False
    images = win["steps"] * t["batch"]
    record = {
        "setup_s": setup_s, "correct": correct,
        "attempted": win["steps"], "failed": 0,
        "checks": {k: c for k, c in checks.items() if c["limit"] is not None},
        "readings": readings, "memory_peak_bytes": peak,
        "compile": dict(compile_setup, compiles_in_window=win[
            "compiles_in_window"], pallas_fallbacks=backends.fallbacks()),
        "train": {"images": images, "window_s": win["window_s"],
                  "profiler_s": win["profiler_s"], "spans": win["spans"],
                  "flops_per_image": counts.resnet_train_flops(numbers),
                  "model": numbers},
        "trace": win["trace"],
        "trace_host": win.get("trace_host"),
        "peaks": (peaks.peaks(devices[0].device_kind)
                  if devices[0].platform == "tpu" else None),
    }
    return record


def readings(cell: dict, *, program: list, control: list, fault: list,
             smoke: bool, log, emit) -> None:
    """Limit readings in one process: the program's numbers on each seed
    of ``program``; the control's (the reference in bfloat16 in the
    program's place) on ``control``; the half-batch fault's (the reference
    on the first half of each batch, the mean over that half) on
    ``fault``.  ``emit(kind, seed, numbers)`` receives each row."""
    ses = Session(cell, (program or control or fault)[0], smoke, log)
    t, numbers = ses.t, ses.numbers
    for kind, seeds in (("program", program), ("control", control),
                        ("half_batch", fault)):
        for seed in seeds:
            chk = ses.check_steps(seed)
            ref = reference_readings(numbers, t, chk)
            if kind == "program":
                got = chk
            elif kind == "control":
                got = reference_readings(numbers, t, chk,
                                         dtype=jnp.bfloat16)
            else:
                got = reference_readings(numbers, t, chk,
                                         batch_rows=t["batch"] // 2)
            emit(kind, seed, compare(got, ref))
