#!/usr/bin/env python3
"""Bring-up check of the main paths on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # one host with four chips

One chip runs three phases through the normal entry points:

train    ``repro.launch.train.main`` on ResNet18 at its published width:
         dpquant, luq_fp4, DP-SGD, batch 256, microbatch 16, scan executor,
         2 epochs x 3 steps, once each with ``--backend ref``,
         ``--backend pallas`` and ``--backend pallas --grad-mode ghost``.
kernels  each Pallas kernel at one real width against its jnp oracle, fed
         the same uniform draws.
serve    ``repro.launch.serve.main`` with the continuous engine on
         stablelm-3b at full width: int8 KV cache, pallas backend, 4 slots,
         8 greedy requests, prompt 32, 16 new tokens.  Its prefill and
         decode programs are compiled first from shapes alone, and their
         memory analysis is checked against the device's memory.

``--chips 4`` runs only one unquantized ResNet18 ghost DP step on a 4x1
data mesh and the same step on one device, and compares the parameters.

Each phase prints what it measured on lines of its own.  The last line of
standard output is ``{"ok": true, "device": {...}}``; it is printed only
when every check passed.  Without a TPU the script exits with code 2
before any phase runs.  Compiled programs go to the persistent compilation
cache (``repro.launch.compile_cache``), where a later run can find them.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TRAIN_ARGV = ["--arch", "resnet18", "--mode", "dpquant", "--fmt", "luq_fp4",
              "--batch", "256", "--microbatch", "16", "--executor", "scan",
              "--epochs", "2", "--steps-per-epoch", "3"]
TRAIN_RUNS = {
    "ref": ["--backend", "ref"],
    "pallas": ["--backend", "pallas"],
    "pallas-ghost": ["--backend", "pallas", "--grad-mode", "ghost"],
}
SERVE_ARCH, SLOTS, REQUESTS, PROMPT, GEN = "stablelm-3b", 4, 8, 32, 16
SERVE_ARGV = ["--arch", SERVE_ARCH, "--engine", "continuous",
              "--kv-fmt", "int8", "--backend", "pallas",
              "--slots", str(SLOTS), "--requests", str(REQUESTS),
              "--prompt-len", str(PROMPT), "--gen", str(GEN)]

# Kernel-vs-oracle tolerances.  Elementwise kernels evaluate the oracle's
# own expressions, so they may differ only where the TPU's vector unit and
# XLA round a division or log2 differently and the uniform draw (or a
# round-half tie) falls between the two results: allow 1e-5 of the
# elements, a few in a million-element tensor.  Kernels with a
# contraction may run it as bf16 passes on the MXU; the oracle runs at
# "highest" precision, and one bf16 rounding of each operand bounds the
# relative error near 2**-8, so allow 1e-2.  A wrong uniform tile or scale
# gives a relative error of order 0.1-1, far above either bound.
ELEMENTWISE_MISMATCH = 1e-5
CONTRACTION_REL = 1e-2
REDUCTION_REL = 1e-5          # clip: f32 sums only, no MXU


# One real width per kernel (ResNet18 at batch 256 / microbatch 16, and
# stablelm-3b decode over a 2048-token cache with 8 slots).
KERNEL_WIDTHS = {
    "luq_quant": (9216, 256),        # the 3x3x512x512 last-stage conv weight
    "quant_matmul": (256, 4608, 512),  # last-stage im2col GEMM, 16 examples
    "clip_rows": 16,                 # x every ResNet18 parameter
    "ghost_norm": (256, 1280),       # 16x16 outputs, 3x3x128 patches
    "decode_slots": 8,
    "decode_seq": 2048,
}


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileLog:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events, read as differences between snapshots."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits

    def since(self, snap) -> str:
        s, c, h = snap
        return (f"compile_s={self.seconds - s:.3f} "
                f"compiles={self.compiles - c} cache_hits={self.cache_hits - h}")


class BackendRecord:
    """Every ``repro.quant.backend.get_impl`` resolution made while active:
    the dispatcher falls back to ``ref`` silently when a backend lacks a
    format, which would hide the kernels from this check."""

    def __init__(self):
        from repro.quant import backend as qb
        self._qb, self._orig, self.calls = qb, qb.get_impl, []

    def __enter__(self):
        def recording(op, fmt, backend=None):
            impl, actual = self._orig(op, fmt, backend)
            self.calls.append((op, fmt, self._qb.resolve_backend(backend),
                               actual))
            return impl, actual
        self._qb.get_impl = recording
        return self

    def __exit__(self, *exc):
        self._qb.get_impl = self._orig

    def check_pallas(self, fmt: str) -> int:
        mine = [c for c in self.calls if c[1] == fmt and c[2] == "pallas"]
        check(bool(mine), f"no {fmt} dispatch was asked for pallas")
        wrong = sorted({c for c in mine if c[3] != "pallas"})
        check(not wrong, f"{fmt} dispatches fell back to ref: {wrong}")
        return len(mine)


# --------------------------------------------------------------------------- #
# train
# --------------------------------------------------------------------------- #
def epoch_line(h) -> str:
    """One ``repro.train_loop.EpochStats`` as ``key=value`` words."""
    return (f"epoch={h.epoch} loss={h.loss!r} eps={h.eps!r} "
            f"quantized_layers={h.quantized_layers} wall_s={h.wall_s!r} "
            f"acc={h.accuracy!r}")


def train_phase(log: CompileLog) -> None:
    from repro.launch import train
    for name, extra in TRAIN_RUNS.items():
        snap = log.snapshot()
        with BackendRecord() as rec:
            tr = train.main(TRAIN_ARGV + extra)
        hist = tr.history
        for h in hist:
            print(f"train {name} {epoch_line(h)}")
        print(f"train {name} {log.since(snap)}")
        check(len(hist) == 2, f"{name}: {len(hist)} epochs ran, not 2")
        check(all(math.isfinite(h.loss) for h in hist),
              f"{name}: non-finite loss")
        check(0 < hist[0].eps < hist[1].eps,
              f"{name}: epsilon did not grow: {[h.eps for h in hist]}")
        check(all(h.quantized_layers > 0 for h in hist),
              f"{name}: no layer was quantized")
        if "pallas" in name:
            n = rec.check_pallas("luq_fp4")
            print(f"train {name} luq_fp4_dispatches_on_pallas={n}")
        del tr
        gc.collect()


# --------------------------------------------------------------------------- #
# kernels
# --------------------------------------------------------------------------- #
def _rel(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def _mismatch(got, want, atol=0.0) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.mean(np.abs(got - want) > atol))


def kernel_phase(log: CompileLog) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.config import QuantConfig
    from repro.configs import get_config
    from repro.dp.ghost import _matpair_sq_norm
    from repro.kernels import ref
    from repro.kernels.ghost_norm import ghost_norm_gram
    from repro.kernels.luq_quant import luq_quant_2d
    from repro.kernels.per_sample_clip import per_sample_clip
    from repro.kernels.quant_matmul import quant_matmul
    from repro.models.registry import build_model
    from repro.quant import backend as qb

    key = jax.random.PRNGKey(0)
    normal = lambda i, shape: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape, jnp.float32)
    uniform = lambda i, shape: jax.random.uniform(  # noqa: E731
        jax.random.fold_in(key, 100 + i), shape, jnp.float32)
    amax = lambda x: jnp.max(jnp.abs(x))  # noqa: E731
    results = []

    def report(name, shape, metric, value, tol):
        ok = value <= tol
        results.append((name, ok))
        print(f"kernel {name} shape={shape} {metric}={value!r} tol={tol!r} "
              f"ok={ok}")

    snap = log.snapshot()
    with jax.default_matmul_precision("highest"):
        w = KERNEL_WIDTHS
        x, u = normal(0, w["luq_quant"]), uniform(0, w["luq_quant"])
        got = luq_quant_2d(x, u, amax(x))
        want = ref.luq_quant_ref(x, u, amax(x))
        report("luq_quant", x.shape, "mismatch_frac",
               _mismatch(got, want, 1e-6 * float(amax(x))),
               ELEMENTWISE_MISMATCH)

        m, k, n = w["quant_matmul"]
        a, b = normal(1, (m, k)), normal(2, (k, n))
        ua, ub = uniform(1, a.shape), uniform(2, b.shape)
        got = quant_matmul(a, b, ua, ub, amax(a), amax(b))
        want = ref.quant_matmul_ref(a, b, ua, ub, amax(a), amax(b))
        report("quant_matmul", (m, k, n), "rel_err", _rel(got, want),
               CONTRACTION_REL)

        model = build_model(get_config("resnet18"), QuantConfig())
        d = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(
            jax.eval_shape(model.init, key)))
        d += (-d) % 512
        g = normal(3, (w["clip_rows"], d)) * 1e-3
        got_sum, got_norms = per_sample_clip(g, 1.0)
        want_sum, want_norms = ref.per_sample_clip_ref(g, 1.0)
        report("per_sample_clip", g.shape, "rel_err",
               max(_rel(got_sum, want_sum), _rel(got_norms, want_norms)),
               REDUCTION_REL)
        del g

        xg, gg = normal(4, w["ghost_norm"]), normal(5, w["ghost_norm"])
        ux, ug = uniform(4, xg.shape), uniform(5, gg.shape)
        ax, ag = amax(xg).reshape(1, 1), amax(gg).reshape(1, 1)
        got = ghost_norm_gram(xg, ux, gg, ug, ax, ag)[0, 0]
        want = _matpair_sq_norm(ref.luq_quant_ref(xg, ux, ax[0, 0]),
                                ref.luq_quant_ref(gg, ug, ag[0, 0]))
        report("ghost_norm", xg.shape, "rel_err", _rel(got, want),
               CONTRACTION_REL)

        # kv_quant + decode_attn through the dispatcher, at the served
        # model's KV heads and head_dim
        cfg = get_config(SERVE_ARCH)
        kv, hd = cfg.n_kv_heads, cfg.head_dim
        s, slots = w["decode_seq"], w["decode_slots"]
        for fmt in ("int8", "luq_fp4"):
            k_rows = normal(6, (slots, kv, s, hd))
            v_rows = normal(7, (slots, kv, s, hd))
            kvq_ref, _ = qb.get_kv_quant(fmt, "ref")
            kvq_pal, be = qb.get_kv_quant(fmt, "pallas")
            check(be == "pallas", f"kv_quant {fmt} resolved to {be}")
            kc, ks = kvq_pal(k_rows)
            kc_ref, ks_ref = kvq_ref(k_rows)
            report(f"kv_quant_{fmt}", k_rows.shape, "mismatch_frac",
                   max(_mismatch(kc, kc_ref), _mismatch(ks, ks_ref)),
                   ELEMENTWISE_MISMATCH)
            vc, vs = kvq_ref(v_rows)
            q = normal(8, (slots, kv, hd))
            pos = jax.random.randint(jax.random.fold_in(key, 9), (slots,),
                                     0, s)
            attn_ref, _ = qb.get_decode_attn(fmt, "ref")
            attn_pal, be = qb.get_decode_attn(fmt, "pallas")
            check(be == "pallas", f"decode_attn {fmt} resolved to {be}")
            kw = dict(n_kv=kv, scale=hd ** -0.5)
            got = attn_pal(q, kc_ref, vc, ks_ref, vs, pos, **kw)
            want = attn_ref(q, kc_ref, vc, ks_ref, vs, pos, **kw)
            report(f"decode_attn_{fmt}", (slots, kv, s, hd), "rel_err",
                   _rel(got, want), CONTRACTION_REL)
    print(f"kernel {log.since(snap)}")
    bad = [n for n, ok in results if not ok]
    check(not bad, f"kernel parity failed: {bad}")


# --------------------------------------------------------------------------- #
# serve
# --------------------------------------------------------------------------- #
def device_memory() -> dict:
    import jax
    return jax.devices()[0].memory_stats()


def serve_rehearsal(log: CompileLog) -> None:
    """Compile the engine's prefill and decode programs from shapes alone
    and check that each fits the device before 11 GB of weights exist."""
    import jax
    import jax.numpy as jnp
    from repro.config import QuantConfig, ServeConfig
    from repro.configs import get_config
    from repro.models.registry import build_model
    from repro.serve import ContinuousEngine

    snap = log.snapshot()
    model = build_model(get_config(SERVE_ARCH),
                        QuantConfig(fmt="none", backend="pallas"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    engine = ContinuousEngine(model, params, ServeConfig(
        max_slots=SLOTS, max_seq=PROMPT + GEN, max_new_tokens=GEN,
        kv_fmt="int8"))
    # the real run's arguments, placement included: the prefill cache is
    # written into the slot pool, and the pool then feeds the decode step
    prefill = engine._prefill.lower(
        params, {"tokens": jax.ShapeDtypeStruct((1, PROMPT), jnp.int32)},
        PROMPT).compile()
    pcache = prefill.out_info[1]
    pool = engine._write.lower(engine.cache, pcache, 0).compile().out_info
    vec = lambda dt: jax.ShapeDtypeStruct(  # noqa: E731
        (SLOTS,), dt, sharding=engine._replicated)
    decode = engine._step.lower(params, pool, vec(jnp.int32),
                                vec(jnp.bool_), vec(jnp.int32)).compile()
    limit = device_memory()["bytes_limit"]
    for name, compiled in (("prefill", prefill), ("decode", decode)):
        ma = compiled.memory_analysis()
        need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(f"serve rehearsal {name} argument_bytes="
              f"{ma.argument_size_in_bytes} output_bytes="
              f"{ma.output_size_in_bytes} temp_bytes={ma.temp_size_in_bytes} "
              f"alias_bytes={ma.alias_size_in_bytes} need_bytes={need} "
              f"device_bytes_limit={limit}")
        check(need <= limit, f"serve {name} needs {need} bytes of {limit}")
    print(f"serve rehearsal {log.since(snap)}")


def serve_phase(log: CompileLog) -> None:
    import jax
    from repro.configs import get_config
    from repro.launch import serve

    serve_rehearsal(log)
    snap = log.snapshot()
    t0 = time.time()
    with BackendRecord() as rec:
        results = serve.main(SERVE_ARGV)
    print(f"serve wall_s={time.time() - t0!r} {log.since(snap)}")
    stats = device_memory()
    print(f"serve peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}")
    n = rec.check_pallas("int8")
    print(f"serve int8_dispatches_on_pallas={n}")
    vocab = get_config(SERVE_ARCH).vocab_size
    check(len(results) == REQUESTS,
          f"serve: {len(results)} of {REQUESTS} requests finished")
    for rid, r in results.items():
        toks = r.tokens.tolist()
        check(r.status == "ok", f"request {rid} ended {r.status}")
        check(len(toks) == GEN, f"request {rid}: {len(toks)} tokens")
        check(all(0 <= t < vocab for t in toks),
              f"request {rid}: token outside the vocabulary: {toks}")


# --------------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------------- #
def sharded_ghost_phase(log: CompileLog) -> None:
    """One ghost DP step on a 4x1 data mesh (the shard_map driver that
    ``launch/steps.py`` selects on data meshes) against the same step, batch
    and seed on one device."""
    import jax
    import numpy as np
    from jax.sharding import AxisType
    from repro.config import DPConfig, OptimConfig, QuantConfig, RunConfig
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import make_dataset
    from repro.train_loop import Trainer

    cfg = get_config("resnet18")
    # unquantized: this phase checks the driver's per-shard taps and its
    # one psum; quantized ghost steps run on one chip above, and against
    # the sharded driver in tests/test_ghost_sharded.py
    run = RunConfig(
        model=cfg, quant=QuantConfig(fmt="none"),
        dp=DPConfig(microbatch_size=16, grad_mode="ghost"),
        optim=OptimConfig(name="sgd", lr=0.5), global_batch=256,
        steps_per_epoch=1, steps=1, seed=0)
    meshes = {
        "4x1": make_host_mesh(),
        "1x1": jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2,
                             devices=jax.devices()[:1]),
    }
    check(meshes["4x1"].devices.shape == (4, 1),
          f"data mesh is {meshes['4x1'].devices.shape}, not (4, 1)")
    params = {}
    for name, mesh in meshes.items():
        snap = log.snapshot()
        tr = Trainer(run, make_dataset(cfg, 4096, run.seq_len, run.seed),
                     mode="static", mesh=mesh)
        h = tr.train(1)[-1]
        print(f"ghost4 mesh={name} {epoch_line(h)} {log.since(snap)}")
        params[name] = jax.tree_util.tree_map(np.asarray, tr.params)
    worst = 0.0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params["1x1"]),
                            jax.tree_util.tree_leaves(params["4x1"])):
        # fp32 tolerance of the CPU sharded-ghost parity test
        err = np.abs(a - b) - (2e-4 + 2e-4 * np.abs(a))
        worst = max(worst, float(np.max(np.abs(a - b))))
        check(np.all(err <= 0), f"params differ at {jax.tree_util.keystr(path)}")
    print(f"ghost4 params_max_abs_diff={worst!r} tol=2e-4+2e-4*|p|")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {devices[0].device_kind} x{len(devices)}")
    log = CompileLog()
    phases = ([sharded_ghost_phase] if args.chips == 4
              else [train_phase, kernel_phase, serve_phase])
    try:
        for phase in phases:
            t0 = time.time()
            phase(log)
            print(f"phase {phase.__name__} wall_s={time.time() - t0!r}",
                  flush=True)
    except Exception:  # noqa: BLE001 - report and fail, never pass
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
