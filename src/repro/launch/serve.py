"""Serving CLI: continuous-batching engine or the oneshot reference driver.

    # continuous batching (default engine)
    PYTHONPATH=src python -m repro.launch.serve --arch gemma-7b --smoke \
        --engine continuous --slots 4 --requests 8 --prompt-len 32 --gen 16

    # legacy oneshot driver (fixed batch, lockstep decode) — kept as the
    # equivalence reference for the engine
    PYTHONPATH=src python -m repro.launch.serve --arch gemma-7b --smoke \
        --engine oneshot --batch 4 --prompt-len 32 --gen 16

Quantized serving: ``--quant-fmt luq_fp4 --backend pallas`` routes the
logits head through the quantizer-backend dispatcher's fused
quantize-matmul (``repro.quant.backend``) on either engine;
``REPRO_QUANT_BACKEND`` overrides ``--backend``.  Independently,
``--kv-fmt int8|luq_fp4`` stores the KV cache itself quantized (codes +
per-row bf16 scales) and decodes through the dispatched ``decode_attn``
op — fused dequant-attention on the pallas backend.  See docs/SERVING.md
for the engine's slot lifecycle and docs/QUANTIZATION.md for the
dispatch rules.

The engine logic lives in ``repro.serve``; this module only parses flags,
builds the model, and prints results.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import (DPConfig, OptimConfig, QuantConfig, RunConfig,
                          ServeConfig)
from repro.configs import get_config, get_smoke_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model
from repro.runtime.faults import FaultPlan
from repro.runtime.supervisor import ServeSupervisor, run_supervised
from repro.serve import ContinuousEngine, build_oneshot_fns, oneshot_generate


def _random_prompt(key, length: int, vocab: int) -> np.ndarray:
    return np.asarray(jax.random.randint(key, (length,), 0, vocab),
                      np.int32)


def _random_batch(model, key, batch: int, prompt_len: int) -> dict:
    """Synthetic inputs for every key the model's batch_spec declares
    (int32 -> token ids, float -> gaussian; vlm/encdec need both)."""
    out = {}
    for k, sds in model.batch_spec(batch, prompt_len).items():
        if sds.dtype == jnp.int32:
            out[k] = jax.random.randint(jax.random.fold_in(key, 1),
                                        sds.shape, 0,
                                        model.config.vocab_size)
        else:
            out[k] = jax.random.normal(jax.random.fold_in(key, 2),
                                       sds.shape, sds.dtype)
    return out


def run_oneshot(model, params, mesh, run, args) -> None:
    """Legacy path: one fixed batch, synchronous prefill, lockstep decode."""
    cache_len = args.prompt_len + args.gen
    prefill, decode = build_oneshot_fns(model, run, mesh, args.batch,
                                        cache_len, kv_fmt=args.kv_fmt)
    key = jax.random.PRNGKey(args.seed)
    batch = _random_batch(model, key, args.batch, args.prompt_len)
    gen, timings = oneshot_generate(prefill, decode, params, batch, args.gen,
                                    temperature=args.temperature,
                                    base_key=key)
    print(f"prefill: {timings['prefill_s']*1e3:.1f} ms "
          f"for {args.batch}x{args.prompt_len}")
    print(f"decode:  {timings['decode_s']*1e3:.1f} ms for {args.gen-1} steps "
          f"({(args.gen-1)*args.batch/max(timings['decode_s'],1e-9):.1f} "
          f"tok/s)")
    print("generated token ids:\n", gen)


def run_continuous(model, params, args) -> dict:
    """Continuous-batching path: slot-pool engine with FCFS admission.

    Returns the engine's ``{request_id: RequestResult}``.

    With ``--fault-seed`` the run goes through the supervisor under a
    seeded ``FaultPlan`` (chaos mode): faults are injected at their
    scheduled counters, recovery counters are printed, and the fired-event
    log lands in ``--fault-log`` for inspection.
    """
    serve = ServeConfig(max_slots=args.slots,
                        max_seq=args.prompt_len + args.gen,
                        max_new_tokens=args.gen,
                        temperature=args.temperature, seed=args.seed,
                        kv_fmt=args.kv_fmt,
                        deadline_s=args.deadline,
                        max_queue=args.max_queue)
    faults = None
    if args.fault_seed is not None:
        faults = FaultPlan.generate(
            args.fault_seed,
            kinds=("prefill_fail", "decode_fail", "slot_corrupt",
                   "clock_freeze"),
            horizon=max(2, args.gen), n_slots=args.slots)
    engine = ContinuousEngine(model, params, serve, faults=faults)
    supervisor = (ServeSupervisor(engine, faults=faults)
                  if faults is not None else None)
    key = jax.random.PRNGKey(args.seed)
    n_requests = args.requests or args.slots
    for i in range(n_requests):
        engine.submit(_random_prompt(jax.random.fold_in(key, 1 + i),
                                     args.prompt_len,
                                     model.config.vocab_size),
                      max_new_tokens=args.gen)
    results = (run_supervised(engine) if supervisor is not None
               else engine.run())
    summary = engine.metrics.summary()
    print(f"served {summary['n_requests']} requests / "
          f"{summary['total_new_tokens']} new tokens in "
          f"{summary['run_wall_s']*1e3:.1f} ms "
          f"({summary['tokens_per_sec']:.1f} tok/s, "
          f"{summary['decode_ticks']} decode ticks)")
    print(f"latency p50/p99: {summary['latency_p50_s']*1e3:.1f}/"
          f"{summary['latency_p99_s']*1e3:.1f} ms; "
          f"ttft p50: {summary['ttft_p50_s']*1e3:.1f} ms")
    if faults is not None or summary["shed"] or summary["deadline_missed"]:
        print(f"recovery: {summary['faults_injected']} faults injected, "
              f"{summary['retried']} retries, {summary['recovered']} "
              f"recovered, {summary['shed']} shed, "
              f"{summary['deadline_missed']} deadline-missed, "
              f"{summary['degraded_events']} degraded events")
    if faults is not None and args.fault_log:
        with open(args.fault_log, "w") as f:
            f.write(faults.log_json(extra={"summary": summary}))
        print(f"fault log written to {args.fault_log}")
    for rid in sorted(results):
        r = results[rid]
        tag = "" if r.status == "ok" else f" [{r.status}]"
        print(f"request {rid}{tag}: {r.tokens.tolist()}")
    return results


def main(argv=None):
    """Parse flags, build the model, and dispatch to the chosen engine.

    Returns the continuous engine's results (``None`` for oneshot).
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", default="continuous",
                    choices=["continuous", "oneshot"],
                    help="continuous = slot-pool engine (repro.serve); "
                         "oneshot = legacy fixed-batch lockstep driver")
    ap.add_argument("--batch", type=int, default=4,
                    help="oneshot: fixed batch size")
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous: slot-pool size (decode batch width)")
    ap.add_argument("--requests", type=int, default=0,
                    help="continuous: number of requests (0 = --slots)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--quant-fmt", default="none",
                    help="logits-head quantization format for serving "
                         "(none | luq_fp4 | int4 | fp8_e4m3 | fp8_e5m2 | "
                         "bf16)")
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"],
                    help="quantizer backend for --quant-fmt "
                         "(REPRO_QUANT_BACKEND overrides)")
    ap.add_argument("--kv-fmt", default="none",
                    choices=["none", "int8", "luq_fp4"],
                    help="KV-cache storage format (both engines): "
                         "quantized caches store codes + per-row bf16 "
                         "scales and attend through the dispatched "
                         "decode_attn op (docs/SERVING.md)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline", type=float, default=None,
                    help="continuous: per-request deadline in seconds from "
                         "arrival (expired requests retire with partial "
                         "results, status timed_out)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="continuous: bound on waiting requests; overflow "
                         "is shed at submit (0 = unbounded)")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="continuous: run under a seeded FaultPlan via the "
                         "supervisor (chaos mode)")
    ap.add_argument("--fault-log", default=None,
                    help="chaos mode: write the fired-fault JSON log here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    if not cfg.has_decoder:
        raise SystemExit(f"{args.arch} has no decoder; nothing to serve")
    quant = QuantConfig(fmt=args.quant_fmt, backend=args.backend)
    model = build_model(cfg, quant)
    params = model.init(jax.random.PRNGKey(args.seed))

    engine = args.engine
    if engine == "continuous" and model.decode_slots is None:
        # only the dense transformer implements slot decoding so far;
        # other decoder families keep working through the legacy driver
        print(f"note: {cfg.family!r} has no continuous-batching support "
              "yet; falling back to --engine oneshot")
        engine = "oneshot"

    if engine == "oneshot":
        mesh = make_host_mesh()
        run = RunConfig(model=cfg, quant=quant,
                        dp=DPConfig(enabled=False), optim=OptimConfig())
        run_oneshot(model, params, mesh, run, args)
        return None
    return run_continuous(model, params, args)


if __name__ == "__main__":
    main()
