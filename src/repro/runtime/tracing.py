"""What the program writes into a JAX profiler trace.

Take a trace around any run with ``jax.profiler.trace(directory)`` (or
``start_trace``/``stop_trace``); the names below then appear in it, on the
same clock as the device ops.

Host spans are ``jax.profiler.TraceAnnotation``s opened where the work
happens.  The profiler keeps them in memory until ``stop_trace``; with no
trace running one costs about a microsecond.  Counts ride as keyword
arguments and read back as the event's stats.

=====================  ==========================  ==========================
span                   args                        covers
=====================  ==========================  ==========================
``train.epoch``        epoch                       ``Trainer.train_epoch``
``train.analysis``     epoch                       probe batches and
                                                   ``scheduler.maybe_analyze``
``train.select``       quantized                   ``scheduler.select``
``train.chunk``        step, k                     one epoch-chunk program
                                                   (scan executor) or one
                                                   step (loop executor), with
                                                   the children below
``train.sample``                                   Poisson sampling
``train.gather``                                   ``dataset.get`` and the
                                                   reshape to ``(k, B, ...)``
``train.feed``                                     seeds and learning rates
                                                   put on the device
``train.dispatch``                                 the step or chunk call
``train.wait``                                     reading the losses: the
                                                   host blocked on the device
``train.account``                                  ``accountant.step``
``train.poll``                                     the preemption poll
``train.epoch_end``                                epsilon, evaluation,
                                                   checkpoint
``serve.admit``        rid, prompt_len, bucket,    one admitted request
                       wait_ms                     (prompt_len counts a
                                                   replayed prefix; wait_ms
                                                   from arrival), with the
                                                   three children below
``serve.prefill``                                  padding and the prefill
``serve.cache_write``                              the slot-cache write
``serve.first_token``                              the first token's draw and
                                                   its host sync
``serve.tick``         tick, active, queued        one decode tick, with the
                                                   four children below
``serve.upload``                                   slot vectors put on the
                                                   device after admission or
                                                   retirement
``serve.dispatch``                                 the decode step call
``serve.wait``                                     copying the tokens back:
                                                   the host blocked on the
                                                   device
``serve.record``                                   per-slot token bookkeeping
``serve.idle``                                     the engine sleeping until
                                                   the next arrival
``host.gc``            generation, collected       a Python garbage
                                                   collection
=====================  ==========================  ==========================

Device scopes are regions of the step programs, made with :func:`scope`.
Each op a region emits carries ``jit(<scope>)`` in its framework name (the
``tf_op`` of the op in the trace), under whatever transforms wrap it
(``transpose(jvp(jit(quantize)))``, ``vmap``, a scan's ``while/body``):

====================  ====================================================
scope                 region
====================  ====================================================
``ghost_norm_pass``   ghost clipping pass 1: taps, unfolds, per-example
                      norms (``dp/ghost.py``)
``ghost_grad_pass``   ghost clipping pass 2: the reweighted backward
``dp_noise``          Gaussian noise on the clipped sum
``opt_update``        the optimizer update
``quantize``          a quantizer call: key folding, random bits, max-abs
                      scale, kernel (``quant/fake_quant.py``)
``attn_proj``         decode: attention norm, QKV and output projections
``kv_write``          decode: quantizing and writing the new K/V rows
``decode_attn``       decode: attention over the cache with its operand
                      layouts and pads
``mlp``               decode: the MLP
``lm_head``           decode: the logits and the token's draw
====================  ====================================================
"""
from __future__ import annotations

import gc
from typing import Callable, List

import jax

# garbage collection is process-wide, and so is the span open across it
_gc_open: List[jax.profiler.TraceAnnotation] = []


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        span = jax.profiler.TraceAnnotation("host.gc",
                                            generation=info["generation"])
        span.__enter__()
        _gc_open.append(span)
    elif _gc_open:
        span = _gc_open.pop()
        span.set_metadata(collected=info["collected"])
        span.__exit__(None, None, None)


def install_gc_span() -> None:
    """Write a ``host.gc`` span around every garbage collection of this
    process; calling it again does nothing."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)


def scope(name: str, fn: Callable) -> Callable:
    """``fn`` as a jitted function named ``name``, for use inside a traced
    program: XLA inlines it, and its ops carry ``jit(<name>)`` in their
    framework names.  Unlike a ``jax.named_scope``, which is debug
    information stripped from the persistent compilation cache's key, the
    name is part of the lowered program, so no cached executable lacks it.
    Pass arrays positionally; close over everything else."""
    def call(*args):
        return fn(*args)
    call.__name__ = call.__qualname__ = name
    return jax.jit(call)
