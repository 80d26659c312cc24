"""Spans the program writes into a profiler trace, and the device scopes of
its step programs (repro.runtime.tracing)."""
import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (DPConfig, ModelConfig, OptimConfig, QuantConfig,
                          RunConfig, ServeConfig)
from repro.data.synthetic import ImageClassDataset
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_train_setup
from repro.models.registry import build_model
from repro.runtime import tracing
from repro.serve import ContinuousEngine
from repro.train_loop import EpochStats, Trainer

TRAIN_SCOPES = ("ghost_norm_pass", "ghost_grad_pass", "dp_noise",
                "opt_update", "quantize")
DECODE_SCOPES = ("attn_proj", "kv_write", "decode_attn", "mlp", "lm_head")


def program_spans(directory):
    """``[(name, start_ns, end_ns, args)]`` of the program's host spans in
    the trace written under ``directory``, in order of start."""
    (path,) = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("train.", "serve.", "host.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def small_run(fmt="none", **dp):
    model = ModelConfig(name="cnn", family="resnet", resnet_blocks=(1, 1),
                        num_classes=8, image_size=8,
                        compute_dtype="float32")
    return RunConfig(
        model=model, quant=QuantConfig(fmt=fmt),
        dp=DPConfig(enabled=True, clip_norm=1.0, noise_multiplier=1.0,
                    microbatch_size=16, quant_fraction=0.6,
                    analysis_interval=1, analysis_reps=1, **dp),
        optim=OptimConfig(name="sgd", lr=0.5),
        global_batch=16, steps_per_epoch=4, steps=100, seed=0,
        epoch_executor="scan", epoch_chunk=2)


def tiny_lm():
    cfg = ModelConfig(name="lm-tiny", family="dense_lm", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                      d_ff=64, vocab_size=64, compute_dtype="float32",
                      remat=False)
    model = build_model(cfg, QuantConfig(fmt="none", backend="pallas"))
    return model, model.init(jax.random.PRNGKey(0))


def test_trainer_writes_nested_spans(tmp_path):
    ds = ImageClassDataset(n=256, num_classes=8, image_size=8, noise=0.4)
    tr = Trainer(small_run(), ds)
    tr.train(1)                                 # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        tr.train(1)
    spans = program_spans(str(tmp_path))
    (epoch,) = [s for s in spans if s[0] == "train.epoch"]
    assert epoch[3] == {"epoch": 1}
    for name in ("train.analysis", "train.select", "train.epoch_end"):
        (span,) = [s for s in spans if s[0] == name]
        assert inside(span, epoch)
    (select,) = [s for s in spans if s[0] == "train.select"]
    assert select[3] == {"quantized": len(tr.scheduler.current)}
    chunks = [s for s in spans if s[0] == "train.chunk"]
    assert [c[3] for c in chunks] == [{"step": 4, "k": 2},
                                      {"step": 6, "k": 2}]
    children = ("train.sample", "train.gather", "train.feed",
                "train.dispatch", "train.wait", "train.account",
                "train.poll")
    for chunk in chunks:
        assert inside(chunk, epoch)
        got = [s[0] for s in spans if s[0] in children and inside(s, chunk)]
        assert got == list(children)


def test_engine_writes_admit_and_tick_spans(tmp_path):
    model, params = tiny_lm()
    eng = ContinuousEngine(model, params, ServeConfig(
        max_slots=2, max_seq=32, max_new_tokens=3, kv_fmt="int8"))
    eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
    eng.submit(np.arange(1, 10, dtype=np.int32), max_new_tokens=3)
    eng.run()                                   # compile outside the trace
    eng.reset()
    eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
    eng.submit(np.arange(1, 10, dtype=np.int32), max_new_tokens=3)
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    spans = program_spans(str(tmp_path))
    admits = [s for s in spans if s[0] == "serve.admit"]
    assert [(a[3]["rid"], a[3]["prompt_len"], a[3]["bucket"])
            for a in admits] == [(0, 5, 8), (1, 9, 16)]
    assert all(a[3]["wait_ms"] >= 0 for a in admits)
    for admit in admits:
        got = [s[0] for s in spans if s[0].startswith("serve.")
               and inside(s, admit) and s is not admit]
        assert got == ["serve.prefill", "serve.cache_write",
                       "serve.first_token"]
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert [t[3] for t in ticks] == [{"tick": 0, "active": 2, "queued": 0},
                                     {"tick": 1, "active": 2, "queued": 0}]
    got = [s[0] for s in spans if s[0].startswith("serve.")
           and inside(s, ticks[0]) and s is not ticks[0]]
    assert got == ["serve.upload", "serve.dispatch", "serve.wait",
                   "serve.record"]


def test_gc_span_installs_once(tmp_path):
    tracing.install_gc_span()
    tracing.install_gc_span()
    assert gc.callbacks.count(tracing._gc_span) == 1
    with jax.profiler.trace(str(tmp_path)):
        gc.collect()
    collections = [s for s in program_spans(str(tmp_path))
                   if s[0] == "host.gc"]
    assert collections
    assert set(collections[-1][3]) == {"generation", "collected"}


def test_train_step_scopes_are_in_the_cache_key():
    run = small_run("luq_fp4", grad_mode="ghost")
    model = build_model(run.model, run.quant)
    setup = build_train_setup(model, run, make_host_mesh())
    text = jax.jit(setup.step_fn).lower(*setup.abstract_args).as_text(
        debug_info=False)
    for name in TRAIN_SCOPES:
        assert f"@{name}" in text, name


def test_decode_step_scopes_are_in_the_cache_key():
    model, params = tiny_lm()
    eng = ContinuousEngine(model, params, ServeConfig(
        max_slots=2, max_seq=32, kv_fmt="int8"))
    vec = jnp.zeros((2,), jnp.int32)
    text = eng._step.lower(params, eng.cache, vec, vec.astype(bool),
                           vec).as_text(debug_info=False)
    for name in DECODE_SCOPES:
        assert f"@{name}" in text, name


def test_history_with_steps_s_restores(tmp_path):
    ds = ImageClassDataset(n=64, num_classes=8, image_size=8, noise=0.4)
    tr = Trainer(small_run(), ds, checkpoint_dir=str(tmp_path))
    tr.history = [EpochStats(epoch=0, loss=1.5, eps=0.5,
                             analysis_eps_fraction=0.1, quantized_layers=2,
                             wall_s=3.0)]
    save = tr.ckpt.save

    def with_steps_s(step, tree, aux):
        aux["history"] = [dict(h, steps_s=2.5) for h in aux["history"]]
        save(step, tree, aux)

    tr.ckpt.save = with_steps_s
    tr.save(0)
    tr.ckpt.wait()
    fresh = Trainer(small_run(), ds, checkpoint_dir=str(tmp_path))
    assert fresh.restore_latest() == 0
    assert fresh.history == tr.history


@pytest.mark.parametrize("scope", ["quantize", "ghost_norm_pass"])
def test_scope_keeps_results(scope):
    def f(x, y):
        return jnp.tanh(x) @ y

    x = jnp.linspace(-1.0, 1.0, 12).reshape(3, 4)
    y = jnp.linspace(0.5, 2.0, 8).reshape(4, 2)
    got = jax.jit(jax.grad(lambda a: tracing.scope(scope, f)(a, y).sum()))(x)
    want = jax.jit(jax.grad(lambda a: f(a, y).sum()))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_chip_smoke_prints_epoch_stats():
    """The on-chip check prints each epoch through ``epoch_line``: it must
    read only fields that ``EpochStats`` has."""
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    h = EpochStats(epoch=1, loss=1.5, eps=0.5, analysis_eps_fraction=0.1,
                   quantized_layers=2, accuracy=0.25, wall_s=3.0)
    assert chip_smoke.epoch_line(h) == (
        "epoch=1 loss=1.5 eps=0.5 quantized_layers=2 wall_s=3.0 acc=0.25")
