"""Each fault a cell can have, planted under the timed path, turns the
run's ``correct`` false.  Runs the cells' drivers at their rehearsal
sizes on the CPU (``--smoke``), with the limits the cells commit.

Training faults: a step that returns its state unchanged; half of the
batch left out with the mean taken over the rest; a chunk of steps the
accountant is not charged for.  Serving faults: a
token altered where the engine records it; a prefill whose cache write
returns the cache unchanged.  The cells run on one chip, so no exchange
between chips exists to leave out.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import harness

SECONDS = 1.0


def _run(cell_name):
    cell = harness.load_cell(cell_name)
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / f"{cell['driver']}.py")
    return driver.run(cell, seed=2 ** 31 + 11, seconds=SECONDS, trace=False,
                      smoke=True, devices=jax.devices()[:1],
                      log=harness.CompileLog())


def _limits_set(cell_name):
    limits = harness.load_cell(cell_name)["check"]["limits"]
    return any(v is not None for v in limits.values())


@pytest.fixture
def train_limits():
    if not _limits_set("resnet18.dpquant"):
        pytest.fail("resnet18.dpquant commits no limit")


def test_train_state_unchanged(monkeypatch, train_limits):
    from repro import train_loop
    orig = train_loop.build_epoch_fn

    def frozen(setup, **kw):
        fn = orig(setup, **kw)

        def run(p, o, *a):
            keep = jax.tree_util.tree_map(jnp.copy, p)
            _, o2, metrics = fn(p, o, *a)
            return keep, o2, metrics
        return run

    monkeypatch.setattr(train_loop, "build_epoch_fn", frozen)
    rec = _run("resnet18.dpquant")
    assert rec["readings"]["change"] > 0.5
    assert rec["correct"] is False


def test_train_half_batch(monkeypatch, train_limits):
    from repro import train_loop
    orig = train_loop.build_train_setup

    def half(model, run, mesh, batch_size=None, seq_len=None):
        st = orig(model, run, mesh, batch_size=run.global_batch // 2)
        step = st.step_fn

        def half_step(p, o, batch, *a):
            b = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch)
            return step(p, o, b, *a)
        return dataclasses.replace(st, step_fn=half_step)

    monkeypatch.setattr(train_loop, "build_train_setup", half)
    rec = _run("resnet18.dpquant")
    assert rec["correct"] is False


def test_train_chunk_uncharged(monkeypatch, train_limits):
    from repro.dp.accountant import RDPAccountant
    orig = RDPAccountant.step

    def skip_first_chunk(self, **kw):
        if kw.get("label") == "train" and not getattr(self, "_skipped", 0):
            self._skipped = 1
            return None
        return orig(self, **kw)

    monkeypatch.setattr(RDPAccountant, "step", skip_first_chunk)
    rec = _run("resnet18.dpquant")
    assert rec["readings"]["window_steps"] > 0
    assert rec["correct"] is False


@pytest.fixture
def serve_limits():
    if not _limits_set("stablelm-3b.chat"):
        pytest.fail("stablelm-3b.chat commits no limit")


def test_serve_token_altered(monkeypatch, serve_limits):
    from repro.serve.engine import ContinuousEngine
    orig = ContinuousEngine._record_token

    def altered(self, slot, req, tok, now):
        vocab = self.model.config.vocab_size
        return orig(self, slot, req, (tok + 1) % vocab, now)

    monkeypatch.setattr(ContinuousEngine, "_record_token", altered)
    rec = _run("stablelm-3b.chat")
    assert rec["correct"] is False


def test_serve_cache_write_unchanged(monkeypatch, serve_limits):
    from repro.serve.engine import ContinuousEngine
    orig = ContinuousEngine._jit_fns

    def no_write(self):
        orig(self)
        self._write = lambda cache, pcache, slot: cache

    monkeypatch.setattr(ContinuousEngine, "_jit_fns", no_write)
    rec = _run("stablelm-3b.chat")
    assert rec["correct"] is False
