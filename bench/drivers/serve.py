"""Serving cells: open-loop traffic through ``repro.serve.ContinuousEngine``.

Set-up makes the weights on the device in one jitted call from the seed,
builds the engine, and warms every prefill bucket the cell's traffic can
reach plus the decode, cache-write and release programs, by serving one
short request per bucket; the engine is then reset (it keeps its compiled
programs).

The window submits the seed's requests with their scheduled arrival times
and runs the engine.  Every token is stamped on the engine's clock where
the engine records it; the window closes at ``seconds``, and the run goes
on until every request due in the window has its first token, at most
``drain_s`` more.  Afterwards the program's state is freed and the plain
reference (``bench/reference/transformer.py``) scores a seeded sample of
the finished requests: for each served token, how far its reference logit
lies below the reference's best.
"""
from __future__ import annotations

import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, peaks, traffic
from bench.trace import Trace


class _WindowClosed(Exception):
    """Raised from the engine's tick hook to end the run."""


class Session:
    """One engine with its compiled programs; ``serve(seed)`` runs one
    window and may be called again with another seed."""

    def __init__(self, cell: dict, seed: int, smoke: bool, log):
        from repro.config import QuantConfig, ServeConfig
        from repro.models.registry import build_model
        from repro.serve import ContinuousEngine

        self.cell, self.log = cell, log
        self.t = harness.traffic_params(cell, smoke)
        self.mc = harness.model_config(cell, smoke)
        self.numbers = harness.model_numbers(cell, smoke)
        t = self.t
        self.model = build_model(self.mc, QuantConfig(fmt="none",
                                                      backend=t["backend"]))
        self._init = jax.jit(self.model.init)
        self.run_seed = traffic.seed_words(seed)[0]
        params = self._init(jax.random.PRNGKey(self.run_seed))
        self.serve_cfg = ServeConfig(
            max_slots=t["slots"], max_seq=t["max_seq"],
            max_new_tokens=t["output"]["max"], temperature=0.0, seed=0,
            kv_fmt=t["kv_fmt"])
        self.engine = ContinuousEngine(self.model, params, self.serve_cfg,
                                       on_tick=self._on_tick)
        self._tick_hook = None
        self._wrap()
        self._warm()

    # ------------------------------------------------------------------ #
    def _on_tick(self, tick, wall_s, now):
        if self._tick_hook is not None:
            self._tick_hook(tick, wall_s, now)

    def _wrap(self):
        """Stamp every recorded token; benchmark spans around the engine's
        admission, tick and run loop."""
        eng = self.engine
        record, admit, tick, run = (eng._record_token, eng._admit,
                                    eng._tick, eng.run)
        self.stamps = {}

        def stamped(slot, req, tok, now):
            self.stamps.setdefault(req.request_id, []).append(now)
            return record(slot, req, tok, now)

        def spanned(name, fn):
            def inner(*a, **k):
                with jax.profiler.TraceAnnotation(name):
                    return fn(*a, **k)
            return inner

        eng._record_token = stamped
        eng._admit = spanned("bench.admit", admit)
        eng._tick = spanned("bench.tick", tick)
        eng.run = spanned("bench.engine_run", run)

    def buckets(self):
        from repro.serve.engine import prefill_bucket
        p, max_seq = self.t["prompt"], self.t["max_seq"]
        out = {}
        for n in range(p["min"], p["max"] + 1):
            out.setdefault(prefill_bucket(n, max_seq), n)
        return sorted(out.values())

    def _warm(self):
        eng = self.engine
        for n in self.buckets():
            eng.submit(np.zeros((n,), np.int32), max_new_tokens=2)
        eng.run()
        eng.reset()
        self.stamps.clear()

    # ------------------------------------------------------------------ #
    def new_weights(self, seed: int):
        eng = self.engine
        eng.params = None
        self.run_seed = traffic.seed_words(seed)[0]
        eng.params = self._init(jax.random.PRNGKey(self.run_seed))
        jax.block_until_ready(eng.params)

    def serve(self, seed: int, seconds: float, trace: bool) -> dict:
        """Run one window of the seed's traffic."""
        eng, t = self.engine, self.t
        eng.reset()
        self.stamps.clear()
        reqs = traffic.open_loop(t, seed, seconds, self.numbers["vocab_size"])
        for r in reqs:
            eng.submit(r.prompt, max_new_tokens=r.max_new_tokens,
                       arrival_time=r.arrival)
        n = len(reqs)
        ticks = []
        prof = {"dir": None, "t0": None, "t1": None, "ann": None}
        lead = max(0.0, seconds / 2 - t["trace_span_s"] / 2)
        deadline = seconds + t["drain_s"]

        def hook(tick, wall_s, now):
            ticks.append((now, wall_s))
            if trace:
                if prof["dir"] is None and now >= lead:
                    prof["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
                    jax.profiler.start_trace(prof["dir"])
                    prof["ann"] = jax.profiler.TraceAnnotation("bench.window")
                    prof["ann"].__enter__()
                    prof["t0"] = now
                elif prof["t0"] is not None and prof["t1"] is None and \
                        now >= prof["t0"] + t["trace_span_s"]:
                    prof["ann"].__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    prof["t1"] = now
            if now >= seconds and (len(self.stamps) == n or now >= deadline):
                raise _WindowClosed

        self._tick_hook = hook
        snap = self.log.snapshot()
        try:
            eng.run()
        except _WindowClosed:
            pass
        self._tick_hook = None
        compiled = self.log.since(snap)
        out = {"requests": [], "ticks": ticks, "window_s": seconds,
               "compiles_in_window": compiled["compiles"], "trace": None}
        for rid, r in enumerate(reqs):
            st = self.stamps.get(rid, [])
            out["requests"].append({
                "arrival": r.arrival, "prompt_len": int(r.prompt.size),
                "stamps": list(st), "max_new": r.max_new_tokens,
                "done": rid in eng.results and
                eng.results[rid].status == "ok"})
        if trace and prof["dir"] is not None:
            if prof["t1"] is None:
                prof["ann"].__exit__(None, None, None)
                jax.profiler.stop_trace()
                prof["t1"] = ticks[-1][0] if ticks else prof["t0"]
            out["trace"] = Trace.load(prof["dir"])
            out["trace_engine"] = (prof["t0"], prof["t1"])
            shutil.rmtree(prof["dir"], ignore_errors=True)
        out["served"] = {rid: (reqs[rid].prompt,
                               np.asarray(eng.results[rid].tokens))
                         for rid in eng.results
                         if eng.results[rid].status == "ok"}
        return out

    def free(self):
        self.engine.params = None
        self.engine.cache = None


def sample_served(served: dict, seed: int, tokens: int) -> list:
    """A seeded sample of finished requests with at least ``tokens``
    served tokens among them, the longest request always included."""
    if not served:
        return []
    rids = sorted(served)
    longest = max(rids, key=lambda r: (served[r][0].size + served[r][1].size,
                                       -r))
    order = [longest] + [int(r) for r in traffic.rng(seed, 5).permutation(
        rids) if r != longest]
    picked, total = [], 0
    for r in order:
        picked.append(r)
        total += served[r][1].size
        if total >= tokens:
            break
    return picked


def reference_gaps(numbers: dict, run_seed: int, served: dict, picked: list,
                   max_seq: int, control: bool = False) -> dict:
    """Widest gap, over the sampled served tokens, by which a token's
    reference logit lies below the reference's best; with ``control`` the
    token is the one the lower-precision control puts first."""
    from bench.reference import transformer as ref
    model = ref.Reference(numbers, run_seed)
    worst, count = 0.0, 0
    for rid in picked:
        prompt, toks = served[rid]
        gaps = model.gaps(prompt, toks, max_seq, control=control)
        worst = max(worst, float(np.max(gaps)))
        count += len(gaps)
    model.close()
    return {"gap": worst, "tokens_compared": count}


def run(cell: dict, *, seed: int, seconds: float, trace: bool, smoke: bool,
        devices, log) -> dict:
    t_setup = time.perf_counter()
    snap = log.snapshot()
    with harness.BackendRecord() as backends:
        ses = Session(cell, seed, smoke, log)
    setup_s = time.perf_counter() - t_setup
    compile_setup = log.since(snap)

    win = ses.serve(seed, seconds, trace)
    peak = harness.memory_peak(devices)
    ses.free()
    t = ses.t
    picked = sample_served(win["served"], seed, t["check_tokens"])
    t_ref = time.perf_counter()
    readings = reference_gaps(ses.numbers, ses.run_seed, win["served"],
                              picked, t["max_seq"])
    readings["reference_s"] = time.perf_counter() - t_ref
    limit = cell["check"]["limits"].get("gap")
    checks = {} if limit is None else {
        "gap": {"value": readings["gap"], "limit": limit}}
    failed = sum(1 for r in win["requests"] if not r["stamps"])
    correct = (bool(picked) and win["compiles_in_window"] == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    return {
        "setup_s": setup_s, "correct": correct,
        "attempted": len(win["requests"]), "failed": failed,
        "checks": checks, "readings": readings,
        "memory_peak_bytes": peak,
        "compile": dict(compile_setup,
                        compiles_in_window=win["compiles_in_window"],
                        pallas_fallbacks=backends.fallbacks()),
        "serve": {"requests": win["requests"], "ticks": win["ticks"],
                  "window_s": seconds, "model": ses.numbers,
                  "trace_engine": win.get("trace_engine")},
        "trace": win["trace"],
        "peaks": (peaks.peaks(devices[0].device_kind)
                  if devices[0].platform == "tpu" else None),
    }


def readings(cell: dict, *, program: list, control: list, fault: list,
             smoke: bool, log, emit) -> None:
    """Limit readings in one process: for each seed one short window at
    the cell's load (``readings_window_s``), then the program's widest gap
    (``program`` seeds) or the control's at the same prompts and tokens
    (``control`` seeds).  The cell has no planted fault to read on the
    chip (``fault`` is ignored): its faults are caught by the tests."""
    seeds = program + control
    ses = Session(cell, seeds[0], smoke, log)
    t = ses.t
    for kind, group in (("program", program), ("control", control)):
        for seed in group:
            ses.new_weights(seed)
            win = ses.serve(seed, t["readings_window_s"], False)
            ses.engine.params = None
            ses.engine.cache = None
            picked = sample_served(win["served"], seed, t["check_tokens"])
            got = reference_gaps(ses.numbers, ses.run_seed, win["served"],
                                 picked, t["max_seq"],
                                 control=kind == "control")
            late = [r for r in win["requests"] if not r["stamps"]]
            emit(kind, seed, dict(got, requests=len(win["requests"]),
                                  without_token=len(late)))
