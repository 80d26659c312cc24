"""Continuous-batching serving engine over a slot-pool KV cache.

One ``ContinuousEngine`` owns a fixed ``max_slots x max_seq`` KV cache and
runs the scheduler loop::

    while queue or active slots:
        admit queued requests into free slots   (batched B=1 prefill each)
        one fused masked decode tick            (all active slots at once)
        sample one token per slot               (per-slot, per-position keys)
        retire finished slots                   (budget / EOS / cache full)

Requests of different prompt and generation lengths therefore share the
device batch: a short request retires and its slot is refilled from the
queue while long requests keep decoding — the decode batch stays full
instead of lockstepping to the longest sequence (the oneshot driver's
failure mode, kept in ``repro.serve.oneshot`` as the reference).

Quantized decode works unchanged: ``decode_slots`` routes each slot's
logits row through the quantizer-backend dispatcher
(``repro.quant.backend``) with the position-derived key
``fold_in(PRNGKey(17), 2*pos + 1)``, so ``--quant-fmt luq_fp4 --backend
pallas`` serves under continuous batching and a single greedy request
reproduces the oneshot tokens bit-for-bit.

Quantized KV cache (``ServeConfig.kv_fmt``): with ``int8`` / ``luq_fp4``
the slot pool stores code arrays plus per-(slot, token, kv-head) bf16
scales; prefill and decode write rows through the dispatched ``kv_quant``
op and attention runs through the dispatched ``decode_attn`` op (fused
dequant on the pallas backend).  Quantization is deterministic (no RNG),
so the engine stays token-identical to the oneshot driver at the same
``kv_fmt``.  On retirement the engine zeroes the slot's scale rows: zero
scale dequantizes every code to exactly 0, so a refilled slot can never
read a predecessor's rows against stale scales even before its own
writes land.

Prefill bucketing: admission pads each prompt to the next power of two
(clamped to ``max_seq``) and passes the true length as a *traced* scalar,
so the engine compiles at most ``ceil(log2(max_seq))`` prefill programs
instead of one per distinct prompt length.  Padding is
semantics-preserving: causal attention hides the pad from real rows, and
cache rows at index >= pos are masked until a decode tick overwrites
them (``prefill_programs`` exposes the jit cache size for tests).

Sampling key schedule (docs/SERVING.md): every sampled token uses
``fold_in(fold_in(fold_in(PRNGKey(seed), SAMPLE_FOLD), request_id),
position)`` — domain-separated from the quantizer streams by SAMPLE_FOLD,
and unique per (request, position) so concurrent slots never share a key.

Failure model (docs/SERVING.md "Failure model & recovery"): the engine is
hardened against per-request deadlines (timeout retirement with partial
results), queue overload (bounded queue + load shedding), and injected
faults (``runtime.faults.FaultPlan``: prefill/decode dispatch failures,
detected slot-cache poison, frozen clocks).  A fault victim is re-queued
with linear backoff and *replayed* by re-prefilling its prompt plus the
generated prefix recorded host-side — because sampling keys derive from
``(request_id, position)`` and KV quantization is deterministic, the
recovered request's tokens are bit-identical to a fault-free run.  Every
request retires with a typed status on its ``RequestResult``.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.config import ServeConfig
from repro.launch.mesh import make_host_mesh
from repro.parallel import partitioner as pt
from repro.parallel.axes import partitioning_context
from repro.runtime.faults import DEFAULT_FREEZE_READS, FaultPlan
from repro.runtime.tracing import install_gc_span, scope
from repro.serve.metrics import ServeMetrics
from repro.serve.slots import SlotPool, init_slot_cache

# Domain-separation fold for sampling keys.  Chosen once and fixed: the
# quantizer streams fold small per-layer seeds (fake_quant) and the logits
# head folds 2*pos(+1) off PRNGKey(17), so a dedicated large fold off the
# *user* seed keeps the sampling stream disjoint from both.
SAMPLE_FOLD = 0x53A7


def prefill_bucket(prompt_len: int, max_seq: int) -> int:
    """Padded prefill length: next power of two, clamped to ``max_seq``.

    The floor of 2 merges the length-1 bucket into length-2, so the
    bucket set is {2, 4, ..., 2^ceil(log2(max_seq))} clamped — at most
    ``ceil(log2(max_seq))`` distinct prefill programs.
    """
    if prompt_len < 1 or prompt_len > max_seq:
        raise ValueError(f"prompt_len={prompt_len} outside [1, {max_seq}]")
    return min(max(2, 1 << (prompt_len - 1).bit_length()), max_seq)


def sampling_key(base_key: jax.Array, request_id, position) -> jax.Array:
    """Per-request, per-position sampling key (see module docstring).

    ``request_id`` and ``position`` may be python ints or traced int32
    scalars; distinct (request_id, position) pairs give distinct keys, so
    two slots decoding the same position draw independent bits.
    """
    k = jax.random.fold_in(base_key, SAMPLE_FOLD)
    k = jax.random.fold_in(k, request_id)
    return jax.random.fold_in(k, position)


@dataclasses.dataclass
class Request:
    """A queued generation request."""

    request_id: int
    prompt: np.ndarray              # (S,) int32 token ids
    max_new_tokens: int
    arrival_time: float = 0.0       # seconds relative to run() start
    eos_id: Optional[int] = None
    deadline_s: Optional[float] = None   # from arrival; None = no deadline
    attempts: int = 0               # fault-triggered re-queues so far
    not_before: float = 0.0         # retry backoff gate (seconds)

    def expiry(self) -> Optional[float]:
        """Absolute deadline instant, or None when unbounded."""
        if self.deadline_s is None:
            return None
        return self.arrival_time + self.deadline_s


@dataclasses.dataclass
class RequestResult:
    """Retired request: generated ids, timing record, terminal status.

    ``status`` is one of ``metrics.REQUEST_STATUSES``: "ok" (possibly
    after fault recovery), "timed_out" (deadline expired; ``tokens`` holds
    the partial result), "shed" (queue full at submit), or "failed" (fault
    retries exhausted; partial tokens).
    """

    request_id: int
    prompt: np.ndarray
    tokens: np.ndarray              # (n_generated,) int32
    timing: object                  # metrics.RequestTiming
    status: str = "ok"


class ContinuousEngine:
    """Slot-pool scheduler running fused masked decode over active slots.

    Parameters
    ----------
    model:
        A ``repro.models.registry.Model`` with the slot hooks
        (``decode_slots`` / ``slot_cache_spec``); currently the dense
        transformer family implements them.
    params:
        The model's parameter pytree.
    serve:
        ``repro.config.ServeConfig`` — slot count, cache length, sampling
        temperature and seed, plus the admission-control knobs (deadline,
        queue bound, retry policy).
    mesh:
        Optional ``jax.sharding.Mesh``; defaults to the host mesh.  The
        prefill/decode functions run under the same partitioning context
        the oneshot driver uses, so sharding annotations resolve
        identically.
    faults:
        Optional ``runtime.faults.FaultPlan``.  The engine polls it at its
        explicit hook points (prefill dispatch, decode tick, slot cache,
        clock reads) and recovers per the retry policy; every recovery
        path is therefore seed-reproducible.
    on_tick:
        Optional callback ``(tick_index, tick_wall_s, now_s)`` invoked
        after every decode-tick attempt — the supervisor's hook for
        heartbeat/straggler instrumentation (``runtime.supervisor``).
    """

    def __init__(self, model, params, serve: ServeConfig, mesh=None,
                 faults: Optional[FaultPlan] = None,
                 on_tick: Optional[Callable[[int, float, float], None]] = None):
        """Allocate the slot cache and jit the engine's device functions."""
        if model.decode_slots is None or model.slot_cache_spec is None:
            raise ValueError(
                f"model family {model.config.family!r} does not support "
                "continuous batching (no decode_slots/slot_cache_spec)")
        extra = set(model.batch_spec(1, 2)) - {"tokens"}
        if extra:
            # fail at construction, not deep inside prefill at admission:
            # _admit builds {"tokens": prompt} only, so families whose
            # batch_spec needs more inputs (encdec enc_embeds, vlm vision
            # embeds) need a prompt-to-batch hook before they can ride the
            # slot engine
            raise ValueError(
                f"continuous batching supports token-only prompts; family "
                f"{model.config.family!r} also requires {sorted(extra)}")
        if serve.kv_fmt not in model.kv_formats:
            raise ValueError(
                f"model family {model.config.family!r} does not support "
                f"kv_fmt={serve.kv_fmt!r} (supported: {model.kv_formats})")
        self.model = model
        self.params = params
        self.serve = serve
        self.faults = faults
        self.on_tick = on_tick
        self.mesh = mesh if mesh is not None else make_host_mesh()
        rules = pt.merge_rules(pt.DEFAULT_RULES,
                               model.config.sharding_overrides)
        self._resolver = pt.activation_resolver(self.mesh, rules)
        self._replicated = NamedSharding(self.mesh, PartitionSpec())
        self._base_key = jax.random.PRNGKey(serve.seed)
        self._jit_fns()
        self.reset()
        install_gc_span()

    # ------------------------------------------------------------------ #
    # device functions
    # ------------------------------------------------------------------ #
    def _jit_fns(self):
        """Build the jitted prefill / cache-write / decode / sample fns."""
        model, resolver = self.model, self._resolver
        temperature, base_key = self.serve.temperature, self._base_key
        kv_fmt = self.serve.kv_fmt
        kv_kw = {} if kv_fmt == "none" else {"kv_fmt": kv_fmt}

        def prefill_fn(params, batch, prompt_len):
            # prompt_len is a traced scalar: the token batch is padded to a
            # power-of-two bucket (prefill_bucket), so the compiled program
            # depends only on the bucket, never on the exact prompt length
            with partitioning_context(resolver):
                return model.prefill(params, batch, prompt_len=prompt_len,
                                     **kv_kw)

        def step_fn(params, cache, tokens, active, rids):
            # fused decode + sample: one dispatch and one (K,) device->host
            # transfer per tick (the (K, V) logits never leave the device)
            with partitioning_context(resolver):
                logits, cache = model.decode_slots(params, cache, tokens,
                                                   active, **kv_kw)
            return scope("lm_head", sample)(logits, rids, cache["pos"]), cache

        def sample(logits, rids, pos):
            if temperature > 0:
                keys = jax.vmap(
                    lambda r, p: sampling_key(base_key, r, p))(rids, pos)
                toks = jax.vmap(lambda k, row: jax.random.categorical(
                    k, row / temperature))(keys, logits)
            else:
                toks = jnp.argmax(logits, -1)
            return toks.astype(jnp.int32)

        def write_fn(cache, pcache, slot):
            # copy every prefill cache array (codes and, when quantized,
            # scales) into the slot's rows; the prefill batch axis is 1 and
            # its seq extent is the bucket length <= max_seq, so one
            # dynamic_update_slice per array covers every layout
            out = {}
            for name, arr in cache.items():
                if name == "pos":
                    out[name] = arr.at[slot].set(pcache["pos"])
                    continue
                upd = pcache[name].astype(arr.dtype)
                start = (0, slot) + (0,) * (arr.ndim - 2)
                out[name] = jax.lax.dynamic_update_slice(arr, upd, start)
            return out

        def release_fn(cache, slot):
            # zero the retiring slot's scale rows: zero scale dequantizes
            # every code to exactly 0, so the next occupant can never read
            # the predecessor's rows against stale scales (the codes
            # themselves are harmless without their scales and are masked
            # by pos regardless)
            out = dict(cache)
            for name in ("k_scale", "v_scale"):
                arr = cache[name]
                zeros = jnp.zeros((arr.shape[0], 1) + arr.shape[2:],
                                  arr.dtype)
                out[name] = jax.lax.dynamic_update_slice(
                    arr, zeros, (0, slot) + (0,) * (arr.ndim - 2))
            return out

        # prefill compiles once per power-of-two bucket (prefill_bucket);
        # step/write/release compile once for the slot geometry
        self._prefill = jax.jit(prefill_fn)
        self._step = jax.jit(step_fn, donate_argnums=(1,))
        self._write = jax.jit(write_fn, donate_argnums=(0,))
        self._release_scales = (jax.jit(release_fn, donate_argnums=(0,))
                                if kv_fmt != "none" else None)

    @property
    def prefill_programs(self) -> int:
        """Number of distinct prefill programs compiled so far.

        Bounded by ``ceil(log2(max_seq))`` for any mix of prompt lengths —
        the bucketing invariant tests assert against.
        """
        return self._prefill._cache_size()

    # ------------------------------------------------------------------ #
    # public API
    # ------------------------------------------------------------------ #
    def reset(self):
        """Clear all queue/slot/cache/metric state (keeps compiled fns).

        Request ids restart from 0 so a reset engine reproduces a fresh
        engine exactly — sampling keys fold the request id, so id reuse
        across resets is what makes reruns deterministic.
        """
        K = self.serve.max_slots
        self._next_id = 0
        # free the old slot cache before allocating its replacement: a
        # reset never holds two on the device
        self.cache = None
        self.cache = jax.device_put(
            init_slot_cache(self.model, K, self.serve.max_seq,
                            kv_fmt=self.serve.kv_fmt), self._replicated)
        self.pool = SlotPool(K)
        self.metrics = ServeMetrics()
        self.queue: collections.deque = collections.deque()
        self.results: Dict[int, RequestResult] = {}
        self._tokens_by_req: Dict[int, List[int]] = {}
        self._live: Dict[int, Request] = {}     # admitted, not yet retired
        self._cur_tokens = np.zeros((K,), np.int32)
        self._active = np.zeros((K,), bool)
        self._rids = np.zeros((K,), np.int32)
        # fault-tolerance state: per-domain counters the FaultPlan is
        # polled against, the clock-freeze window, and the degraded-mode
        # admission cap (shrunk by the supervisor on replica loss)
        self._tick_index = 0
        self._prefill_count = 0
        self._freeze_reads = 0
        self._freeze_val = 0.0
        self.slot_cap = K
        # device copies of the three slot vectors; re-uploaded only after
        # admission/retirement events (``_dirty``), so an event-free tick
        # costs exactly one dispatch + one (K,) sync
        self._dirty = True
        self._tokens_dev = None
        self._active_dev = None
        self._rids_dev = None

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               arrival_time: float = 0.0,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None) -> int:
        """Queue a request; returns its request id.

        ``arrival_time`` is in seconds relative to the start of ``run()``;
        the scheduler will not admit the request before that time (this is
        how benchmark traces model Poisson arrivals).  ``deadline_s``
        (default ``ServeConfig.deadline_s``) bounds the request's life from
        arrival: expiry in the queue rejects it un-admitted, expiry in
        flight retires it with partial tokens (status "timed_out").

        When ``ServeConfig.max_queue`` > 0 and that many requests are
        already waiting, the request is *shed*: it is never queued, its
        result (status "shed", no tokens) is recorded immediately, and the
        shed counter increments — bounded memory under overload instead of
        unbounded queue growth.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if prompt.size > self.serve.max_seq:
            raise ValueError(
                f"prompt of {prompt.size} tokens exceeds max_seq="
                f"{self.serve.max_seq}")
        rid = self._next_id
        self._next_id += 1
        budget = (self.serve.max_new_tokens if max_new_tokens is None
                  else max_new_tokens)
        if budget < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline_s is None:
            deadline_s = self.serve.deadline_s
        self.metrics.on_submit(rid, prompt.size, arrival_time)
        self._tokens_by_req[rid] = []
        req = Request(request_id=rid, prompt=prompt, max_new_tokens=budget,
                      arrival_time=arrival_time, eos_id=eos_id,
                      deadline_s=deadline_s)
        if (self.serve.max_queue > 0
                and len(self.queue) >= self.serve.max_queue):
            self.metrics.on_shed(rid, arrival_time)
            self.results[rid] = RequestResult(
                request_id=rid, prompt=prompt,
                tokens=np.zeros((0,), np.int32),
                timing=self.metrics.timings[rid], status="shed")
            return rid
        self.queue.append(req)
        return rid

    def run(self, clock: Optional[Callable[[], float]] = None
            ) -> Dict[int, RequestResult]:
        """Drive the scheduler until every submitted request completes.

        ``clock`` (for tests) overrides the default wall clock, which is
        seconds since ``run()`` was called.  Generated tokens are
        clock-independent — the clock only gates admission times.
        """
        self.queue = collections.deque(
            sorted(self.queue, key=lambda r: r.arrival_time))
        t0 = time.perf_counter()
        raw_now = clock or (lambda: time.perf_counter() - t0)

        def now_fn():
            # clock_freeze fault: hold time still for the injected window
            # (a bounded number of *reads*, so the loop always thaws well
            # before the frozen-clock stall guard below can trip)
            if self._freeze_reads > 0:
                self._freeze_reads -= 1
                return self._freeze_val
            return raw_now()

        last_idle_now, stalled = None, 0
        try:
            while self.queue or self.pool.n_active:
                self._expire_deadlines(now_fn)
                self._admit(now_fn)
                if self.pool.n_active:
                    self._tick(now_fn)
                    stalled = 0
                    continue
                if not self.queue:
                    break
                # idle: nothing decodable until the next eligible request
                # (arrival in the future, or retry backoff gate not open)
                now = now_fn()
                next_ready = min(max(r.arrival_time, r.not_before)
                                 for r in self.queue)
                if next_ready > now:
                    if clock is None:
                        t_sleep = time.perf_counter()
                        with jax.profiler.TraceAnnotation("serve.idle"):
                            time.sleep(min(next_ready - now, 0.05))
                        self.metrics.idle_wall += (time.perf_counter()
                                                   - t_sleep)
                    else:
                        # injected clocks must advance on their own; guard
                        # against a frozen clock turning this into a hang
                        stalled = stalled + 1 if now == last_idle_now else 0
                        if stalled > 1000:
                            raise RuntimeError(
                                "injected clock is not advancing past the "
                                f"next eligible time ({next_ready}); engine "
                                "cannot make progress")
                    last_idle_now = now
        finally:
            # accumulate (not overwrite): timings persist across run()
            # calls, so throughput over multiple runs must divide by their
            # total wall.  raw_now sidesteps any still-open freeze window.
            self.metrics.run_wall += raw_now()
        return dict(self.results)

    # ------------------------------------------------------------------ #
    # scheduler internals
    # ------------------------------------------------------------------ #
    def _next_eligible(self, now: float) -> Optional[Request]:
        """Pop the first queued request that may run now (FCFS order).

        Eligibility = arrived (``arrival_time <= now``) and past its retry
        backoff gate (``not_before <= now``).  Returns None when nothing
        is eligible yet.
        """
        for i, req in enumerate(self.queue):
            if req.arrival_time <= now and req.not_before <= now:
                del self.queue[i]
                return req
        return None

    def _admit(self, now_fn):
        """FCFS admission: fill free slots with eligible requests.

        Prompts are zero-padded to their power-of-two bucket
        (``prefill_bucket``) before prefill, with the true length passed
        as a traced scalar — one compiled prefill program per bucket.

        A *replayed* request (fault victim, ``attempts > 0``) is
        re-admitted by prefilling its prompt concatenated with the
        generated prefix recorded host-side; the first fresh token is then
        sampled at position ``prompt_len + len(prefix)`` with the same
        ``(request_id, position)`` key a fault-free run would have used,
        so recovery is token-bit-identical.  ``SlotState.prompt_len``
        keeps the *original* prompt length so the cache-index/retirement
        arithmetic in ``_record_token`` is invariant under replay.

        Admission is capped at ``slot_cap`` (<= max_slots); the supervisor
        shrinks it in degraded mode after replica loss.
        """
        while self.pool.n_free and self.pool.n_active < self.slot_cap:
            now = now_fn()
            req = self._next_eligible(now)
            if req is None:
                return
            prefix = self._tokens_by_req[req.request_id]
            total = req.prompt.size + len(prefix)
            if self.faults is not None:
                attempt = self._prefill_count
                self._prefill_count += 1
                due = self.faults.take("prefill_fail", attempt)
                if due:
                    # injected prefill dispatch failure: the request never
                    # touches a slot; re-queue it behind its backoff gate
                    self.metrics.faults_injected += len(due)
                    self._requeue(req, now_fn())
                    continue
            bucket = prefill_bucket(total, self.serve.max_seq)
            with jax.profiler.TraceAnnotation(
                    "serve.admit", rid=req.request_id, prompt_len=total,
                    bucket=bucket,
                    wait_ms=1000.0 * (now - req.arrival_time)):
                self._admit_one(req, prefix, total, bucket, now_fn)

    def _admit_one(self, req: Request, prefix: List[int], total: int,
                   bucket: int, now_fn):
        """Prefill one request into a free slot and record its first
        token."""
        slot = self.pool.acquire(req.request_id, req.prompt.size,
                                 req.max_new_tokens - len(prefix))
        with jax.profiler.TraceAnnotation("serve.prefill"):
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :req.prompt.size] = req.prompt
            if prefix:
                padded[0, req.prompt.size:total] = prefix
            logits, pcache = self._prefill(
                self.params, {"tokens": jnp.asarray(padded)}, total)
        with jax.profiler.TraceAnnotation("serve.cache_write"):
            self.cache = self._write(self.cache, pcache, slot)
        # first generated token, drawn at position == total sequence
        # length so far (== prompt_len on a fresh admission)
        with jax.profiler.TraceAnnotation("serve.first_token"):
            if self.serve.temperature > 0:
                key = sampling_key(self._base_key, req.request_id, total)
                tok = int(jax.random.categorical(
                    key, logits[0] / self.serve.temperature))
            else:
                tok = int(jnp.argmax(logits[0]))
        now = now_fn()
        self._live[req.request_id] = req
        self.metrics.on_admit(req.request_id, now)
        self.metrics.on_first_token(req.request_id, now)
        self._record_token(slot, req, tok, now)

    def _record_token(self, slot: int, req: Request, tok: int, now: float):
        """Append one generated token; retire the slot if finished."""
        state = self.pool.state(slot)
        toks = self._tokens_by_req[req.request_id]
        toks.append(tok)
        state.remaining -= 1
        # the token just recorded will occupy cache index prompt_len +
        # len(toks) - 1 on its decode tick; retire when that index would
        # fall outside the slot (cache full), on EOS, or on budget
        pos_next = state.prompt_len + len(toks) - 1
        done = (state.remaining <= 0
                or (req.eos_id is not None and tok == req.eos_id)
                or pos_next >= self.serve.max_seq)
        if done:
            self._retire(slot, req, now)
        else:
            if not self._active[slot]:
                self._dirty = True          # admission: slot newly active
            self._active[slot] = True
            self._cur_tokens[slot] = tok
            self._rids[slot] = req.request_id

    def _tick(self, now_fn):
        """One fused decode+sample step over every active slot.

        Fault hook point: ``clock_freeze`` / ``slot_corrupt`` /
        ``decode_fail`` events are polled against the tick counter before
        the fused step runs; a decode failure victimizes every active slot
        (the whole fused dispatch failed) and re-queues them for replay.
        ``on_tick`` fires after every attempt — including failed ones —
        with the tick's real wall time, which is what the supervisor's
        heartbeat/straggler instrumentation consumes.
        """
        tick = self._tick_index
        self._tick_index += 1
        t_start = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(
                    "serve.tick", tick=tick, active=self.pool.n_active,
                    queued=len(self.queue)):
                self._decode_tick(tick, now_fn)
        finally:
            if self.on_tick is not None:
                self.on_tick(tick, time.perf_counter() - t_start, now_fn())

    def _decode_tick(self, tick: int, now_fn):
        """The body of ``_tick``: fault hooks, then the fused step."""
        if self.faults is not None:
            for ev in self.faults.take("clock_freeze", tick):
                self.metrics.faults_injected += 1
                # read the instant *before* opening the window so the
                # frozen value is the current time, then hold it for
                # the next `duration` reads
                self._freeze_val = now_fn()
                self._freeze_reads = ev.duration or DEFAULT_FREEZE_READS
            for ev in self.faults.take("slot_corrupt", tick):
                self.metrics.faults_injected += 1
                self.metrics.slot_faults += 1
                self._corrupt_slot(ev, now_fn)
            due = self.faults.take("decode_fail", tick)
            if due:
                self.metrics.faults_injected += len(due)
                self.metrics.slot_faults += len(due)
                self._fail_tick(now_fn)
                return
            if not self.pool.n_active:
                # every occupant was a corruption victim; nothing to
                # decode this tick
                return
        if self._dirty:
            # placed like the step's own outputs, which feed back in
            # below: one decode program, not one per placement
            with jax.profiler.TraceAnnotation("serve.upload"):
                self._tokens_dev, self._active_dev, self._rids_dev = (
                    jax.device_put((self._cur_tokens, self._active,
                                    self._rids), self._replicated))
            self._dirty = False
        with jax.profiler.TraceAnnotation("serve.dispatch"):
            toks_dev, self.cache = self._step(
                self.params, self.cache, self._tokens_dev, self._active_dev,
                self._rids_dev)
        with jax.profiler.TraceAnnotation("serve.wait"):
            toks = np.asarray(toks_dev)
        self.metrics.decode_ticks += 1
        now = now_fn()
        with jax.profiler.TraceAnnotation("serve.record"):
            for slot in np.nonzero(self._active)[0]:
                slot = int(slot)
                rid = self.pool.state(slot).request_id
                self._record_token(slot, self._live[rid], int(toks[slot]),
                                   now)
        if not self._dirty:
            # no retirement this tick: the sampled tokens feed straight
            # back in without a host->device upload
            self._tokens_dev = toks_dev

    # ------------------------------------------------------------------ #
    # fault recovery
    # ------------------------------------------------------------------ #
    def _evict(self, slot: int) -> Request:
        """Tear a live request out of ``slot`` without finalizing it."""
        rid = self.pool.state(slot).request_id
        req = self._live.pop(rid)
        self._active[slot] = False
        self._dirty = True
        self.pool.release(slot)
        if self._release_scales is not None:
            self.cache = self._release_scales(self.cache, slot)
        return req

    def _requeue(self, req: Request, now: float):
        """Re-queue a fault victim with linear backoff (or fail it out).

        The generated prefix stays in ``_tokens_by_req``; re-admission
        replays it (see ``_admit``).  When the retry budget is exhausted
        the request retires with status "failed" and its partial tokens.
        """
        req.attempts += 1
        if req.attempts > self.serve.max_retries:
            self._finalize(req, now, status="failed")
            return
        req.not_before = now + req.attempts * self.serve.retry_backoff_s
        self.metrics.on_retry(req.request_id)
        self.queue.append(req)

    def _fail_tick(self, now_fn):
        """Injected decode dispatch failure: all active slots are victims."""
        now = now_fn()
        for slot in np.nonzero(self._active)[0]:
            self._requeue(self._evict(int(slot)), now)

    def _corrupt_slot(self, ev, now_fn):
        """Overwrite one slot's cache rows with deterministic garbage.

        Modelled as *detected* poison (ECC-style): the scrubber knows the
        slot is bad, so the occupant (if any) is evicted for deterministic
        replay and the slot's scale rows are zeroed before reuse.  Under
        ``kv_fmt=none`` (no scale rows) the garbage codes are neutralized
        by pos-masking plus the next occupant's prefill overwrite.
        """
        K = self.serve.max_slots
        slot = ev.target % K if ev.target >= 0 else 0
        rng = np.random.default_rng((self.faults.seed, ev.at, slot))
        cache = dict(self.cache)
        for name, arr in cache.items():
            if name == "pos":
                continue
            junk = rng.integers(-100, 100,
                                size=(arr.shape[0], 1) + arr.shape[2:])
            cache[name] = jax.lax.dynamic_update_slice(
                arr, jnp.asarray(junk).astype(arr.dtype),
                (0, slot) + (0,) * (arr.ndim - 2))
        self.cache = cache
        if self._active[slot]:
            self._requeue(self._evict(slot), now_fn())
        elif self._release_scales is not None:
            self.cache = self._release_scales(self.cache, slot)

    def _expire_deadlines(self, now_fn):
        """Retire every request whose deadline has passed.

        Queued requests that were never admitted land in the metrics'
        rejected bucket (``on_queue_timeout``); previously-admitted
        victims awaiting replay, and in-flight requests, retire with
        status "timed_out" and whatever tokens they generated.
        """
        if not self.queue and not self._live:
            return
        now = now_fn()
        keep: collections.deque = collections.deque()
        for req in self.queue:
            exp = req.expiry()
            if exp is None or exp > now:
                keep.append(req)
                continue
            self._finalize(req, now, status="timed_out")
        self.queue = keep
        for slot in np.nonzero(self._active)[0]:
            slot = int(slot)
            req = self._live[self.pool.state(slot).request_id]
            exp = req.expiry()
            if exp is not None and exp <= now:
                self._retire(slot, req, now, status="timed_out")

    def _finalize(self, req: Request, now: float, status: str):
        """Materialize a terminal result for a request not holding a slot."""
        rid = req.request_id
        toks = np.asarray(self._tokens_by_req.get(rid, []), np.int32)
        if status == "timed_out" and self.metrics.timings[rid].admitted is None:
            self.metrics.on_queue_timeout(rid, now)
        else:
            self.metrics.on_complete(rid, now, n_generated=int(toks.size),
                                     status=status)
        self.results[rid] = RequestResult(
            request_id=rid, prompt=req.prompt, tokens=toks,
            timing=self.metrics.timings[rid], status=status)

    # ------------------------------------------------------------------ #
    # degraded-mode hooks (runtime.supervisor)
    # ------------------------------------------------------------------ #
    def set_slot_cap(self, cap: int):
        """Cap concurrent admissions (degraded mode); clamped to [1, K]."""
        self.slot_cap = max(1, min(int(cap), self.serve.max_slots))

    def takeover_unfinished(self) -> List[Tuple[Request, List[int]]]:
        """Drain every unfinished request for an external driver.

        Evicts all live slots and empties the queue, returning
        ``(request, generated_prefix)`` pairs in request-id order.  The
        supervisor's oneshot fallback finishes each with the *engine's*
        sampling-key schedule and reports results via
        ``finalize_external`` — tokens stay bit-identical to a fault-free
        continuous run.
        """
        out = []
        for slot in np.nonzero(self._active)[0]:
            req = self._evict(int(slot))
            out.append((req, list(self._tokens_by_req[req.request_id])))
        while self.queue:
            req = self.queue.popleft()
            out.append((req, list(self._tokens_by_req[req.request_id])))
        return sorted(out, key=lambda p: p[0].request_id)

    def finalize_external(self, req: Request, tokens, now: float,
                          status: str = "ok"):
        """Record a result completed outside the engine (oneshot fallback)."""
        self._tokens_by_req[req.request_id] = [int(t) for t in tokens]
        self._finalize(req, now, status=status)

    def _retire(self, slot: int, req: Request, now: float,
                status: str = "ok"):
        """Release a finished slot and materialize its result."""
        if self._active[slot]:
            self._dirty = True
        self._active[slot] = False
        self.pool.release(slot)
        if self._release_scales is not None:
            # quantized cache: invalidate the slot's scale rows so the next
            # occupant can never dequantize this occupant's leftovers
            self.cache = self._release_scales(self.cache, slot)
        self._live.pop(req.request_id, None)
        toks = np.asarray(self._tokens_by_req[req.request_id], np.int32)
        self.metrics.on_complete(req.request_id, now,
                                 n_generated=int(toks.size), status=status)
        self.results[req.request_id] = RequestResult(
            request_id=req.request_id, prompt=req.prompt, tokens=toks,
            timing=self.metrics.timings[req.request_id], status=status)
