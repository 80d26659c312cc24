"""Continuous-batching engine: oneshot equivalence, slot lifecycle,
quantized decode, the quantized slot-pool KV cache, prefill bucketing,
and the sampling-key schedule (docs/SERVING.md)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (DPConfig, ModelConfig, OptimConfig, QuantConfig,
                          RunConfig, ServeConfig)
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model
from repro.serve import (ContinuousEngine, SlotPool, build_oneshot_fns,
                         oneshot_generate, sampling_key)

VOCAB = 64


def tiny_cfg():
    return ModelConfig(name="lm-tiny", family="dense_lm", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                       d_ff=64, vocab_size=VOCAB, compute_dtype="float32",
                       remat=False)


def make_model(fmt="none", backend="ref"):
    cfg = tiny_cfg()
    model = build_model(cfg, QuantConfig(fmt=fmt, backend=backend))
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def prompt_of(seed, length):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (length,), 0, VOCAB), np.int32)


def oneshot_reference(model, params, prompt, gen, kv_fmt="none"):
    """Tokens from the lockstep reference driver for one greedy request."""
    run = RunConfig(model=model.config, quant=model.quant,
                    dp=DPConfig(enabled=False), optim=OptimConfig())
    prefill, decode = build_oneshot_fns(model, run, make_host_mesh(), 1,
                                        prompt.size + gen, kv_fmt=kv_fmt)
    tokens, _ = oneshot_generate(prefill, decode, params,
                                 {"tokens": jnp.asarray(prompt)[None, :]},
                                 gen)
    return tokens[0].tolist()


# --------------------------------------------------------------------------- #
# engine vs oneshot token equivalence
# --------------------------------------------------------------------------- #
@pytest.mark.slow
@pytest.mark.parametrize("fmt,backend", [("none", "ref"),
                                         ("luq_fp4", "ref"),
                                         ("luq_fp4", "pallas")])
def test_engine_matches_oneshot_single_greedy(fmt, backend):
    """A single greedy request on a fixed seed must be token-identical to
    the oneshot driver — including through the quantized logits head on
    both dispatcher backends (same per-position fold of PRNGKey(17))."""
    model, params = make_model(fmt, backend)
    prompt, gen = prompt_of(1, 7), 5
    ref = oneshot_reference(model, params, prompt, gen)
    engine = ContinuousEngine(model, params,
                              ServeConfig(max_slots=1,
                                          max_seq=prompt.size + gen))
    rid = engine.submit(prompt, max_new_tokens=gen)
    out = engine.run()
    assert out[rid].tokens.tolist() == ref


@pytest.mark.slow
def test_mixed_length_requests_each_match_their_oneshot_reference():
    """Multiple requests with different prompt/generation lengths sharing
    two slots must each reproduce their own single-request reference —
    continuous batching may not leak state across slots."""
    model, params = make_model()
    engine = ContinuousEngine(model, params,
                              ServeConfig(max_slots=2, max_seq=24))
    specs = [(3, 6), (9, 3), (5, 2), (4, 7)]       # (prompt_len, gen)
    rids = [engine.submit(prompt_of(10 + i, pl), max_new_tokens=g)
            for i, (pl, g) in enumerate(specs)]
    out = engine.run()
    assert sorted(out) == sorted(rids)
    for rid, (pl, g) in zip(rids, specs):
        assert out[rid].tokens.size == g
        ref = oneshot_reference(model, params, prompt_of(10 + rids.index(rid), pl), g)
        assert out[rid].tokens.tolist() == ref


# --------------------------------------------------------------------------- #
# slot lifecycle
# --------------------------------------------------------------------------- #
def test_slot_reuse_after_retirement():
    """With one slot and three queued requests the slot must be acquired
    three times and every request must complete."""
    model, params = make_model()
    engine = ContinuousEngine(model, params,
                              ServeConfig(max_slots=1, max_seq=16))
    rids = [engine.submit(prompt_of(20 + i, 4 + i), max_new_tokens=3)
            for i in range(3)]
    out = engine.run()
    assert sorted(out) == sorted(rids)
    assert engine.pool.admissions == [3]
    assert engine.pool.n_free == 1 and engine.pool.n_active == 0
    for rid in rids:
        assert out[rid].tokens.size == 3


def test_cache_full_truncates_generation():
    """A slot retires when its next token would not fit in max_seq."""
    model, params = make_model()
    engine = ContinuousEngine(model, params,
                              ServeConfig(max_slots=1, max_seq=10))
    rid = engine.submit(prompt_of(3, 7), max_new_tokens=50)
    out = engine.run()
    # tokens occupy cache indices prompt_len + n - 1; the last admissible
    # token is the one whose write index is max_seq - 1, plus the final
    # sampled-but-never-cached token
    assert out[rid].tokens.size == 10 - 7 + 1


def test_eos_retires_slot_early():
    """EOS seen in the sampled stream stops the request immediately."""
    model, params = make_model()
    prompt, gen = prompt_of(1, 7), 6
    full = oneshot_reference(model, params, prompt, gen)
    eos = full[2]       # third greedy token acts as the EOS id
    engine = ContinuousEngine(model, params,
                              ServeConfig(max_slots=1,
                                          max_seq=prompt.size + gen))
    rid = engine.submit(prompt, max_new_tokens=gen, eos_id=eos)
    out = engine.run()
    assert out[rid].tokens.tolist() == full[:3]


def test_submit_validation():
    model, params = make_model()
    engine = ContinuousEngine(model, params,
                              ServeConfig(max_slots=1, max_seq=8))
    with pytest.raises(ValueError, match="empty"):
        engine.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="max_seq"):
        engine.submit(np.zeros((9,), np.int32))
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit(np.zeros((3,), np.int32), max_new_tokens=0)


def test_engine_requires_slot_decode_support():
    cfg = ModelConfig(name="rn", family="resnet", resnet_blocks=(1,),
                      num_classes=4, image_size=8, compute_dtype="float32")
    model = build_model(cfg, QuantConfig(fmt="none"))
    with pytest.raises(ValueError, match="continuous batching"):
        ContinuousEngine(model, params=None,
                         serve=ServeConfig(max_slots=1, max_seq=8))


def test_serve_config_validation():
    with pytest.raises(ValueError, match="max_slots"):
        ServeConfig(max_slots=0)
    with pytest.raises(ValueError, match="max_seq"):
        ServeConfig(max_seq=1)


def test_injected_clock_gates_admission_and_frozen_clock_raises():
    """An advancing fake clock delays admission until arrival_time; a
    frozen fake clock must raise instead of hanging the scheduler."""
    model, params = make_model()
    engine = ContinuousEngine(model, params,
                              ServeConfig(max_slots=1, max_seq=16))
    engine.submit(prompt_of(1, 4), max_new_tokens=2, arrival_time=0.5)

    ticks = {"n": 0}

    def advancing_clock():
        ticks["n"] += 1
        return ticks["n"] * 0.01            # 10 ms per observation

    out = engine.run(clock=advancing_clock)
    assert len(out) == 1
    (timing,) = [r.timing for r in out.values()]
    assert timing.admitted >= 0.5           # arrival-gated

    engine.reset()
    engine.submit(prompt_of(1, 4), max_new_tokens=2, arrival_time=1e9)
    with pytest.raises(RuntimeError, match="not advancing"):
        engine.run(clock=lambda: 0.0)


def test_metrics_accumulate_across_sequential_runs():
    """Two run() calls without reset(): throughput must divide the summed
    token count by the summed wall, not by the latest run's wall only."""
    model, params = make_model()
    engine = ContinuousEngine(model, params,
                              ServeConfig(max_slots=1, max_seq=16))
    a = engine.submit(prompt_of(1, 4), max_new_tokens=3)
    engine.run()
    wall1 = engine.metrics.run_wall
    assert wall1 > 0
    b = engine.submit(prompt_of(2, 4), max_new_tokens=3)
    engine.run()
    s = engine.metrics.summary()
    assert engine.metrics.run_wall > wall1
    assert s["n_requests"] == 2 and s["total_new_tokens"] == 6
    assert s["run_wall_s"] == engine.metrics.run_wall
    assert a in engine.results and b in engine.results


def test_reset_restarts_request_ids_for_deterministic_reruns():
    """reset() must reproduce a fresh engine: request ids restart at 0,
    so temperature-sampling keys (which fold the id) are identical."""
    model, params = make_model()
    serve = ServeConfig(max_slots=1, max_seq=16, temperature=0.9, seed=5)
    engine = ContinuousEngine(model, params, serve)

    def one_run():
        rid = engine.submit(prompt_of(2, 5), max_new_tokens=4)
        assert rid == 0
        out = engine.run()
        engine.reset()
        return out[rid].tokens.tolist()

    assert one_run() == one_run()


def test_slot_pool_free_list():
    pool = SlotPool(2)
    a = pool.acquire(0, 4, 8)
    b = pool.acquire(1, 4, 8)
    assert {a, b} == {0, 1} and pool.acquire(2, 4, 8) is None
    pool.release(a)
    assert pool.n_free == 1 and pool.acquire(3, 2, 2) == a


# --------------------------------------------------------------------------- #
# quantized serving smoke
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_quantized_continuous_serving_smoke(backend):
    """luq_fp4 serving under continuous batching completes on both
    dispatcher backends and stays in the (padded) vocab range."""
    model, params = make_model("luq_fp4", backend)
    engine = ContinuousEngine(model, params,
                              ServeConfig(max_slots=2, max_seq=20))
    rids = [engine.submit(prompt_of(30 + i, 4 + 2 * i), max_new_tokens=3)
            for i in range(3)]
    out = engine.run()
    vpad = model.config.padded_vocab
    for rid in rids:
        toks = out[rid].tokens
        assert toks.size == 3
        assert ((toks >= 0) & (toks < vpad)).all()


# --------------------------------------------------------------------------- #
# quantized slot-pool KV cache (ServeConfig.kv_fmt)
# --------------------------------------------------------------------------- #
@pytest.mark.slow
@pytest.mark.parametrize("kv_fmt", ["int8", "luq_fp4"])
def test_engine_matches_oneshot_per_kv_fmt(kv_fmt):
    """With a quantized slot-pool cache the engine must stay token-identical
    to the oneshot driver at the same kv_fmt: quantization is deterministic
    (round-to-nearest against a bf16 scale, no RNG), so both drivers write
    and read bit-identical rows regardless of batching order."""
    model, params = make_model()
    engine = ContinuousEngine(model, params,
                              ServeConfig(max_slots=2, max_seq=24,
                                          kv_fmt=kv_fmt))
    specs = [(3, 6), (9, 3), (5, 2), (4, 7)]       # (prompt_len, gen)
    rids = [engine.submit(prompt_of(50 + i, pl), max_new_tokens=g)
            for i, (pl, g) in enumerate(specs)]
    out = engine.run()
    for i, (rid, (pl, g)) in enumerate(zip(rids, specs)):
        ref = oneshot_reference(model, params, prompt_of(50 + i, pl), g,
                                kv_fmt=kv_fmt)
        assert out[rid].tokens.tolist() == ref, (kv_fmt, i)


def test_release_zeroes_scale_rows_and_slot_reuse_is_clean():
    """Retiring a slot must zero its scale rows (zero scale dequantizes any
    stored codes to exactly 0), and a request decoded in a *reused* slot
    must produce the same tokens as the same request in a fresh engine —
    the regression for stale-scale leakage across slot generations."""
    model, params = make_model()
    serve = ServeConfig(max_slots=1, max_seq=16, kv_fmt="int8")
    engine = ContinuousEngine(model, params, serve)
    a = engine.submit(prompt_of(60, 5), max_new_tokens=4)
    b = engine.submit(prompt_of(61, 7), max_new_tokens=3)   # reuses slot 0
    out = engine.run()
    reused = out[b].tokens.tolist()
    # every request retired -> every slot's scale rows are zeroed again
    for name in ("k_scale", "v_scale"):
        assert name in engine.cache
        assert (np.asarray(engine.cache[name]) == 0.0).all()
    assert out[a].tokens.size == 4
    # same request, fresh slot generation: must match the reused-slot run
    engine.reset()
    b2 = engine.submit(prompt_of(61, 7), max_new_tokens=3)
    assert engine.run()[b2].tokens.tolist() == reused


def test_unquantized_cache_has_no_scale_arrays():
    """kv_fmt="none" keeps the original cache pytree (k, v, pos only) so
    the unquantized path pays zero memory or dispatch overhead."""
    model, params = make_model()
    engine = ContinuousEngine(model, params,
                              ServeConfig(max_slots=1, max_seq=8))
    assert sorted(engine.cache) == ["k", "pos", "v"]
    assert engine._release_scales is None


def test_engine_rejects_unsupported_kv_fmt():
    """ServeConfig validates against the global format list; the engine
    additionally validates against the *model family's* advertised
    kv_formats so unsupported combinations fail at construction."""
    with pytest.raises(ValueError, match="kv_fmt"):
        ServeConfig(kv_fmt="int4")                 # not a known format
    model, params = make_model()
    limited = dataclasses.replace(model, kv_formats=("none",))
    with pytest.raises(ValueError, match="does not support"):
        ContinuousEngine(limited, params,
                         ServeConfig(max_slots=1, max_seq=8, kv_fmt="int8"))


# --------------------------------------------------------------------------- #
# prefill bucketing (pow2 jit-cache bound)
# --------------------------------------------------------------------------- #
def test_prefill_bucketing_bounds_jit_cache():
    """Admission pads prompts to the next power of two, so a trace with
    many distinct prompt lengths compiles at most log2(max_seq) prefill
    programs instead of one per length."""
    model, params = make_model()
    max_seq = 32
    engine = ContinuousEngine(model, params,
                              ServeConfig(max_slots=2, max_seq=max_seq))
    lengths = [1, 2, 3, 5, 6, 9, 13, 17, 26]       # 9 distinct lengths
    rids = [engine.submit(prompt_of(70 + i, pl), max_new_tokens=2)
            for i, pl in enumerate(lengths)]
    out = engine.run()
    assert sorted(out) == sorted(rids)
    bound = math.ceil(math.log2(max_seq))
    assert engine.prefill_programs <= bound        # 5 buckets for these
    # bucketed (padded) prefill must not change the tokens
    for rid, pl in zip(rids[:2], lengths[:2]):
        ref = oneshot_reference(model, params, prompt_of(70 + rids.index(rid), pl), 2)
        assert out[rid].tokens.tolist() == ref


# --------------------------------------------------------------------------- #
# sampling key schedule (satellite: per-slot, per-position keys)
# --------------------------------------------------------------------------- #
def test_sampling_keys_unique_per_request_and_position():
    """No two (request, position) pairs may share a sampling key — in
    particular two slots decoding the same position draw independent
    bits (the legacy oneshot driver shared one key across the batch)."""
    def key_bits(k):
        try:                       # typed PRNG keys (newer jax defaults)
            return tuple(np.asarray(jax.random.key_data(k)).ravel().tolist())
        except TypeError:          # legacy raw uint32 key arrays
            return tuple(np.asarray(k).ravel().tolist())

    base = jax.random.PRNGKey(0)
    seen = {}
    for rid in range(6):
        for pos in range(20):
            k = key_bits(sampling_key(base, rid, pos))
            assert k not in seen, (rid, pos, seen[k])
            seen[k] = (rid, pos)
    # two slots, same position: distinct keys AND distinct drawn bits
    logits = jnp.zeros((VOCAB,))
    k0 = sampling_key(base, 0, 9)
    k1 = sampling_key(base, 1, 9)
    draws0 = [int(jax.random.categorical(jax.random.fold_in(k0, i), logits))
              for i in range(8)]
    draws1 = [int(jax.random.categorical(jax.random.fold_in(k1, i), logits))
              for i in range(8)]
    assert draws0 != draws1


def test_temperature_sampling_deterministic_across_runs():
    """Same seed + same request ids -> identical sampled tokens, because
    keys depend only on (seed, request_id, position), never on wall time."""
    model, params = make_model()
    serve = ServeConfig(max_slots=2, max_seq=20, temperature=0.9, seed=7)

    def one_run():
        engine = ContinuousEngine(model, params, serve)
        rids = [engine.submit(prompt_of(40 + i, 5), max_new_tokens=4)
                for i in range(3)]
        out = engine.run()
        return [out[r].tokens.tolist() for r in rids]

    assert one_run() == one_run()


# --------------------------------------------------------------------------- #
# decode trunk: the stacked cache carried through the layer scan
# --------------------------------------------------------------------------- #
def xs_ys_trunk(params, cache, token, pos, cfg, quant=None, kv_fmt="none"):
    """The decode trunk as it was when each layer's cache entered the scan
    as ``xs`` and left as ``ys``: the reference the carried trunk must
    match bit for bit."""
    from repro.models import common as cm
    from repro.models import transformer as tfm
    from repro.runtime.tracing import scope
    cd = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], token, axis=0).astype(cd)
    x = x * jnp.asarray(math.sqrt(cfg.d_model), cd)
    quantized = kv_fmt != "none"
    kvq, attend = tfm._kv_impls(kv_fmt, quant)
    write = jax.vmap(
        lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (0, p, 0)))
    swrite = jax.vmap(
        lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (0, p)))

    def project_qkv(x, blk):
        h = cm.rmsnorm(x, blk["attn_norm"]).astype(cd)
        q, k, v = (jnp.einsum("bd,dhk->bhk", h, blk[w].astype(cd))
                   for w in ("wq", "wk", "wv"))
        q = cm.rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        k = cm.rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        return q, k, v

    def write_kv(k, v, kc, vc, ksc, vsc):
        if quantized:
            (k, k_sc), (v, v_sc) = kvq(k), kvq(v)
            ksc = swrite(ksc, k_sc[:, :, None].astype(ksc.dtype), pos)
            vsc = swrite(vsc, v_sc[:, :, None].astype(vsc.dtype), pos)
        kc = write(kc, k[:, :, None, :].astype(kc.dtype), pos)
        vc = write(vc, v[:, :, None, :].astype(vc.dtype), pos)
        return kc, vc, ksc, vsc

    def attend_cache(q, kc, vc, ksc, vsc):
        return attend(q, kc, vc, ksc, vsc, pos, n_kv=cfg.n_kv_heads,
                      scale=1.0 / math.sqrt(cfg.head_dim))

    def project_out(ctx, wo):
        return jnp.einsum("bhk,hkd->bd", ctx.astype(cd), wo.astype(cd))

    def mlp(x, blk):
        h2 = cm.rmsnorm(x, blk["mlp_norm"]).astype(cd)
        gate = jnp.einsum("bd,df->bf", h2, blk["wi_gate"].astype(cd))
        up = jnp.einsum("bd,df->bf", h2, blk["wi_up"].astype(cd))
        act = tfm._activation(gate, up, cfg.mlp_activation)
        return jnp.einsum("bf,fd->bd", act, blk["wo_mlp"].astype(cd))

    names = ("k", "v", "k_scale", "v_scale") if quantized else ("k", "v")

    def body(carry, xs):
        blk, layer = xs[0], list(xs[1:]) + [None] * (4 - len(names))
        q, k, v = scope("attn_proj", project_qkv)(carry, blk)
        layer = scope("kv_write", write_kv)(k, v, *layer)
        ctx = scope("decode_attn", attend_cache)(q, *layer)
        x2 = carry + scope("attn_proj", project_out)(ctx, blk["wo"])
        return x2 + scope("mlp", mlp)(x2, blk), layer[:len(names)]

    x, ys = jax.lax.scan(body, x, (params["blocks"],)
                         + tuple(cache[n] for n in names))
    return (cm.rmsnorm(x, params["final_norm"]).astype(jnp.float32),
            dict(zip(names, ys)))


def filled_cache(model, n, max_seq, kv_fmt, pos):
    """A cache whose every row holds seeded values, as if prompts had
    been prefilled, with ``pos`` as given."""
    kw = {} if kv_fmt == "none" else {"kv_fmt": kv_fmt}
    spec = model.slot_cache_spec(n, max_seq, **kw)
    cache = {}
    for i, name in enumerate(sorted(set(spec) - {"pos"})):
        sds, key = spec[name], jax.random.PRNGKey(100 + i)
        if jnp.issubdtype(sds.dtype, jnp.integer):
            info = jnp.iinfo(sds.dtype)
            cache[name] = jax.random.randint(key, sds.shape, info.min,
                                             info.max, jnp.int32
                                             ).astype(sds.dtype)
        elif name.endswith("scale"):
            cache[name] = jax.random.uniform(key, sds.shape, jnp.float32,
                                             0.01, 0.1).astype(sds.dtype)
        else:
            cache[name] = jax.random.normal(key, sds.shape, sds.dtype)
    cache["pos"] = jnp.asarray(pos, jnp.int32)
    return cache


def _bits(a):
    a = np.asarray(a)
    return a if a.dtype.kind in "iub" else a.view(f"u{a.dtype.itemsize}")


@pytest.mark.parametrize("mode,kv_fmt,backend", [
    ("slots", "none", "ref"),
    ("slots", "int8", "ref"),
    ("slots", "int8", "pallas"),
    ("slots", "luq_fp4", "ref"),
    ("slots", "luq_fp4", "pallas"),
    ("lockstep", "int8", "pallas"),
])
def test_carried_cache_trunk_matches_xs_ys_trunk(mode, kv_fmt, backend,
                                                 monkeypatch):
    """Carrying the stacked cache through the layer scan and writing only
    the new rows gives the logits and every cache array of the xs/ys
    trunk, bit for bit, tick after tick: slots at staggered positions,
    one at position 0, inactive ones (one parked at max_seq, whose write
    is clamped onto the last row)."""
    from repro.configs import get_smoke_config
    from repro.models import transformer as tfm
    model = build_model(get_smoke_config("stablelm-3b"),
                        QuantConfig(fmt="none", backend=backend))
    params = model.init(jax.random.PRNGKey(0))
    max_seq, kw = 16, {} if kv_fmt == "none" else {"kv_fmt": kv_fmt}
    if mode == "slots":
        cache = filled_cache(model, 5, max_seq, kv_fmt, [0, 5, 11, 3, 16])
        active = jnp.asarray([True, True, False, True, False])

        def step(p, c, t):
            return model.decode_slots(p, c, t, active, **kw)
    else:
        cache = filled_cache(model, 3, max_seq, kv_fmt, 5)

        def step(p, c, t):
            return model.decode_step(p, c, t, **kw)
    tokens = jnp.arange(cache["k"].shape[1], dtype=jnp.int32) + 7
    # jit caches a trace by function: each side gets a function of its own
    carried = jax.jit(lambda *a: step(*a)).lower(params, cache,
                                                 tokens).compile()
    with monkeypatch.context() as m:
        m.setattr(tfm, "_decode_trunk", xs_ys_trunk)
        reference = jax.jit(lambda *a: step(*a)).lower(params, cache,
                                                       tokens).compile()
    got = want = cache
    for _ in range(4):
        logits, got = carried(params, got, tokens)
        ref_logits, want = reference(params, want, tokens)
        np.testing.assert_array_equal(_bits(logits), _bits(ref_logits))
        assert sorted(got) == sorted(want)
        for name in want:
            np.testing.assert_array_equal(_bits(got[name]),
                                          _bits(want[name]), err_msg=name)
        tokens = jnp.argmax(ref_logits, -1).astype(jnp.int32)
