"""The reading of the program's own spans and device scopes
(bench/program_trace.py): framework names from a trace recorded on a TPU
v5e chip, program spans with their args, gaps named by the innermost span
with their offsets, and the numbers read from spans and scopes on
hand-made events."""
from pathlib import Path

import pytest

from bench import kernels
from bench.program_trace import (SCOPES, Op, ProgramTrace, boundary_ms_p50,
                                 main, tick_host_ms_p50, under_scope)
from bench.trace import Event

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6        # ns


def test_recorded_chip_trace_framework_names():
    tr = ProgramTrace.load(str(DATA / "v5e_probe.xplane.pb"))
    luq = [e for e in tr.ops if kernels.is_kernel(e, "luq")]
    attn = [e for e in tr.ops if kernels.is_kernel(e, "decode_attn")]
    assert luq and attn
    for e in luq:
        assert under_scope(e.tf_op, "luq_quantize")
        assert not under_scope(e.tf_op, "decode_attn_fused")
    for e in attn:
        assert under_scope(e.tf_op, "decode_attn_fused")
    pads = [e for e in tr.ops if e.name.startswith("%pad")]
    assert pads and all(under_scope(e.tf_op, "decode_attn_fused")
                        for e in pads)
    # the ops of the two wrappers are a part of the busy time
    both = (tr.scope_time_s("luq_quantize")
            + tr.scope_time_s("decode_attn_fused"))
    assert 0 < both <= tr.busy_s()


@pytest.mark.parametrize("tf_op,scope,hit", [
    ("jit(step_fn)/while/body/jit(decode_attn)/jit(_pad)/pad",
     "decode_attn", True),
    ("jit(epoch_fn)/while/body/transpose(jvp(jit(quantize)))/mul",
     "quantize", True),
    ("jit(epoch_fn)/jvp(vmap(jit(ghost_norm_pass)))/conv", "ghost_norm_pass",
     True),
    ("jit(f)/jit(g)/add;jit(f)/jit(quantize)/mul", "quantize", True),
    ("jit(f)/jit(luq_quantize)/pallas_call", "quantize", False),
    ("jit(f)/jit(ghost_norm_pass)/x", "ghost_norm", False),
    ("", "quantize", False),
])
def test_under_scope(tf_op, scope, hit):
    assert under_scope(tf_op, scope) is hit


def test_program_spans_and_args_load(tmp_path, capsys):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("serve.tick", tick=4, active=3, queued=1):
                with TraceAnnotation("serve.wait"):
                    jnp.ones(8).block_until_ready()
            with TraceAnnotation("train.select") as span:
                span.set_metadata(quantized=7)
    tr = ProgramTrace.load(str(tmp_path))
    assert [s.name for s in tr.spans] == ["bench.window"]
    (tick,) = tr.program("serve.tick")
    assert tick.stats == {"tick": 4, "active": 3, "queued": 1}
    wait = tr.child(tick, "serve.wait")
    assert wait is not None and wait.dur <= tick.dur
    assert tr.program("train.select")[0].stats == {"quantized": 7}
    assert main([str(tmp_path)]) == 0
    assert '"tick_host_ms_p50"' in capsys.readouterr().out


def test_gap_named_by_innermost_program_span():
    ops = [Event("fusion.1", 0, 10, {}), Event("fusion.2", 30, 20, {})]
    spans = [Event("bench.window", 0, 50, {}),
             Event("bench.epoch_host", 0, 50, {})]
    program = [Event("train.chunk", 5, 40, {"step": 0, "k": 9}),
               Event("train.account", 12, 4, {}),
               Event("host.gc", 18, 8, {"generation": 0, "collected": 3})]
    tr = ProgramTrace(ops, [], spans, program)
    assert tr.span_at(14) == "train.account"
    assert tr.span_at(25) == "host.gc"
    assert tr.span_at(17) == "train.chunk"
    # the gap from 10 to 30 ns, 10 ns into the window
    assert tr.gaps_at() == [["host.gc", pytest.approx(20e-9),
                             pytest.approx(10e-9)]]
    # the benchmark's own reduction names it by its spans alone
    assert tr.idle_gaps()[0] == [["host.gc", pytest.approx(20e-9)]]


def _chunk(step, start, end, wait_end, dispatch_start, k=9):
    return [Event("train.chunk", start, end - start, {"step": step, "k": k}),
            Event("train.dispatch", dispatch_start, 1 * MS, {}),
            Event("train.wait", wait_end - 2 * MS, 2 * MS, {})]


def _trace(program, ops=(), end=1000 * MS):
    return ProgramTrace(list(ops), [], [Event("bench.window", 0, end, {})],
                        program)


def test_boundary_median_skips_epoch_ends():
    program = (_chunk(0, 0, 100 * MS, 98 * MS, 5 * MS)
               + _chunk(9, 110 * MS, 200 * MS, 198 * MS, 118 * MS)
               + _chunk(18, 210 * MS, 300 * MS, 298 * MS, 222 * MS)
               + [Event("train.epoch_end", 301 * MS, 30 * MS, {})]
               + _chunk(27, 400 * MS, 500 * MS, 498 * MS, 401 * MS)
               + _chunk(36, 510 * MS, 600 * MS, 598 * MS, 526 * MS))
    # boundaries 20, 24 and 28 ms; the one across the epoch end is left out
    assert boundary_ms_p50(_trace(program)) == pytest.approx(24.0)


def test_boundary_needs_consecutive_chunks():
    program = (_chunk(0, 0, 100 * MS, 98 * MS, 5 * MS)
               + _chunk(27, 110 * MS, 200 * MS, 198 * MS, 118 * MS))
    assert boundary_ms_p50(_trace(program)) is None
    assert boundary_ms_p50(_trace([])) is None


def _ops():
    return [Op("%while.1", 0, 100, {}, tf_op="jit(epoch_fn)/while"),
            Op("%fusion.1", 0, 40, {},
               tf_op="jit(epoch_fn)/while/body/"
                     "jvp(vmap(jit(ghost_norm_pass)))/conv"),
            Op("%luq_quant.1", 40, 20, {},
               tf_op="jit(epoch_fn)/while/body/jvp(vmap(jit("
                     "ghost_norm_pass)))/jit(quantize)/luq_quant/"
                     "pallas_call"),
            Op("%luq_quant.2", 60, 10, {},
               tf_op="jit(epoch_fn)/while/body/transpose(jvp(jit("
                     "ghost_grad_pass)))/jit(quantize)/pallas_call"),
            Op("%fusion.2", 120, 80, {},
               tf_op="jit(epoch_fn)/jit(opt_update)/add")]


@pytest.mark.parametrize("scope,share", [
    # own times over the busy union (180 of the 200-ns window)
    ("ghost_norm_pass", 100.0 * 60 / 180),
    ("quantize", 100.0 * 30 / 180),
    ("opt_update", 100.0 * 80 / 180),
    (None, 100.0 * 30 / 180),       # the while loop's own time
])
def test_scope_shares(scope, share):
    assert _trace([], _ops(), 200).scope_share(scope) == pytest.approx(share)


def test_unscoped_share_counts_unnamed_ops():
    ops = _ops() + [Op("%copy.1", 200, 20, {})]
    # the while loop's own 30 ns and the unnamed copy's 20, of 200 busy
    assert _trace([], ops, 220).scope_share(None) == pytest.approx(25.0)


@pytest.mark.parametrize("scope", ["ghost_norm_pass", "quantize"])
def test_scope_shares_none_without_scopes(scope):
    unnamed = [Op(e.name, e.start, e.dur, {}) for e in _ops()]
    assert _trace([], unnamed, 200).scope_share(scope) is None
    # framework names without the program's scopes (a program that has
    # none) read nothing either, never 0
    plain = [Op(e.name, e.start, e.dur, {}, tf_op="jit(epoch_fn)/add")
             for e in _ops()]
    assert _trace([], plain, 200).scope_share(scope) is None


def test_tick_host_median():
    program = []
    for i, (tick, wait) in enumerate([(135, 131), (136, 133), (140, 132)]):
        t0 = i * 200 * MS
        program += [Event("serve.tick", t0, tick * MS,
                          {"tick": i, "active": 3, "queued": 0}),
                    Event("serve.dispatch", t0 + 0.5 * MS, 1 * MS, {}),
                    Event("serve.wait", t0 + 2 * MS, wait * MS, {})]
    # a tick that decoded nothing has no wait and is left out
    program.append(Event("serve.tick", 700 * MS, 1 * MS, {}))
    assert tick_host_ms_p50(_trace(program)) == pytest.approx(4.0)
    assert tick_host_ms_p50(_trace([])) is None


def test_decode_attn_scope_share():
    ops = [Op("%decode_attn.1", 0, 30, {},
              tf_op="jit(step_fn)/while/body/jit(decode_attn)/"
                    "decode_attn/pallas_call"),
           Op("%pad.1", 30, 20, {},
              tf_op="jit(step_fn)/while/body/jit(decode_attn)/jit(_pad)/pad"),
           Op("%fusion.3", 50, 50, {},
              tf_op="jit(step_fn)/while/body/jit(mlp)/dot_general")]
    assert _trace([], ops, 100).scope_share("decode_attn") == \
        pytest.approx(50.0)
    assert set(SCOPES) >= {"decode_attn", "mlp", "quantize"}
