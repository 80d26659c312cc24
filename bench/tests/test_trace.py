"""The trace reduction (bench/trace.py): device busy union, idle gaps,
per-name op time and the naming of gaps by host spans, on hand-made
events and on a small trace recorded on a TPU v5e chip."""
from pathlib import Path

import pytest

from bench.trace import Event, Trace

DATA = Path(__file__).resolve().parent / "data"


def _synthetic():
    ops = [Event("fusion.1", 0, 10, {}), Event("fusion.2", 5, 15, {}),
           Event("luq_kernel", 30, 10, {}),
           Event("luq_kernel", 100, 10, {})]          # outside the window
    spans = [Event("bench.window", 0, 50, {}),
             Event("bench.tick", 18, 17, {}),
             Event("bench.admit", 38, 12, {}),
             Event("bench.engine_run", 0, 60, {})]
    return Trace(ops, [Event("jit_prefill_fn", 0, 20, {})], spans)


def test_busy_union_and_window():
    tr = _synthetic()
    assert tr.window() == (0, 50)
    assert tr.busy_intervals(0) == [(0, 20), (30, 40)]
    assert tr.busy_s() == pytest.approx(30e-9)
    assert tr.window_s() == pytest.approx(50e-9)


def test_op_time_per_name():
    tr = _synthetic()
    assert tr.op_time_s(lambda e: "luq" in e.name) == pytest.approx(10e-9)
    assert tr.module_time_s(lambda e: "prefill" in e.name) == \
        pytest.approx(20e-9)
    top = dict(tr.top_ops())
    assert top["fusion.2"] == pytest.approx(15e-9)
    assert top["luq_kernel"] == pytest.approx(10e-9)


def test_idle_gaps_named_by_innermost_span():
    gaps, totals = _synthetic().idle_gaps()
    assert gaps == [["bench.tick", pytest.approx(10e-9)],
                    ["bench.admit", pytest.approx(10e-9)]]
    assert set(totals) == {"bench.tick", "bench.admit"}


def test_recorded_chip_trace():
    path = DATA / "v5e_probe.xplane.pb"
    tr = Trace.load(str(path))
    assert tr.devices() == [0]
    t0, t1 = tr.window()
    assert t1 > t0
    busy = tr.busy_s()
    assert 0 < busy <= tr.window_s()
    # the probe ran the LUQ quantize and decode-attention kernels, each
    # three times, between host spans named bench.step
    assert any("bench.step" == s.name for s in tr.spans)
    luq = tr.op_time_s(lambda e: "luq" in e.name)
    attn = tr.op_time_s(lambda e: "decode_attn" in e.name)
    assert 0 < luq < busy and 0 < attn < busy
    gaps, totals = tr.idle_gaps()
    assert sum(totals.values()) == pytest.approx(tr.window_s() - busy,
                                                 rel=1e-6)


def test_enclosing_op_keeps_only_its_own_time():
    # a loop op that encloses its body's ops on the same line
    ops = [Event("%while.1 = (f32[]) while(...)", 0, 100, {}),
           Event("%fusion.2 = f32[8] fusion(...)", 10, 30, {}),
           Event("%luq_quantize.3 = f32[8] custom-call(...)", 50, 40, {})]
    tr = Trace(ops, [], [Event("bench.window", 0, 100, {})])
    own = {e.name.split(" = ")[0]: t for e, t in tr.self_times(0)}
    assert own == {"%while.1": 30, "%fusion.2": 30, "%luq_quantize.3": 40}
    assert tr.busy_s() == pytest.approx(100e-9)
    top = dict(tr.top_ops())
    assert top["%luq_quantize.3"] == pytest.approx(40e-9)
