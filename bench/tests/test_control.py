"""The control — the plain reference in the precision below the one the
configuration states, put in the program's place — comes out as not
correct under each cell's committed limits.  Rehearsal sizes on the CPU;
the chip readings at the cells' own sizes are in PERF.md."""
import jax.numpy as jnp

from bench import harness


def _driver(cell):
    return harness.load_module(harness.BENCH / "drivers"
                               / f"{cell['driver']}.py")


def _fails(numbers, limits):
    compared = {k: v for k, v in limits.items() if v is not None}
    assert compared, "the cell commits no limit"
    return any(numbers[k] > v for k, v in compared.items())


def test_train_control_fails():
    cell = harness.load_cell("resnet18.dpquant")
    drv = _driver(cell)
    ses = drv.Session(cell, 7, True, harness.CompileLog())
    chk = ses.check_steps(7)
    ref = drv.reference_readings(ses.numbers, ses.t, chk)
    ctrl = drv.reference_readings(ses.numbers, ses.t, chk,
                                  dtype=jnp.bfloat16)
    assert _fails(drv.compare(ctrl, ref), cell["check"]["limits"])


def test_serve_control_fails():
    cell = harness.load_cell("stablelm-3b.chat")
    drv = _driver(cell)
    ses = drv.Session(cell, 7, True, harness.CompileLog())
    win = ses.serve(7, ses.t["readings_window_s"], False)
    ses.free()
    picked = drv.sample_served(win["served"], 7, ses.t["check_tokens"])
    got = drv.reference_gaps(ses.numbers, ses.run_seed, win["served"],
                             picked, ses.t["max_seq"], control=True)
    assert _fails(got, cell["check"]["limits"])
