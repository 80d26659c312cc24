"""Readings for the limits of a cell's correctness check, on the chip.

    python3 bench/readings.py --workload <cell> --program 1,2,... \
        --control 3,4,5 --fault 6,7,8

One process builds the cell once and reads, for each seed, the numbers the
check compares: from the program (the lower readings), from the control
(the reference in the precision below the configuration's, in the
program's place) and from the cell's planted faults (the upper readings).
Each row is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", default="")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    for p in (str(_ROOT / "src"), str(_ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell["chips"], allow_cpu=args.smoke)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / f"{cell['driver']}.py")

    last = [time.perf_counter()]

    def emit(kind, seed, numbers):
        now = time.perf_counter()
        print(json.dumps({"kind": kind, "seed": seed, **numbers,
                          "seconds": round(now - last[0], 3)}), flush=True)
        last[0] = now

    driver.readings(cell, program=_seeds(args.program),
                    control=_seeds(args.control), fault=_seeds(args.fault),
                    smoke=args.smoke, log=harness.CompileLog(), emit=emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
