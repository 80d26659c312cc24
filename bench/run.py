"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's files are found by its name (``bench/workloads/<cell>.json``);
its driver builds the system from the seed, warms every shape the cell's
traffic uses, measures for ``--seconds``, then checks what the timed path
produced against the plain reference.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last the numbers
compared with their limits under ``checks``.  Without the TPU chips the
cell asks for it exits non-zero before any work and prints no result.

``--smoke`` runs the cell's reduced rehearsal sizes on whatever device
JAX has (the CPU here); it exists for rehearsals and tests only.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    return args


def main(argv=None) -> int:
    args = parse(argv)
    for p in (str(_ROOT / "src"), str(_ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness

    bench = harness.load_benchmark()
    cell = harness.load_cell(args.workload)
    devices = harness.require_chips(cell["chips"], allow_cpu=args.smoke)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    log = harness.CompileLog()
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / f"{cell['driver']}.py")
    record = driver.run(cell, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), smoke=args.smoke,
                        devices=devices, log=log)
    print("bench: compile " + json.dumps(
        {"cache_dir": cache_dir, **record["compile"]}), file=sys.stderr)

    metrics = harness.read_metrics(
        harness.cell_metrics(bench, args.workload, bool(args.trace)), record)
    print("bench: readings " + json.dumps(record["readings"]),
          file=sys.stderr)
    checks = record["checks"]
    for name, c in checks.items():
        print(f"bench: check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    device = harness.device_info(devices, record["memory_peak_bytes"])
    out = {"correct": bool(record["correct"]),
           "attempted": int(record["attempted"]),
           "failed": int(record["failed"]),
           "metrics": metrics, "device": device}
    if args.trace:
        tr = record["trace"]
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        gaps, _ = tr.idle_gaps(10)
        out["breakdown"] = {"device_ops": tr.top_ops(10), "idle_gaps": gaps}
    out["checks"] = checks
    sys.stdout.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
