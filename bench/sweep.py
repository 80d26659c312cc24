"""Find a serving cell's knee on the chip: its traffic at several offered
rates, one window each, in one process.

    python3 bench/sweep.py --workload stablelm-3b.chat --rates 2,3,4,5,6 \
        --seconds 40 --seed 1

For each rate prints one JSON line: requests offered and completed per
second, tokens per second, the queue (arrived, no first token yet) at the
middle and at the end of the window, and TTFT / ITL percentiles.  The knee
is the highest rate whose completions keep within 5% of the offered rate
and whose queue does not grow over the window.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]


def queue_at(requests, t):
    return sum(1 for r in requests if r["arrival"] <= t
               and not (r["stamps"] and r["stamps"][0] <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
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
    drv = harness.load_module(harness.BENCH / "drivers" / "serve.py")
    ses = drv.Session(cell, args.seed, args.smoke, harness.CompileLog())
    T = args.seconds
    for rate in (float(r) for r in args.rates.split(",")):
        ses.t["rate"] = rate
        win = ses.serve(args.seed, T, False)
        reqs = win["requests"]
        done = [r for r in reqs if r["done"] and r["stamps"]
                and r["stamps"][-1] <= T]
        record = {"serve": {"requests": reqs, "ticks": win["ticks"],
                            "window_s": T}}
        row = {"rate": rate, "offered": len(reqs) / T,
               "completed_per_s": len(done) / T,
               "queue_mid": queue_at(reqs, T / 2),
               "queue_end": queue_at(reqs, T)}
        ttft = [(r["stamps"][0] - r["arrival"]) if r["stamps"] else math.inf
                for r in reqs]
        row["ttft_p50_ms"] = 1000.0 * harness.nearest_rank(ttft, 0.5)
        for name in ("serve_tokens_per_s", "itl_p95_ms", "serve.tick_ms_p50"):
            reader = harness.load_module(harness.BENCH / "metrics"
                                         / f"{name}.py")
            row[name] = reader.read(record)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
