"""Model FLOP utilisation of the decode step, the program every decode
tick runs: FLOPs of every token the ticks of the window decoded (2 per
weight plus attention over its live positions; bench/counts.py), over
the ticks' summed host wall time (the engine's ``on_tick`` report) and
the chip's bf16 peak. Prefills run between ticks and are not counted."""
from bench import counts


def read(record):
    sv, pk = record.get("serve"), record.get("peaks")
    if sv is None or pk is None:
        return None
    T, m = sv["window_s"], sv["model"]
    busy = sum(w for now, w in sv["ticks"] if now <= T)
    flops = sum(counts.token_flops(m, r["prompt_len"] + k)
                for r in sv["requests"]
                for k, s in enumerate(r["stamps"]) if k > 0 and s <= T)
    if busy <= 0 or flops == 0:
        return None
    return 100.0 * flops / busy / pk["bf16_flops"]
