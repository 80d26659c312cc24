"""Model FLOP utilisation of the whole served step over the traced
stretch: FLOPs of every prompt prefilled (unpadded) and every token
decoded there, counted from shapes (2 per weight plus attention over the
live positions; bench/counts.py), over the stretch's seconds and the
chip's bf16 peak."""
from bench import counts


def read(record):
    sv, tr, pk = record.get("serve"), record.get("trace"), \
        record.get("peaks")
    if sv is None or tr is None or pk is None or not sv["trace_engine"]:
        return None
    t0, t1 = sv["trace_engine"]
    m = sv["model"]
    flops = 0
    for r in sv["requests"]:
        for k, s in enumerate(r["stamps"]):
            if not t0 <= s <= t1:
                continue
            flops += (counts.prefill_flops(m, r["prompt_len"]) if k == 0
                      else counts.token_flops(m, r["prompt_len"] + k))
    if flops == 0:
        return None
    return 100.0 * flops / (t1 - t0) / pk["bf16_flops"]
