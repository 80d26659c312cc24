"""Roofline share of the ``decode_attn`` kernel: the bytes the algorithm
needs in the traced stretch (int8 codes and bf16 scales of the live
positions of the active slots, for every decode tick whose tokens were
stamped inside it; bench/counts.py) at the chip's HBM bandwidth, over the
kernel's summed device time.  Rows past a slot's live length are not
counted, so a kernel that stops at the live length reads higher."""
from bench import counts, kernels


def read(record):
    sv, tr, pk = record.get("serve"), record.get("trace"), \
        record.get("peaks")
    if sv is None or tr is None or pk is None or not sv["trace_engine"]:
        return None
    t0, t1 = sv["trace_engine"]
    live = [r["prompt_len"] + k for r in sv["requests"]
            for k, s in enumerate(r["stamps"]) if k > 0 and t0 <= s <= t1]
    busy = tr.op_time_s(lambda e: kernels.is_kernel(e, "decode_attn"))
    if not live or busy <= 0:
        return None
    need = counts.decode_attn_bytes(live, sv["model"])
    return 100.0 * need / pk["hbm_bytes_per_s"] / busy
