"""Model FLOP utilisation of the whole DP step: forward and backward
FLOPs per image counted from shapes (bench/counts.py; ghost clipping's
recomputing pass not counted) times images per second, over the chip's
bf16 peak."""


def read(record):
    tr, pk = record.get("train"), record.get("peaks")
    if tr is None or pk is None or tr["images"] == 0:
        return None
    rate = tr["images"] / (tr["window_s"] - tr["profiler_s"])
    return 100.0 * tr["flops_per_image"] * rate / pk["bf16_flops"]
