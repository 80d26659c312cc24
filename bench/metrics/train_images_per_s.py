"""Images of every DP step finished in the window over the window's
seconds (host clock); the window holds the DPQuant analysis, selection,
sampling and accounting as well as the epoch programs."""


def read(record):
    tr = record.get("train")
    if tr is None:
        return None
    return tr["images"] / (tr["window_s"] - tr["profiler_s"])
