"""Share of the window (host clock, profiler start and stop left out)
spent outside the benchmark's spans around the Trainer's epoch-program
calls: DPQuant analysis, selection, Poisson sampling, batch gathering and
accounting.  Each span ends when its chunk has finished on the device."""


def read(record):
    tr = record.get("train")
    if tr is None or not tr["spans"]:
        return None
    inside = sum(t1 - t0 for t0, t1 in tr["spans"])
    return 100.0 * (1.0 - inside / (tr["window_s"] - tr["profiler_s"]))
