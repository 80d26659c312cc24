"""95th percentile (nearest rank) of every gap between consecutive tokens
of a request, over all requests, for gaps that end inside the window."""
from bench.harness import nearest_rank


def read(record):
    sv = record.get("serve")
    if sv is None:
        return None
    T = sv["window_s"]
    gaps = [b - a for r in sv["requests"]
            for a, b in zip(r["stamps"], r["stamps"][1:]) if b <= T]
    if not gaps:
        return None
    return 1000.0 * nearest_rank(gaps, 0.95)
