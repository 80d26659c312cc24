"""Tokens served in the window (first tokens from prefill included), by
their stamps on the engine's clock, over the window's seconds, at the
cell's fixed offered rate."""


def read(record):
    sv = record.get("serve")
    if sv is None:
        return None
    T = sv["window_s"]
    n = sum(1 for r in sv["requests"] for s in r["stamps"] if s <= T)
    return n / T
