"""Device time of the prefill programs over the device's busy time, in the
traced stretch."""
from bench import kernels


def read(record):
    if record.get("serve") is None or record.get("trace") is None:
        return None
    tr = record["trace"]
    busy = tr.busy_s()
    if busy <= 0:
        return None
    return 100.0 * kernels.program_time_s(tr, "prefill") / busy
