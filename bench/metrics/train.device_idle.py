"""Share of the traced stretch in which no operation ran on the device."""


def read(record):
    if record.get("train") is None or record.get("trace") is None:
        return None
    tr = record["trace"]
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s())
