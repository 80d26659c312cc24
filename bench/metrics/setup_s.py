"""Set-up seconds: loading, making weights and data, warming every shape
the window uses (compiling, or loading from the persistent cache), and
driving the first checked steps or requests."""


def read(record):
    return record["setup_s"]
