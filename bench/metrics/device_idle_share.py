"""Device: share of the traced window in which no operation ran on the
chip (1 - union of op intervals / window, from the profiler trace), in %."""


def read(record):
    dev = record["device"]
    if not dev or not dev["window_s"]:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
