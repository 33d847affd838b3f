"""Device: ``peak_bytes_in_use`` over ``bytes_limit`` of the chip, read
after the window, in %."""


def read(record):
    mem = record["memory"]
    if not mem.get("peak") or not mem.get("limit"):
        return None
    return 100.0 * mem["peak"] / mem["limit"]
