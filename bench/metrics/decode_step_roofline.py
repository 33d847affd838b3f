"""Kernels: the decode tick program's share of its roofline in the traced
span: summed least time of the ticks (``bench/roofline.py``) over the
device time of the ``jit_tick`` program in the profiler trace, in %."""
from bench import roofline

PROGRAM = "jit_tick"


def read(record):
    dev = record["device"]
    if not dev:
        return None
    secs = sum(v for k, v in dev["programs"].items()
               if k.split("(")[0].split(".")[0] == PROGRAM)
    need = sum(roofline.least_time(*roofline.decode_tick(
        record["dims"], p.rows, p.context), record["peaks"])
        for p in record["pumps"] if p.decoded)
    if not secs or not need:
        return None
    return 100.0 * need / secs
