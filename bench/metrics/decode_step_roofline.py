"""Kernels: the decode tick program's share of its roofline in the traced
span: summed least time of the ticks (the configuration's ``decode_tick``
count) over the device time of the ``jit_tick`` program in the profiler
trace, in %."""
from bench.roofline import least_time

PROGRAM = "jit_tick"


def read(record):
    dev = record["device"]
    if not dev:
        return None
    secs = sum(v for k, v in dev["programs"].items()
               if k.split("(")[0].split(".")[0] == PROGRAM)
    count = record["counts"]
    need = sum(least_time(*count("decode_tick", p), record["peaks"])
               for p in record["pumps"] if p.decoded)
    if not secs or not need:
        return None
    return 100.0 * need / secs
