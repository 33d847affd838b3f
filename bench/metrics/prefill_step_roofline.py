"""Kernels: the packed-prefill program's share of its roofline in the
traced span: summed least time of the prefill dispatches (the
configuration's ``prefill`` count, one entry per dispatch) over the
device time of the ``jit_pf`` program in the profiler trace, in %."""
from bench.roofline import least_time

PROGRAM = "jit_pf"


def read(record):
    dev = record["device"]
    if not dev:
        return None
    secs = sum(v for k, v in dev["programs"].items()
               if k.split("(")[0].split(".")[0] == PROGRAM)
    count = record["counts"]
    need = sum(least_time(ops, nbytes, record["peaks"])
               for p in record["pumps"]
               for ops, nbytes in count("prefill", p))
    if not secs or not need:
        return None
    return 100.0 * need / secs
