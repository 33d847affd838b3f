"""Kernels: the packed-prefill program's share of its roofline in the
traced span: summed least time of the prefill dispatches
(``bench/roofline.py``, one dispatch per admitted prompt) over the
device time of the ``jit_pf`` program in the profiler trace, in %."""
from bench import roofline

PROGRAM = "jit_pf"


def read(record):
    dev = record["device"]
    if not dev:
        return None
    secs = sum(v for k, v in dev["programs"].items()
               if k.split("(")[0].split(".")[0] == PROGRAM)
    need = sum(roofline.least_time(*roofline.prefill(
        record["dims"], [seg]), record["peaks"])
        for p in record["pumps"] for seg in p.segments)
    if not secs or not need:
        return None
    return 100.0 * need / secs
