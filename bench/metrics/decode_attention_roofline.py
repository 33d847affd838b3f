"""Kernels: decode attention's share of its roofline in the traced span:
summed least time of the decode ticks' attention (the configuration's
``decode_attention`` count: each row's K and V read once at its true
length, q and the output) over the device time of the ``jit_tick``
scopes that gather the paged KV and attend (``paged_gather``, and
``attention`` with every scope under it), in %."""
from bench.roofline import least_time

PROGRAM = "jit_tick"
SCOPES = ("paged_gather", "attention")


def read(record):
    dev = record["device"]
    if not dev:
        return None
    secs = sum(t for scope, t in dev["scopes"].get(PROGRAM, {}).items()
               if scope.split("/")[0] in SCOPES)
    count = record["counts"]
    need = sum(least_time(*count("decode_attention", p), record["peaks"])
               for p in record["pumps"] if p.decoded)
    if not secs or not need:
        return None
    return 100.0 * need / secs
