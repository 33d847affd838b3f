"""Kernels: the expert matmuls' share of their roofline in the traced
span: summed least time of the decode ticks' expert matmuls (the
configuration's ``moe_experts`` count: ``2 * 3 * d * expert_ff`` ops a
routed pair, each touched expert's three matrices read once, the routed
rows in and out) over the device time of ``jit_tick``'s ``moe/experts``
scope (with every scope under it), in %."""
from bench.roofline import least_time

PROGRAM = "jit_tick"
SCOPE = "moe/experts"


def read(record):
    dev = record["device"]
    if not dev:
        return None
    secs = sum(t for scope, t in dev["scopes"].get(PROGRAM, {}).items()
               if scope == SCOPE or scope.startswith(SCOPE + "/"))
    if not secs:
        return None
    count = record["counts"]
    need = sum(least_time(*count("moe_experts", p), record["peaks"])
               for p in record["pumps"] if p.decoded)
    if not need:
        return None
    return 100.0 * need / secs
