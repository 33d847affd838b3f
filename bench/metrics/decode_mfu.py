"""Model step: operations the decode ticks of the traced span need (from
true context lengths, ``bench/roofline.py``) over their summed host time
times the chip's peak, in %."""
from bench import roofline


def read(record):
    dm, peak = record["dims"], record["peaks"]["flops_per_s"]
    ops = secs = 0.0
    for p in record["pumps"]:
        if p.decoded and not p.segments:
            ops += roofline.decode_tick(dm, p.rows, p.context)[0]
            secs += p.t1 - p.t0
    if not secs:
        return None
    return 100.0 * ops / (secs * peak)
