"""Model step: operations the prefill ticks of the traced span need (true
fresh and cached lengths, ``bench/roofline.py``) over their summed host
time times the chip's peak, in %."""
from bench import roofline


def read(record):
    dm, peak = record["dims"], record["peaks"]["flops_per_s"]
    ops = secs = 0.0
    for p in record["pumps"]:
        if p.segments and not p.decoded:
            ops += roofline.prefill(dm, p.segments)[0]
            secs += p.t1 - p.t0
    if not secs:
        return None
    return 100.0 * ops / (secs * peak)
