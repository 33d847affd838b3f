"""Model step: operations the decode ticks of the traced span need (the
configuration's ``decode_tick`` count, from true context lengths) over
their summed host time times the chip's peak, in %."""


def read(record):
    count, peak = record["counts"], record["peaks"]["flops_per_s"]
    ops = secs = 0.0
    for p in record["pumps"]:
        if p.decoded and not p.segments:
            ops += count("decode_tick", p)[0]
            secs += p.t1 - p.t0
    if not secs:
        return None
    return 100.0 * ops / (secs * peak)
