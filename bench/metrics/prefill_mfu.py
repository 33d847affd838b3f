"""Model step: operations the prefill ticks of the traced span need (the
configuration's ``prefill`` count, from true fresh and cached lengths)
over their summed host time times the chip's peak, in %."""


def read(record):
    count, peak = record["counts"], record["peaks"]["flops_per_s"]
    ops = secs = 0.0
    for p in record["pumps"]:
        if p.segments and not p.decoded:
            ops += sum(o for o, _ in count("prefill", p))
            secs += p.t1 - p.t0
    if not secs:
        return None
    return 100.0 * ops / (secs * peak)
