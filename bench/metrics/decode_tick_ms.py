"""Engine: mean duration of the pipeline's decode tick events in the
traced span (a tick ends in the host read of the stop flags), in ms."""
DECODE = ("decode", "chunk+decode")


def read(record):
    durs = [e["dur"] for e in record["ticks"] if e["name"] in DECODE]
    if not durs:
        return None
    return sum(durs) / len(durs) * 1e3
