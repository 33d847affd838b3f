"""Engine: mean host time of the pipeline's decode ticks in the traced
span, in ms: each tick's duration less the ``engine.wait`` spans recorded
inside it (the read that waits for the tick's program), so the time the
host spends in the tick while the chip has no program of the tick queued.
Ticks and spans are the program's own trace events; a program that
records no spans (ticks without an ``id``) reads nothing."""
DECODE = ("decode", "chunk+decode")
WAIT = "engine.wait"


def read(record):
    waited = {}
    for e in record["ticks"]:
        if e["name"] == WAIT:
            parent = e["args"]["parent"]
            waited[parent] = waited.get(parent, 0.0) + e["dur"]
    host = [e["dur"] - waited.get(e["id"], 0.0) for e in record["ticks"]
            if e["name"] in DECODE and "id" in e]
    if not host:
        return None
    return sum(host) / len(host) * 1e3
