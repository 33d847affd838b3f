"""Scheduler: mean decode batch, from the ``batch`` argument of the
pipeline's decode tick events in the traced span, in rows."""
DECODE = ("decode", "chunk+decode")


def read(record):
    rows = [e["args"]["batch"] for e in record["ticks"]
            if e["name"] in DECODE]
    if not rows:
        return None
    return sum(rows) / len(rows)
