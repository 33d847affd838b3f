"""Engine: time of the pipeline's prefill and chunk ticks in the traced
span per 1000 fresh (uncached) prompt tokens admitted in it, in ms."""
PREFILL = ("prefill", "chunk")


def read(record):
    secs = sum(e["dur"] for e in record["ticks"] if e["name"] in PREFILL)
    fresh = sum(a["seq_len"] - a["cached"] for a in record["admitted"])
    if not fresh or not secs:
        return None
    return secs / fresh * 1e6
