"""Scheduler: 95th percentile of the time from arrival to admission of
the requests admitted in the traced span (the program's own session
timestamps), in ms."""
import numpy as np


def read(record):
    waits = [a["admit"] - a["arrival"] for a in record["admitted"]]
    if not waits:
        return None
    return float(np.percentile(waits, 95)) * 1e3
