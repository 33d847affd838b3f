"""Engine: programs JAX lowered from the window's opening to the end of
its drain: the program's ``engine.lowerings`` gauge (a process-wide count
of lowerings, eager operations at a new shape included) in the registry
snapshot taken after the drain less the one taken as the window opened.
A program without the gauge reads nothing."""
GAUGE = "engine.lowerings"


def read(record):
    counters = record["counters"]
    opened = counters.get("open", {}).get("gauges", {}).get(GAUGE)
    closed = counters.get("closed", {}).get("gauges", {}).get(GAUGE)
    if opened is None or closed is None:
        return None
    return float(closed - opened)
