"""Reduce a JAX profiler trace to device busy time, per-program time and
idle gaps labelled by what the host was doing.

``load(logdir)`` reads the newest ``*.xplane.pb`` under the profiler's
directory into plain tuples; ``reduce(...)`` works on those tuples only,
so it is tested on a small recorded trace with no chip.

- device planes are those whose name holds ``/device:TPU``; their
  ``XLA Ops`` lines give the intervals in which an operation ran, their
  ``XLA Modules`` lines the executions of whole compiled programs;
- host spans are the ``TraceAnnotation`` events of the harness, named
  ``bench.<what>``; ``bench.window`` marks the traced window.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)


def load(logdir: str) -> Dict[str, List[Event]]:
    """``{"ops": [...], "modules": [...], "host": [...]}`` from the newest
    trace under ``logdir`` (ops and modules of every TPU plane)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {logdir}")
    data = ProfileData.from_file(paths[-1])
    out: Dict[str, List[Event]] = {"ops": [], "modules": [], "host": []}
    for plane in data.planes:
        device = "/device:TPU" in plane.name
        for line in plane.lines:
            if device and line.name == "XLA Ops":
                dest = out["ops"]
            elif device and line.name == "XLA Modules":
                dest = out["modules"]
            elif not device:
                dest = out["host"]
            else:
                continue
            for ev in line.events:
                if dest is out["host"] and not ev.name.startswith("bench."):
                    continue
                dest.append((short_name(ev.name), int(ev.start_ns),
                             int(ev.duration_ns)))
    return out


def short_name(name: str) -> str:
    """An op's name without its HLO text (``%fusion.3 = bf16[...] ...``)."""
    return name.split(" = ", 1)[0]


#: ops that only hold other ops (their children are on the same line)
CONTAINERS = ("%while", "%conditional", "%call")


def clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    """Events cut to [lo, hi); those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: Sequence[Event]) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals covered by any event."""
    spans = sorted((s, s + d) for _, s, d in events)
    merged: List[Tuple[int, int]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def per_name(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds per event name."""
    out: Dict[str, float] = defaultdict(float)
    for name, _, d in events:
        out[name] += d * 1e-9
    return dict(out)


def label_at(host: Sequence[Event], t: int) -> str:
    """Name of the innermost host span covering time ``t``."""
    best, best_d = "none", None
    for name, s, d in host:
        if s <= t < s + d and name != "bench.window" and \
                (best_d is None or d < best_d):
            best, best_d = name[len("bench."):], d
    return best


def reduce(trace: Dict[str, List[Event]], lo: int, hi: int,
           top: int = 10) -> dict:
    """Busy and idle time of the device inside [lo, hi) ns.

    ``busy_s`` is the union of op intervals (one device; with several,
    the mean over devices is the caller's).  ``programs`` sums module
    time by program name, ``device_ops`` and ``idle_gaps`` are the
    ``top`` largest, gaps labelled by the host span covering their
    middle."""
    ops = clip(trace["ops"], lo, hi)
    busy = union(ops)
    gaps = []
    prev = lo
    for a, b in busy + [(hi, hi)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    op_time = sorted(((n, t) for n, t in per_name(ops).items()
                      if not n.startswith(CONTAINERS)),
                     key=lambda kv: -kv[1])
    return {
        "busy_s": sum(b - a for a, b in busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "programs": per_name(clip(trace["modules"], lo, hi)),
        "device_ops": [[n, s] for n, s in op_time[:top]],
        "idle_gaps": [[label_at(trace["host"], (a + b) // 2),
                       (b - a) * 1e-9] for a, b in gaps[:top]],
    }


def window_of(trace: Dict[str, List[Event]]) -> Tuple[int, int]:
    """[start, end) ns of the harness's ``bench.window`` span."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == "bench.window"]
    if not spans:
        raise ValueError("trace holds no bench.window span")
    return spans[0]
