"""Reduce a JAX profiler trace to device busy time, per-program time,
device time per named scope and idle gaps labelled by what the host was
doing.

``load(logdir)`` reads the newest ``*.xplane.pb`` under the profiler's
directory into plain tuples; ``reduce(...)`` works on those tuples only,
so it is tested on a small recorded trace with no chip.

- device planes are those whose name holds ``/device:TPU``; their
  ``XLA Ops`` lines give the intervals in which an operation ran, their
  ``XLA Modules`` lines the executions of whole compiled programs;
- an op's program is the module running when it started, and its scope
  the name path it was traced under with JAX's wrappers and the final
  primitive stripped (``jit(tick)/while/body/closed_call/attention/
  gqa_grouped/dot_general`` is ``attention/gqa_grouped``). The TPU's
  trace carries no path (an op event has its HLO text, offset and
  duration only), so ``reduce`` takes it from the ``op_name`` metadata
  of the program's optimized HLO (``hlo_paths``), by the op's ``head``;
- host spans are the ``TraceAnnotation`` events of the harness, named
  ``bench.<what>``; ``bench.window`` marks the traced window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)
# a device op also has its ``head`` (``%name = type``)


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
    heads: Dict[str, str] = {}
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
                e = (short_name(ev.name), int(ev.start_ns),
                     int(ev.duration_ns))
                if dest is out["ops"]:
                    h = head(ev.name)
                    e += (heads.setdefault(h, h),)
                dest.append(e)
    return out


#: the ``op_name`` in an HLO instruction's metadata
OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def head(text: str) -> str:
    """``%name = type`` of an HLO instruction's text, as the profiler
    prints it or as a module's text holds it (operands, attributes and
    metadata cut off)."""
    name, sep, rest = text.strip().removeprefix("ROOT ").partition(" = ")
    name = "%" + name.lstrip("%")
    if not sep:
        return name
    if rest.startswith("("):                 # a tuple: up to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if not depth:
                return f"{name} = {rest[:i + 1]}"
    return f"{name} = {rest.split(' ', 1)[0]}"


def hlo_paths(texts: Sequence[str]) -> Dict[str, str]:
    """Name path of each instruction in the HLO texts of one program's
    executables (a fusion carries its root's), by its ``head``; a head
    that two variants give different paths is left out, so a variant's
    op is never given another's scope."""
    out: Dict[str, Optional[str]] = {}
    for text in texts:
        for line in text.splitlines():
            m = OP_NAME.search(line)
            if m is not None and " = " in line:
                h, path = head(line), m.group(1)
                out[h] = path if out.get(h, path) == path else None
    return {k: v for k, v in out.items() if v is not None}


def short_name(name: str) -> str:
    """An op's name without its HLO text (``%fusion.3 = bf16[...] ...``)."""
    return name.split(" = ", 1)[0]


#: ops that only hold other ops (their children are on the same line)
CONTAINERS = ("%while", "%conditional", "%call")

#: the bucket of a program's ops traced under no model scope
UNSCOPED = "(unscoped)"

#: components of a name path that JAX adds around the model's own scopes
WRAPPERS = re.compile(r"^(?:(?:jit|pjit|vmap)\(.*\)|while|body|cond|"
                      r"branch_\d+|closed_call)$")


def scope_of(path: str) -> str:
    """The model scopes of an op's name path, outermost first, with JAX's
    wrappers (``jit(...)``, ``vmap(...)``, ``while/body``,
    ``closed_call``, ...), einsum's equation (``bkgd,bskd->bkgs``) and the
    final primitive stripped; of the paths XLA joins with ``;`` when it
    fuses, the last. ``UNSCOPED`` where no scope is left."""
    parts = [part for part in path.rsplit(";", 1)[-1].split("/")[:-1]
             if part and "->" not in part and not WRAPPERS.match(part)]
    return "/".join(parts) or UNSCOPED


def program_name(name: str) -> str:
    """A program's name without its id or suffix (``jit_tick(12)`` and
    ``jit_tick.3`` are ``jit_tick``)."""
    return name.split("(")[0].split(".")[0]


def clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    """Events cut to [lo, hi); those wholly outside are dropped."""
    out = []
    for name, s, d, *rest in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a, *rest))
    return out


def union(events: Sequence[Event]) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals covered by any event."""
    spans = sorted((s, s + d) for _, s, d, *_ in events)
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
    for name, _, d, *_ in events:
        out[name] += d * 1e-9
    return dict(out)


def per_scope(ops: Sequence[tuple], modules: Sequence[Event],
              hlo: Optional[Dict[str, Dict[str, str]]] = None
              ) -> Dict[str, Dict[str, float]]:
    """Seconds per program and scope, over ops that hold no other ops:
    each op goes to the program whose module ran when it started
    (``"(none)"`` outside every module) and to the scope of the name
    path ``hlo[program]`` (``hlo_paths``) gives its head, so a program's
    scopes sum to its op time."""
    hlo = hlo or {}
    mods = sorted((s, s + d, program_name(n)) for n, s, d, *_ in modules)
    starts = [m[0] for m in mods]
    out: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for name, s, d, *rest in ops:
        if name.startswith(CONTAINERS):
            continue
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and s < mods[i][1] else "(none)"
        path = hlo.get(prog, {}).get(rest[0] if rest else name, "")
        out[prog][scope_of(path)] += d * 1e-9
    return {p: dict(v) for p, v in out.items()}


def label_at(host: Sequence[Event], t: int) -> str:
    """Name of the innermost host span covering time ``t``."""
    best, best_d = "none", None
    for name, s, d in host:
        if s <= t < s + d and name != "bench.window" and \
                (best_d is None or d < best_d):
            best, best_d = name[len("bench."):], d
    return best


def reduce(trace: Dict[str, List[Event]], lo: int, hi: int,
           top: int = 10,
           hlo: Optional[Dict[str, Dict[str, str]]] = None) -> dict:
    """Busy and idle time of the device inside [lo, hi) ns.

    ``busy_s`` is the union of op intervals (one device; with several,
    the mean over devices is the caller's).  ``programs`` sums module
    time by program name, ``scopes`` op time by program and scope
    (``per_scope``, with the name paths ``hlo`` holds). ``top_scopes``
    (named ``program:scope``), ``device_ops`` and ``idle_gaps`` are the
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
    modules = clip(trace["modules"], lo, hi)
    scopes = per_scope(ops, modules, hlo)
    return {
        "busy_s": sum(b - a for a, b in busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "programs": per_name(modules),
        "scopes": scopes,
        "top_scopes": sorted(([f"{p}:{sc}", t] for p, v in scopes.items()
                              for sc, t in v.items()),
                             key=lambda kv: -kv[1])[:top],
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
