"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- configuration: the entry's ``file`` (``bench/configs/<config>.json``);
- traffic mix:   ``bench/traffic/<traffic>.json``;
- cell:          ``bench/cells/<workload>.json``;
- per-layer metric: ``bench/metrics/<metric>.py`` with ``read(record)``;
- plain reference: ``bench/references/<config["reference"]>.py``.

A reference owns its configuration's counts as well as its forward pass:
besides ``dims``, ``make_params`` and ``logits`` it exports
``count_<name>(dm, pump, events)`` for each name in ``COUNTS``, the
(ops, bytes) the roofline and utilization readers price a pump at
(``bench/roofline.py`` holds the dense GQA counts and ``least_time``).
A new architecture (sparse experts, window layers) is then new files
only: a configuration, a reference with its dims, weights, logits and
counts, a cell, and readers of its own scopes.

Adding any of them is adding a file and an entry; nothing here changes.
"""
from __future__ import annotations

import bisect
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional, Sequence

ROOT = Path(__file__).resolve().parent.parent
BENCH = Path(__file__).resolve().parent

#: the counts every reference exports, as ``count_<name>(dm, pump,
#: events)``: the decode tick's (ops, bytes), a list of one (ops, bytes)
#: per prefill dispatch, and the decode tick's attention's (ops, bytes)
COUNTS = ("decode_tick", "prefill", "decode_attention")


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a Python file by path (metric and reference names may hold
    dots, so they are not importable as package modules)."""
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path.relative_to(path.parents[1])))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with every file it names."""
    name: str
    entry: dict             # the workloads entry
    cell: dict              # bench/cells/<name>.json
    config: dict            # the configuration file
    config_name: str
    traffic: dict           # bench/traffic/<mix>.json
    end_to_end: List[dict]  # end-to-end metrics this cell reports
    per_layer: List[dict]   # per-layer metrics this cell reports
    root: Path

    def metric_reader(self, name: str) -> ModuleType:
        return load_module(self.root / "bench" / "metrics" / f"{name}.py")

    def reference(self) -> ModuleType:
        """The configuration's plain reference; raises AttributeError where
        it lacks one of the ``COUNTS``."""
        ref = load_module(self.root / "bench" / "references" /
                          f"{self.config['reference']}.py")
        missing = [f"count_{n}" for n in COUNTS
                   if not callable(getattr(ref, f"count_{n}", None))]
        if missing:
            raise AttributeError(f"reference {self.config['reference']!r} "
                                 f"lacks {', '.join(missing)}")
        return ref


class Counts:
    """A configuration's counts, bound to its sizes and to the program's
    trace events: ``counts(name, pump)`` is the reference's
    ``count_<name>(dm, pump, events)`` with the events (dicts with a
    ``ts``) that began inside the pump."""

    def __init__(self, ref: ModuleType, dm: dict,
                 events: Sequence[dict]) -> None:
        self._ref, self._dm = ref, dm
        self._events = sorted(events, key=lambda e: e["ts"])
        self._ts = [e["ts"] for e in self._events]

    def __call__(self, name: str, pump):
        i = bisect.bisect_left(self._ts, pump.t0)
        j = bisect.bisect_left(self._ts, pump.t1)
        return getattr(self._ref, f"count_{name}")(self._dm, pump,
                                                   self._events[i:j])


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def resolve(workload: str, root: Path = ROOT,
            bench: Optional[dict] = None) -> Cell:
    """The cell named ``workload`` with its configuration, traffic mix and
    metrics; raises KeyError for a name ``BENCHMARK.json`` lacks and
    FileNotFoundError for a file that is missing."""
    bench = bench if bench is not None else load_benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    entry = entries[workload]
    configs: Dict[str, dict] = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[entry["config"]]
    return Cell(
        name=workload, entry=entry,
        cell=_load_json(root / "bench" / "cells" / f"{workload}.json"),
        config=_load_json(root / cfg_entry["file"]),
        config_name=entry["config"],
        traffic=_load_json(root / "bench" / "traffic" /
                           f"{entry['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root)
