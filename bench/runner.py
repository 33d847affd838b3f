"""One run of one cell: set-up, window, per-layer record, comparison.

``run_cell`` is what ``bench/run.py`` calls on the chip; the knee sweep
(``bench/sweep.py``), the readings that limits are set from
(``bench/calibrate.py``) and the CPU tests call it too.
"""
from __future__ import annotations

import gc
import os
import shutil
import sys
import time
from collections import Counter
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import check, harness, spec, trace_reduce
from bench import traffic as traffic_mod


#: JAX's event for a program read from the persistent compilation cache
CACHE_HITS = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class GcPauses:
    """This process's garbage-collection pauses, from ``gc.callbacks``:
    (start, seconds, generation) for each collection while it is in that
    list (from the lead-in to the end of the drain)."""

    def __init__(self) -> None:
        self.pauses: List[Tuple[float, float, int]] = []
        self._t0: Optional[float] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.perf_counter() - self._t0,
                                info["generation"]))
            self._t0 = None


def log_stalls(driver, win: harness.Window, gcs: GcPauses) -> None:
    """The window's longest pump calls and its garbage collections, on
    stderr: a host stall shows as a long pump with no long collection."""
    in_win = [p for p in driver.pumps if win.opened <= p.t0 < win.closed]
    top = sorted(in_win, key=lambda p: p.t0 - p.t1)[:3]
    log("longest pumps in the window: " + ", ".join(
        f"{(p.t1 - p.t0) * 1e3:.1f} ms at +{p.t0 - win.opened:.2f}s "
        f"(decode {p.decoded}, {p.prefilled} prefill, "
        f"{sum(f for f, _ in p.segments)} fresh tokens)" for p in top))
    inside = [g for g in gcs.pauses if win.opened <= g[0] < win.closed]
    full = [g for g in gcs.pauses if g[2] == 2]
    longest = max(inside, key=lambda g: g[1], default=None)
    log(f"garbage collection: {len(inside)} collections in the window "
        f"({sum(g[2] == 2 for g in inside)} of generation 2), longest "
        + (f"{longest[1] * 1e3:.1f} ms (generation {longest[2]}) at "
           f"+{longest[0] - win.opened:.2f}s" if longest else "none")
        + f"; longest generation-2 collection since the lead-in "
        f"{max((g[1] for g in full), default=0.0) * 1e3:.1f} ms")


def per_layer_record(cell: spec.Cell, ref, dm: dict, peaks: dict, driver,
                     win: harness.Window, counters: dict,
                     device: Optional[dict], memory: dict) -> dict:
    """What the per-layer readers read, cut to the traced span; ``counts``
    prices a pump by the configuration's reference ``ref``."""
    lo, hi = win.traced if win.traced else (win.opened, win.closed)
    ticks = [e for e in driver.client.trace_events()
             if "dur" in e and lo <= e["ts"] < hi]
    admitted = [r.session for r in driver.recs.values()
                if r.req.idx >= 0 and r.session.prefill_time is not None
                and lo <= r.session.prefill_time < hi]
    return {
        "dims": dm, "peaks": peaks, "counts": spec.Counts(ref, dm, ticks),
        "block_size": cell.cell["serving"].get("block_size", 16),
        "span": (lo, hi),
        "pumps": [p for p in driver.pumps if lo <= p.t0 < hi],
        "ticks": ticks,
        "admitted": [{"arrival": s.arrival_time, "admit": s.prefill_time,
                      "seq_len": s.seq_len, "cached": s.cached_tokens}
                     for s in admitted],
        "counters": counters,
        "device": device,
        "memory": memory,
    }


def scope_paths(programs: harness.Programs, trace: dict, lo: int,
                hi: int) -> Dict[str, Dict[str, str]]:
    """``{program: {op: name path}}`` (``trace_reduce.hlo_paths``) for the
    programs that ran in [lo, hi), from their optimized HLO."""
    names = {trace_reduce.program_name(n)
             for n, *_ in trace_reduce.clip(trace["modules"], lo, hi)}
    return {n: trace_reduce.hlo_paths(programs.hlo_texts(n))
            for n in sorted(names)}


def run_cell(cell: spec.Cell, seed: int, seconds: float, *, trace: bool,
             peaks: dict, events: Counter, t_start: float,
             trace_dir: Optional[Path] = None, control: bool = False,
             rate: Optional[float] = None, warm: bool = True) -> dict:
    """One run; returns the result line's fields plus what the sweep and
    the calibration read (``extra``).  ``warm=False`` skips the warm-up,
    for a later run in a process that already ran every shape."""
    import jax
    programs = harness.Programs()
    if trace:
        programs.start()
    ref = cell.reference()
    dm = ref.dims(cell.config)
    cfg = harness.program_config(cell.config_name, cell.config)
    settings = dict(cell.cell)
    if rate is not None:
        settings["rate"] = rate
    mix = cell.traffic
    traffic = traffic_mod.build(mix, settings, dm["vocab"], seed, seconds)
    params = ref.make_params(cell.config, seed,
                             dtype=cell.config.get("torch_dtype",
                                                   "bfloat16"))
    jax.block_until_ready(params)
    client = harness.build_client(settings, cfg, params, trace=trace)
    del params
    driver = harness.Driver(client, annotate=trace)
    n_warm = harness.warm_up(driver, settings, traffic, dm["vocab"],
                             seed) if warm else 0
    log(f"warm-up: {n_warm} requests; {events['compiles']} compiles "
        f"({events['compile_seconds']:.1f}s) so far, persistent cache "
        f"hits={events[CACHE_HITS]}")
    compiles_setup = events["compiles"]
    counters: Dict[str, dict] = {}
    marks: Dict[str, float] = {}
    profiling = {"on": False}
    lowered = harness.CompileNames()

    def prepare():
        if trace:
            jax.profiler.start_trace(str(trace_dir))
            profiling["on"] = True

    def on_open():
        marks["setup_s"] = time.perf_counter() - t_start
        marks["compiles_open"] = events["compiles"]
        marks["hits_open"] = events[CACHE_HITS]
        counters["open"] = client.metrics()
        log(f"window opens: set-up {marks['setup_s']:.3f}s")
        lowered.start()

    def on_close():
        lowered.stop()
        marks["compiles_close"] = events["compiles"]
        marks["hits_close"] = events[CACHE_HITS]

    def on_trace_end():
        counters["traced"] = client.metrics()
        jax.profiler.stop_trace()
        profiling["on"] = False

    gcs = GcPauses()
    gc.callbacks.append(gcs)
    try:
        win = harness.drive(driver, traffic, settings, seconds,
                            on_prepare=prepare if trace else None,
                            on_open=on_open, on_close=on_close,
                            trace_seconds=(min(seconds,
                                               settings.get("trace_s", 6.0))
                                           if trace else 0.0),
                            on_trace_end=on_trace_end)
    finally:
        gc.callbacks.remove(gcs)
        if profiling["on"]:
            jax.profiler.stop_trace()
        lowered.stop()
        programs.stop()
    counters["closed"] = client.metrics()
    compiles_window = marks["compiles_close"] - marks["compiles_open"]
    hits_window = marks["hits_close"] - marks["hits_open"]
    log(f"window closed; drain ended {win.ended - win.closed:.3f}s later")
    e2e = harness.end_to_end(driver, win)
    late = e2e["late_s"]
    in_win = [p for p in driver.pumps if win.opened <= p.t0 < win.closed]
    dec = [p for p in in_win if p.decoded]
    log(f"window pumps: {len(in_win)}, {len(dec)} decode ticks of "
        f"{sum(p.rows for p in dec) / max(len(dec), 1):.2f} rows, "
        f"{sum(p.prefilled for p in in_win)} prefill dispatches; "
        f"{sum(p.t1 - p.t0 for p in in_win):.3f}s of "
        f"{win.closed - win.opened:.3f}s inside pump calls")
    log(f"lead-in and window: {compiles_window} compiles inside the "
        f"window ({hits_window} of them read from the persistent cache), "
        f"{marks['compiles_open'] - compiles_setup} in the "
        f"lead-in; generator lateness p50 "
        f"{harness.percentile(late, 50) * 1e3 if late else 0:.3f} ms, "
        f"max {max(late) * 1e3 if late else 0:.3f} ms; "
        f"{e2e['attempted']} requests due in the window, {e2e['failed']} "
        f"failed, {len(e2e['ttft_s'])} TTFT and {len(e2e['itl_s'])} ITL "
        f"samples, {e2e['tokens']} tokens")
    log_stalls(driver, win, gcs)
    if lowered.names:
        log("lowered inside the window: " + ", ".join(
            f"{k} x{v}" for k, v in lowered.names.most_common()))
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    memory = {"peak": stats.get("peak_bytes_in_use"),
              "limit": stats.get("bytes_limit")}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory["peak"]}
    reduced = None
    if trace:
        t = trace_reduce.load(str(trace_dir))
        lo, hi = trace_reduce.window_of(t)
        hlo = scope_paths(programs, t, lo, hi)
        reduced = trace_reduce.reduce(t, lo, hi, hlo=hlo)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        log("device programs (s): " + ", ".join(
            f"{k}={v:.6f}" for k, v in sorted(reduced["programs"].items(),
                                               key=lambda kv: -kv[1])[:12]))
        log("device scopes (s): " + ", ".join(
            f"{prog}:{scope}={t:.6f}"
            for prog, v in sorted(reduced["scopes"].items())
            for scope, t in sorted(v.items(), key=lambda kv: -kv[1])))
    record = per_layer_record(cell, ref, dm, peaks, driver, win, counters,
                              reduced, memory) if trace else None
    recs = list(driver.recs.values())
    # free the program's state before the reference runs
    driver.client = None
    del client
    gc.collect()
    t_ref = time.perf_counter()
    corr = settings["correctness"]
    result = check.compare(ref, cell.config, seed, recs,
                           corr["min_tokens"], corr["max_requests"],
                           control=control)
    log(f"reference: {result['requests']} requests, {result['tokens']} "
        f"tokens in {time.perf_counter() - t_ref:.1f}s")
    limit = corr.get("worst_gap_sigma")
    metrics: Dict[str, dict] = {}
    if not trace:
        values = harness.e2e_metrics(e2e)
        values["setup_s"] = marks["setup_s"]
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = cell.metric_reader(m["name"]).read(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {
        "correct": check.verdict(result, limit),
        "attempted": e2e["attempted"], "failed": e2e["failed"],
        "metrics": metrics, "device": device,
    }
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"],
                            "scopes": reduced["top_scopes"]}
    out["checks"] = {"worst_gap_sigma": {
        "value": result["worst_gap_sigma"], "limit": limit}}
    out["extra"] = {"check": result, "e2e": e2e,
                    "compiles_window": compiles_window,
                    "cache_hits_window": hits_window,
                    "queue_at_close": counters["closed"].get(
                        "gauges", {}).get("pipeline.queue_depth")}
    return out


def trace_path(root: Path) -> Path:
    """A fresh directory for this run's profiler trace, inside the
    checkout (listed in .gitignore)."""
    d = root / "bench" / ".trace"
    if d.exists():
        shutil.rmtree(d)
    d.mkdir(parents=True)
    return d


def compile_cache_dir(root: Path) -> str:
    return str(root / ".jax_cache")


def configure_jax(root: Path) -> None:
    """The persistent compilation cache at a fixed path inside the
    checkout, holding every program (the served path compiles small
    host-side operations per shape, and those are worth keeping too)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir(root))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def chip_peaks(root: Path, chips: int) -> dict:
    """The peak table's entry for the chips JAX finds; raises RuntimeError
    with no TPU, fewer chips than asked for, or a device kind the table
    lacks (a measurement never falls back to another device)."""
    import json
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise RuntimeError(f"needs {chips} TPU chip(s), JAX found "
                           f"{len(devices)} {devices[0].platform} device(s)")
    with open(root / "bench" / "peaks.json") as f:
        peaks = json.load(f)["devices"]
    kind = devices[0].device_kind
    if kind not in peaks:
        raise RuntimeError(f"device kind {kind!r} is not in "
                           "bench/peaks.json")
    return peaks[kind]


def env_for_cache(root: Path) -> None:
    """The compile cache and the TPU runtime's logs inside the checkout
    (the runtime would otherwise log to a fixed directory under /tmp)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir(root)
    os.environ.setdefault("TPU_LOG_DIR", str(root / "bench" / ".tpu_logs"))
