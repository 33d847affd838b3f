"""Readings the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 10

Runs the cell once per seed in one process (each run as ``bench/run.py``
makes it, at the cell's own size and load, with a short window) and
prints one JSON line per seed: the widest gap the program's served
tokens read against the plain reference and, for the control seeds, the
widest gap of the tokens the float8 reference puts first.  The limit in
``bench/cells/<cell>.json`` is set between the largest program reading
and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--log-compiles", action="store_true",
                    help="log every compile with its shapes to stderr")
    args = ap.parse_args(argv)
    from bench import harness, runner, spec
    cell = spec.resolve(args.workload, ROOT)
    runner.env_for_cache(ROOT)
    runner.configure_jax(ROOT)
    peaks = runner.chip_peaks(ROOT, int(cell.entry["chips"]))
    if args.log_compiles:
        import jax
        jax.config.update("jax_log_compiles", True)
    events: Counter = Counter()
    harness.count_compiles(events)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = runner.run_cell(cell, seed, args.seconds, trace=False,
                              peaks=peaks, events=events,
                              t_start=time.perf_counter(),
                              control=seed in control, warm=i == 0)
        check = out["extra"]["check"]
        print(json.dumps({"seed": seed, **check,
                          "correct": out["correct"],
                          "compiles_window":
                              out["extra"]["compiles_window"],
                          "cache_hits_window":
                              out["extra"]["cache_hits_window"],
                          "metrics": {k: v["value"] for k, v in
                                      out["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
