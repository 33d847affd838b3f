"""Find an open-loop cell's knee on the chip: the highest offered rate at
which the backlog does not grow over the window.

    python3 bench/sweep.py --workload <cell> --rates 1,2,4,8 --seconds 20

Runs the cell once per rate in one process and prints one JSON line per
rate: TTFT median and tail, the median TTFT of the requests due in the
window's first and last thirds (a growing backlog makes the last third
wait longer), and the queue left at the close.  The cell's ``rate`` is
then set by hand to about four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
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
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        out = runner.run_cell(cell, args.seed, args.seconds, trace=False,
                              peaks=peaks, events=events,
                              t_start=time.perf_counter(), rate=rate,
                              warm=i == 0)
        e2e = out["extra"]["e2e"]
        ttft = e2e["ttft_s"]
        third = max(len(ttft) // 3, 1)
        print(json.dumps({
            "rate": rate,
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "ttft_first_third_ms": statistics.median(ttft[:third]) * 1e3,
            "ttft_last_third_ms": statistics.median(ttft[-third:]) * 1e3,
            "queue_at_close": out["extra"]["queue_at_close"],
            "attempted": out["attempted"], "failed": out["failed"],
            "correct": out["correct"],
            "compiles_window": out["extra"]["compiles_window"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
