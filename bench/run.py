"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` (see ``bench/spec.py``).  With ``--trace 0`` the
result line carries the cell's end-to-end metrics; with ``--trace 1``
its per-layer metrics, the device's busy time and a breakdown, read
from a profiler trace of the first seconds of the window.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared with its limit).  The last
lines of stderr repeat the numbers compared.

With no TPU, fewer chips than the cell asks for, a device kind missing
from ``bench/peaks.json``, or without the program beside it, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import Counter  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, runner, spec
    try:
        cell = spec.resolve(args.workload, ROOT)
    except (KeyError, FileNotFoundError) as e:
        return fail(f"cannot resolve {args.workload!r}: {e}")
    try:
        import repro  # noqa: F401
    except ImportError as e:
        return fail(f"the program is not beside the benchmark: {e}")
    runner.env_for_cache(ROOT)
    runner.configure_jax(ROOT)
    try:
        peaks = runner.chip_peaks(ROOT, int(cell.entry["chips"]))
    except RuntimeError as e:
        return fail(str(e))
    events: Counter = Counter()
    harness.count_compiles(events)
    out = runner.run_cell(
        cell, args.seed, args.seconds, trace=bool(args.trace),
        peaks=peaks, events=events, t_start=T_START,
        trace_dir=runner.trace_path(ROOT) if args.trace else None)
    out.pop("extra")
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
