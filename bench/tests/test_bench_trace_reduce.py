"""Trace reduction on a small recorded trace: busy union, idle share,
time per program, idle gaps labelled by the host span around them."""
import pytest

from bench import trace_reduce as tr

MS = 1_000_000

# a 100 ms window: two decode ticks (overlapping ops), one prefill, host
# spans around pumps and one wait for an arrival
TRACE = {
    "ops": [("fusion.1", 10 * MS, 5 * MS), ("fusion.2", 12 * MS, 6 * MS),
            ("scatter", 30 * MS, 2 * MS), ("fusion.1", 40 * MS, 5 * MS),
            ("fusion.9", 70 * MS, 20 * MS), ("early", -5 * MS, 7 * MS)],
    "modules": [("jit_tick(1)", 10 * MS, 8 * MS),
                ("jit_tick(1)", 40 * MS, 5 * MS),
                ("jit_pf(2)", 70 * MS, 20 * MS),
                ("jit_scatter", 30 * MS, 2 * MS)],
    "host": [("bench.window", 0, 100 * MS), ("bench.pump", 8 * MS, 30 * MS),
             ("bench.pump", 39 * MS, 10 * MS),
             ("bench.wait", 50 * MS, 19 * MS),
             ("bench.pump", 69 * MS, 25 * MS)],
}


def test_window_of():
    assert tr.window_of(TRACE) == (0, 100 * MS)
    with pytest.raises(ValueError):
        tr.window_of({"host": []})


def test_union_merges_overlaps():
    assert tr.union([("a", 0, 10), ("b", 5, 10), ("c", 20, 5)]) == \
        [(0, 15), (20, 25)]


def test_reduce_busy_idle_programs_and_gaps():
    out = tr.reduce(TRACE, 0, 100 * MS)
    # busy: [0,2) clipped early op, [10,18), [30,32), [40,45), [70,90)
    assert out["busy_s"] == pytest.approx((2 + 8 + 2 + 5 + 20) * 1e-3)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["programs"]["jit_tick(1)"] == pytest.approx(13e-3)
    assert out["programs"]["jit_pf(2)"] == pytest.approx(20e-3)
    names = [n for n, _ in out["device_ops"]]
    assert names[0] == "fusion.9"
    gaps = out["idle_gaps"]
    # the longest gap [45, 70) is covered by pump then wait; its middle
    # (57.5 ms) sits in the wait
    assert gaps[0] == ["wait", pytest.approx(25e-3)]
    assert gaps[1] == ["pump", pytest.approx(12e-3)]     # [18, 30)
    assert sum(s for _, s in gaps) == pytest.approx(0.1 - out["busy_s"])


def test_reduce_keeps_top_entries_only():
    out = tr.reduce(TRACE, 0, 100 * MS, top=2)
    assert len(out["device_ops"]) == 2 and len(out["idle_gaps"]) == 2
