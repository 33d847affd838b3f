"""The readers of the program's phase spans and lowering counter, on a
hand-built record: host time per tick (duration less its ``engine.wait``
spans), programs lowered over the window, nothing read from a program
that records neither, and the tick readers unmoved by spans beside the
ticks."""
import pytest

from bench import spec

READERS = {name: spec.load_module(spec.BENCH / "metrics" / f"{name}.py")
           for name in ("decode_host_ms", "window_lowerings",
                        "decode_tick_ms", "decode_batch_mean",
                        "prefill_ms_per_ktok")}


def tick(name, tid, ts, dur, batch):
    return {"name": name, "track": name, "ts": ts, "dur": dur, "id": tid,
            "args": {"batch": batch, "queue": 0, "live": batch}}


def span(name, parent, ts, dur, sid):
    return {"name": name, "track": name.split(".")[0], "ts": ts,
            "dur": dur, "id": sid, "args": {"parent": parent}}


TICKS = [tick("decode", 1, 0.000, 0.040, 4),
         tick("prefill", 2, 0.050, 0.300, 4),
         tick("chunk+decode", 3, 0.400, 0.060, 3),
         tick("chunk", 4, 0.500, 0.020, 3)]

SPANS = [span("engine.blocks", 1, 0.001, 0.002, 10),
         span("engine.dispatch", 1, 0.003, 0.002, 11),
         span("engine.wait", 1, 0.005, 0.030, 12),
         span("engine.stream", 1, 0.035, 0.001, 13),
         span("engine.dispatch", 2, 0.060, 0.010, 20),
         span("jax.lower", 20, 0.061, 0.005, 21),
         span("engine.wait", 2, 0.070, 0.200, 22),
         span("engine.splice", 2, 0.270, 0.020, 23),
         span("engine.wait", 2, 0.290, 0.005, 24),
         span("engine.wait", 3, 0.410, 0.045, 30),
         span("engine.dispatch", 4, 0.505, 0.005, 40)]


def record(ticks, gauges_open=None, gauges_closed=None):
    return {"ticks": ticks,
            "admitted": [{"arrival": 0.0, "admit": 0.05, "seq_len": 1500,
                          "cached": 0}],
            "counters": {"open": {"gauges": gauges_open or {}},
                         "closed": {"gauges": gauges_closed or {}}}}


def read(name, rec):
    return READERS[name].read(rec)


def test_decode_host_ms_is_tick_less_its_wait():
    rec = record(TICKS + SPANS)
    # decode: 40 - 30 ms; chunk+decode: 60 - 45 ms
    assert read("decode_host_ms", rec) == pytest.approx((10 + 15) / 2)


def test_window_lowerings_is_the_gauge_difference():
    rec = record(TICKS, {"engine.lowerings": 120},
                 {"engine.lowerings": 123})
    assert read("window_lowerings", rec) == 3.0
    assert read("window_lowerings", record(TICKS, {}, {})) is None


@pytest.mark.parametrize("name", ["decode_host_ms", "window_lowerings"])
def test_program_without_spans_reads_nothing(name):
    """A program that records no spans and no lowering gauge (its tick
    events carry no id) reads nothing, and does not raise."""
    bare = [{k: v for k, v in t.items() if k != "id"} for t in TICKS]
    assert read(name, record(bare)) is None


@pytest.mark.parametrize("name", ["decode_tick_ms", "decode_batch_mean",
                                  "prefill_ms_per_ktok"])
def test_tick_readers_ignore_spans(name):
    """Phase spans beside the tick events leave the tick readers exactly
    where the tick events alone put them."""
    bare = [{k: v for k, v in t.items() if k != "id"} for t in TICKS]
    alone = read(name, record(bare))
    assert alone is not None
    assert read(name, record(TICKS + SPANS)) == alone
    assert read(name, record(SPANS + TICKS)) == alone
