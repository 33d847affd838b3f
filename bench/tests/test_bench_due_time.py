"""The due-time arithmetic of the end-to-end metrics: TTFT counts from
when a request was due, ITL from consecutive deliveries in the window,
and a stall inserted in the window moves both tails."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, traffic


def fake_run(stall_at=None, stall=0.0):
    """200 requests due every 50 ms from t=0, each served 10 tokens: the
    first 40 ms after it is due, then one every 20 ms.  A stall slows the
    server for the second after ``stall_at``: deliveries in it land
    ``1 + stall`` times as far apart, and every later one ``stall``
    seconds late, while requests keep arriving on time."""
    recs = {}
    for i in range(200):
        due = i * 0.05
        times = [due + 0.04 + 0.02 * k for k in range(10)]
        if stall_at is not None:
            times = [t + stall * min(max(t - stall_at, 0.0), 1.0)
                     for t in times]
        session = SimpleNamespace(error=None)
        req = traffic.Request(idx=i, prompt=np.zeros(4, np.int32),
                              max_new=10, due=due)
        recs[i] = harness.Rec(req=req, due=due,
                              session=session,
                              submitted=due, times=times, counts=[1] * 10)
    driver = SimpleNamespace(recs=recs)
    win = harness.Window(opened=1.0, closed=9.0, ended=10.0)
    return harness.end_to_end(driver, win)


def test_steady_run():
    e2e = fake_run()
    assert e2e["attempted"] == 160           # due in [1, 9)
    assert e2e["failed"] == 0
    assert all(t == pytest.approx(0.04) for t in e2e["ttft_s"])
    assert all(g == pytest.approx(0.02) for g in e2e["itl_s"])
    m = harness.e2e_metrics(e2e)
    assert m["ttft_p95_ms"] == pytest.approx(40.0)
    assert m["itl_p95_ms"] == pytest.approx(20.0)
    assert m["output_tokens_per_s"] == pytest.approx(10 / 0.05, rel=0.01)


def test_stall_moves_both_tails():
    base = harness.e2e_metrics(fake_run())
    stalled = harness.e2e_metrics(fake_run(stall_at=5.0, stall=1.0))
    assert stalled["itl_p95_ms"] == pytest.approx(40.0)
    assert stalled["ttft_p95_ms"] > 10 * base["ttft_p95_ms"]
    # requests due after the stall wait for it: TTFT counts from due
    # time, not from when the server got round to them
    e2e = fake_run(stall_at=5.0, stall=1.0)
    assert max(e2e["ttft_s"]) == pytest.approx(1.04)


def test_request_without_first_token_fails():
    recs = {}
    for i in range(3):
        req = traffic.Request(idx=i, prompt=np.zeros(4, np.int32),
                              max_new=2, due=1.0 + i)
        recs[i] = harness.Rec(
            req=req, due=1.0 + i,
            session=SimpleNamespace(error=None),
            submitted=1.0 + i, times=[] if i == 1 else [1.5 + i],
            counts=[] if i == 1 else [1])
    out = harness.end_to_end(SimpleNamespace(recs=recs),
                             harness.Window(0.0, 5.0, 6.0))
    assert out["attempted"] == 3 and out["failed"] == 1
    assert len(out["ttft_s"]) == 2
