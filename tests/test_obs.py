"""Unified serving observability (`repro.obs`): metrics registry
semantics, per-request span completeness, simulator-vs-wall-clock trace
structural parity, and the Chrome-trace exporter."""
import json
from collections import deque

import pytest

from repro.api import GenerationParams, TurboClient
from repro.core.cost_model import AnalyticCostModel
from repro.core.pipeline import ServingPipeline
from repro.core.simulator import (SimConfig, VirtualBackend, VirtualClock,
                                  Workload, simulate)
from repro.obs import (TERMINAL_EVENTS, Counter, Gauge, Histogram,
                       MetricsRegistry, Observability, TraceRecorder,
                       chrome_trace)
from repro.runtime.session import Session

CM = AnalyticCostModel(flops_per_token=1e6, bytes_per_token=1e3,
                       weight_bytes=1e6, overhead=1e-4)


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------

def test_histogram_empty():
    h = Histogram()
    assert h.count == 0 and h.total == 0.0
    assert h.min is None and h.max is None and h.mean == 0.0
    assert h.percentile(0.5) == 0.0 and h.percentile(1.0) == 0.0
    snap = h.snapshot()
    assert snap["count"] == 0 and snap["buckets"] == {}


def test_histogram_single_value_percentiles_exact():
    h = Histogram()
    h.observe(3.7)
    # clamping to observed [min, max] makes a single value exact at
    # every quantile, not "the bucket's upper edge"
    for q in (0.01, 0.5, 0.99, 1.0):
        assert h.percentile(q) == pytest.approx(3.7)
    assert h.min == h.max == pytest.approx(3.7)


def test_histogram_bucket_edges_and_overflow():
    h = Histogram(lo=1.0, growth=2.0, n=3)       # edges 1, 2, 4
    for v in (0.5, 1.0, 1.5, 4.0, 100.0):        # 100 -> overflow
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["buckets"]["+inf"] == 1          # only the 100
    assert snap["max"] == pytest.approx(100.0)
    # overflow percentile clamps to the observed max, never infinity
    assert h.percentile(1.0) == pytest.approx(100.0)


def test_histogram_nonpositive_lands_in_first_bucket():
    h = Histogram(lo=1e-6)
    h.observe(0.0)
    h.observe(-1.0)
    assert h.count == 2 and h.min == pytest.approx(-1.0)
    assert h.percentile(0.5) <= 0.0              # clamped to observed


def test_histogram_percentile_monotone():
    h = Histogram()
    for i in range(1, 200):
        h.observe(i * 1e-4)
    qs = [0.1, 0.5, 0.9, 0.99, 1.0]
    ps = [h.percentile(q) for q in qs]
    assert ps == sorted(ps)
    assert h.percentile(1.0) == pytest.approx(h.max)
    # log-bucketed: relative error bounded by the growth factor
    assert h.percentile(0.5) == pytest.approx(1e-2, rel=1.0)


def test_histogram_validation():
    with pytest.raises(ValueError):
        Histogram(lo=0.0)
    with pytest.raises(ValueError):
        Histogram(growth=1.0)
    with pytest.raises(ValueError):
        Histogram(n=0)
    with pytest.raises(ValueError):
        Histogram().percentile(0.0)
    with pytest.raises(ValueError):
        Histogram().percentile(1.5)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_create_on_first_use_and_identity():
    m = MetricsRegistry()
    c = m.counter("a.b")
    c.inc()
    c.inc(4)
    assert m.counter("a.b") is c and c.value == 5
    g = m.gauge("a.g")
    g.set(7)
    assert m.gauge("a.g").value == 7
    h = m.histogram("a.h")
    h.observe(0.5)
    assert m.histogram("a.h").count == 1


def test_registry_type_collision_raises():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")
    with pytest.raises(TypeError):
        m.histogram("x")


def test_registry_snapshot_shape():
    m = MetricsRegistry()
    m.counter("c").inc(3)
    m.gauge("g").set(9)
    m.histogram("h").observe(2.0)
    snap = m.snapshot()
    assert snap["counters"] == {"c": 3}
    assert snap["gauges"] == {"g": 9}
    assert snap["histograms"]["h"]["count"] == 1
    json.dumps(snap)                             # JSON-safe throughout


def test_disabled_registry_is_noop():
    m = MetricsRegistry(enabled=False)
    c, g, h = m.counter("c"), m.gauge("g"), m.histogram("h")
    c.inc(10)
    g.set(5)
    h.observe(1.0)
    assert c.value == 0 and g.value == 0 and h.count == 0
    assert m.snapshot() == {}
    # null instruments are shared singletons — no per-name allocation
    assert m.counter("other") is c
    assert isinstance(c, Counter) and isinstance(g, Gauge)


def test_disabled_registry_pipeline_runs_and_drains():
    # a disabled registry must not change scheduling: drain()'s
    # no-progress guard cannot read counters that never move
    obs = Observability(metrics=MetricsRegistry(enabled=False))
    clock = VirtualClock()
    cfg = SimConfig()
    backend = VirtualBackend(CM, clock, lambda t: t, cfg, {}, [])
    pipe = ServingPipeline(backend, CM, cfg.pipeline_config(), clock,
                           obs=obs)
    pipe.submit(Session(0, 4, 0.0, max_new_tokens=5))
    pipe.submit(Session(1, 7, 0.0, max_new_tokens=3))
    out = pipe.drain()
    assert len(out) == 2 and all(s.is_finished for s in out)
    assert pipe.obs.metrics.snapshot() == {}
    assert pipe.stats.decode_ticks == 0              # compat view: zeros
    assert pipe.stats.admitted == 0


# ---------------------------------------------------------------------------
# Pipeline integration: stats fold + spans
# ---------------------------------------------------------------------------

def test_stats_property_mirrors_registry():
    client = TurboClient.simulated(cost_model=CM)
    for i in range(3):
        client.submit([1, 2, 3, i], GenerationParams(max_new_tokens=4))
    client.drain()
    stats = client.pipeline.stats
    snap = client.metrics()
    assert stats.admitted == 3
    for field in ("prefill_ticks", "decode_ticks", "admitted",
                  "cancelled"):
        assert getattr(stats, field) == \
            snap["counters"]["pipeline." + field]
    assert snap["histograms"]["pipeline.ttft_seconds"]["count"] == 3
    assert snap["histograms"]["pipeline.tick_seconds"]["count"] >= 1
    assert snap["counters"]["pipeline.tokens_delivered"] == \
        sum(len(s.generated) for s in client.pipeline.finished)


def _span_names(client, rid):
    return client.obs.trace.request_names(rid)


def test_span_completeness_normal_finish():
    client = TurboClient.simulated(cost_model=CM, trace=True)
    h = client.submit([1, 2, 3], GenerationParams(max_new_tokens=4))
    h.result()
    names = _span_names(client, h.req_id)
    assert names[0] == "enqueue" and names[-1] == "finish"
    assert sum(1 for n in names if n in TERMINAL_EVENTS) == 1
    for marker in ("admit", "prefill", "splice", "decode", "stream"):
        assert marker in names
    fin = client.obs.trace.request_events(h.req_id)[-1]
    assert fin["args"]["reason"] == "budget"
    assert fin["args"]["generated"] == 4


def test_span_exactly_one_terminal_under_cancel():
    # cancel in every live state: QUEUED, mid-chunked-prefill, mid-DECODE
    cfg = SimConfig(chunked_prefill=True, kv_block_size=16,
                    prefill_chunk_tokens=64)
    client = TurboClient.simulated(cost_model=CM, sim_config=cfg,
                                   trace=True)
    anchor = client.submit([1] * 8, GenerationParams(max_new_tokens=12))
    client.pump(max_ticks=2)                     # anchor reaches DECODE
    long = client.submit([2] * 600, GenerationParams(max_new_tokens=8))
    client.pump(max_ticks=2)                     # long begins chunking
    assert long.session.state.value == "prefill"
    queued = client.submit([3] * 4, GenerationParams(max_new_tokens=4))
    assert queued.session.state.value == "queued"
    assert long.cancel() and queued.cancel() and anchor.cancel()
    client.drain()
    for h, was in ((queued, "queued"), (long, "prefill"),
                   (anchor, "decode")):
        names = _span_names(client, h.req_id)
        assert names[-1] == "cancel", (h.req_id, names)
        assert sum(1 for n in names if n in TERMINAL_EVENTS) == 1
        ev = client.obs.trace.request_events(h.req_id)[-1]
        assert ev["args"]["was"] == was


def test_every_submitted_session_gets_one_terminal():
    wl = Workload(rate=60, duration=0.4, len_min=4, len_max=30, seed=3,
                  gen_tokens=8, gen_min=2)
    res = simulate(wl, CM, SimConfig(), trace=True)
    by_req = {}
    for ev in res.trace:
        if ev["track"] == "request":
            by_req.setdefault(ev["req"], []).append(ev["name"])
    assert len(by_req) == res.offered
    for rid, names in by_req.items():
        assert names[0] == "enqueue"
        assert sum(1 for n in names if n in TERMINAL_EVENTS) == 1, rid
        assert names[-1] in TERMINAL_EVENTS


def test_chunked_prefill_span_has_chunk_events():
    cfg = SimConfig(chunked_prefill=True, kv_block_size=16,
                    prefill_chunk_tokens=64)
    client = TurboClient.simulated(cost_model=CM, sim_config=cfg,
                                   trace=True)
    anchor = client.submit([1] * 8, GenerationParams(max_new_tokens=16))
    client.pump(max_ticks=2)
    long = client.submit([2] * 600, GenerationParams(max_new_tokens=4))
    anchor.result()
    long.result()
    names = _span_names(client, long.req_id)
    chunks = [ev for ev in client.obs.trace.request_events(long.req_id)
              if ev["name"] == "prefill"]
    assert len(chunks) > 1                       # resumable, not one pass
    assert chunks[-1]["args"]["upto"] == 600
    assert sum(c["args"]["fresh"] + c["args"]["cached"]
               for c in chunks) >= 600
    assert "splice" in names and names[-1] == "finish"


# ---------------------------------------------------------------------------
# Sim-vs-wall-clock structural parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def real_client():
    client = TurboClient.from_arch(
        "internlm2-1.8b", seq_buckets=(32, 64), batch_buckets=(1, 2, 4),
        max_slots=4, cap_new=16, warmup=False, cost_model=CM,
        trace=True)
    yield client
    client.close()


def test_trace_parity_sim_vs_real(real_client):
    """The same submissions produce STRUCTURALLY identical spans under
    the wall-clock engine and the virtual-clock simulator: same event
    names in the same order, chunk/decode event for chunk/decode tick
    — only the timestamps differ."""
    prompts = [[1, 2, 3], [4, 5, 6, 7], [7, 8, 9, 1, 2]]
    budgets = [4, 3, 5]

    sim = TurboClient.simulated(cost_model=CM, trace=True)
    spans = {}
    for client in (real_client, sim):
        handles = []
        for p, b in zip(prompts, budgets):
            handles.append(client.submit(
                list(p), GenerationParams(max_new_tokens=b)))
        for h in handles:
            h.result()
        spans[client] = [client.obs.trace.request_names(h.req_id)
                         for h in handles]
    assert spans[real_client] == spans[sim]
    # and the span structure is the lifecycle the budget implies:
    # 1 enqueue/admit/prefill/splice, budget-1 decode ticks after the
    # splice token, budget streamed, one finish
    for names, b in zip(spans[sim], budgets):
        assert names.count("decode") == b - 1
        assert names.count("finish") == 1


def test_real_engine_metrics_gauges(real_client):
    h = real_client.submit([5, 6, 7], GenerationParams(max_new_tokens=4))
    h.result()
    snap = real_client.metrics()
    g = snap["gauges"]
    assert g["engine.lowerings"] >= 1
    assert g["engine.prefill_tokens"] >= 3
    assert g["kv.blocks_free"] >= 0 and g["kv.capacity_tokens"] > 0
    assert g["kv.live_tokens"] == 0              # drained
    assert snap["counters"]["pipeline.admitted"] >= 1


# ---------------------------------------------------------------------------
# Chrome-trace exporter
# ---------------------------------------------------------------------------

def test_chrome_trace_structure(tmp_path):
    client = TurboClient.simulated(cost_model=CM, trace=True)
    h1 = client.submit([1, 2, 3], GenerationParams(max_new_tokens=4))
    h2 = client.submit([4, 5], GenerationParams(max_new_tokens=3))
    h1.result()
    h2.result()
    out = tmp_path / "trace.json"
    doc = client.save_trace(str(out))
    reread = json.loads(out.read_text())
    assert reread == doc
    evs = doc["traceEvents"]
    assert all(isinstance(e["ph"], str) for e in evs)
    procs = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs == {"scheduler", "requests"}
    ticks = [e for e in evs if e["ph"] == "X" and e["cat"] == "tick"]
    assert ticks and all(e["dur"] >= 1 for e in ticks)
    assert {"prefill", "decode"} <= {e["name"] for e in ticks}
    # every request: a connected flow chain with exactly one end
    flows = [e for e in evs if e["name"] == "req-flow"]
    starts = [e for e in flows if e["ph"] == "s"]
    ends = [e for e in flows if e["ph"] == "f"]
    assert len(starts) == 2 and len(ends) == 2
    assert all(e["bp"] == "e" for e in ends)
    # phase slices per request: queued -> prefill -> decode
    req_slices = [e for e in evs
                  if e["ph"] == "X" and e.get("cat") == "request"]
    assert {"queued", "prefill", "decode"} <= \
        {e["name"] for e in req_slices}
    # timestamps normalized to non-negative microseconds
    assert min(e["ts"] for e in evs if "ts" in e) >= 0


def test_chrome_trace_live_request_gets_open_slice():
    client = TurboClient.simulated(cost_model=CM, trace=True)
    client.submit([1, 2, 3], GenerationParams(max_new_tokens=50))
    client.pump(max_ticks=3)                     # mid-decode, not done
    doc = client.obs.trace.chrome_trace()
    live = [e for e in doc["traceEvents"]
            if e.get("cat") == "request" and e["ph"] == "X"
            and e["name"].endswith("(live)")]
    assert len(live) == 1


def test_recorder_cap_counts_drops():
    rec = TraceRecorder(max_events=3)
    for i in range(5):
        rec.record("tick", "decode", float(i))
    assert len(rec.events) == 3 and rec.dropped == 2
    assert chrome_trace(rec.events)["traceEvents"]


def test_trace_off_costs_nothing_and_trace_events_empty():
    client = TurboClient.simulated(cost_model=CM)
    h = client.submit([1, 2, 3], GenerationParams(max_new_tokens=4))
    h.result()
    assert client.obs.trace is None
    assert client.trace_events() == []
    with pytest.raises(RuntimeError):
        client.save_trace("nope.json")


# ---------------------------------------------------------------------------
# Client ITL telemetry: bounded buffers + histogram percentiles
# ---------------------------------------------------------------------------

def test_handle_itl_ring_buffer_bounded():
    client = TurboClient.simulated(cost_model=CM)
    h = client.submit([1, 2, 3], GenerationParams(max_new_tokens=40))
    h._token_times = deque(maxlen=8)     # shrink the telemetry ring
    h.result()
    assert len(h.tokens()) == 40                 # results never truncated
    assert len(h._token_times) == 8              # telemetry ring bounded
    assert len(h.inter_token_latencies()) == 7   # window-local gaps
    # the histogram saw EVERY gap, not just the window
    assert h._itl_hist.count == 39
    assert h.itl_percentile(0.5) >= 0.0
    assert h.ttft is not None and h.ttft >= 0.0  # survives the ring


def test_handle_itl_matches_full_history_when_short():
    client = TurboClient.simulated(cost_model=CM)
    h = client.submit([1, 2, 3], GenerationParams(max_new_tokens=6))
    streamed = list(h.stream())
    itls = h.inter_token_latencies()
    assert len(itls) == len(streamed) - 1
    assert h._itl_hist.count == len(itls)
    assert h.itl_percentile(1.0) == pytest.approx(max(itls))


# ---------------------------------------------------------------------------
# Phase spans inside the served tick, and the lowering counter
# ---------------------------------------------------------------------------

TICK_KINDS = ("prefill", "decode", "chunk", "chunk+decode")
PHASES = ("sched.admit", "engine.blocks", "engine.dispatch", "engine.wait",
          "engine.stream", "engine.pack", "engine.sample", "engine.splice",
          "pipeline.deliver", "pipeline.observe")


def _serve(client, n=3, budget=5):
    """Greedy and sampled requests of different lengths, to the end."""
    handles = [client.submit(
        list(range(1, 6 + 7 * i)),
        GenerationParams(max_new_tokens=budget, temperature=0.7 * (i % 2),
                         seed=i)) for i in range(n)]
    for h in handles:
        h.result()


def _ticks(events):
    """Tick events (request events reuse the names ``prefill`` and
    ``decode`` on the ``request`` track)."""
    return [e for e in events if e["name"] in TICK_KINDS
            and e["track"] == e["name"]]


def _small_client(trace):
    return TurboClient.from_arch(
        "internlm2-1.8b", seq_buckets=(32, 64), batch_buckets=(1, 2, 4),
        max_slots=4, cap_new=16, warmup=False, cost_model=CM, trace=trace)


def test_recorder_span_nesting_and_ids():
    clock = iter(range(100)).__next__
    rec = TraceRecorder()
    rec.clock = lambda: float(clock())
    tick_id = rec.begin()
    with rec.span("engine.dispatch") as outer:
        assert rec.innermost() == outer
        with rec.span("engine.sample") as inner:
            assert rec.innermost() == inner
    rec.end(tick_id)
    rec.tick("decode", 0.0, 10.0, tick_id, batch=1)
    assert rec.innermost() is None
    by_name = {e["name"]: e for e in rec.events}
    assert by_name["engine.sample"]["args"]["parent"] == outer
    assert by_name["engine.dispatch"]["args"]["parent"] == tick_id
    assert by_name["decode"]["id"] == tick_id
    assert by_name["decode"]["args"] == {"batch": 1}
    assert by_name["engine.dispatch"]["track"] == "engine"
    # an exception inside a span still closes and records it
    with pytest.raises(KeyError):
        with rec.span("sched.admit"):
            raise KeyError("x")
    assert rec.events[-1]["name"] == "sched.admit"
    assert rec.innermost() is None


def test_phase_spans_lie_inside_their_tick(real_client):
    rec = real_client.obs.trace
    start = len(rec.events)
    _serve(real_client)
    evs = rec.events[start:]
    ticks = {e["id"]: e for e in _ticks(evs)}
    spans = [e for e in evs if e["name"] in PHASES]
    assert ticks and {e["name"] for e in spans} >= {
        "sched.admit", "engine.blocks", "engine.dispatch", "engine.wait",
        "engine.stream", "engine.pack", "engine.splice",
        "pipeline.deliver", "pipeline.observe"}
    assert not {e["name"] for e in spans} & set(TICK_KINDS)
    by_id = {e["id"]: e for e in spans}
    for s in spans:
        parent = s["args"]["parent"]
        if s["name"] == "engine.sample":
            # first-token sampling runs inside the packed prefill's splice
            assert by_id[parent]["name"] == "engine.splice"
            parent = by_id[parent]["args"]["parent"]
        t = ticks[parent]
        assert t["ts"] <= s["ts"]
        assert s["ts"] + s["dur"] <= t["ts"] + t["dur"]
    # the tick events keep their names, tracks and args
    for t in ticks.values():
        assert t["track"] == t["name"]
        assert set(t["args"]) == {"batch", "queue", "live"}


def test_every_dispatching_tick_waits_once(real_client):
    rec = real_client.obs.trace
    start = len(rec.events)
    _serve(real_client)
    evs = rec.events[start:]
    ticks = [e for e in _ticks(evs) if e["name"] in ("prefill", "decode")]
    assert {t["name"] for t in ticks} == {"prefill", "decode"}
    for t in ticks:
        inside = [e["name"] for e in evs
                  if e.get("args", {}).get("parent") == t["id"]]
        assert inside.count("engine.dispatch") == 1
        assert inside.count("engine.wait") == 1


def test_idle_tick_records_nothing(real_client):
    """Ticks that execute nothing record no tick event, so they leave no
    spans behind either (a span's parent is always a recorded tick)."""
    rec = real_client.obs.trace
    start = len(rec.events)
    for _ in range(5):
        real_client.pipeline.tick()
    assert rec.events[start:] == []
    _serve(real_client, n=1)
    ticks = {e["id"] for e in _ticks(rec.events[start:])}
    spans = {e["id"]: e for e in rec.events[start:]
             if "parent" in e.get("args", {}) and "id" in e}
    for s in spans.values():
        up = s
        while up["args"]["parent"] in spans:
            up = spans[up["args"]["parent"]]
        assert up["args"]["parent"] in ticks


def test_chrome_trace_nests_spans_in_their_tick(real_client):
    rec = real_client.obs.trace
    start = len(rec.events)
    _serve(real_client, n=1)
    doc = chrome_trace(rec.events[start:])
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"
          and e["pid"] == 1]
    ticks = [e for e in xs if e["cat"] == "tick"]
    for s in (e for e in xs if e["cat"] == "span"
              and e["name"] != "jax.lower"):
        assert any(t["tid"] == s["tid"] and t["ts"] <= s["ts"] and
                   s["ts"] + s["dur"] <= t["ts"] + t["dur"]
                   for t in ticks), s


def test_tracing_off_records_nothing_and_never_annotates(monkeypatch):
    import contextlib

    import jax
    calls = []

    def fake(name):
        calls.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", fake)
    client = _small_client(trace=False)
    _serve(client, n=2)
    assert client.obs.trace is None and client.backend.trace is None
    assert client.trace_events() == []
    assert calls == []
    client.close()
    # the same hook, with tracing on, is called (so the check above
    # would have seen a call)
    on = _small_client(trace=True)
    _serve(on, n=1)
    assert calls and set(calls) <= set(PHASES)
    on.close()


def test_annotate_sees_the_recorded_span_names():
    import contextlib
    seen = []

    def annotate(name):
        seen.append(name)
        return contextlib.nullcontext()

    client = _small_client(trace=TraceRecorder(annotate=annotate))
    _serve(client)
    recorded = [e["name"] for e in client.trace_events()
                if "parent" in e.get("args", {})
                and e["name"] != "jax.lower"]
    assert recorded and sorted(seen) == sorted(recorded)
    client.close()


def test_fresh_shape_lowers_inside_the_tick_that_needs_it():
    client = _small_client(trace=True)
    before = client.metrics()["gauges"].get("engine.lowerings", 0)
    # a prompt bucket no program of this engine has seen yet
    h = client.submit(list(range(1, 41)),
                      GenerationParams(max_new_tokens=3))
    h.result()
    g = client.metrics()["gauges"]
    assert g["engine.lowerings"] > before
    assert g["engine.lowering_seconds"] > 0.0
    evs = client.trace_events()
    by_id = {e["id"]: e for e in evs if "id" in e}
    lowered = [e for e in evs if e["name"] == "jax.lower"]
    assert lowered
    # the prefill program lowers where the engine dispatches it
    assert any(e["args"]["fun"] == "jit(pf)" and
               by_id[e["args"]["parent"]]["name"] == "engine.dispatch"
               for e in lowered)
    for e in lowered:
        up = by_id[e["args"]["parent"]]
        while "parent" in up.get("args", {}):
            up = by_id[up["args"]["parent"]]
        assert up in _ticks(evs)
        assert up["ts"] <= e["ts"] + 1e-6
        assert e["ts"] + e["dur"] <= up["ts"] + up["dur"] + 1e-6
    client.close()


def test_every_engine_sync_sits_in_a_wait_or_stream_span():
    """Each blocking device read of `ContinuousEngine` (a
    ``turbolint: allow-sync`` site) lies inside an ``engine.wait`` or
    ``engine.stream`` span, so a traced tick's host time excludes it."""
    import ast
    import inspect

    from repro.runtime import engine
    src = inspect.getsource(engine)
    tree = ast.parse(src)
    cls = next(n for n in tree.body if isinstance(n, ast.ClassDef)
               and n.name == "ContinuousEngine")
    spans = []
    for node in ast.walk(cls):
        if isinstance(node, ast.With):
            for item in node.items:
                call = item.context_expr
                if isinstance(call, ast.Call) and \
                        getattr(call.func, "id", None) == "span_of" and \
                        call.args[1].value in ("engine.wait",
                                               "engine.stream"):
                    spans.append((node.lineno, node.end_lineno))
    syncs = [i for i, line in enumerate(src.splitlines(), 1)
             if "turbolint: allow-sync" in line
             and cls.lineno <= i <= cls.end_lineno]
    assert len(syncs) >= 7
    for line in syncs:
        assert any(a <= line <= b for a, b in spans), line


def test_served_device_reads_happen_in_wait_or_stream(monkeypatch):
    """At run time: every read of a device value inside a served tick
    (greedy, sampled, packed prefill, decode) happens while an
    ``engine.wait`` or ``engine.stream`` span is open."""
    import contextlib

    import jax
    import numpy as np
    from jax._src import array as jax_array

    from repro.runtime import engine
    open_spans = []

    @contextlib.contextmanager
    def annotate(name):
        open_spans.append(name)
        try:
            yield
        finally:
            open_spans.pop()

    rec = TraceRecorder(annotate=annotate)
    client = _small_client(trace=rec)
    _serve(client, n=1)      # compile outside the checked serve
    reads, outside = [], []

    def note(x):
        if isinstance(x, jax.Array) and rec.innermost() is not None:
            reads.append(tuple(open_spans))
            if not {"engine.wait", "engine.stream"} & set(open_spans):
                outside.append(tuple(open_spans))

    class Numpy:
        """The engine's `np`, noting each device array it converts
        (on the CPU `np.asarray` reads through the buffer protocol)."""
        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, x, *a, **k):
            note(x)
            return np.asarray(x, *a, **k)

        def array(self, x, *a, **k):
            note(x)
            return np.array(x, *a, **k)

    value = jax_array.ArrayImpl._value

    def checked(arr):        # int(), float(), .item(), __array__
        note(arr)
        return value.fget(arr)

    monkeypatch.setattr(engine, "np", Numpy())
    monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(checked))
    _serve(client)
    monkeypatch.undo()
    client.close()
    assert reads and outside == []
