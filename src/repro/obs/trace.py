"""Per-tick / per-request trace recording and the Chrome-trace exporter.

The :class:`TraceRecorder` collects three kinds of events, all
timestamped by the **pipeline's own clock** (wall clock under
`ContinuousEngine`, virtual clock under the simulator) so both
execution modes produce structurally identical traces:

- **tick events** — one duration event per executed scheduler tick
  (``prefill`` / ``decode`` / ``chunk`` / ``chunk+decode``), each on
  its own component track;
- **spans** — one duration event per phase inside a tick
  (``sched.admit``, ``engine.dispatch``, ``engine.wait``, ...), from
  :meth:`TraceRecorder.span`.  Ticks and spans carry an ``id``; a span's
  ``args["parent"]`` is the id of the tick or span open around it in the
  same thread.  An optional ``annotate`` callable (``TurboClient`` passes
  ``jax.profiler.TraceAnnotation``) mirrors every span, by name, onto the
  profiler's host plane, which shares the device trace's clock;
- **request lifecycle events** — ``enqueue``, ``admit``, ``prefill``
  (one per chunk, with cached/fresh token counts), ``splice``,
  ``decode`` (one per decode tick the request participated in),
  ``stream`` (token delivery), and exactly one terminal ``finish`` or
  ``cancel`` with a reason.

Events are plain dicts (host scalars only — recording in the tick loop
must never touch a device value; turbolint TL001 covers this module).
:func:`chrome_trace` renders them in the Chrome trace-event JSON format
(`chrome://tracing` / Perfetto): ticks become duration events on
per-component threads of a "scheduler" process, requests become
per-request threads of a "requests" process with queued/prefill/decode
phase slices, instant lifecycle markers, and flow arrows connecting
enqueue -> admit -> splice -> finish.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time
from typing import (Callable, ContextManager, Dict, Iterable, Iterator,
                    List, Optional, Sequence)

__all__ = ["NULL_SPAN", "TraceRecorder", "chrome_trace",
           "save_chrome_trace", "span_of", "TERMINAL_EVENTS"]

#: lifecycle event names that end a request's span (exactly one of
#: these per submitted request — asserted by tests/test_obs.py)
TERMINAL_EVENTS = ("finish", "cancel")

#: default cap on retained events; beyond it the recorder drops new
#: events and counts them in ``dropped`` (a trace, unlike a metric, is
#: unbounded in event count — long soak runs must not OOM the host)
DEFAULT_MAX_EVENTS = 1_000_000

#: what a span call site gets when tracing is off: one shared, stateless
#: context that records nothing
NULL_SPAN: ContextManager[None] = contextlib.nullcontext()


class TraceRecorder:
    """Append-only event log.  Producers call :meth:`tick` and
    :meth:`req_event`; consumers read ``events`` (raw, for structural
    assertions) or :meth:`chrome_trace` (for Perfetto).

    No internal locking: producers record under the pipeline owner's
    lock (`TurboClient._cv` when a pump thread exists), and exports
    snapshot under the same lock.  The stack of open tick and span ids
    is kept per thread.

    ``clock`` stamps spans (the pipeline binds its own clock);
    ``annotate(name)``, when given, returns a context manager entered
    around every span (a profiler annotation of the same name).
    """

    def __init__(self, max_events: int = DEFAULT_MAX_EVENTS, *,
                 annotate: Optional[
                     Callable[[str], ContextManager]] = None) -> None:
        self.events: List[dict] = []
        self.dropped = 0
        self._max_events = max_events
        self.clock: Callable[[], float] = time.monotonic
        self.annotate = annotate
        self._ids = itertools.count(1)
        self._local = threading.local()

    # -- recording -----------------------------------------------------
    def record(self, name: str, track: str, ts: float, *,
               dur: Optional[float] = None, req: Optional[int] = None,
               trace_id: Optional[int] = None, eid: Optional[int] = None,
               **args) -> None:
        if len(self.events) >= self._max_events:
            self.dropped += 1
            return
        ev = {"name": name, "track": track, "ts": ts}
        if dur is not None:
            ev["dur"] = dur
        if eid is not None:
            ev["id"] = eid
        if req is not None:
            ev["req"] = req
            ev["trace_id"] = trace_id
        if args:
            ev["args"] = args
        self.events.append(ev)

    def tick(self, kind: str, t0: float, t1: float,
             tick_id: Optional[int] = None, **args) -> None:
        """One executed scheduler tick as a duration event on the
        ``kind`` component track (slice name = kind, so Perfetto labels
        read ``prefill`` / ``decode`` / ``chunk+decode``); ``tick_id``
        is the id :meth:`begin` gave the tick, which its spans name as
        ``parent``."""
        self.record(kind, kind, t0, dur=t1 - t0, eid=tick_id, **args)

    # -- nesting ---------------------------------------------------------
    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def begin(self) -> int:
        """Open a tick or span in this thread: a fresh id, which the
        spans opened inside it name as ``parent``."""
        sid = next(self._ids)
        self._stack().append(sid)
        return sid

    def end(self, sid: int) -> None:
        """Close what :meth:`begin` opened (and anything left open in
        it by an exception)."""
        stack = self._stack()
        if sid in stack:
            del stack[stack.index(sid):]

    def discard(self, tick_id: int, start: int) -> None:
        """Forget the spans recorded, from event index ``start`` on,
        inside the tick ``tick_id`` (a tick that executed nothing
        records no tick event for them to name)."""
        self.events[start:] = [
            e for e in self.events[start:]
            if e.get("args", {}).get("parent", -1) < tick_id]

    def innermost(self) -> Optional[int]:
        """Id of the innermost tick or span open in this thread."""
        stack = self._stack()
        return stack[-1] if stack else None

    @contextlib.contextmanager
    def span(self, name: str, **args) -> Iterator[int]:
        """One phase as a duration event on the ``name``'s prefix track
        (``engine.wait`` -> ``engine``), with ``args["parent"]`` the
        tick or span open around it; mirrored onto the profiler by
        ``annotate``."""
        parent = self.innermost()
        sid = self.begin()
        note = self.annotate(name) if self.annotate is not None \
            else NULL_SPAN
        t0 = self.clock()
        try:
            with note:
                yield sid
        finally:
            t1 = self.clock()
            self.end(sid)
            self.record(name, name.split(".", 1)[0], t0, dur=t1 - t0,
                        eid=sid, parent=parent, **args)

    def req_event(self, session, name: str, ts: float, **args) -> None:
        """One request-lifecycle event, keyed by the session's trace
        id (assigned at submit by the pipeline)."""
        self.record(name, "request", ts, req=session.req_id,
                    trace_id=session.trace_id, **args)

    # -- structural queries (tests / summaries) ------------------------
    def request_ids(self) -> List[int]:
        seen: Dict[int, None] = {}
        for ev in self.events:
            if ev["track"] == "request":
                seen.setdefault(ev["req"], None)
        return list(seen)

    def request_events(self, req_id: int) -> List[dict]:
        return [ev for ev in self.events
                if ev["track"] == "request" and ev["req"] == req_id]

    def request_names(self, req_id: int) -> List[str]:
        """Event-name sequence of one request's span — the unit of
        simulator-vs-wall-clock structural parity."""
        return [ev["name"] for ev in self.request_events(req_id)]

    # -- export --------------------------------------------------------
    def chrome_trace(self) -> dict:
        return chrome_trace(self.events)

    def save(self, path: str) -> dict:
        return save_chrome_trace(self.events, path)


# ---------------------------------------------------------------------------
# Chrome trace-event JSON rendering
# ---------------------------------------------------------------------------

_SCHED_PID = 1
_REQ_PID = 2
# phase slices synthesized per request from its lifecycle events
_PHASE_STARTS = {"enqueue": "queued", "admit": "prefill",
                 "splice": "decode"}


def _meta(pid: int, tid: int, what: str, name: str) -> dict:
    return {"ph": "M", "pid": pid, "tid": tid, "name": what,
            "args": {"name": name}}


def span_of(trace: Optional[TraceRecorder], name: str,
            **args) -> ContextManager:
    """``trace.span(name, **args)``, or the shared null context when
    tracing is off (``trace`` is None): one call site for both."""
    return NULL_SPAN if trace is None else trace.span(name, **args)


def chrome_trace(events: Sequence[dict]) -> dict:
    """Render recorder events as a Chrome trace-event JSON object
    (``{"traceEvents": [...]}`` — loadable in Perfetto and
    ``chrome://tracing``).

    Layout: process 1 "scheduler" holds one thread per tick kind with
    the tick duration events and, nested in them, the spans recorded
    inside each tick (a span outside any tick keeps a thread named for
    its prefix); process 2 "requests" holds one thread
    per request with queued/prefill/decode phase slices, instant
    markers for every lifecycle event, and flow arrows (``s``/``t``/
    ``f``) tying enqueue -> admit -> splice -> terminal together so a
    request's full journey is one connected chain on screen.
    """
    if not events:
        return {"traceEvents": [],
                "displayTimeUnit": "ms"}
    t_zero = min(ev["ts"] for ev in events)

    def us(ts: float) -> int:
        return int(round((ts - t_zero) * 1e6))

    out: List[dict] = [
        _meta(_SCHED_PID, 0, "process_name", "scheduler"),
        _meta(_REQ_PID, 0, "process_name", "requests"),
    ]
    track_tid: Dict[str, int] = {}
    by_req: Dict[int, List[dict]] = {}
    by_id = {ev["id"]: ev for ev in events if "id" in ev}

    def thread_of(ev: dict) -> str:
        # a span shares the thread of the tick it runs in, so the two
        # nest on screen; a span outside any recorded tick keeps its own
        root = ev
        while root.get("args", {}).get("parent") in by_id:
            root = by_id[root["args"]["parent"]]
        return root["track"]

    for ev in events:
        if ev["track"] == "request":
            by_req.setdefault(ev["req"], []).append(ev)
            continue
        track = thread_of(ev)
        tid = track_tid.get(track)
        if tid is None:
            tid = len(track_tid) + 1
            track_tid[track] = tid
            out.append(_meta(_SCHED_PID, tid, "thread_name", track))
        t0 = us(ev["ts"])
        dur = max(us(ev["ts"] + ev.get("dur", 0.0)) - t0, 1)
        out.append({"name": ev["name"],
                    "cat": "span" if "parent" in ev.get("args", {})
                    else "tick", "ph": "X",
                    "pid": _SCHED_PID, "tid": tid, "ts": t0,
                    "dur": dur, "args": ev.get("args", {})})

    for req_id, evs in by_req.items():
        tid = evs[0].get("trace_id") or (req_id + 1)
        out.append(_meta(_REQ_PID, tid, "thread_name", f"req {req_id}"))
        # phase slices: each lifecycle boundary closes the previous
        # phase and opens the next; the terminal event closes the last
        open_name: Optional[str] = None
        open_ts = 0.0
        flow_done = False
        for ev in evs:
            name, ts = ev["name"], ev["ts"]
            boundary = name in _PHASE_STARTS or name in TERMINAL_EVENTS
            if boundary and open_name is not None:
                out.append({"name": open_name, "cat": "request",
                            "ph": "X", "pid": _REQ_PID, "tid": tid,
                            "ts": us(open_ts),
                            "dur": max(us(ts) - us(open_ts), 1)})
                open_name = None
            if name in _PHASE_STARTS:
                open_name, open_ts = _PHASE_STARTS[name], ts
            # instant marker for every lifecycle event
            out.append({"name": name, "cat": "request", "ph": "i",
                        "pid": _REQ_PID, "tid": tid, "ts": us(ts),
                        "s": "t", "args": ev.get("args", {})})
            # flow chain: start at enqueue, step through the phase
            # boundaries, end exactly once at the terminal event
            flow_ph = None
            if name == "enqueue":
                flow_ph = "s"
            elif name in TERMINAL_EVENTS and not flow_done:
                flow_ph, flow_done = "f", True
            elif name in ("admit", "splice"):
                flow_ph = "t"
            if flow_ph is not None:
                flow = {"name": "req-flow", "cat": "request",
                        "ph": flow_ph, "id": tid, "pid": _REQ_PID,
                        "tid": tid, "ts": us(ts)}
                if flow_ph == "f":
                    flow["bp"] = "e"
                out.append(flow)
        if open_name is not None:   # request still live at export time
            last = evs[-1]["ts"]
            out.append({"name": open_name + " (live)", "cat": "request",
                        "ph": "X", "pid": _REQ_PID, "tid": tid,
                        "ts": us(open_ts),
                        "dur": max(us(last) - us(open_ts), 1)})
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def save_chrome_trace(events: Iterable[dict], path: str) -> dict:
    doc = chrome_trace(list(events))
    with open(path, "w") as f:
        json.dump(doc, f)
    return doc
