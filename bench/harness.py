"""Build the served system for a cell, warm it, and drive its traffic.

The system under test is the program's normal serving path:
``TurboClient`` -> ``ServingPipeline`` -> ``ContinuousEngine`` -> paged
KV -> packed prefill -> fused sampling.  The harness owns the loop: with
``auto_pump="sync"`` it submits each request when it is due, calls
``pump(max_ticks=1)``, and stamps every token delivery with its own
clock.  Every request is timed from its due time.

A run is: set-up (weights from the seed, the engine, warm-up of every
shape the cell's traffic uses), a lead-in that brings the system to a
steady state (and fills the prefix cache where prompts share prefixes),
the measured window, and a drain that waits for the first token of every
request due in the window.
"""
from __future__ import annotations

import contextlib
import logging
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import traffic as traffic_mod

CLOCK = time.perf_counter

#: configuration-file keys -> the program's ModelConfig fields
CONFIG_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "d_head",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


def program_config(name: str, config: dict):
    """The program's ModelConfig for a configuration file: the mapped
    keys above plus the file's ``program`` fields (family, norm, act)."""
    from repro.configs.base import ModelConfig
    kw = {f: config[k] for k, f in CONFIG_FIELDS.items() if k in config}
    kw.update(config["program"])
    return ModelConfig(name=name, source=config["source"], **kw)


def count_compiles(events: Counter) -> None:
    """Count JAX's backend compiles and persistent-cache reads into
    ``events`` (a process-wide listener: register once)."""
    import jax

    def on_event(event, **kw):
        events[event] += 1

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            events["compiles"] += 1
            events["compile_seconds"] += secs

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


class CompileNames(logging.Handler):
    """Names of the programs JAX lowers between ``start`` and ``stop``,
    counted and not printed (``jax_log_compiles`` on for that span
    only; eager operations are lowered as programs too).  JAX's other
    messages that the flag turns on are kept off stderr for that span."""

    LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch",
               "jax._src.compiler")

    def __init__(self) -> None:
        super().__init__(logging.WARNING)
        self.names: Counter = Counter()

    def emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            name, _, rest = msg[len("Compiling "):].partition(
                " with global shapes and types ")
            shapes = rest.split(". Argument mapping")[0]
            shapes = shapes.replace("ShapedArray", "")[:120]
            self.names[f"{name}{shapes}"] += 1

    def start(self) -> None:
        import jax
        for name in self.LOGGERS:
            logger = logging.getLogger(name)
            logger.addHandler(self)
            logger.propagate = False
        jax.config.update("jax_log_compiles", True)

    def stop(self) -> None:
        import jax
        jax.config.update("jax_log_compiles", False)
        for name in self.LOGGERS:
            logger = logging.getLogger(name)
            logger.removeHandler(self)
            logger.propagate = True


class Programs:
    """The executables JAX compiles or reads from its persistent cache
    between ``start`` and ``stop`` (its compile path is wrapped for that
    span), kept to read each one's optimized HLO: the TPU's profiler
    trace names an op (``%fusion.200 = bf16[...] fusion(...)``) and its
    program (``jit_tick(<id>)``), but not the scope it was traced under."""

    def __init__(self) -> None:
        self.made: List[Tuple[str, object]] = []    # (module name, exe)
        self._orig: Optional[Callable] = None

    def start(self) -> None:
        from jax._src import compiler
        from jax._src.lib.mlir import ir
        orig = self._orig = compiler.compile_or_get_cached

        def capture(backend, computation, *args, **kw):
            exe = orig(backend, computation, *args, **kw)
            name = ir.StringAttr(
                computation.operation.attributes["sym_name"]).value
            self.made.append((name, exe))
            return exe
        compiler.compile_or_get_cached = capture

    def stop(self) -> None:
        if self._orig is not None:
            from jax._src import compiler
            compiler.compile_or_get_cached = self._orig
            self._orig = None

    def hlo_texts(self, name: str) -> List[str]:
        """Optimized HLO text, with metadata, of every executable of the
        program ``name`` (``jit_tick``: one per compiled variant)."""
        from jax._src.lib import _jax
        opts = _jax.HloPrintOptions()
        opts.print_metadata = True
        opts.print_large_constants = False
        return [m.to_string(opts) for n, exe in self.made if n == name
                for m in exe.hlo_modules()]


def build_client(cell: dict, cfg, params, *, trace: bool,
                 clock: Callable[[], float] = CLOCK):
    """The serving stack with the cell's settings (``serving`` and
    ``pipeline`` keys of the cell file)."""
    from repro.api import TurboClient
    from repro.core.cost_model import AnalyticCostModel
    from repro.core.pipeline import PipelineConfig
    from repro.runtime.bucketing import BucketLadder
    from repro.runtime.engine import ContinuousEngine, InferenceEngine
    sv = cell["serving"]
    engine = InferenceEngine(cfg, params, ladder=BucketLadder(
        seq_buckets=tuple(sv["seq_buckets"]),
        batch_buckets=tuple(sv["batch_buckets"])))
    backend = ContinuousEngine(
        engine, max_slots=sv["max_slots"], cap_new=sv["cap_new"],
        clock=clock, num_blocks=sv["num_blocks"],
        prefix_cache=sv["prefix_cache"])
    return TurboClient(backend,
                       cost_model=AnalyticCostModel.for_model(cfg),
                       config=PipelineConfig(**cell["pipeline"]),
                       clock=clock, auto_pump="sync", warmup=False,
                       trace=trace)


@dataclass
class Rec:
    """One submitted request as the harness saw it (its session holds
    host values only, so keeping it keeps no device memory alive)."""
    req: traffic_mod.Request
    due: float
    session: object
    submitted: float
    times: List[float] = field(default_factory=list)    # deliveries
    counts: List[int] = field(default_factory=list)     # tokens each
    delivered: int = 0


@dataclass
class Pump:
    """One ``pump`` call: its span and what the tick worked on."""
    t0: float
    t1: float
    rows: int                 # decoding rows before the tick
    context: int              # their summed KV context, tokens
    decoded: bool             # a decode tick ran
    segments: List[Tuple[int, int]]   # admitted (fresh, cached) prompts
    prefilled: int            # prefill dispatches in the tick
    blocks_used: int          # kv.blocks_used after the tick
    live_tokens: int          # prompt + delivered of admitted requests


class Driver:
    """Submits requests, pumps the pipeline and records what happened."""

    def __init__(self, client, clock: Callable[[], float] = CLOCK,
                 annotate: bool = False) -> None:
        self.client = client
        self.clock = clock
        self.recs: Dict[int, Rec] = {}
        self.pumps: List[Pump] = []
        self._annotate = annotate
        self._ids = 0
        pipe = client.pipeline
        inner = pipe.on_token

        def on_token(session, toks):
            rec = self.recs.get(session.req_id)
            if rec is not None:
                rec.times.append(self.clock())
                rec.counts.append(len(toks))
                rec.delivered += len(toks)
            inner(session, toks)
        pipe.on_token = on_token

    def span(self, name: str):
        if not self._annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    def submit(self, req: traffic_mod.Request, due: float) -> Rec:
        from repro.api import GenerationParams
        gp = GenerationParams(max_new_tokens=req.max_new,
                              temperature=req.temperature, top_p=req.top_p,
                              top_k=req.top_k, seed=req.sample_seed)
        with self.span("submit"):
            h = self.client.submit(req.prompt.tolist(), gp,
                                   req_id=self._ids)
        self._ids += 1
        rec = Rec(req=req, due=due, session=h.session,
                  submitted=self.clock())
        self.recs[h.req_id] = rec
        return rec

    def pump(self) -> None:
        from repro.runtime.session import SessionState
        pipe = self.client.pipeline
        backend = self.client.backend
        decoding = [s for s in pipe.live if s.state is SessionState.DECODE]
        ctx = sum(s.seq_len + max(len(s.generated), 1) - 1
                  for s in decoding)
        queued = list(pipe.queue)
        ticks0, disp0 = backend.decode_ticks, backend.prefill_dispatches
        t0 = self.clock()
        with self.span("pump"):
            self.client.pump(max_ticks=1)
        t1 = self.clock()
        segs = [(s.seq_len - s.cached_tokens, s.cached_tokens)
                for s in queued if s.state is not SessionState.QUEUED]
        live = 0
        for s in pipe.live:
            rec = self.recs.get(s.req_id)
            if rec is not None:
                live += s.seq_len + rec.delivered
        btm = backend.block_table
        self.pumps.append(Pump(
            t0, t1, len(decoding), ctx, backend.decode_ticks > ticks0,
            segs, backend.prefill_dispatches - disp0,
            btm.used_blocks if btm is not None else 0, live))

    def idle(self) -> bool:
        return self.client.pipeline.idle()

    def run_until_idle(self) -> None:
        while not self.idle():
            self.pump()


# -- warm-up -------------------------------------------------------------
def warm_up(driver: Driver, cell: dict, traffic, vocab: int,
            seed: int) -> int:
    """Run every shape the window will use once, through the served path:

    - for k = slots .. 1, k requests whose prompts end one token before
      a block boundary, so k rows append a block (or copy a shared block)
      on the same tick and finish on the same tick: the per-count
      host-side table updates;
    - one request per prompt length the traffic holds, greedy and sampled
      alternately, one at a time (each prefill dispatch then holds one
      prompt, as in the window), so every packed-prefill bucket, the
      per-length host-side pool writes and both decode-tick variants are
      compiled; with shared prefixes each length runs behind an uncached
      prefix and behind a cached one, and every shared prefix is cached
      as the lead-in would cache it.

    Returns the number of warm requests."""
    rng = np.random.default_rng([int(seed), 3])
    n = 0
    block = cell["serving"].get("block_size", 16)
    for k in range(cell["serving"]["max_slots"], 0, -1):
        for j in range(k):
            body = rng.integers(0, vocab, 4 * block - 1, dtype=np.int32)
            driver.submit(traffic_mod.Request(idx=-1, prompt=body, max_new=3,
                                              temperature=0.7 * (j % 2),
                                              top_p=0.9, sample_seed=j),
                          driver.clock())
            n += 1
        driver.run_until_idle()
    # prompt lengths last, so that shared prefixes are the freshest
    # entries of the prefix cache when the lead-in starts: each length
    # once behind a prefix nobody shares (what a request pays when its
    # prefix was evicted), each shared prefix once, then each length
    # behind a cached prefix
    lengths = traffic_mod.warm_lengths(traffic)
    prefixes = traffic.prefixes or []
    plen = len(prefixes[0]) if prefixes else 0
    bodies = []
    for length in lengths:
        fresh = rng.integers(0, vocab, plen + length, dtype=np.int32)
        bodies.append(fresh)
    for i, pre in enumerate(prefixes):
        bodies.append(np.concatenate(
            [pre, rng.integers(0, vocab, lengths[i % len(lengths)],
                               dtype=np.int32)]))
    if prefixes:
        for i, length in enumerate(lengths):
            bodies.append(np.concatenate(
                [prefixes[i % len(prefixes)],
                 rng.integers(0, vocab, length, dtype=np.int32)]))
    for i, body in enumerate(bodies):
        req = traffic_mod.Request(idx=-1, prompt=body, max_new=2,
                                  temperature=0.7 if i % 2 else 0.0,
                                  top_p=0.9, sample_seed=i)
        driver.submit(req, driver.clock())
        driver.run_until_idle()
        n += 1
    return n


# -- the run -------------------------------------------------------------
@dataclass
class Window:
    opened: float
    closed: float
    ended: float                   # when the drain stopped
    traced: Optional[Tuple[float, float]] = None


def drive(driver: Driver, traffic, cell: dict, seconds: float, *,
          on_prepare: Optional[Callable[[], None]] = None,
          on_open: Optional[Callable[[], None]] = None,
          on_close: Optional[Callable[[], None]] = None,
          trace_seconds: float = 0.0,
          on_trace_end: Optional[Callable[[], None]] = None) -> Window:
    """Lead-in, the window of ``seconds``, then the drain.

    Open loop: each request is submitted at its due time (or at the first
    pump boundary after it).  Closed loop: each client submits its next
    request as soon as its previous one finished, due at that finish.
    ``on_prepare`` runs a second before the window opens (the profiler
    starts there), ``on_open`` as it opens, ``on_close`` as it closes; with ``trace_seconds`` the
    ``bench.window`` span covers that much of the window and
    ``on_trace_end`` runs when it ends."""
    clock = driver.clock
    lead = float(cell["lead_in_s"])
    drain = float(cell.get("drain_s", 30.0))
    start = clock()
    t_open = start + lead
    t_close = t_open + seconds
    reqs = list(traffic.requests)
    nxt = 0
    inflight: Dict[int, Rec] = {}
    if traffic.loop == "closed":
        for c in range(traffic.clients):
            inflight[c] = driver.submit(reqs[nxt], start)
            nxt += 1
    prepared = on_prepare is None
    opened = closed = False
    traced = None
    window_span = None
    while True:
        now = clock()
        if not prepared and now >= t_open - 1.0:
            prepared = True
            on_prepare()
            now = clock()
        if not opened and now >= t_open:
            opened = True
            if on_open is not None:
                on_open()
            if trace_seconds:
                window_span = driver.span("window")
                window_span.__enter__()
                traced = (clock(), None)
        if window_span is not None and now >= traced[0] + trace_seconds:
            window_span.__exit__(None, None, None)
            window_span = None
            traced = (traced[0], clock())
            if on_trace_end is not None:
                on_trace_end()
        if traffic.loop == "open":
            while nxt < len(reqs) and t_open + reqs[nxt].due <= now:
                driver.submit(reqs[nxt], t_open + reqs[nxt].due)
                nxt += 1
        if not closed and now >= t_close:
            closed = True
            if on_close is not None:
                on_close()
        if now >= t_close and window_span is None:
            due_in = [r for r in driver.recs.values()
                      if t_open <= r.due < t_close]
            if all(r.times for r in due_in) or now >= t_close + drain:
                return Window(t_open, t_close, now, traced)
        if driver.idle():
            wait = 0.001
            if traffic.loop == "open" and nxt < len(reqs):
                wait = min(t_open + reqs[nxt].due - now, 0.05)
            with driver.span("wait"):
                time.sleep(max(0.0, wait))
            continue
        driver.pump()
        if traffic.loop == "closed":
            for c, rec in list(inflight.items()):
                if rec.session.is_finished and nxt < len(reqs):
                    due = rec.times[-1] if rec.times else clock()
                    inflight[c] = driver.submit(reqs[nxt], due)
                    nxt += 1


# -- end-to-end numbers --------------------------------------------------
def percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(driver: Driver, win: Window) -> dict:
    """TTFT from due time over requests due in the window, the gaps
    between deliveries that land in it, and tokens delivered in it."""
    due_in = [r for r in driver.recs.values()
              if win.opened <= r.due < win.closed]
    ttft = [r.times[0] - r.due for r in due_in if r.times]
    failed = sum(1 for r in due_in
                 if not r.times or r.session.error is not None)
    gaps: List[float] = []
    tokens = 0
    for r in driver.recs.values():
        for i, (t, n) in enumerate(zip(r.times, r.counts)):
            if not win.opened <= t < win.closed:
                continue
            tokens += n
            if i > 0:
                gaps.append(t - r.times[i - 1])
                gaps.extend([0.0] * (n - 1))
    late = [r.submitted - r.due for r in due_in]
    return {
        "attempted": len(due_in), "failed": failed,
        "ttft_s": ttft, "itl_s": gaps, "tokens": tokens,
        "seconds": win.closed - win.opened,
        "late_s": late,
    }


def e2e_metrics(e2e: dict) -> Dict[str, float]:
    """The end-to-end metric values a cell may report."""
    out = {"output_tokens_per_s": e2e["tokens"] / e2e["seconds"]}
    if e2e["itl_s"]:
        out["itl_p95_ms"] = percentile(e2e["itl_s"], 95) * 1e3
    if e2e["ttft_s"]:
        out["ttft_p95_ms"] = percentile(e2e["ttft_s"], 95) * 1e3
        out["ttft_p50_ms"] = percentile(e2e["ttft_s"], 50) * 1e3
    return out
