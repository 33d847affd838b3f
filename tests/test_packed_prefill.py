"""Packed segment-id prefill: ONE dispatch serves many prompts/chunks.

Covers the engine primitive (packing must not change a single logit),
the pack scheduler (round-robin rotation keeps every resumable prefill
progressing, queued shorts ride chunk turns, group failures are atomic
and leak nothing), and the end-to-end equivalence property: any mix of
prompt lengths, prefix-cache hits, chunked long prompts and mid-pack
cancellations generates bit-identical tokens and leaves block-pool
accounting identical to the sequential one-dispatch-per-part path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AnalyticCostModel, ServingConfig, ServingSystem,
                        SimConfig, VirtualClock)
from repro.core.pipeline import ServingPipeline
from repro.core.simulator import VirtualBackend
from repro.configs import get_smoke_config
from repro.models import init_params
from repro.runtime import BucketLadder, InferenceEngine
from repro.runtime.engine import ContinuousEngine
from repro.runtime.session import Session

from tests._hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

CM = AnalyticCostModel(flops_per_token=1e6, bytes_per_token=1e3,
                       weight_bytes=1e6, overhead=1e-4)


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("internlm2-1.8b")
    params = init_params(cfg, jax.random.key(0))
    return InferenceEngine(cfg, params, ladder=BucketLadder(
        seq_buckets=(32, 64), batch_buckets=(1, 2, 4)))


def _virtual_pipeline(config: SimConfig, cost=CM):
    clock = VirtualClock()
    backend = VirtualBackend(cost, clock, lambda t: t, config, {}, [])
    return ServingPipeline(backend, cost,
                           config.pipeline_config(), clock), clock


# ---------------------------------------------------------------------------
# Engine primitive: packing never changes a logit
# ---------------------------------------------------------------------------

def test_packed_flat_matches_single_segment(engine):
    """The same suffix packed alone vs packed beside another segment
    produces the same last-token logits — segment masking is exact.

    The pack widths differ, and XLA picks its f32 reduction order by
    shape, so the logits may differ in the last bits (4e-7 seen).  A
    leak across segments would move them by orders of magnitude more
    than the f32 tolerance below."""
    a = [5, 9, 13, 2, 7]
    b = [3, 3, 8, 1]
    cfg = engine.cfg
    dh = cfg.d_model // cfg.num_heads
    kv = getattr(cfg, "num_kv_heads", cfg.num_heads) or cfg.num_heads
    zero = jnp.zeros((cfg.num_layers, 0, kv, dh), jnp.float32)
    zseg = jnp.asarray(np.zeros((0,), np.int32))
    la, _, _ = engine.prefill_packed_flat([a], [0], zero, zero, zseg, zseg)
    lab, _, _ = engine.prefill_packed_flat([a, b], [0, 0], zero, zero,
                                           zseg, zseg)
    lb, _, _ = engine.prefill_packed_flat([b], [0], zero, zero, zseg, zseg)
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(la[0]), np.asarray(lab[0]), **tol)
    np.testing.assert_allclose(np.asarray(lb[0]), np.asarray(lab[1]), **tol)


def test_packed_flat_requires_fresh_tokens(engine):
    cfg = engine.cfg
    dh = cfg.d_model // cfg.num_heads
    kv = getattr(cfg, "num_kv_heads", cfg.num_heads) or cfg.num_heads
    zero = jnp.zeros((cfg.num_layers, 0, kv, dh), jnp.float32)
    zseg = jnp.asarray(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="fresh token"):
        engine.prefill_packed_flat([[1, 2], []], [0, 0], zero, zero,
                                   zseg, zseg)


# ---------------------------------------------------------------------------
# End-to-end equivalence: packed vs sequential serving
# ---------------------------------------------------------------------------

LONG_PROMPT = [(i * 7) % 50 + 2 for i in range(40)]
SHARED_PREFIX = [11, 12, 13, 14, 15, 16, 17, 18]


def _serve_mixed(engine, packed: bool, specs, prefix_cache: bool = False,
                 cancel_idx=None, cancel_after: int = 0):
    """Serve ``specs`` = [(prompt, max_new), ...]: head admitted first,
    the rest land mid-decode (longs go through the resumable-chunk
    queue).  Optionally cancel ``specs[cancel_idx]`` after
    ``cancel_after`` extra ticks.  Returns (results, backend)."""
    ce = ContinuousEngine(engine, max_slots=4, cap_new=16,
                          kv_layout="paged", prefix_cache=prefix_cache,
                          packed_prefill=packed)
    sys_ = ServingSystem(backend=ce, cost_model=CM,
                         config=ServingConfig(policy="dp",
                                              max_batch_size=4,
                                              chunked_prefill=True,
                                              prefill_chunk_tokens=16))
    sessions = [Session(i, len(p), 0.0, prompt=list(p), max_new_tokens=m)
                for i, (p, m) in enumerate(specs)]
    sys_.submit(sessions[0])
    sys_.step()                          # prefill the head
    sys_.step()                          # it starts decoding
    for s in sessions[1:]:
        sys_.submit(s)                   # the rest arrive mid-decode
    if cancel_idx is not None:
        for _ in range(cancel_after):
            if sys_.pipeline.idle():
                break
            sys_.step()
        sys_.cancel(sessions[cancel_idx])
    sys_.drain()
    assert all(s.is_finished for s in sessions)
    assert engine.kv_slab.live_bytes == 0
    if prefix_cache:
        residue = ce.block_table.used_blocks
        assert residue == ce.prefix_cache.cached_blocks
        assert ce.prefix_cache.evict(residue) == residue
    assert ce.block_table.used_blocks == 0
    assert not ce._chunk_slots and not ce._reserved
    assert not ce._last_pack
    return [s.result for s in sessions], ce


def test_packed_tokens_identical_mixed(engine):
    """Acceptance: the packed path generates token-for-token what the
    sequential path generates on a mixed long/short workload, with
    strictly fewer device dispatches."""
    specs = [([1, 2, 3], 10), (list(LONG_PROMPT), 6), ([9, 8, 7], 8),
             ([4, 5], 6), ([6, 5, 4, 3], 6)]
    seq, ce_seq = _serve_mixed(engine, packed=False, specs=specs)
    packed, ce_pack = _serve_mixed(engine, packed=True, specs=specs)
    assert packed == seq
    assert ce_pack.pack_dispatches > 0
    assert ce_pack.prefill_dispatches < ce_seq.prefill_dispatches


def test_packed_tokens_identical_with_prefix_hits(engine):
    """Prefix-cache hits pack too (the suffix runs at its real position
    offset against the cached prefix KV) — tokens stay identical."""
    specs = [(SHARED_PREFIX + [30, 31, 32], 8),
             (SHARED_PREFIX + [40, 41], 8),
             (SHARED_PREFIX + [50], 6)]
    seq, _ = _serve_mixed(engine, packed=False, specs=specs,
                          prefix_cache=True)
    packed, ce = _serve_mixed(engine, packed=True, specs=specs,
                              prefix_cache=True)
    assert packed == seq
    assert ce.pack_dispatches > 0


def test_pack_wider_than_ladder_splits(engine):
    """An admission group wider than the ladder's top batch bucket
    (scheduler max_batch_size above it, or a failover burst) splits
    into ladder-sized sub-packs instead of minting an impossible
    segment bucket — tokens identical to isolated greedy runs."""
    ce = ContinuousEngine(engine, max_slots=8, cap_new=16,
                          kv_layout="paged", packed_prefill=True)
    sys_ = ServingSystem(backend=ce, cost_model=CM,
                         config=ServingConfig(policy="dp",
                                              max_batch_size=8))
    n = engine.ladder.batch_buckets[-1] + 2
    prompts = [[9 + i] * 10 for i in range(n)]
    sessions = [Session(i, 10, 0.0, prompt=list(p), max_new_tokens=6)
                for i, p in enumerate(prompts)]
    for s in sessions:
        sys_.submit(s)
    sys_.drain()
    assert all(s.is_finished for s in sessions)
    for s, p in zip(sessions, prompts):
        want = list(engine.generate([p], max_new_tokens=6)[0])
        assert list(p) + list(s.generated) == want
    assert engine.kv_slab.live_bytes == 0


def test_packed_sampled_rows_identical(engine):
    """Per-row seeded sampling is pack-composition invariant: the same
    (seed, step) stream lands on a session wherever it sits in the
    pack, so sampled generations match the sequential path too."""
    specs = [([1, 2, 3], 8), ([9, 8], 8), ([7, 6, 5], 8)]
    kw = dict(temperature=0.8, top_p=0.9)
    results = {}
    for packed in (False, True):
        ce = ContinuousEngine(engine, max_slots=4, cap_new=16,
                              kv_layout="paged", packed_prefill=packed)
        sys_ = ServingSystem(backend=ce, cost_model=CM,
                             config=ServingConfig(policy="dp",
                                                  max_batch_size=4))
        sessions = [Session(i, len(p), 0.0, prompt=list(p),
                            max_new_tokens=m, seed=i + 1, **kw)
                    for i, (p, m) in enumerate(specs)]
        for s in sessions:
            sys_.submit(s)
        sys_.drain()
        results[packed] = [s.result for s in sessions]
        assert ce.block_table.used_blocks == 0
    assert results[True] == results[False]


# ---------------------------------------------------------------------------
# Pack scheduler (virtual clock)
# ---------------------------------------------------------------------------

def test_pack_rotation_no_starvation():
    """Two interleaved long prompts BOTH advance every pack turn — the
    old one-chunk-per-tick turn starved every session but the head."""
    cfg = SimConfig(policy="dp", chunked_prefill=True,
                    prefill_chunk_tokens=32)
    pipe, _ = _virtual_pipeline(cfg)
    pipe.submit(Session(0, 8, 0.0, max_new_tokens=64))
    pipe.tick()
    pipe.tick()                          # head is decoding
    longs = [Session(1, 400, 0.0, max_new_tokens=4),
             Session(2, 400, 0.0, max_new_tokens=4)]
    for s in longs:
        pipe.submit(s)
    while len(pipe.chunking) < 2:
        pipe.tick()
    # every K=4 ticks from here, both resumable prefills made progress
    while pipe.chunking:
        before = {s.req_id: s.prefilled_tokens for s in pipe.chunking}
        for _ in range(4):
            pipe.tick()
        for s in list(pipe.chunking):
            if s.req_id in before:
                assert s.prefilled_tokens > before[s.req_id], \
                    f"session {s.req_id} starved in the pack rotation"
    pipe.drain()
    assert all(s.is_finished for s in longs)


def test_pack_pulls_queued_shorts_into_chunk_turn():
    """While a long prompt chunks, queued shorts ride the pack turn
    instead of paying their own dispatch (pipeline.pack.segments grows
    faster than pipeline.pack.dispatches)."""
    cfg = SimConfig(policy="dp", chunked_prefill=True,
                    prefill_chunk_tokens=64)
    pipe, _ = _virtual_pipeline(cfg)
    pipe.submit(Session(0, 8, 0.0, max_new_tokens=128))
    pipe.tick()
    pipe.tick()
    pipe.submit(Session(1, 300, 0.0, max_new_tokens=4))
    while not pipe.chunking:
        pipe.tick()
    for i in range(2, 8):
        pipe.submit(Session(i, 8, 0.0, max_new_tokens=4))
    pipe.drain()
    snap = pipe.obs.metrics.snapshot()
    packs = snap["counters"]["pipeline.pack.dispatches"]
    segs = snap["counters"]["pipeline.pack.segments"]
    assert packs > 0 and segs > packs, \
        "shorts must have been packed into chunk turns"
    assert pipe.backend.pack_segments == segs


def test_packed_group_failure_is_atomic():
    """A dispatch failure fails the WHOLE pack group terminally and
    cleans every member's KV charge."""
    cfg = SimConfig(policy="dp", chunked_prefill=True,
                    prefill_chunk_tokens=16)
    pipe, _ = _virtual_pipeline(cfg)
    pipe.submit(Session(0, 8, 0.0, max_new_tokens=8))
    pipe.tick()
    long_s = Session(1, 60, 0.0, max_new_tokens=4)
    short_s = Session(2, 6, 0.0, max_new_tokens=4)
    pipe.submit(long_s)
    while not pipe.chunking:
        pipe.tick()
    pipe.submit(short_s)
    backend = pipe.backend

    def boom(admissions, chunks, decoding=None):
        raise RuntimeError("pack died")

    backend.prefill_pack = boom
    with pytest.raises(RuntimeError, match="pack died"):
        while not pipe.idle():
            pipe.tick()
    assert long_s.is_finished and long_s.error == "pack died"
    assert long_s.req_id not in backend.kv_live
    assert not pipe.chunking
    if short_s.is_finished:              # it was in the failed group
        assert short_s.error == "pack died"
        assert short_s.req_id not in backend.kv_live


def test_real_engine_packed_failure_sweeps_pool(engine):
    """Real-engine packed dispatch failure: every admission's tables,
    reserves and prefix refs are swept before the raise."""
    ce = ContinuousEngine(engine, max_slots=4, cap_new=16,
                          kv_layout="paged", packed_prefill=True)
    sys_ = ServingSystem(backend=ce, cost_model=CM,
                         config=ServingConfig(policy="dp",
                                              max_batch_size=4))
    orig = engine.prefill_packed_flat

    def boom(*a, **k):
        raise RuntimeError("packed dispatch died")

    engine.prefill_packed_flat = boom
    try:
        s1 = Session(0, 3, 0.0, prompt=[1, 2, 3], max_new_tokens=4)
        s2 = Session(1, 2, 0.0, prompt=[9, 8], max_new_tokens=4)
        sys_.submit(s1)
        sys_.submit(s2)
        with pytest.raises(RuntimeError, match="packed dispatch died"):
            sys_.drain()
    finally:
        engine.prefill_packed_flat = orig
    assert ce.block_table.used_blocks == 0
    assert engine.kv_slab.live_bytes == 0
    assert not ce._reserved and not ce._last_pack


# ---------------------------------------------------------------------------
# Property: arbitrary mixes are packing-invariant
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(
    specs=st.lists(
        st.tuples(
            st.sampled_from(["short", "prefix", "long"]),
            st.integers(min_value=2, max_value=12),   # length seedling
            st.integers(min_value=2, max_value=8)),   # new tokens
        min_size=2, max_size=4),
    cancel=st.one_of(
        st.none(),
        st.tuples(st.integers(min_value=0, max_value=3),
                  st.integers(min_value=0, max_value=3))),
)
def test_packed_equivalence_property(engine, specs, cancel):
    """Random mixes of prompt lengths, prefix-cache hits and chunked
    long prompts — with an optional mid-flight cancellation applied at
    the same point in both runs — generate bit-identical tokens for
    every surviving session, and both paths drain to the same empty
    block-pool accounting."""
    built = []
    for kind, n, m in specs:
        if kind == "short":
            prompt = [(n * 3 + i) % 50 + 1 for i in range(n)]
        elif kind == "prefix":
            prompt = SHARED_PREFIX + [(n + i) % 50 + 1 for i in range(3)]
        else:
            prompt = [(i * 5 + n) % 50 + 1 for i in range(34 + n)]
        built.append((prompt, m))
    if cancel is not None:
        idx, after = cancel
        idx %= len(built)
    else:
        idx = after = None
    seq, _ = _serve_mixed(engine, packed=False, specs=built,
                          prefix_cache=True, cancel_idx=idx,
                          cancel_after=after or 0)
    packed, ce = _serve_mixed(engine, packed=True, specs=built,
                              prefix_cache=True, cancel_idx=idx,
                              cancel_after=after or 0)
    survivors = [i for i in range(len(built)) if i != idx]
    for i in survivors:
        assert packed[i] == seq[i], \
            f"session {i} diverged under packing"
