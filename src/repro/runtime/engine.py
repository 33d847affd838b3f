"""InferenceEngine: the computing runtime of the serving system.

Responsibilities (paper §4 mapped to TPU/XLA):
 - variable-length requests -> (seq bucket, batch bucket) cells with one
   compiled executable per cell (compile cache, warmed up front);
 - per-request last-token gathering so padding never contaminates results;
 - resumable generation primitives — :meth:`InferenceEngine.prefill_batch`
   / :meth:`InferenceEngine.decode_step_batch` — whose state lives on
   device between scheduler ticks (no per-token host round-trips: emitted
   tokens accumulate in a device buffer and transfer once per flush);
 - KV slab accounting via :class:`KVSlabManager` (C2 at serving time),
   with regions freed the moment a sequence hits EOS or its budget;
 - ``warmup()`` produces the cached_cost table the DP scheduler (C3) uses.

:class:`ContinuousEngine` layers iteration-level continuous batching on
top: a persistent slot cache that newly admitted prefills join while other
sequences are mid-decode.  It implements the
`repro.core.pipeline.PipelineBackend` protocol, so the shared
ServingPipeline loop drives it exactly as it drives the simulator's
virtual backend.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.core.cost_model import TableCostModel, block_round
from repro.core.pipeline import PipelineBackend
from repro.core.serving import Request
from repro.models import (ModelRuntime, DEFAULT_RUNTIME, decode_step,
                          forward_hidden, make_cache, make_paged_cache,
                          prefill, prefill_packed, prefill_suffix)
from repro.models.layers import lm_logits
from repro.runtime import sanitizer
from repro.runtime.bucketing import BucketLadder
from repro.runtime.kv_cache import (DEFAULT_KV_BLOCK, BlockExhausted,
                                    BlockTableManager, KVSlabManager,
                                    kv_bytes_per_token, ssm_state_bytes)
from repro.runtime.prefix_cache import PrefixMatch, RadixPrefixCache
from repro.runtime.sampling import DEFAULT_SAMPLE_CANDIDATES, sample_tokens
from repro.runtime.session import GenerationParams, Session

# cache pytree leaves whose batch axis is 0 (everything else batches on
# axis 1: k/v/conv/state are (L, B, ...), shared_k/v are (n_apps, B, ...))
_BATCH_AXIS0 = ("len", "pos_offset")

# stop-id slots per row in GenState.eos: column 0 is the request's eos_id,
# the rest hold extra GenerationParams.stop ids (-1 = unused).  Fixed so
# freshly prefilled rows always splice into the persistent slot cache.
STOP_SLOTS = 4


@dataclass
class GenState:
    """Device-resident state of an in-flight generation batch.

    Everything needed to advance decoding one token per tick without
    touching the host: the KV cache, the last sampled token per row, the
    emitted-token accumulation buffer, and per-row stop bookkeeping plus
    sampling params (temperature / top-k / top-p / PRNG seed).
    """
    cache: Dict[str, jax.Array]
    cur: jax.Array                    # (B,) or (B,K) last sampled token
    emitted: jax.Array                # (B, cap) generated tokens
    counts: jax.Array                 # (B,) number emitted
    done: jax.Array                   # (B,) bool
    budget: jax.Array                 # (B,) per-row max_new_tokens
    eos: jax.Array                    # (B, STOP_SLOTS) stop ids, -1 unused
    temp: jax.Array                   # (B,) temperature (0 = greedy)
    top_k: jax.Array                  # (B,) top-k cutoff (0 = off)
    top_p: jax.Array                  # (B,) nucleus mass (1 = off)
    seed: jax.Array                   # (B,) per-request PRNG seed
    # host-side: does any live row sample?  Greedy-only batches compile
    # and run the exact pre-sampling tick (bit-identical streams).
    sampling: bool = False

    @property
    def capacity(self) -> int:
        """Per-row emission capacity (the cap in the (B, cap) buffer)."""
        return self.emitted.shape[1]


def _rows(value: jax.Array, key: Optional[str], k: int) -> jax.Array:
    """First ``k`` batch rows of a state leaf."""
    if key is None or key not in _BATCH_AXIS0:
        return value[:, :k] if key is not None else value[:k]
    return value[:k]


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, params: Any,
                 rt: ModelRuntime = DEFAULT_RUNTIME,
                 ladder: BucketLadder = BucketLadder(),
                 pad_id: int = 0,
                 sample_candidates: Optional[int] = None) -> None:
        self.cfg = cfg
        self.params = params
        # the one device the params live on (None when they span several):
        # state built outside the compiled cells is made there too, so a
        # replica placed on its own chip keeps all of its arrays on it
        devices = {d for leaf in jax.tree.leaves(params)
                   for d in leaf.devices()}
        self.device = devices.pop() if len(devices) == 1 else None
        # KV is kept in the model's dtype: the projections that make it are
        # computed in that dtype, so a wider cache holds no more precision,
        # and decode attention over it must hand the layer carry back in
        # that dtype
        self.cache_dtype = jnp.dtype(cfg.dtype)
        self.rt = rt
        self.ladder = ladder
        self.pad_id = pad_id
        # fused-sampler candidate-set size: the sampling tick masks and
        # draws over only the top-`sample_candidates` logits per row (a
        # compile-time shape, fixed per engine — see runtime/sampling.py)
        if sample_candidates is None:
            sample_candidates = DEFAULT_SAMPLE_CANDIDATES
        if sample_candidates < 1:
            raise ValueError(f"sample_candidates must be >= 1, got "
                             f"{sample_candidates}")
        self.sample_candidates = sample_candidates
        self.kv_slab = KVSlabManager()
        self._classify_cache: Dict[Tuple[int, int], Callable] = {}
        self._prefill_cache: Dict[Tuple[int, int, int], Callable] = {}
        self._decode_cache: Dict[Any, Callable] = {}
        self.compile_count = 0
        self._next_gen_id = 0

    # ------------------------------------------------------------------
    # Compiled-cell management
    # ------------------------------------------------------------------
    def _classify_fn(self, seq_b: int, batch_b: int) -> Callable:
        key = (seq_b, batch_b)
        if key not in self._classify_cache:
            cfg, rt = self.cfg, self.rt

            @jax.jit
            def run(params, tokens, last_idx):
                h, _, _ = forward_hidden(cfg, params, tokens, rt=rt)
                hx = jnp.take_along_axis(
                    h, last_idx[:, None, None].astype(jnp.int32), axis=1)
                logits = lm_logits(cfg, params["embed"], hx)
                return logits[:, 0] if not cfg.num_codebooks \
                    else logits[:, :, 0]

            self._classify_cache[key] = run
            self.compile_count += 1
        return self._classify_cache[key]

    def _decode_fn(self) -> Callable:
        """Plain one-step decode (legacy host-synced loop)."""
        key = "step"
        if key not in self._decode_cache:
            cfg, rt = self.cfg, self.rt

            @partial(jax.jit, donate_argnums=(1,))
            def step(params, cache, tokens_t):
                return decode_step(cfg, params, cache, tokens_t, rt=rt)

            self._decode_cache[key] = step
            self.compile_count += 1
        return self._decode_cache[key]

    def _tick_fn(self, tok_ndim: int, sampling: bool) -> Callable:
        """Fused decode tick: one decode step + token selection + device-
        side emission + stop-flag update.  No host transfer anywhere —
        the whole generation loop runs on device until a flush.

        Two compiled variants per token rank: ``sampling=False`` is the
        pure-greedy tick (argmax only — the pre-sampling fast path);
        ``sampling=True`` adds per-row categorical sampling, with greedy
        (temperature 0) rows still taking the identical argmax value.
        Codebook models (tok_ndim 2) are always greedy."""
        key = ("tick", tok_ndim, sampling)
        if key not in self._decode_cache:
            cfg, rt = self.cfg, self.rt
            cands = self.sample_candidates

            @partial(jax.jit, donate_argnums=(1, 2, 3, 4, 5))
            def tick(params, cache, cur, emitted, counts, done, budget,
                     eos, temp, top_k, top_p, seed):
                prev_len = cache["len"]
                logits, cache2 = decode_step(cfg, params, cache, cur,
                                             rt=rt)
                if sampling and tok_ndim == 1:
                    nxt = sample_tokens(logits, temperature=temp,
                                        top_k=top_k, top_p=top_p,
                                        seed=seed, step=counts,
                                        candidates=cands)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                tok = nxt if nxt.ndim == 1 else nxt[:, 0]
                # finished rows are frozen: no KV advance, no emission
                cache2["len"] = jnp.where(done, prev_len, cache2["len"])
                written = jax.vmap(
                    lambda e, t, c: lax.dynamic_update_slice(
                        e, t[None], (c,)))(emitted, tok, counts)
                emitted2 = jnp.where(done[:, None], emitted, written)
                counts2 = jnp.where(done, counts, counts + 1)
                done2 = done | (counts2 >= budget) | \
                    jnp.any(tok[:, None] == eos, axis=-1)
                mask = done if cur.ndim == 1 else done[:, None]
                cur2 = jnp.where(mask, cur, nxt)
                return cache2, cur2, emitted2, counts2, done2

            self._decode_cache[key] = tick
            self.compile_count += 1
        return self._decode_cache[key]

    def _prefill_fn(self, max_len: int, batch_b: int,
                    prompt_b: int) -> Callable:
        key = (max_len, batch_b, prompt_b)
        if key not in self._prefill_cache:
            cfg, rt, dt = self.cfg, self.rt, self.cache_dtype

            @jax.jit
            def pf(params, tokens, true_lengths):
                return prefill(
                    cfg, params, tokens, max_len=max_len, rt=rt,
                    true_lengths=(true_lengths if (cfg.family not in
                                                   ("ssm", "hybrid"))
                                  else None),
                    cache_dtype=dt)

            self._prefill_cache[key] = pf
            self.compile_count += 1
        return self._prefill_cache[key]

    def _suffix_fn(self, prefix_len: int, suffix_b: int,
                   batch_b: int) -> Callable:
        """Compiled suffix prefill, one cell per (exact prefix length,
        suffix bucket, batch bucket).  The prefix length is a static
        shape — prefix KV arrives unpadded, gathered straight from the
        paged pool — so workloads with a few distinct shared prefixes
        compile a few cells, like any other bucket."""
        key = ("sfx", prefix_len, suffix_b, batch_b)
        if key not in self._prefill_cache:
            cfg, rt, dt = self.cfg, self.rt, self.cache_dtype

            @jax.jit
            def pf(params, tokens, true_lengths, prefix_k, prefix_v):
                return prefill_suffix(
                    cfg, params, tokens, prefix_k, prefix_v,
                    prefix_len=prefix_len, rt=rt,
                    true_lengths=true_lengths, cache_dtype=dt)

            self._prefill_cache[key] = pf
            self.compile_count += 1
        return self._prefill_cache[key]

    # ------------------------------------------------------------------
    # Batch padding
    # ------------------------------------------------------------------
    def _pad_batch(self, token_lists: Sequence[Sequence[int]]
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, int, int]:
        lens = [len(t) for t in token_lists]
        seq_b = self.ladder.seq_bucket(max(lens))
        batch_b = self.ladder.batch_bucket(len(token_lists))
        toks = np.full((batch_b, seq_b), self.pad_id, np.int32)
        for i, t in enumerate(token_lists):
            toks[i, :len(t)] = t
        last = np.array([l - 1 for l in lens] +
                        [0] * (batch_b - len(lens)), np.int32)
        return jnp.asarray(toks), jnp.asarray(last), seq_b, batch_b

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------
    def classify(self, token_lists: Sequence[Sequence[int]]) -> List[int]:
        """Last-token classification over a variable-length batch (the
        paper's BERT-based service)."""
        toks, last, seq_b, batch_b = self._pad_batch(token_lists)
        fn = self._classify_fn(seq_b, batch_b)
        logits = fn(self.params, toks, last)
        # turbolint: allow-sync(one-shot classification returns host ints)
        preds = np.asarray(jnp.argmax(logits, axis=-1))
        return [int(preds[i]) for i in range(len(token_lists))]

    def execute_requests(self, requests: List[Request], padded_len: int
                         ) -> List[Any]:
        """ServingSystem adapter: requests carry token payloads."""
        return self.classify([r.payload for r in requests])

    # ------------------------------------------------------------------
    # Resumable generation primitives
    # ------------------------------------------------------------------
    def prefill_batch(self, token_lists: Sequence[Sequence[int]], *,
                      max_len: int,
                      max_new_tokens,
                      eos_id=None,
                      cap_new: Optional[int] = None,
                      sampling: Optional[
                          Sequence[GenerationParams]] = None) -> GenState:
        """Prompt pass producing a device-resident :class:`GenState` that
        :meth:`decode_step_batch` advances one token per call.

        ``max_new_tokens`` / ``eos_id`` may be scalars or per-request
        sequences.  ``sampling`` (optional, per request) carries each
        row's temperature / top-k / top-p / seed / extra stop ids; None
        is classic greedy.  The KV cache is sized to ``max_len`` so
        states built against the same ``max_len`` are row-compatible
        (the continuous engine splices them into its slot cache).
        """
        cfg = self.cfg
        n = len(token_lists)
        lens = [len(t) for t in token_lists]
        ragged = len(set(lens)) > 1
        if ragged and cfg.family in ("ssm", "hybrid"):
            raise ValueError("SSM prompts must be grouped by exact length")
        if cfg.family in ("ssm", "hybrid"):
            prompt_b = max(lens)   # no pad: state would roll through it
        else:
            prompt_b = self.ladder.seq_bucket(max(lens))
        batch_b = self.ladder.batch_bucket(n)
        budgets = list(max_new_tokens) if hasattr(max_new_tokens, "__len__") \
            else [int(max_new_tokens)] * n
        eos_ids = list(eos_id) if hasattr(eos_id, "__len__") \
            else [eos_id] * n
        if max(lens[i] + budgets[i] for i in range(n)) > max_len:
            raise ValueError(f"prompt+budget exceeds max_len {max_len}")
        cap = cap_new if cap_new is not None else max(max(budgets), 1)
        if cap < max(budgets):
            raise ValueError(f"cap_new={cap} cannot hold a "
                             f"max_new_tokens={max(budgets)} budget")

        toks = np.full((batch_b, prompt_b), self.pad_id, np.int32)
        for i, t in enumerate(token_lists):
            toks[i, :len(t)] = t
        true_lens = np.array(lens + [1] * (batch_b - n), np.int32)
        logits, cache = self._prefill_fn(max_len, batch_b, prompt_b)(
            self.params, jnp.asarray(toks), jnp.asarray(true_lens))
        return self._finish_gen_state(logits, cache, n, batch_b, budgets,
                                      eos_ids, cap, sampling)

    def _finish_gen_state(self, logits, cache, n: int, batch_b: int,
                          budgets: Sequence[int], eos_ids: Sequence,
                          cap: int,
                          sampling: Optional[
                              Sequence[GenerationParams]] = None
                          ) -> GenState:
        """Shared tail of the prefill paths: seed the per-row control
        state (first token — sampled with each row's params at step 0 —
        emission buffer, budget/stops/done) around an already-populated
        cache pytree."""
        specs = list(sampling) if sampling is not None else []
        specs += [GenerationParams(max_new_tokens=0)] * (batch_b -
                                                         len(specs))
        over = [i for i, p in enumerate(specs)
                if len(p.stop) > STOP_SLOTS - 1]
        if over:
            raise ValueError(f"rows {over}: at most {STOP_SLOTS - 1} "
                             "extra stop ids per request")
        temp = jnp.asarray(np.array([p.temperature for p in specs],
                                    np.float32))
        top_k = jnp.asarray(np.array([p.top_k for p in specs], np.int32))
        top_p = jnp.asarray(np.array([p.top_p for p in specs],
                                     np.float32))
        seed = jnp.asarray(np.array([p.seed for p in specs], np.int32))
        stops = np.full((batch_b, STOP_SLOTS), -1, np.int32)
        for i, e in enumerate(eos_ids):
            if e is not None:
                stops[i, 0] = e
        for i, p in enumerate(specs):
            for j, t in enumerate(p.stop):
                stops[i, 1 + j] = t
        eos = jnp.asarray(stops)
        use_sampling = any(p.temperature > 0 for p in specs)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if use_sampling and greedy.ndim != 1:
            raise ValueError("temperature sampling is unsupported for "
                             "codebook models (greedy only)")
        if use_sampling:
            # first generated token: drawn at step 0 with the row's key
            cur = sample_tokens(
                logits, temperature=temp, top_k=top_k, top_p=top_p,
                seed=seed, step=jnp.zeros((batch_b,), jnp.int32),
                candidates=self.sample_candidates)
        else:
            cur = greedy
        tok0 = cur if cur.ndim == 1 else cur[:, 0]
        budget = jnp.asarray(np.array(
            list(budgets) + [0] * (batch_b - n), np.int32))
        emitted = jnp.zeros((batch_b, cap), jnp.int32)
        emitted = emitted.at[:, 0].set(tok0)
        counts = jnp.minimum(jnp.ones((batch_b,), jnp.int32), budget)
        done = (counts >= budget) | \
            (jnp.any(tok0[:, None] == eos, axis=-1) & (counts > 0))
        return GenState(cache, cur, emitted, counts, done, budget, eos,
                        temp, top_k, top_p, seed, sampling=use_sampling)

    def prefill_suffix_batch(self, token_lists: Sequence[Sequence[int]], *,
                             prefix_k: jax.Array, prefix_v: jax.Array,
                             prefix_len: int,
                             max_new_tokens,
                             eos_id=None,
                             cap_new: Optional[int] = None,
                             sampling: Optional[
                                 Sequence[GenerationParams]] = None
                             ) -> GenState:
        """Resumable suffix prefill: like :meth:`prefill_batch`, but the
        first ``prefix_len`` tokens of every prompt are served from
        ``prefix_k``/``prefix_v`` (shared-prefix KV gathered from the
        paged pool, shape (L, B, prefix_len, KV, dh)) and only the
        remaining suffix runs through the model, at positions offset by
        the prefix.

        The returned GenState's cache holds ONLY the suffix KV (k/v:
        (L, B, suffix_bucket, ...)) with ``cache['len']`` already at the
        FULL prompt lengths; it is meant for the continuous engine's
        paged splice, which scatters the suffix into the request's own
        blocks — never into the shared prefix blocks.
        """
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid"):
            raise ValueError("suffix prefill requires an attention-family "
                             "model")
        n = len(token_lists)
        suffixes = [list(t)[prefix_len:] for t in token_lists]
        lens = [len(s) for s in suffixes]
        if min(lens) < 1:
            raise ValueError("every prompt must keep >= 1 uncached token "
                             "(the last position's logits seed decoding)")
        suffix_b = self.ladder.seq_bucket(max(lens))
        batch_b = self.ladder.batch_bucket(n)
        budgets = list(max_new_tokens) if hasattr(max_new_tokens, "__len__") \
            else [int(max_new_tokens)] * n
        eos_ids = list(eos_id) if hasattr(eos_id, "__len__") \
            else [eos_id] * n
        cap = cap_new if cap_new is not None else max(max(budgets), 1)
        if cap < max(budgets):
            raise ValueError(f"cap_new={cap} cannot hold a "
                             f"max_new_tokens={max(budgets)} budget")
        toks = np.full((batch_b, suffix_b), self.pad_id, np.int32)
        for i, t in enumerate(suffixes):
            toks[i, :len(t)] = t
        true_lens = np.array(lens + [1] * (batch_b - n), np.int32)
        if prefix_k.shape[1] < batch_b:
            pad = [(0, 0)] * prefix_k.ndim
            pad[1] = (0, batch_b - prefix_k.shape[1])
            prefix_k = jnp.pad(prefix_k, pad)
            prefix_v = jnp.pad(prefix_v, pad)
        logits, parts = self._suffix_fn(prefix_len, suffix_b, batch_b)(
            self.params, jnp.asarray(toks), jnp.asarray(true_lens),
            prefix_k, prefix_v)
        cache = {
            "len": jnp.asarray(np.array(
                [prefix_len + ln for ln in lens] +
                [1] * (batch_b - n), np.int32)),
            "pos_offset": jnp.zeros((batch_b,), jnp.int32),
            "k": parts["k"],
            "v": parts["v"],
        }
        return self._finish_gen_state(logits, cache, n, batch_b, budgets,
                                      eos_ids, cap, sampling)

    def _packed_fn(self, pack_b: int, pre_b: int, seg_b: int) -> Callable:
        """Compiled packed prefill, one cell per (pack bucket, prefix
        bucket, segment-slots bucket).  All three are ladder outputs —
        the pack/prefix buckets come from ``BucketLadder.pack_bucket``
        (doubling past the top seq bucket) and the segment slots from
        the batch ladder — so the compiled-cell set stays bounded."""
        key = ("pack", pack_b, pre_b, seg_b)
        if key not in self._prefill_cache:
            cfg, rt, dt = self.cfg, self.rt, self.cache_dtype

            @jax.jit
            def pf(params, tokens, seg_ids, positions, last_idx,
                   prefix_k, prefix_v, prefix_seg, prefix_pos):
                return prefill_packed(
                    cfg, params, tokens, seg_ids, positions, last_idx,
                    prefix_k, prefix_v, prefix_seg, prefix_pos, rt=rt,
                    cache_dtype=dt)

            self._prefill_cache[key] = pf
            self.compile_count += 1
        return self._prefill_cache[key]

    def prefill_packed_flat(self, suffixes: Sequence[Sequence[int]],
                            offsets: Sequence[int], prefix_k, prefix_v,
                            prefix_seg, prefix_pos):
        """ONE device dispatch prefilling many independent segments.

        ``suffixes[i]`` is segment i's fresh (uncached) tokens and
        ``offsets[i]`` how many of its tokens are already cached — the
        segment's queries run at positions ``offsets[i]..`` against its
        own prefix slots in ``prefix_k``/``prefix_v`` (L, P_pre, KV, dh:
        every segment's cached prefix concatenated, labelled by
        ``prefix_seg``/``prefix_pos``).  Everything is padded here to
        (pack, prefix, segment) buckets so callers never mint new cells.

        Returns ``(logits, parts)``: per-segment last-token logits
        (seg_b, V) — rows past ``len(suffixes)`` are padding — and flat
        suffix KV (L, pack_b, KV, dh) laid out exactly as the
        concatenated suffixes, for per-segment scatter into paged blocks.
        """
        n = len(suffixes)
        lens = [len(s) for s in suffixes]
        if min(lens) < 1:
            raise ValueError("every packed segment needs >= 1 fresh token")
        flat = sum(lens)
        pack_b = self.ladder.pack_bucket(flat)
        seg_b = self.ladder.batch_bucket(n)
        toks = np.full((1, pack_b), self.pad_id, np.int32)
        seg_ids = np.full((pack_b,), -1, np.int32)
        positions = np.zeros((pack_b,), np.int32)
        last_idx = np.zeros((seg_b,), np.int32)
        at = 0
        for i, (s, off) in enumerate(zip(suffixes, offsets)):
            toks[0, at:at + len(s)] = s
            seg_ids[at:at + len(s)] = i
            positions[at:at + len(s)] = np.arange(off, off + len(s))
            last_idx[i] = at + len(s) - 1
            at += len(s)
        pre = int(prefix_k.shape[1])
        pre_b = self.ladder.pack_bucket(pre) if pre else 0
        if pre_b > pre:
            pad = [(0, 0)] * prefix_k.ndim
            pad[1] = (0, pre_b - pre)
            prefix_k = jnp.pad(prefix_k, pad)
            prefix_v = jnp.pad(prefix_v, pad)
            prefix_seg = jnp.pad(prefix_seg, (0, pre_b - pre),
                                 constant_values=-1)
            prefix_pos = jnp.pad(prefix_pos, (0, pre_b - pre))
        return self._packed_fn(pack_b, pre_b, seg_b)(
            self.params, jnp.asarray(toks), jnp.asarray(seg_ids),
            jnp.asarray(positions), jnp.asarray(last_idx),
            prefix_k, prefix_v, prefix_seg, prefix_pos)

    def decode_step_batch(self, state: GenState) -> GenState:
        """One decode tick for every live row of ``state`` — entirely on
        device; finished rows are frozen.  Greedy-only states run the
        pure-argmax tick; states with sampled rows run the per-row
        categorical variant (greedy rows still take the argmax value)."""
        tick = self._tick_fn(state.cur.ndim, state.sampling)
        cache, cur, emitted, counts, done = tick(
            self.params, state.cache, state.cur, state.emitted,
            state.counts, state.done, state.budget, state.eos,
            state.temp, state.top_k, state.top_p, state.seed)
        return replace(state, cache=cache, cur=cur, emitted=emitted,
                       counts=counts, done=done)

    def read_out(self, state: GenState,
                 token_lists: Sequence[Sequence[int]]) -> List[List[int]]:
        """ONE host transfer for the whole batch: prompt + emitted."""
        em = np.asarray(state.emitted)    # turbolint: allow-sync(final flush)
        cnt = np.asarray(state.counts)    # turbolint: allow-sync(final flush)
        return [list(t) + [int(x) for x in em[i, :cnt[i]]]
                for i, t in enumerate(token_lists)]

    def generate(self, token_lists: Sequence[Sequence[int]],
                 max_new_tokens: int = 16, eos_id: Optional[int] = None,
                 per_token_host_sync: bool = False) -> List[List[int]]:
        """Greedy decode over a ragged batch (right-padded; per-request
        last-token gather). KV regions tracked in the slab manager.

        The decode loop accumulates tokens on device and transfers once
        at the end; ``per_token_host_sync=True`` keeps the old
        round-trip-per-token loop as a benchmark baseline."""
        cfg = self.cfg
        lens = [len(t) for t in token_lists]
        seq_b = self.ladder.seq_bucket(max(lens) + max_new_tokens)
        per_tok = kv_bytes_per_token(cfg)
        fixed = ssm_state_bytes(cfg)
        # negative ids: a namespace disjoint from serving req_ids, so a
        # generate() call never collides with ContinuousEngine regions
        # living in the same slab manager
        req_ids = [-(self._next_gen_id + i + 1)
                   for i in range(len(token_lists))]
        self._next_gen_id += len(token_lists)
        try:
            for rid, l in zip(req_ids, lens):
                self.kv_slab.allocate(
                    rid,
                    per_tok * seq_b + fixed if per_tok else max(fixed, 1),
                    tokens=l + max_new_tokens)
            if max_new_tokens == 0:
                return [list(t) for t in token_lists]
            if per_token_host_sync:
                return self._generate_host_synced(token_lists,
                                                  max_new_tokens, seq_b)
            state = self.prefill_batch(token_lists, max_len=seq_b,
                                       max_new_tokens=max_new_tokens,
                                       eos_id=eos_id)
            for _ in range(max_new_tokens - 1):
                state = self.decode_step_batch(state)
            return self.read_out(state, token_lists)
        finally:
            # allocate() may have failed partway (e.g. a duplicate id):
            # freeing a never-allocated id would raise KeyError here and
            # mask the original exception
            for rid in req_ids:
                if self.kv_slab.has_region(rid):
                    self.kv_slab.free(rid)
            self.kv_slab.gc()

    def _generate_host_synced(self, token_lists, max_new_tokens, seq_b):
        """Pre-refactor decode loop: np.asarray(cur) every iteration (a
        device->host sync per token).  Kept only so benchmarks can show
        the cost it used to impose."""
        state = self.prefill_batch(token_lists, max_len=seq_b,
                                   max_new_tokens=max_new_tokens)
        step = self._decode_fn()
        outs = [list(t) for t in token_lists]
        cache, cur = state.cache, state.cur
        for _ in range(max_new_tokens):
            # turbolint: allow-sync(deliberate per-token baseline for benchmarks)
            cur_np = np.asarray(cur)
            for i in range(len(token_lists)):
                outs[i].append(int(cur_np[i].reshape(-1)[0]))
            cur_logits, cache = step(self.params, cache, cur)
            cur = jnp.argmax(cur_logits, axis=-1)
        return outs

    # ------------------------------------------------------------------
    # Warm-up (paper §5: builds cached_cost)
    # ------------------------------------------------------------------
    def warmup(self, lengths: Optional[Sequence[int]] = None,
               batches: Optional[Sequence[int]] = None,
               repeats: int = 3) -> TableCostModel:
        lengths = list(lengths or self.ladder.seq_buckets[:4])
        batches = list(batches or self.ladder.batch_buckets[:4])

        def measure(seq_len: int, batch: int) -> float:
            token_lists = [[1] * seq_len for _ in range(batch)]
            self.classify(token_lists)          # compile + first run
            t0 = time.perf_counter()
            for _ in range(repeats):
                self.classify(token_lists)
            return (time.perf_counter() - t0) / repeats

        return TableCostModel.warmup(measure, lengths, batches)


class ContinuousEngine(PipelineBackend):
    """Iteration-level continuous batching over a persistent slot cache.

    ``max_slots`` sequences decode concurrently in one fused device step;
    newly admitted prefills are spliced into free slots *between* decode
    ticks, so arrivals join the next tick without waiting for in-flight
    generations to drain.  A sequence's KV is freed the moment it hits
    EOS or its token budget — footprint tracks the live token set, not
    the batch horizon.

    Two KV layouts, selected by ``kv_layout``:

    - ``"paged"`` (default, attention families only): K/V live in one
      preallocated pool of ``block_size``-token blocks managed by a
      :class:`BlockTableManager`.  Blocks covering the prompt are
      allocated at admission and appended one at a time as decoding
      crosses block boundaries, so a sequence longer than anything seen
      so far needs no cache re-materialization — the old grow-by-pad
      path is gone — and a prefill that cannot get blocks is vetoed at
      admission (free-*block* accounting, not slot count).
    - ``"contiguous"``: the PR-1 slot cache, each row a ``max_len``
      stripe, kept as the equivalence baseline and for SSM/hybrid
      families (their O(1) state cannot be paged; cross-layer shared-KV
      leaves ride in the contiguous cache).  Hybrid/SSM admission is
      restricted to equal-length prefill groups (ragged SSM prefill is
      unsupported; see ROADMAP open items).

    ``prefix_cache=True`` (paged only) adds cross-request prompt-prefix
    sharing: admissions are matched against a
    :class:`repro.runtime.prefix_cache.RadixPrefixCache`, matched blocks
    are mapped straight into the new request's table (refcounted), only
    the uncached suffix is prefilled (``prefill_suffix_batch``), a
    partially-valid matched block is copied before the suffix writes into
    it, a live sequence's first decode token copies its cached tail block
    (copy-on-write), and unreferenced cached blocks are LRU-evicted when
    admissions need the space.  Generated tokens are identical with the
    cache on or off — only the prefill work and block footprint shrink.
    """

    def __init__(self, engine: InferenceEngine, max_slots: int = 8,
                 max_len: Optional[int] = None, cap_new: int = 64,
                 sync_every: int = 1,
                 clock: Callable[[], float] = time.monotonic, *,
                 kv_layout: str = "paged",
                 block_size: int = DEFAULT_KV_BLOCK,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 packed_prefill: bool = True) -> None:
        cfg = engine.cfg
        if cfg.num_codebooks:
            raise ValueError("ContinuousEngine supports single-codebook "
                             "token models only")
        if kv_layout not in ("paged", "contiguous"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if kv_layout == "paged" and cfg.family in ("ssm", "hybrid"):
            raise ValueError("paged KV requires an attention-family "
                             "model; use kv_layout='contiguous' for "
                             "SSM/hybrid")
        if prefix_cache and kv_layout != "paged":
            raise ValueError("prefix_cache requires kv_layout='paged' "
                             "(sharing happens at block granularity)")
        self.engine = engine
        self.max_slots = max_slots
        self.cap_new = cap_new
        self.sync_every = sync_every
        self.clock = clock
        self.kv_layout = kv_layout
        self.block_size = block_size
        self.block_table: Optional[BlockTableManager] = None
        self._prefix_enabled = prefix_cache
        self.prefix_cache: Optional[RadixPrefixCache] = None
        self.prefill_tokens = 0      # tokens actually run through prefill
        self.cow_blocks = 0          # copy-on-write block copies made
        # packed prefill: many segments (admissions and/or chunks) per
        # device dispatch.  False keeps the sequential per-group path —
        # the equivalence baseline the packed path is tested against.
        self.packed_prefill = packed_prefill
        self.prefill_dispatches = 0  # prefill device dispatches issued
        self.pack_dispatches = 0     # ... of which were packed
        self.pack_segments = 0       # segments across all packed ones
        # pack ledger: req_id -> pool blocks the most recent packed
        # dispatch scattered into (check_invariants audits ownership)
        self._last_pack: Dict[int, List[int]] = {}
        if kv_layout == "paged":
            if max_len is None:
                max_len = engine.ladder.seq_buckets[-1]
            if max_len % block_size:
                raise ValueError(f"max_len {max_len} must be a multiple "
                                 f"of block_size {block_size}")
            bad = [b for b in engine.ladder.seq_buckets
                   if b % block_size]
            if bad:
                raise ValueError(f"ladder buckets {bad} not multiples of "
                                 f"block_size {block_size}")
            self.max_blocks = max_len // block_size
            if num_blocks is not None:
                self.block_table = sanitizer.make_block_manager(
                    num_blocks, block_size)
                if prefix_cache:
                    self.prefix_cache = RadixPrefixCache(self.block_table)
            # num_blocks=None: the pool is sized at the FIRST prefill to
            # max_slots x that admission's bucket — workload-derived like
            # the contiguous lazy max_len, but shared: the token capacity
            # is fungible across slots, so one later sequence may use
            # many slots' worth of blocks (up to max_len) while short
            # ones use few.  Pass num_blocks to size it explicitly.
        self.max_len = max_len      # contiguous: fixed at first prefill
        # cluster-tier donation seam: forwarded onto the prefix cache's
        # `on_insert` whenever the (lazily created) cache materializes,
        # so a ReplicaPool can subscribe before the first prefill
        self.on_prefix_insert: Optional[
            Callable[[List[int], List[int]], None]] = None
        self.sessions: List[Optional[Session]] = [None] * max_slots
        self.state: Optional[GenState] = None
        # next KV write position per slot (mirrors device cache['len'];
        # advanced conservatively, so a row that finished on device
        # between host syncs may hold one extra block until the sync
        # frees its table)
        self._slot_len: List[int] = [0] * max_slots
        # blocks a live request will still append (admission reserved
        # them, so mid-decode appends can never fail)
        self._reserved: Dict[int, int] = {}
        # chunked prefills in flight: req_id -> the decode slot reserved
        # for it at admission (claimed when the final chunk splices)
        self._chunk_slots: Dict[int, int] = {}
        self._since_sync = 0
        self.decode_ticks = 0
        # throwaway-session id namespace for warmup_aot (far below the
        # generate() negative ids; decremented per warm session)
        self._warm_id = -(10 ** 9)
        self.warmup_stats: Optional[Dict[str, float]] = None

    # -- PipelineBackend -------------------------------------------------
    def free_slots(self) -> int:
        return sum(1 for s in self.sessions if s is None) \
            - len(self._chunk_slots)

    def observe_metrics(self, m) -> None:
        """Tick-boundary gauge sampling for the observability registry
        (the duck-typed hook `ServingPipeline._tick_boundary` calls).
        Every value set here is host-side Python bookkeeping the engine
        already maintains — no device value is ever read."""
        m.gauge("engine.compile_count").set(self.engine.compile_count)
        m.gauge("engine.prefill_tokens").set(self.prefill_tokens)
        m.gauge("engine.prefill_dispatches").set(self.prefill_dispatches)
        m.gauge("engine.decode_ticks").set(self.decode_ticks)
        m.gauge("engine.cow_blocks").set(self.cow_blocks)
        for k, v in self.engine.kv_slab.metrics().items():
            m.gauge("slab." + k).set(v)
        if self.block_table is not None:
            for k, v in self.block_table.metrics().items():
                m.gauge("kv." + k).set(v)
            m.gauge("kv.reserved_blocks").set(
                sum(self._reserved.values()))
        if self.prefix_cache is not None:
            for k, v in self.prefix_cache.metrics().items():
                m.gauge("prefix." + k).set(v)

    def free_kv_tokens(self) -> Optional[int]:
        """Token capacity of blocks neither held nor reserved — the
        admission budget the pipeline charges ``kv_demand`` against.
        With the prefix cache on, cached blocks nobody else references
        count as free: admission may reclaim them by LRU eviction.
        Unbounded until the pool exists (the first prefill sizes it to
        fit whatever batch triggered it)."""
        if self.block_table is None:
            return None
        free = self.block_table.free_blocks - sum(self._reserved.values())
        if self.prefix_cache is not None:
            free += self.prefix_cache.evictable_blocks()
        return max(free, 0) * self.block_size

    def kv_demand(self, session: Session) -> int:
        if self.kv_layout != "paged":
            return session.total_len
        demand = block_round(session.total_len, self.block_size)
        if self.prefix_cache is not None and session.prompt:
            # Discount only matched full blocks OTHER holders already pin
            # (ref >= 2): sharing those costs no capacity.  A matched
            # block held only by the cache (ref 1) was counted evictable
            # in free_kv_tokens, so discounting it too would double-count
            # its capacity; a partial tail match is never discounted (its
            # copy-on-write consumes a fresh block anyway).
            m = self.prefix_cache.match(list(session.prompt),
                                        take_refs=False)
            shared = sum(1 for b in m.full_blocks
                         if self.block_table.ref_count(b) >= 2)
            demand -= shared * self.block_size
        return max(demand, self.block_size)

    def validate(self, session: Session) -> None:
        """Reject un-servable sessions at submit time, before the
        pipeline transitions them out of QUEUED."""
        if session.prompt is None:
            raise ValueError(f"session {session.req_id} has no prompt "
                             "tokens")
        if session.max_new_tokens > self.cap_new:
            raise ValueError(
                f"session {session.req_id}: max_new_tokens="
                f"{session.max_new_tokens} exceeds cap_new={self.cap_new}")
        if session.temperature < 0:
            raise ValueError(f"session {session.req_id}: temperature "
                             "must be >= 0")
        if not 0.0 < session.top_p <= 1.0:
            raise ValueError(f"session {session.req_id}: top_p must be "
                             "in (0, 1]")
        if len(session.stop) > STOP_SLOTS - 1:
            raise ValueError(
                f"session {session.req_id}: at most {STOP_SLOTS - 1} "
                f"extra stop ids (got {len(session.stop)})")
        if self.engine.kv_slab.has_region(session.req_id):
            raise ValueError(f"session {session.req_id}: req_id already "
                             "in flight")
        if self.kv_layout == "paged":
            if session.total_len > self.max_len:
                raise ValueError(
                    f"session {session.req_id}: prompt+budget="
                    f"{session.total_len} exceeds max_len {self.max_len}")
            if self.block_table is not None:
                demand = self.block_table.blocks_needed(session.total_len)
                if demand > self.block_table.num_blocks - 1:
                    raise ValueError(
                        f"session {session.req_id}: needs {demand} KV "
                        f"blocks but the pool holds "
                        f"{self.block_table.num_blocks - 1}")
            return
        # contiguous: once the slot cache exists it can grow up to the
        # top ladder bucket; a constructor-fixed max_len with no state
        # yet is the one hard ceiling below that
        if self.state is None and self.max_len is not None:
            ceiling = self.max_len
        else:
            ceiling = self.engine.ladder.seq_buckets[-1]
        if session.total_len > ceiling:
            raise ValueError(
                f"session {session.req_id}: prompt+budget="
                f"{session.total_len} exceeds max_len {ceiling}")

    def check_invariants(self, pipeline) -> None:
        """Sanitizer cross-check of engine accounting against the
        pipeline's live set, run at every tick boundary when the
        sanitizer is enabled (see `repro.runtime.sanitizer`):

        - slot<->session bijection: every pipeline-live session occupies
          the slot it claims, no slot is shared, and no occupied slot
          holds a session the pipeline no longer tracks;
        - chunk-slot ledger matches the pipeline's chunking queue;
        - block conservation + shadow refcount agreement (paged pool);
        - reservation balance: reserved blocks never exceed the free
          list, and every reservation belongs to a live session;
        - leak check at idle: with nothing in flight, every used block
          must be accounted for by the prefix cache.
        """
        seen_slots: Dict[int, int] = {}
        for s in pipeline.live:
            slot = s.slot
            if not 0 <= slot < self.max_slots or \
                    self.sessions[slot] is not s:
                raise sanitizer.SanitizerError(
                    f"slot<->session bijection broken: live session "
                    f"{s.req_id} claims slot {slot} but the engine maps "
                    "it elsewhere")
            if slot in seen_slots:
                raise sanitizer.SanitizerError(
                    f"slot {slot} shared by sessions "
                    f"{seen_slots[slot]} and {s.req_id}")
            seen_slots[slot] = s.req_id
        occupied = {i for i, s in enumerate(self.sessions)
                    if s is not None}
        stray = occupied - set(seen_slots)
        if stray:
            held = [self.sessions[i].req_id for i in sorted(stray)]
            raise sanitizer.SanitizerError(
                f"slots {sorted(stray)} hold sessions {held} the "
                "pipeline no longer tracks")
        chunk_reqs = {s.req_id for s in pipeline.chunking}
        if set(self._chunk_slots) != chunk_reqs:
            raise sanitizer.SanitizerError(
                f"chunk-slot ledger {sorted(self._chunk_slots)} does not "
                f"match the pipeline's chunking queue "
                f"{sorted(chunk_reqs)}")
        btm = self.block_table
        if btm is None:
            return
        resv = sum(self._reserved.values())
        if resv > btm.free_blocks:
            raise sanitizer.SanitizerError(
                f"reservation balance broken: {resv} blocks reserved "
                f"but only {btm.free_blocks} free")
        allowed = {s.req_id for s in pipeline.live} | chunk_reqs
        stray_resv = set(self._reserved) - allowed
        if stray_resv:
            raise sanitizer.SanitizerError(
                f"reservations held for sessions {sorted(stray_resv)} "
                "that are neither live nor chunking")
        # pack ledger: every block the most recent packed dispatch wrote
        # must still be owned by the segment it was written for (a freed
        # or re-assigned block would mean the pack scattered into memory
        # another request now owns).  The ledger tracks ownership moves:
        # a copy-on-write swaps the recorded id for the private copy, and
        # a freed session's entry is dropped with its table.
        for req, blocks in self._last_pack.items():
            if not btm.has_request(req):
                continue
            owned = set(btm.block_table(req))
            stray_blocks = [b for b in blocks if b not in owned]
            if stray_blocks:
                raise sanitizer.SanitizerError(
                    f"pack ledger: session {req} no longer owns blocks "
                    f"{stray_blocks} its packed prefill scattered into")
        if isinstance(btm, sanitizer.SanitizedBlockTableManager):
            btm.check_conservation()
            if pipeline.idle():
                cache_blocks = self.prefix_cache.cached_blocks \
                    if self.prefix_cache is not None else 0
                btm.check_idle(live_requests=(),
                               cache_blocks=cache_blocks)

    def prefill_batch(self, sessions: List[Session],
                      padded_len: int) -> None:
        if self.supports_packed_prefill():
            # one flat dispatch for the whole admission group, prefix
            # hits included — heterogeneous cached lengths no longer
            # split into one padded dispatch per cached-length part
            self.prefill_pack(sessions, [])
            return
        eng = self.engine
        # everything that can fail is checked BEFORE any device-state or
        # slab mutation — a partial prefill must not poison the slot cache
        over = [s.req_id for s in sessions
                if s.max_new_tokens > self.cap_new]
        if over:
            raise ValueError(
                f"sessions {over} exceed the emission buffer "
                f"(max_new_tokens > cap_new={self.cap_new}); raise "
                f"cap_new or lower the budget")
        dup = [s.req_id for s in sessions
               if eng.kv_slab.has_region(s.req_id)]
        if dup:
            raise ValueError(f"req_ids {dup} already hold KV regions "
                             "(duplicate in-flight submission?)")
        need = eng.ladder.seq_bucket(max(s.total_len for s in sessions))
        self._ensure_state(need)
        # slots reserved for in-flight chunked prefills are NOT free: a
        # final chunk will splice there, and a row spliced in meanwhile
        # would be overwritten mid-decode
        taken = set(self._chunk_slots.values())
        slots = [i for i, s in enumerate(self.sessions)
                 if s is None and i not in taken]
        slots = slots[:len(sessions)]
        assert len(slots) == len(sessions), "admitted beyond free slots"
        # prefix matching takes refcount holds on every matched block up
        # front, so one session's LRU eviction (below) can never reclaim
        # blocks a sibling in the same batch is about to share; every
        # exit past this point either adopts the holds into a table or
        # releases them (deficit veto below, parts-loop except sweep)
        matches: Optional[List[PrefixMatch]] = None
        if self.prefix_cache is not None:
            matches = [self.prefix_cache.match(list(s.prompt))
                       for s in sessions]
        if self.block_table is not None:
            btm = self.block_table
            want = 0
            for i, s in enumerate(sessions):
                covered = len(matches[i].full_blocks) if matches else 0
                want += btm.blocks_needed(s.total_len) - covered
            deficit = want + sum(self._reserved.values()) - btm.free_blocks
            if deficit > 0 and self.prefix_cache is not None:
                deficit -= self.prefix_cache.evict(deficit)
            if deficit > 0:
                if matches:
                    for m in matches:
                        self.prefix_cache.release(m)
                raise ValueError(
                    f"prefill batch needs {want} fresh KV blocks beyond "
                    f"reservations, pool has {btm.free_blocks} free — "
                    "the admission planner should have vetoed this batch")
        # ragged prefill is unsupported for SSM state, so SSM/hybrid
        # admissions run as equal-prompt-length sub-batches; prefix-cache
        # hits group by cached length (one suffix-prefill cell per
        # distinct shared-prefix length); other attention families
        # prefill the whole (right-padded) group at once
        if eng.cfg.family in ("ssm", "hybrid"):
            groups: Dict[int, List[int]] = {}
            for i, s in enumerate(sessions):
                groups.setdefault(s.seq_len, []).append(i)
            parts = list(groups.values())
        elif matches is not None:
            groups = {}
            for i, m in enumerate(matches):
                groups.setdefault(m.cached_tokens, []).append(i)
            parts = list(groups.values())
        else:
            parts = [list(range(len(sessions)))]
        try:
            for part in parts:
                part_sessions = [sessions[i] for i in part]
                part_slots = [slots[i] for i in part]
                part_matches = [matches[i] for i in part] \
                    if matches is not None else None
                cached = part_matches[0].cached_tokens \
                    if part_matches is not None else 0
                if cached:
                    pk, pv = self._gather_prefix(part_matches, cached)
                    rows = eng.prefill_suffix_batch(
                        [list(s.prompt) for s in part_sessions],
                        prefix_k=pk, prefix_v=pv, prefix_len=cached,
                        max_new_tokens=[s.max_new_tokens
                                        for s in part_sessions],
                        eos_id=[s.eos_id for s in part_sessions],
                        cap_new=self.cap_new,
                        sampling=[s.params for s in part_sessions])
                else:
                    prefill_len = need if self.kv_layout == "paged" \
                        else self.max_len
                    rows = eng.prefill_batch(
                        [list(s.prompt) for s in part_sessions],
                        max_len=prefill_len,
                        max_new_tokens=[s.max_new_tokens
                                        for s in part_sessions],
                        eos_id=[s.eos_id for s in part_sessions],
                        cap_new=self.cap_new,
                        sampling=[s.params for s in part_sessions])
                if self.kv_layout == "paged":
                    self._splice_paged(rows, part_slots, part_sessions,
                                       part_matches)
                else:
                    self._splice(rows, part_slots)
                self.prefill_dispatches += 1
                self.prefill_tokens += sum(s.seq_len - cached
                                           for s in part_sessions)
                for s in part_sessions:
                    s.cached_tokens = cached
        except Exception:
            # a failed part must not leak the batch's tables or the
            # matcher's holds: free() is a safe no-op for sessions that
            # never got a table, release() for matches never adopted.
            # Slots whose device rows an earlier part already spliced
            # must ALSO be neutralized (tables -> trash block, done=True)
            # — their freed blocks may be reallocated, and a still-live
            # row would keep writing KV into them (cross-request
            # corruption, not just a leak).
            bad_slots: List[int] = []
            for i, s in enumerate(sessions):
                if self.block_table is not None and \
                        self.block_table.has_request(s.req_id):
                    bad_slots.append(slots[i])
                    self.block_table.free(s.req_id)
                    self._reserved.pop(s.req_id, None)
                if matches is not None:
                    self.prefix_cache.release(matches[i])
            if bad_slots and self.kv_layout == "paged" \
                    and self.state is not None:
                st = self.state
                idx = jnp.asarray(np.array(bad_slots, np.int32))
                cache = dict(st.cache)
                cache["block_tables"] = \
                    cache["block_tables"].at[idx].set(0)
                self.state = replace(st, cache=cache,
                                     done=st.done.at[idx].set(True))
            raise
        now = self.clock()
        per_tok = kv_bytes_per_token(eng.cfg)
        for slot, s in zip(slots, sessions):
            self.sessions[slot] = s
            self._slot_len[slot] = s.seq_len
            eng.kv_slab.allocate(s.req_id, max(per_tok * s.total_len, 1),
                                 tokens=s.total_len)
            s.start_decode(now, slot=slot)
        if self.prefix_cache is not None:
            self._donate_prompts(sessions)
        # a budget-1 or instant-EOS prompt may be done already
        self._sync()
        self._publish_stream()     # the prefill's seed token streams too

    def decode_tick(self, sessions: List[Session]) -> None:
        if self.kv_layout == "paged":
            self._append_blocks()
        self.state = self.engine.decode_step_batch(self.state)
        self.decode_ticks += 1
        self._since_sync += 1
        if self._since_sync >= self.sync_every:
            self._sync()
        self._publish_stream()

    def _publish_stream(self) -> None:
        """Incremental token delivery for streaming sessions: one (tiny)
        host read of the counts/emitted buffers per tick, updating each
        ``stream=True`` session's ``generated`` in place so the pipeline
        token callback can hand fresh tokens to client handles.  Costs
        nothing when no occupied slot streams — the classic no-per-token-
        host-sync decode loop is untouched."""
        wanted = [(slot, s) for slot, s in enumerate(self.sessions)
                  if s is not None and s.stream]
        if not wanted:
            return
        # turbolint: allow-sync(per-tick streaming flush for stream=True rows)
        counts = np.asarray(self.state.counts)
        # turbolint: allow-sync(per-tick streaming flush for stream=True rows)
        emitted = np.asarray(self.state.emitted)
        for slot, s in wanted:
            s.generated = [int(x) for x in emitted[slot, :counts[slot]]]

    # -- AOT warmup ------------------------------------------------------
    def warmup_aot(self, progress: Optional[Callable[[int], None]] = None
                   ) -> Dict[str, float]:
        """Compile every reachable serving-path variant BEFORE the first
        request, so no client call ever pays a first-hit JIT on the
        serving path (the 3.7 s TTFT / 1.26 s ITL outliers in the
        pre-warmup bench).

        Execution-based: jit ``lower().compile()`` would not populate
        the ``__call__`` fast path the tick actually takes, so instead
        throwaway sessions (far-negative req_ids, never streamed, prefix
        cache suspended) are run through the REAL ``prefill_batch`` /
        ``decode_tick`` machinery:

        1. the slot cache is materialized at the top bucket up front —
           the lazy pool sizing otherwise depends on the first
           admission, which would change later tick signatures;
        2. one *sampled* prefill round per reachable (seq bucket,
           prompt bucket, admission size) cell — warming the prefill
           executable, the eager splice/scatter chains for every
           admission size, and the per-batch-shape first-token sampler;
        3. one greedy and one sampled decode round (the two tick
           variants), after which the sticky ``sampling`` flag is reset
           so greedy-only serving still runs the pure-argmax tick.

        Bucketed attention families are covered exactly; SSM/hybrid
        prompts key prefill cells by exact length, so for them only the
        tick variants and canonical rounds warm.  Telemetry counters
        are saved/restored — warmup is invisible in serving stats.
        Returns ``{"compile_count", "warmup_seconds", "rounds"}``.

        ``progress`` (if given) is called with the cumulative round
        count after every warm round — the incremental-warmup seam: a
        background-warming client yields its lock there so early
        traffic interleaves between rounds, and may raise to abort the
        remaining ladder (each round leaves the engine fully drained,
        so aborting between rounds is always safe).
        """
        eng = self.engine
        ladder = eng.ladder
        t0 = time.perf_counter()
        compiles0 = eng.compile_count
        top = self.max_len if self.max_len is not None \
            else ladder.seq_buckets[-1]
        saved = (self.prefill_tokens, self.decode_ticks, self.cow_blocks)
        prefix_was, pc = self._prefix_enabled, self.prefix_cache
        self._prefix_enabled, self.prefix_cache = False, None
        rounds = 0

        def _bump() -> None:
            nonlocal rounds
            rounds += 1
            if progress is not None:
                progress(rounds)

        try:
            self._ensure_state(top)
            seqs = [b for b in ladder.seq_buckets if b <= top]
            sizes = [n for n in range(1, self.max_slots + 1)
                     if n <= ladder.batch_buckets[-1]]
            cells = []
            for need in seqs:
                below = [b for b in seqs if b < need]
                prev = below[-1] if below else 0
                for pb in [b for b in seqs if b <= need]:
                    if pb == need:
                        plen, budget = need - 1, 1
                    else:
                        plen = pb
                        budget = prev + 1 - plen
                        if budget > self.cap_new:
                            plen = prev + 1 - self.cap_new
                            budget = self.cap_new
                    if plen < 1 or budget < 1 or budget > self.cap_new \
                            or ladder.seq_bucket(plen) != pb:
                        continue
                    cells.append((plen, budget))
            for plen, budget in cells:
                for n in sizes:
                    if self.block_table is not None:
                        bn = self.block_table.blocks_needed(plen + budget)
                        if bn * n > self.block_table.num_blocks - 1:
                            continue
                    self._warm_round(plen, budget, n, temperature=0.8)
                    _bump()
            # greedy admissions per batch shape (budget 1: the eager
            # first-token argmax is the only cold piece left), then the
            # two decode-tick variants at already-warm prefill shapes
            self.state = replace(self.state, sampling=False)
            plen = max(seqs[0] - 3, 1)
            for n in sizes:
                self._warm_round(plen, 1, n, temperature=0.0)
                _bump()
            n = min(2, self.max_slots)
            for temp in (0.0, 0.8):
                self._warm_round(plen, 3, n, temperature=temp)
                _bump()
            if self.supports_packed_prefill():
                # admission packs above warmed the prefix-free packed
                # cells; chunk packs also gather each segment's own
                # prefix KV, so warm one with-prefix cell too — the
                # first resumable chunk pays no JIT
                ks = self.state.cache["k"].shape   # (L, NB, BS, KV, dh)
                bs = self.block_size
                pre = jnp.zeros((ks[0], 2 * bs) + ks[3:],
                                self.state.cache["k"].dtype)
                pre_seg = jnp.asarray(
                    np.repeat(np.arange(2, dtype=np.int32), bs))
                pre_pos = jnp.asarray(
                    np.tile(np.arange(bs, dtype=np.int32), 2))
                eng.prefill_packed_flat(
                    [[1] * bs, [2] * bs], [bs, bs], pre, pre, pre_seg,
                    pre_pos)
                _bump()
                # admission rounds above packed n segments of ~bucket
                # length each, landing in the LARGE pack buckets; real
                # traffic also packs n tiny prompts into the smallest
                # bucket, so warm that cell per segment-slot count
                zero = jnp.zeros((ks[0], 0) + ks[3:],
                                 self.state.cache["k"].dtype)
                zseg = jnp.asarray(np.zeros((0,), np.int32))
                for n in sizes:
                    eng.prefill_packed_flat([[1]] * n, [0] * n, zero,
                                            zero, zseg, zseg)
                    _bump()
        finally:
            # all warm rows are done; a fresh greedy admission must get
            # the pure-argmax tick back
            if self.state is not None:
                self.state = replace(self.state, sampling=False)
            self.prefill_tokens, self.decode_ticks, self.cow_blocks = saved
            self._prefix_enabled = prefix_was
            if prefix_was:
                self.prefix_cache = pc if pc is not None else \
                    RadixPrefixCache(self.block_table)
                self.prefix_cache.on_insert = self.on_prefix_insert
        self.warmup_stats = {
            "compile_count": eng.compile_count - compiles0,
            "warmup_seconds": time.perf_counter() - t0,
            "rounds": rounds}
        return self.warmup_stats

    def _warm_round(self, plen: int, budget: int, n: int, *,
                    temperature: float) -> None:
        """One throwaway admission: ``n`` sessions of ``plen`` prompt
        tokens decoding ``budget`` tokens, run to completion so every
        slot frees again."""
        bucket = self.engine.ladder.seq_bucket(plen)
        sessions = []
        for j in range(n):
            rid = self._warm_id
            self._warm_id -= 1
            prompt = [(7 * j + i) % 17 + 1 for i in range(plen)]
            s = Session.from_params(rid, prompt, GenerationParams(
                max_new_tokens=budget, temperature=temperature,
                seed=j + 1))
            s.start_prefill(0.0, n, bucket)
            sessions.append(s)
        self.prefill_batch(sessions, bucket)
        for _ in range((budget + 2) * max(self.sync_every, 1) + 4):
            if all(s.is_finished for s in sessions):
                break
            self.decode_tick(sessions)
        else:
            raise RuntimeError("warmup round failed to converge")

    # -- chunked prefill -------------------------------------------------
    def supports_chunked_prefill(self) -> bool:
        """Chunked prefill scatters each chunk's KV into the request's
        own pool blocks, so it needs the paged layout (the contiguous
        slot cache has no per-request home for a half-built prompt)."""
        return self.kv_layout == "paged"

    def supports_fused_chunk_decode(self) -> bool:
        """Non-final prefill chunks are pure device work — gather the
        prefix KV, run the suffix cell, scatter — with no host sync, so
        the inherited ``chunk_decode_tick`` (chunk then decode tick)
        dispatches both back-to-back as one async group and the decode
        batch never stalls on the chunk's completion."""
        return self.kv_layout == "paged"

    def chunk_quantum(self) -> int:
        return self.block_size

    # -- packed prefill --------------------------------------------------
    def supports_packed_prefill(self) -> bool:
        """Packed prefill concatenates many segments into one flat
        dispatch and scatters per-segment KV into paged blocks, so it
        needs the paged layout; that already excludes SSM/hybrid, whose
        state rolls through padding and keeps the equal-length
        sequential fallback."""
        return self.kv_layout == "paged" and self.packed_prefill

    def pack_bucket(self, flat_tokens: int) -> int:
        """Pack bucket a flat token count pads to (the occupancy
        histogram's denominator)."""
        return self.engine.ladder.pack_bucket(flat_tokens)

    def prefill_pack(self, admissions: List[Session],
                     chunks: List[Tuple[Session, int]],
                     decoding: Optional[List[Session]] = None) -> None:
        """ONE packed device dispatch serving a whole pack group:
        ``admissions`` (newly planned sessions — whole prompts, or
        uncached suffixes on a prefix-cache hit) and ``chunks``
        (``(session, upto)`` next-chunk advances for resumable
        prefills), concatenated with segment ids and per-token
        positions, prefilled once, then scattered into each session's
        own block table (`sanitizer.check_write` on every segment's
        exact block range).  Admissions and final chunks seed their
        decode rows from their segment's last-token logits and splice
        into the slot cache together.

        ``decoding`` (only legal when nothing in the pack splices) fuses
        the decode tick behind the pack the way ``chunk_decode_tick``
        does — both dispatch back-to-back as one async group.
        """
        eng = self.engine
        if not self.supports_packed_prefill():
            raise ValueError("packed prefill requires kv_layout='paged' "
                             "with packed_prefill enabled")
        if not admissions and not chunks:
            return
        # the segment-id row caps at the ladder's top batch bucket; a
        # group the scheduler composed past it (max_batch_size above the
        # ladder, or a failover burst) splits into ladder-sized packs
        cap = eng.ladder.batch_buckets[-1]
        if len(admissions) + len(chunks) > cap:
            work = [("a", s) for s in admissions] + \
                [("c", c) for c in chunks]
            for at in range(0, len(work), cap):
                grp = work[at:at + cap]
                last = at + cap >= len(work)
                self.prefill_pack(
                    [w for k, w in grp if k == "a"],
                    [w for k, w in grp if k == "c"],
                    decoding if last else None)
            return
        # ---- admission pre-checks (nothing mutated before they pass) --
        over = [s.req_id for s in admissions
                if s.max_new_tokens > self.cap_new]
        if over:
            raise ValueError(
                f"sessions {over} exceed the emission buffer "
                f"(max_new_tokens > cap_new={self.cap_new}); raise "
                f"cap_new or lower the budget")
        dup = [s.req_id for s in admissions
               if eng.kv_slab.has_region(s.req_id)]
        if dup:
            raise ValueError(f"req_ids {dup} already hold KV regions "
                             "(duplicate in-flight submission?)")
        if admissions:
            need = eng.ladder.seq_bucket(
                max(s.total_len for s in admissions))
            self._ensure_state(need)
        taken = set(self._chunk_slots.values())
        slots = [i for i, s in enumerate(self.sessions)
                 if s is None and i not in taken][:len(admissions)]
        assert len(slots) == len(admissions), "admitted beyond free slots"
        matches: Optional[List[PrefixMatch]] = None
        if self.prefix_cache is not None and admissions:
            matches = [self.prefix_cache.match(list(s.prompt))
                       for s in admissions]
        btm = self.block_table
        if admissions:
            want = 0
            for i, s in enumerate(admissions):
                covered = len(matches[i].full_blocks) if matches else 0
                want += btm.blocks_needed(s.total_len) - covered
            deficit = want + sum(self._reserved.values()) - \
                btm.free_blocks
            if deficit > 0 and self.prefix_cache is not None:
                deficit -= self.prefix_cache.evict(deficit)
            if deficit > 0:
                if matches:
                    for m in matches:
                        self.prefix_cache.release(m)
                raise ValueError(
                    f"packed prefill needs {want} fresh KV blocks beyond "
                    f"reservations, pool has {btm.free_blocks} free — "
                    "the admission planner should have vetoed this pack")
        # ---- chunk validation + block ensure (reserved at admission,
        # so ensure cannot exhaust the pool) -----------------------------
        for s, upto in chunks:
            req = s.req_id
            off = s.prefilled_tokens
            if req not in self._chunk_slots:
                raise ValueError(f"session {req} has no chunked prefill "
                                 "in flight")
            if not off < upto <= s.seq_len:
                raise ValueError(f"chunk [{off}, {upto}) out of range "
                                 f"for prompt length {s.seq_len}")
            final = upto == s.seq_len
            cover = min(s.seq_len + 1, s.total_len) if final else upto
            fresh = btm.ensure(req, cover)
            if fresh:
                self._reserved[req] = max(
                    self._reserved[req] - len(fresh), 0)
        # ---- segment descriptors: admissions first, then chunks -------
        # (suffix tokens, position offset, prefix pool indices)
        bs = self.block_size
        suffixes: List[List[int]] = []
        offsets: List[int] = []
        pre_fidx: List[np.ndarray] = []
        pre_seg: List[np.ndarray] = []
        pre_pos: List[np.ndarray] = []

        def add_prefix(seg: int, blocks: List[int], length: int) -> None:
            pos = np.arange(length)
            ids = np.asarray(blocks, np.int32)
            pre_fidx.append(ids[pos // bs] * bs + pos % bs)
            pre_seg.append(np.full((length,), seg, np.int32))
            pre_pos.append(pos.astype(np.int32))

        for i, s in enumerate(admissions):
            cached = matches[i].cached_tokens if matches else 0
            suffixes.append(list(s.prompt)[cached:])
            offsets.append(cached)
            if cached:
                blocks = list(matches[i].full_blocks)
                if matches[i].tail_block is not None:
                    blocks.append(matches[i].tail_block)
                add_prefix(i, blocks, cached)
        for j, (s, upto) in enumerate(chunks):
            off = s.prefilled_tokens
            suffixes.append(list(s.prompt)[off:upto])
            offsets.append(off)
            if off:
                add_prefix(len(admissions) + j,
                           list(btm.block_table(s.req_id)), off)
        # ---- gather every segment's prefix KV in one pool read --------
        st = self.state
        k_pool, v_pool = st.cache["k"], st.cache["v"]
        pool_blocks = k_pool.shape[1]
        flat_shape = (k_pool.shape[0], pool_blocks * bs) + \
            k_pool.shape[3:]
        if pre_fidx:
            gidx = jnp.asarray(np.concatenate(pre_fidx))
            prefix_k = k_pool.reshape(flat_shape)[:, gidx]
            prefix_v = v_pool.reshape(flat_shape)[:, gidx]
            prefix_seg = jnp.asarray(np.concatenate(pre_seg))
            prefix_pos = jnp.asarray(np.concatenate(pre_pos))
        else:
            prefix_k = jnp.zeros(
                (k_pool.shape[0], 0) + k_pool.shape[3:], k_pool.dtype)
            prefix_v = prefix_k
            prefix_seg = jnp.zeros((0,), jnp.int32)
            prefix_pos = jnp.zeros((0,), jnp.int32)
        try:
            # ---- THE dispatch -----------------------------------------
            logits, parts = eng.prefill_packed_flat(
                suffixes, offsets, prefix_k, prefix_v, prefix_seg,
                prefix_pos)
            # ---- allocate admission tables (prefix refs adopted, tail
            # copy-on-write) and collect every segment's scatter target -
            cache = dict(st.cache)
            k_pool, v_pool = cache["k"], cache["v"]
            tables = cache["block_tables"]
            tgt: List[np.ndarray] = []
            pack_ledger: Dict[int, List[int]] = {}
            written: set = set()
            seg_bids: List[List[int]] = []
            for i, s in enumerate(admissions):
                m = matches[i] if matches is not None else None
                cached = 0
                prefix_blocks: List[int] = []
                if m is not None:
                    m.consumed = True   # holds transfer to the table
                    cached = m.cached_tokens
                    prefix_blocks = list(m.full_blocks)
                    if m.tail_block is not None:
                        try:
                            cow = btm.take(1)[0]
                        except BlockExhausted:
                            for b in prefix_blocks:
                                btm.unref(b)
                            btm.unref(m.tail_block)
                            raise
                        k_pool = k_pool.at[:, cow].set(
                            k_pool[:, m.tail_block])
                        v_pool = v_pool.at[:, cow].set(
                            v_pool[:, m.tail_block])
                        btm.unref(m.tail_block)
                        prefix_blocks.append(cow)
                        self.cow_blocks += 1
                alloc_tokens = min(s.seq_len + 1, s.total_len)
                try:
                    bids = btm.allocate(s.req_id, alloc_tokens,
                                        prefix_blocks=prefix_blocks)
                except BlockExhausted:
                    for b in prefix_blocks:
                        btm.unref(b)
                    raise
                self._reserved[s.req_id] = max(
                    btm.blocks_needed(s.total_len) - len(bids), 0)
                seg_bids.append(bids)
            for s, upto in chunks:
                seg_bids.append(btm.block_table(s.req_id))
            spans = [(s, off, s.seq_len)
                     for s, off in zip(admissions, offsets)] + \
                    [(s, s.prefilled_tokens, upto) for s, upto in chunks]
            for (s, off, end), bids in zip(spans, seg_bids):
                seg_blocks = bids[off // bs:(end - 1) // bs + 1]
                sanitizer.check_write(btm, s.req_id, seg_blocks)
                overlap = [b for b in seg_blocks if b in written]
                if overlap:
                    raise sanitizer.SanitizerError(
                        f"pack segments overlap on blocks {overlap} "
                        f"(session {s.req_id}) — cross-request KV "
                        "corruption")
                written.update(seg_blocks)
                pack_ledger[s.req_id] = list(seg_blocks)
                pos = np.arange(off, end)
                tgt.append(np.asarray(bids, np.int32)[pos // bs] * bs +
                           pos % bs)
            # ---- ONE scatter: the flat pack lines up with the
            # concatenated per-segment targets ---------------------------
            flat = sum(len(s) for s in suffixes)
            fidx = jnp.asarray(np.concatenate(tgt))
            k_pool = k_pool.reshape(flat_shape).at[:, fidx].set(
                parts["k"][:, :flat]).reshape(k_pool.shape)
            v_pool = v_pool.reshape(flat_shape).at[:, fidx].set(
                parts["v"][:, :flat]).reshape(v_pool.shape)
            cache["k"], cache["v"] = k_pool, v_pool
            # ---- splice decode rows: admissions + final chunks --------
            splicers: List[Tuple[int, int, Session]] = []
            for i, (slot, s) in enumerate(zip(slots, admissions)):
                splicers.append((i, slot, s))
            for j, (s, upto) in enumerate(chunks):
                if upto == s.seq_len:
                    splicers.append((len(admissions) + j,
                                     self._chunk_slots[s.req_id], s))
            if splicers:
                ns = len(splicers)
                batch_b = eng.ladder.batch_bucket(ns)
                sel = jnp.asarray(np.array(
                    [seg for seg, _, _ in splicers] +
                    [0] * (batch_b - ns), np.int32))
                ctl_cache = {
                    "len": jnp.asarray(np.array(
                        [s.seq_len for _, _, s in splicers] +
                        [1] * (batch_b - ns), np.int32)),
                    "pos_offset": jnp.zeros((batch_b,), jnp.int32),
                }
                rows = eng._finish_gen_state(
                    logits[sel], ctl_cache, ns, batch_b,
                    budgets=[s.max_new_tokens for _, _, s in splicers],
                    eos_ids=[s.eos_id for _, _, s in splicers],
                    cap=self.cap_new,
                    sampling=[s.params for _, _, s in splicers])
                for (seg, slot, s) in splicers:
                    row = np.zeros((self.max_blocks,), np.int32)
                    bids = seg_bids[seg]
                    row[:len(bids)] = bids
                    tables = tables.at[slot].set(jnp.asarray(row))
                cache["block_tables"] = tables
                idx = jnp.asarray(np.array(
                    [slot for _, slot, _ in splicers], np.int32))
                for key in _BATCH_AXIS0:
                    cache[key] = cache[key].at[idx].set(
                        _rows(rows.cache[key], key, ns))
                self.state = self._spliced(cache, rows, idx, ns)
            else:
                self.state = replace(st, cache=cache)
        except Exception:
            # mirror prefill_batch's sweep: free admission tables and
            # holds, neutralize any slot whose row state may have been
            # touched; chunk sessions keep their reservations — the
            # pipeline aborts them explicitly
            bad_slots: List[int] = []
            for i, s in enumerate(admissions):
                if btm.has_request(s.req_id):
                    bad_slots.append(slots[i])
                    btm.free(s.req_id)
                    self._reserved.pop(s.req_id, None)
                if matches is not None:
                    self.prefix_cache.release(matches[i])
            if bad_slots and self.state is not None:
                bst = self.state
                bidx = jnp.asarray(np.array(bad_slots, np.int32))
                bcache = dict(bst.cache)
                bcache["block_tables"] = \
                    bcache["block_tables"].at[bidx].set(0)
                self.state = replace(bst, cache=bcache,
                                     done=bst.done.at[bidx].set(True))
            raise
        # ---- host bookkeeping -----------------------------------------
        self._last_pack = pack_ledger
        self.prefill_dispatches += 1
        self.pack_dispatches += 1
        self.pack_segments += len(suffixes)
        self.prefill_tokens += flat
        now = self.clock()
        per_tok = kv_bytes_per_token(eng.cfg)
        for i, (slot, s) in enumerate(zip(slots, admissions)):
            cached = matches[i].cached_tokens if matches else 0
            s.cached_tokens = cached
            self.sessions[slot] = s
            self._slot_len[slot] = s.seq_len
            eng.kv_slab.allocate(s.req_id, max(per_tok * s.total_len, 1),
                                 tokens=s.total_len)
            s.start_decode(now, slot=slot)
        finals: List[Session] = []
        for s, upto in chunks:
            s.prefilled_tokens = upto
            if upto == s.seq_len:
                slot = self._chunk_slots.pop(s.req_id)
                self.sessions[slot] = s
                self._slot_len[slot] = s.seq_len
                s.start_decode(now, slot=slot)
                finals.append(s)
        if self.prefix_cache is not None and (admissions or finals):
            self._donate_prompts(list(admissions) + finals)
        if admissions or finals:
            # a budget-1 or instant-EOS prompt may be done already
            self._sync()
            self._publish_stream()
        if decoding is not None:
            assert not admissions and not finals, \
                "fused pack+decode is only legal for non-splicing packs"
            self.decode_tick(decoding)

    def begin_prefill_chunks(self, session: Session) -> None:
        """Reserve everything the resumable prefill will need — a decode
        slot and blocks/reservations covering the WHOLE prompt + first
        decode write — before any chunk runs, so no chunk can fail on
        capacity mid-prompt.  With the prefix cache on, the matched
        prefix maps in here (tail copy-on-write included) and
        ``session.prefilled_tokens`` starts at the cached length: the
        chunks only cover the uncached remainder."""
        if self.kv_layout != "paged":
            raise ValueError("chunked prefill requires kv_layout='paged'")
        eng = self.engine
        if eng.kv_slab.has_region(session.req_id):
            raise ValueError(f"req_id {session.req_id} already holds a "
                             "KV region (duplicate in-flight submission?)")
        need = eng.ladder.seq_bucket(session.total_len)
        self._ensure_state(need)
        taken = set(self._chunk_slots.values())
        free = [i for i, s in enumerate(self.sessions)
                if s is None and i not in taken]
        assert free, "chunked admission beyond free slots"
        slot = free[0]
        btm = self.block_table
        match: Optional[PrefixMatch] = None
        cached = 0
        if self.prefix_cache is not None:
            match = self.prefix_cache.match(list(session.prompt))
            cached = match.cached_tokens
        covered = len(match.full_blocks) if match is not None else 0
        want = btm.blocks_needed(session.total_len) - covered
        deficit = want + sum(self._reserved.values()) - btm.free_blocks
        if deficit > 0 and self.prefix_cache is not None:
            deficit -= self.prefix_cache.evict(deficit)
        if deficit > 0:
            if match is not None:
                self.prefix_cache.release(match)
            raise ValueError(
                f"chunked prefill needs {want} fresh KV blocks beyond "
                f"reservations, pool has {btm.free_blocks} free — the "
                "admission planner should have vetoed this session")
        prefix_blocks: List[int] = []
        if match is not None:
            match.consumed = True    # holds transfer to the table below
            prefix_blocks = list(match.full_blocks)
            if match.tail_block is not None:
                try:
                    cow = btm.take(1)[0]
                except BlockExhausted:
                    for b in prefix_blocks:
                        btm.unref(b)
                    btm.unref(match.tail_block)
                    raise
                st = self.state
                cache = dict(st.cache)
                cache["k"] = cache["k"].at[:, cow].set(
                    cache["k"][:, match.tail_block])
                cache["v"] = cache["v"].at[:, cow].set(
                    cache["v"][:, match.tail_block])
                self.state = replace(st, cache=cache)
                btm.unref(match.tail_block)
                prefix_blocks.append(cow)
                self.cow_blocks += 1
        try:
            bids = btm.allocate(session.req_id, max(cached, 1),
                                prefix_blocks=prefix_blocks)
        except BlockExhausted:
            for b in prefix_blocks:
                btm.unref(b)
            raise
        self._reserved[session.req_id] = max(
            btm.blocks_needed(session.total_len) - len(bids), 0)
        self._chunk_slots[session.req_id] = slot
        per_tok = kv_bytes_per_token(eng.cfg)
        eng.kv_slab.allocate(session.req_id,
                             max(per_tok * session.total_len, 1),
                             tokens=session.total_len)
        session.cached_tokens = cached
        session.prefilled_tokens = cached

    def prefill_chunk(self, session: Session, upto: int) -> None:
        """One resumable-prefill pass over prompt positions
        ``[prefilled_tokens, upto)``: gather the already-built prefix KV
        from the session's own blocks, run the suffix cell at that
        offset (causal attention continued across the chunk seam), and
        scatter the chunk's KV into the session's blocks.  The final
        chunk (``upto == seq_len``) seeds the decode row from its
        last-token logits and splices it into the reserved slot."""
        eng = self.engine
        req = session.req_id
        off = session.prefilled_tokens
        if req not in self._chunk_slots:
            raise ValueError(f"session {req} has no chunked prefill in "
                             "flight")
        if not off < upto <= session.seq_len:
            raise ValueError(f"chunk [{off}, {upto}) out of range for "
                             f"prompt length {session.seq_len}")
        btm = self.block_table
        final = upto == session.seq_len
        cover = min(session.seq_len + 1, session.total_len) if final \
            else upto
        fresh = btm.ensure(req, cover)
        if fresh:
            self._reserved[req] = max(self._reserved[req] - len(fresh), 0)
        pk, pv = self._gather_own_prefix(req, off)
        rows = eng.prefill_suffix_batch(
            [list(session.prompt)[:upto]], prefix_k=pk, prefix_v=pv,
            prefix_len=off, max_new_tokens=[session.max_new_tokens],
            eos_id=[session.eos_id], cap_new=self.cap_new,
            sampling=[session.params])
        bids = btm.block_table(req)
        bs = self.block_size
        # sanitizer: the chunk scatters into exactly these blocks
        sanitizer.check_write(btm, req,
                              bids[off // bs:(upto - 1) // bs + 1])
        st = self.state
        cache = dict(st.cache)
        k_pool, v_pool = cache["k"], cache["v"]
        pos = np.arange(off, upto)
        fidx = jnp.asarray(
            np.asarray(bids, np.int32)[pos // bs] * bs + pos % bs)
        flat_shape = (k_pool.shape[0], k_pool.shape[1] * bs) + \
            k_pool.shape[3:]
        k_pool = k_pool.reshape(flat_shape).at[:, fidx].set(
            rows.cache["k"][:, 0, :upto - off]).reshape(k_pool.shape)
        v_pool = v_pool.reshape(flat_shape).at[:, fidx].set(
            rows.cache["v"][:, 0, :upto - off]).reshape(v_pool.shape)
        cache["k"], cache["v"] = k_pool, v_pool
        self.state = replace(st, cache=cache)
        session.prefilled_tokens = upto
        self.prefill_dispatches += 1
        self.prefill_tokens += upto - off
        if not final:
            return
        # final chunk: claim the reserved slot and splice the control row
        slot = self._chunk_slots.pop(req)
        idx = jnp.asarray(np.array([slot], np.int32))
        st = self.state
        cache = dict(st.cache)
        row = np.zeros((self.max_blocks,), np.int32)
        row[:len(bids)] = bids
        cache["block_tables"] = cache["block_tables"].at[slot].set(
            jnp.asarray(row))
        for key in _BATCH_AXIS0:
            cache[key] = cache[key].at[idx].set(
                _rows(rows.cache[key], key, 1))
        self.state = self._spliced(cache, rows, idx, 1)
        self.sessions[slot] = session
        self._slot_len[slot] = session.seq_len
        session.start_decode(self.clock(), slot=slot)
        if self.prefix_cache is not None:
            self._donate_prompts([session])
        # a budget-1 or instant-EOS prompt may be done already
        self._sync()
        self._publish_stream()

    def abort_chunked(self, session: Session) -> None:
        """Drop every hold a failed (or cancelled) chunked prefill still
        has.  Its slot was never claimed and its block-table row was
        never published, so freeing the blocks is safe — no device row
        can write into them.  Matched shared-prefix blocks were adopted
        into the table at ``begin_prefill_chunks``, so ``free`` unrefs
        them back to the trie without disturbing other holders."""
        req = session.req_id
        if self.block_table is not None:
            self.block_table.free(req)
        self._reserved.pop(req, None)
        self._chunk_slots.pop(req, None)
        self._last_pack.pop(req, None)
        if self.engine.kv_slab.has_region(req):
            self.engine.kv_slab.free(req)
            self.engine.kv_slab.gc()

    def cancel_session(self, session: Session) -> None:
        """Tear down a mid-decode session NOW: publish its partial
        generation (one row read), release its KV slab region, drop its
        block table (shared prefix blocks just lose one holder — sibling
        sequences and the prefix trie keep theirs), clear reservations,
        and neutralize the device row (done=True, block table row ->
        trash) so the freed physical blocks can be reallocated without
        the stale row writing into them."""
        slot = session.slot
        if slot < 0 or self.sessions[slot] is not session:
            raise ValueError(f"session {session.req_id} holds no decode "
                             "slot")
        st = self.state
        # turbolint: allow-sync(cancellation reads the partial result once)
        counts = int(np.asarray(st.counts[slot]))
        # turbolint: allow-sync(cancellation reads the partial result once)
        emitted = np.asarray(st.emitted[slot])
        session.generated = [int(x) for x in emitted[:counts]]
        self.engine.kv_slab.free(session.req_id)
        self.engine.kv_slab.gc()
        if self.block_table is not None:
            self.block_table.free(session.req_id)
            self._reserved.pop(session.req_id, None)
        self._last_pack.pop(session.req_id, None)
        self.sessions[slot] = None
        self._slot_len[slot] = 0
        cache = dict(st.cache)
        if self.block_table is not None:
            cache["block_tables"] = cache["block_tables"].at[slot].set(0)
        self.state = replace(st, cache=cache,
                             done=st.done.at[slot].set(True))

    def _gather_own_prefix(self, req_id: int, length: int
                           ) -> Tuple[jax.Array, jax.Array]:
        """Prefix KV ``[0, length)`` gathered from the request's OWN
        block table — the left side of a chunk seam (shape
        (L, 1, length, KV, dh); length 0 yields empty arrays for the
        first chunk of a cold prompt)."""
        bs = self.block_size
        nb = max(-(-length // bs), 1)
        table = self.block_table.block_table(req_id)
        ids = np.zeros((1, nb), np.int32)
        ids[0, :min(len(table), nb)] = table[:nb]
        idx = jnp.asarray(ids)

        def gather(pool):
            g = pool[:, idx]                 # (L, 1, nb, BS, kv, dh)
            flat = (pool.shape[0], 1, nb * bs) + pool.shape[3:]
            return g.reshape(flat)[:, :, :length]

        return (gather(self.state.cache["k"]),
                gather(self.state.cache["v"]))

    # -- internals -------------------------------------------------------
    def _ensure_state(self, need_len: int) -> None:
        eng = self.engine
        if self.state is None:
            with jax.default_device(eng.device):
                self._make_state(need_len)
            return
        if self.kv_layout == "paged":
            return      # pool and tables are fixed-shape for life
        if need_len > self.max_len:
            # contiguous fallback: re-materialize the slot cache with a
            # longer sequence axis.  Every leaf with a seq axis must be
            # padded — k/v AND the shared_k/shared_v leaves of
            # cross-layer KV-sharing (hybrid) models, which the original
            # version silently dropped, leaving their writes to clamp at
            # the stale boundary.
            grow = need_len - self.max_len
            cache = dict(self.state.cache)
            for k in ("k", "v", "shared_k", "shared_v"):
                if k not in cache:
                    continue
                pad = [(0, 0)] * cache[k].ndim
                pad[2] = (0, grow)      # (L|n_apps, B, S, kv, dh) seq axis
                cache[k] = jnp.pad(cache[k], pad)
            self.state = replace(self.state, cache=cache)
            self.max_len = need_len

    def _make_state(self, need_len: int) -> None:
        """The slot cache and per-row control state, zeroed and committed
        to the engine's device (made there under ``_ensure_state``'s
        default device, so committing copies nothing).  An uncommitted
        array would follow the next uncommitted operand of an eager op
        back to the default device."""
        eng = self.engine
        B = self.max_slots
        if self.kv_layout == "paged":
            if self.block_table is None:
                # lazy pool: max_slots x this admission's bucket of
                # blocks (+ trash) — workload-derived capacity that
                # any mix of sequence lengths up to max_len shares
                self.block_table = sanitizer.make_block_manager(
                    B * (need_len // self.block_size) + 1,
                    self.block_size)
            if self._prefix_enabled and self.prefix_cache is None:
                self.prefix_cache = RadixPrefixCache(self.block_table)
                self.prefix_cache.on_insert = self.on_prefix_insert
            cache = make_paged_cache(
                eng.cfg, B, self.block_table.num_blocks,
                self.block_size, self.max_blocks, eng.cache_dtype)
        else:
            if self.max_len is None:
                self.max_len = need_len
            if need_len > self.max_len:
                raise ValueError(f"prompt+budget needs {need_len} > "
                                 f"slot cache max_len {self.max_len}")
            cache = make_cache(eng.cfg, B, self.max_len, eng.cache_dtype)
        fields = dict(
            cache=cache,
            cur=jnp.zeros((B,), jnp.int32),
            emitted=jnp.zeros((B, self.cap_new), jnp.int32),
            counts=jnp.zeros((B,), jnp.int32),
            done=jnp.ones((B,), bool),
            budget=jnp.zeros((B,), jnp.int32),
            eos=jnp.full((B, STOP_SLOTS), -1, jnp.int32),
            temp=jnp.zeros((B,), jnp.float32),
            top_k=jnp.zeros((B,), jnp.int32),
            top_p=jnp.ones((B,), jnp.float32),
            seed=jnp.zeros((B,), jnp.int32))
        if eng.device is not None:
            fields = jax.device_put(fields, eng.device)
        self.state = GenState(**fields)

    def _spliced(self, cache: Dict[str, jax.Array], rows: GenState,
                 idx: jax.Array, k: int) -> GenState:
        """New GenState: ``cache`` plus the first ``k`` per-row control
        leaves of ``rows`` written at ``idx`` (shared by both layouts)."""
        st = self.state
        return GenState(
            cache=cache,
            cur=st.cur.at[idx].set(_rows(rows.cur, None, k)),
            emitted=st.emitted.at[idx].set(_rows(rows.emitted, None, k)),
            counts=st.counts.at[idx].set(_rows(rows.counts, None, k)),
            done=st.done.at[idx].set(_rows(rows.done, None, k)),
            budget=st.budget.at[idx].set(_rows(rows.budget, None, k)),
            eos=st.eos.at[idx].set(_rows(rows.eos, None, k)),
            temp=st.temp.at[idx].set(_rows(rows.temp, None, k)),
            top_k=st.top_k.at[idx].set(_rows(rows.top_k, None, k)),
            top_p=st.top_p.at[idx].set(_rows(rows.top_p, None, k)),
            seed=st.seed.at[idx].set(_rows(rows.seed, None, k)),
            # sticky: once a sampled row joins, the sampling tick serves
            # the whole slot cache (greedy rows keep argmax values)
            sampling=st.sampling or rows.sampling)

    def _splice(self, rows: GenState, slots: List[int]) -> None:
        """Insert the first ``len(slots)`` rows of a freshly prefilled
        GenState into the persistent slot cache."""
        st = self.state
        k = len(slots)
        idx = jnp.asarray(np.array(slots, np.int32))
        cache = {}
        for key, leaf in st.cache.items():
            src = _rows(rows.cache[key], key, k)
            if key in _BATCH_AXIS0:
                cache[key] = leaf.at[idx].set(src)
            else:
                cache[key] = leaf.at[:, idx].set(src)
        self.state = self._spliced(cache, rows, idx, k)

    def _donate_prompts(self, sessions: List[Session]) -> None:
        """Donate every admitted prompt to the trie.  A donated partial
        tail makes the owner's first decode write copy-on-write, which
        needs one extra block later — so the tail is donated only when
        that block can be reserved NOW (evicting warm cache if needed);
        otherwise only the full-block prefix is cached.  This keeps the
        reservation invariant (free blocks always cover reservations)
        without charging speculative COW blocks at admission."""
        btm = self.block_table
        bs = self.block_size
        for s in sessions:
            table = btm.block_table(s.req_id)
            tokens = list(s.prompt)
            donate_tail = bool(s.seq_len % bs) and s.max_new_tokens > 0
            if donate_tail:
                deficit = sum(self._reserved.values()) + 1 - \
                    btm.free_blocks
                if deficit > 0:
                    self.prefix_cache.evict(deficit)
                if sum(self._reserved.values()) + 1 <= btm.free_blocks:
                    self._reserved[s.req_id] += 1
                else:
                    donate_tail = False
            if not donate_tail and s.seq_len % bs:
                tokens = tokens[:(s.seq_len // bs) * bs]
            self.prefix_cache.insert(tokens, table)
            if donate_tail:
                tail = table[(s.seq_len - 1) // bs]
                if btm.ref_count(tail) == 1:
                    # tail deduped against an existing node: the owner
                    # keeps writing its private block, no COW coming
                    self._reserved[s.req_id] -= 1

    def _gather_prefix(self, matches: List[PrefixMatch], cached: int
                       ) -> Tuple[jax.Array, jax.Array]:
        """Materialize the matched prefix KV for a suffix-prefill group:
        gather each session's matched blocks from the pool and trim to
        the exact cached length (L, B, cached, KV, dh)."""
        bs = self.block_size
        nb = -(-cached // bs)
        ids = np.zeros((len(matches), nb), np.int32)
        for i, m in enumerate(matches):
            blocks = list(m.full_blocks)
            if m.tail_block is not None:
                blocks.append(m.tail_block)
            ids[i, :len(blocks)] = blocks
        idx = jnp.asarray(ids)

        def gather(pool):
            g = pool[:, idx]                     # (L, B, nb, BS, kv, dh)
            flat = (pool.shape[0], len(matches), nb * bs) + pool.shape[3:]
            return g.reshape(flat)[:, :, :cached]

        return (gather(self.state.cache["k"]),
                gather(self.state.cache["v"]))

    def _splice_paged(self, rows: GenState, slots: List[int],
                      sessions: List[Session],
                      matches: Optional[List[PrefixMatch]] = None) -> None:
        """Allocate block tables for newly admitted sessions and scatter
        their prefilled KV from the (temporary) contiguous prefill cache
        into the paged pool — existing rows' blocks are untouched.

        With prefix matches, a session's table opens with the matched
        shared blocks (refs transferred from the matcher); a partially
        valid matched tail is copied into a private block first
        (copy-on-write — the suffix writes into it); only the uncached
        suffix KV is scattered."""
        btm = self.block_table
        bs = self.block_size
        st = self.state
        k = len(slots)
        idx = jnp.asarray(np.array(slots, np.int32))
        cache = dict(st.cache)
        k_pool, v_pool = cache["k"], cache["v"]
        tables = cache["block_tables"]
        pool_blocks = k_pool.shape[1]
        for i, (slot, s) in enumerate(zip(slots, sessions)):
            m = matches[i] if matches is not None else None
            cached = 0
            prefix_blocks: List[int] = []
            if m is not None:
                m.consumed = True      # holds transfer to the table below
                cached = m.cached_tokens
                prefix_blocks = list(m.full_blocks)
                if m.tail_block is not None:
                    try:
                        cow = btm.take(1)[0]
                    except BlockExhausted:
                        for b in prefix_blocks:
                            btm.unref(b)
                        btm.unref(m.tail_block)
                        raise
                    k_pool = k_pool.at[:, cow].set(k_pool[:, m.tail_block])
                    v_pool = v_pool.at[:, cow].set(v_pool[:, m.tail_block])
                    btm.unref(m.tail_block)
                    prefix_blocks.append(cow)
                    self.cow_blocks += 1
            # blocks covering the prompt plus the first decode write; the
            # rest of the budget is reserved and appended mid-decode
            alloc_tokens = min(s.seq_len + 1, s.total_len)
            try:
                bids = btm.allocate(s.req_id, alloc_tokens,
                                    prefix_blocks=prefix_blocks)
            except BlockExhausted:
                for b in prefix_blocks:
                    btm.unref(b)
                raise
            self._reserved[s.req_id] = max(
                btm.blocks_needed(s.total_len) - len(bids), 0)
            # scatter ONLY the uncached suffix KV into this request's
            # blocks (flat pool indices; shared prefix blocks untouched)
            suffix_len = s.seq_len - cached
            sanitizer.check_write(
                btm, s.req_id,
                bids[cached // bs:(s.seq_len - 1) // bs + 1])
            pos = np.arange(cached, s.seq_len)
            fidx = jnp.asarray(
                np.asarray(bids, np.int32)[pos // bs] * bs + pos % bs)
            flat_shape = (k_pool.shape[0], pool_blocks * bs) + \
                k_pool.shape[3:]
            k_pool = k_pool.reshape(flat_shape).at[:, fidx].set(
                rows.cache["k"][:, i, :suffix_len]).reshape(k_pool.shape)
            v_pool = v_pool.reshape(flat_shape).at[:, fidx].set(
                rows.cache["v"][:, i, :suffix_len]).reshape(v_pool.shape)
            row = np.zeros((self.max_blocks,), np.int32)
            row[:len(bids)] = bids
            tables = tables.at[slot].set(jnp.asarray(row))
        cache["k"], cache["v"] = k_pool, v_pool
        cache["block_tables"] = tables
        for key in _BATCH_AXIS0:
            cache[key] = cache[key].at[idx].set(
                _rows(rows.cache[key], key, k))
        self.state = self._spliced(cache, rows, idx, k)

    def _append_blocks(self) -> None:
        """Before a decode tick: every occupied slot is about to write KV
        at its current length — append a pool block to any row crossing a
        block boundary and publish it in the device block table.  With the
        prefix cache on, a row whose write position lands in a block other
        holders also map (its own prompt tail donated to the trie, e.g.)
        copies that block first — copy-on-write keeps shared prompt KV
        immutable."""
        btm = self.block_table
        upd_slots: List[int] = []
        upd_idx: List[int] = []
        upd_bid: List[int] = []
        cow_old: List[int] = []
        cow_new: List[int] = []
        for slot, s in enumerate(self.sessions):
            if s is None:
                continue
            pos = self._slot_len[slot]
            if pos >= s.total_len:
                continue      # budget exhausted; row is (about to be) done
            fresh = btm.ensure(s.req_id, pos + 1)
            if fresh:
                self._reserved[s.req_id] = max(
                    self._reserved[s.req_id] - len(fresh), 0)
                base = btm.blocks_of(s.req_id) - len(fresh)
                for off, bid in enumerate(fresh):
                    upd_slots.append(slot)
                    upd_idx.append(base + off)
                    upd_bid.append(bid)
            elif self.prefix_cache is not None:
                bidx = pos // self.block_size
                bid = btm.block_table(s.req_id)[bidx]
                if btm.ref_count(bid) > 1:
                    new = btm.copy_on_write(s.req_id, bidx)
                    self._reserved[s.req_id] = max(
                        self._reserved.get(s.req_id, 0) - 1, 0)
                    self.cow_blocks += 1
                    if s.req_id in self._last_pack:
                        # the packed KV was copied with the block: the
                        # ledger follows ownership to the private copy
                        self._last_pack[s.req_id] = [
                            new if b == bid else b
                            for b in self._last_pack[s.req_id]]
                    cow_old.append(bid)
                    cow_new.append(new)
                    upd_slots.append(slot)
                    upd_idx.append(bidx)
                    upd_bid.append(new)
            self._slot_len[slot] = pos + 1
        if upd_slots:
            st = self.state
            cache = dict(st.cache)
            if cow_old:
                oi = jnp.asarray(np.array(cow_old, np.int32))
                ni = jnp.asarray(np.array(cow_new, np.int32))
                cache["k"] = cache["k"].at[:, ni].set(cache["k"][:, oi])
                cache["v"] = cache["v"].at[:, ni].set(cache["v"][:, oi])
            cache["block_tables"] = cache["block_tables"].at[
                jnp.asarray(np.array(upd_slots, np.int32)),
                jnp.asarray(np.array(upd_idx, np.int32))].set(
                jnp.asarray(np.array(upd_bid, np.int32)))
            self.state = replace(st, cache=cache)

    def _sync(self) -> None:
        """Flush: read the (tiny) stop flags; only when an occupied slot
        newly finished is the token buffer transferred — the hot decode
        loop moves no per-token data to the host."""
        self._since_sync = 0
        st = self.state
        done = np.asarray(st.done)    # turbolint: allow-sync(stop-flag flush)
        if not any(done[slot] for slot, s in enumerate(self.sessions)
                   if s is not None):
            return
        # turbolint: allow-sync(finished rows only — the once-per-generation flush)
        counts = np.asarray(st.counts)
        # turbolint: allow-sync(finished rows only — the once-per-generation flush)
        emitted = np.asarray(st.emitted)
        now = self.clock()
        freed_slots: List[int] = []
        for slot, s in enumerate(self.sessions):
            if s is None or not done[slot]:
                continue
            s.generated = [int(x) for x in emitted[slot, :counts[slot]]]
            s.result = list(s.prompt or []) + s.generated
            s.finish(now)
            self.engine.kv_slab.free(s.req_id)
            if self.block_table is not None:
                self.block_table.free(s.req_id)
                self._reserved.pop(s.req_id, None)
            self._last_pack.pop(s.req_id, None)
            self.sessions[slot] = None
            self._slot_len[slot] = 0
            freed_slots.append(slot)
        if freed_slots:
            self.engine.kv_slab.gc()
            if self.block_table is not None:
                # point freed rows at the trash block: their device rows
                # keep writing at a frozen position until re-admission,
                # and the freed physical blocks may be re-assigned
                st = self.state
                cache = dict(st.cache)
                cache["block_tables"] = cache["block_tables"].at[
                    jnp.asarray(np.array(freed_slots, np.int32))].set(0)
                self.state = replace(st, cache=cache)

    @property
    def live_tokens(self) -> int:
        return self.engine.kv_slab.live_tokens

    @property
    def kv_footprint_tokens(self) -> int:
        """Token capacity of the KV actually held: live paged blocks
        (cached prefix blocks included — they occupy pool capacity until
        evicted), or the contiguous slab's live reservations."""
        if self.block_table is not None:
            return self.block_table.footprint_tokens
        return self.engine.kv_slab.live_tokens

    def prefix_stats(self) -> Dict[str, int]:
        """Prefix-cache telemetry plus engine-side integration counters
        (empty when prefix caching is off or the pool does not exist
        yet)."""
        if self.prefix_cache is None:
            return {}
        out = self.prefix_cache.stats()
        out["cow_blocks"] = self.cow_blocks
        out["prefill_tokens"] = self.prefill_tokens
        return out
