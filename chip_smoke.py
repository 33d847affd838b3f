"""Chip smoke run: serve internlm2-1.8b at its published widths on a TPU.

Drives the main serving path once through the entry points a user calls
-- ``TurboClient.from_arch`` -> ServingPipeline -> ContinuousEngine ->
paged KV -> fused sampling -- with random weights made from a seed, and
checks what comes out:

* every request finishes, with its full token budget;
* the paged KV pool and the KV slab are empty after the drain;
* every greedy token is a near-argmax of a plain causal forward pass over
  the same tokens (teacher-forced reference, tolerance stated below);
* the sampled decode tick holds the compiled Pallas sampling kernel, and
  that kernel picks the same tokens as the XLA reference.

The numbers it prints (compile time, TTFT, ITL, peak bytes) are smoke
values, not a benchmark.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # replica pool, one replica per chip,
                                     # against one replica in the same process

It needs a TPU: with no TPU it exits non-zero and prints no ``ok`` line.
Compiled programs go to JAX's persistent cache (``JAX_COMPILATION_CACHE_DIR``
when set, else ``.jax_cache/`` here), so a second run skips most compiles.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import statistics
import sys
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import GenerationParams, TurboClient  # noqa: E402
from repro.data import LengthDistribution, RequestGenerator  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import forward_hidden  # noqa: E402
from repro.models.layers import lm_logits  # noqa: E402
from repro.runtime.sampling import DEFAULT_SAMPLE_CANDIDATES  # noqa: E402

ARCH = "internlm2-1.8b"
SEED = 0
NEW_TOKENS = 32
PROMPT_LENS = (100, 500)          # uniform, as the paper's request stream

# One chip.  Four slots keep every packed prefill at or under 4 x 1023
# tokens (a 4096-token pack: 2.2 GB of temporaries on a v5e; an
# 8192-token pack would need 8.6 GB).  The top bucket admits a 992-token
# prompt with its 32 new tokens.
ONE_CHIP = dict(smoke=False, max_slots=4, cap_new=NEW_TOKENS,
                seq_buckets=(128, 256, 512, 1024), batch_buckets=(1, 2, 4),
                # 16,400 tokens of bf16 KV = 1.6 GB; the decode scan keeps
                # a second pool-sized buffer (ROADMAP A1)
                num_blocks=1025)
# Four chips: one slot per replica, so every request runs alone at the
# same shapes in the pool and in the single replica (bitwise comparable),
# and warmup off: each replica compiles for its own device.
FOUR_CHIPS = dict(smoke=False, max_slots=1, cap_new=NEW_TOKENS,
                  seq_buckets=(256,), batch_buckets=(1,), num_blocks=33,
                  warmup=False)
FOUR_CHIP_PROMPT_LENS = (64, 200)

# A served greedy token must score within REF_TOL standard deviations of
# the reference row's maximum logit.  The logits are bf16: near a row
# maximum of ~4 sigma they sit 1/32 apart (~0.03 sigma for these weights),
# and the served path (packed prefill, paged decode) and the reference
# (one causal pass) round differently through 24 layers, which flips
# near-ties: on a v5e the served tokens sat at most 0.062 sigma (two bf16
# steps) below the maximum, and a greedy stream served in a batch was not
# token-for-token the one ``engine.generate`` gave alone, so equality is
# no check at these widths.  A broken path sits far lower: a KV write one
# position off put tokens up to 0.86 sigma below on the smoke config.
REF_TOL = 0.25


def log(msg: str) -> None:
    print(msg, flush=True)


def compile_note(events: Counter) -> str:
    """Backend compiles so far (eager ops included; a persistent-cache
    hit counts too) and the persistent cache's reads and writes."""
    return (f"{events['compiles']} backend compiles "
            f"({events['compile_seconds']:.1f}s), persistent cache "
            f"hits={events['/jax/compilation_cache/cache_hits']} "
            f"writes={events['/jax/compilation_cache/cache_misses']}")


def count_events(events: Counter) -> None:
    """Count JAX's compile and compile-cache events into ``events``."""
    def on_event(event, **kw):
        events[event] += 1

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            events["compiles"] += 1
            events["compile_seconds"] += secs

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def seeded_prompts(vocab: int, n: int, lo: int, hi: int, seed: int):
    reqs = RequestGenerator(rate=1.0, lengths=LengthDistribution(
        "uniform", lo, hi), vocab_size=vocab, seed=seed).generate(64.0)
    if len(reqs) < n:
        raise RuntimeError(f"request generator gave {len(reqs)} < {n}")
    return [r.payload for r in reqs[:n]]


def serve(client, prompts, params):
    """Submit every prompt, consume each stream; returns handles and
    their token lists."""
    handles = [client.submit(p, g) for p, g in zip(prompts, params)]
    tokens = [list(h.stream()) for h in handles]
    for h, toks, g in zip(handles, tokens, params):
        if not h.done or h.cancelled or len(toks) != g.max_new_tokens:
            raise RuntimeError(f"request {h.req_id}: state {h.state}, "
                               f"{len(toks)}/{g.max_new_tokens} tokens")
    return handles, tokens


def check_drained(backend, label: str) -> None:
    used = backend.block_table.used_blocks
    live = backend.engine.kv_slab.live_bytes
    log(f"{label}: after drain used_blocks={used} kv_slab.live_bytes={live}")
    if used or live:
        raise RuntimeError(f"{label}: KV left after drain")


@functools.partial(jax.jit, static_argnums=0)
def reference_logits(cfg, params, toks, at):
    """Logits at positions ``at`` of one plain causal forward pass."""
    h, _, _ = forward_hidden(cfg, params, toks)
    return lm_logits(cfg, params["embed"], h[:, at])[0]


def reference_deficits(engine, prompt, tokens):
    """Teacher-forced reference: one causal forward pass (no KV cache, no
    paging, no packing) over ``prompt + tokens[:-1]``; for each served
    token, how far its logit sits below the row maximum, in units of
    that row's standard deviation, and whether it is the row's argmax."""
    seq = list(prompt) + list(tokens[:-1])
    toks = np.zeros((1, engine.ladder.seq_bucket(len(seq))), np.int32)
    toks[0, :len(seq)] = seq
    at = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
    rows = np.asarray(reference_logits(engine.cfg, engine.params,
                                       jnp.asarray(toks), jnp.asarray(at)),
                      np.float32)
    picked = rows[np.arange(len(tokens)), np.asarray(tokens)]
    return (rows.max(-1) - picked) / rows.std(-1), \
        rows.argmax(-1) == np.asarray(tokens)


def check_sampling_kernel(backend) -> None:
    """The sampled decode tick that served the requests lowers to the
    compiled Pallas kernel (a ``tpu_custom_call``) on this device."""
    eng = backend.engine
    tick = eng._decode_cache.get(("tick", 1, True))
    if tick is None:
        raise RuntimeError("the sampled decode tick never ran")
    st = backend.state
    text = tick.lower(eng.params, st.cache, st.cur, st.emitted, st.counts,
                      st.done, st.budget, st.eos, st.temp, st.top_k,
                      st.top_p, st.seed).as_text()
    if "tpu_custom_call" not in text:
        raise RuntimeError("the sampled decode tick holds no Pallas kernel")
    log("sampled decode tick: compiled Pallas sampling kernel present")


def sampler_parity(seed: int) -> None:
    """ops.fused_sample: compiled kernel against the XLA reference, on
    full-vocab logits and one draw of Gumbel noise, f32 and bf16."""
    vocab = 92_544
    k1, k2 = jax.random.split(jax.random.key(seed))
    t = jnp.array([0.0, 0.8, 1.3, 0.8, 0.5, 0.8, 1.0, 0.7], jnp.float32)
    k = jnp.array([0, 40, 0, 1, 64, 5, 40, 10_000], jnp.int32)
    p = jnp.array([1.0, 0.9, 0.95, 0.9, 0.3, 1.0, 0.9, 0.9], jnp.float32)
    g = jax.random.gumbel(k2, (8, DEFAULT_SAMPLE_CANDIDATES), jnp.float32)
    for dtype in (jnp.float32, jnp.bfloat16):
        logits = (4.0 * jax.random.normal(k1, (8, vocab))).astype(dtype)
        got = np.asarray(ops.fused_sample(logits, t, k, p, g,
                                          impl="pallas"))
        want = np.asarray(ops.fused_sample(logits, t, k, p, g, impl="xla"))
        log(f"fused_sample {jnp.dtype(dtype).name}: pallas {got.tolist()} "
            f"xla {want.tolist()}")
        if not np.array_equal(got, want):
            raise RuntimeError(f"pallas and xla samplers disagree "
                               f"({jnp.dtype(dtype).name})")


def one_chip(events: Counter) -> None:
    t0 = time.perf_counter()
    client = TurboClient.from_arch(ARCH, init_seed=SEED, **ONE_CHIP)
    build = time.perf_counter() - t0
    prompts = seeded_prompts(client.backend.engine.cfg.vocab_size, 8,
                             *PROMPT_LENS, SEED)
    ws = client.warmup_stats
    in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    log(f"built {ARCH} (published widths, random weights, seed {SEED}) "
        f"in {build:.1f}s; warmup {ws['compile_count']} engine compiles, "
        f"{ws['rounds']} rounds, {ws['warmup_seconds']:.1f}s; "
        f"{compile_note(events)}; bytes_in_use={in_use}")

    # even requests greedy, odd ones sampled: sampled rows go through the
    # compiled Pallas kernel, greedy ones are checked against a reference
    params = [GenerationParams(max_new_tokens=NEW_TOKENS, seed=i,
                               **({} if i % 2 == 0 else dict(
                                   temperature=0.8, top_k=40, top_p=0.9)))
              for i in range(len(prompts))]
    t0, before = time.perf_counter(), events["compiles"]
    handles, tokens = serve(client, prompts, params)
    wall = time.perf_counter() - t0
    log(f"serving compiled {events['compiles'] - before} programs the "
        f"warmup did not")
    for h, p, toks, g in zip(handles, prompts, tokens, params):
        kind = "greedy" if g.temperature == 0 else "sampled"
        log(f"req {h.req_id} ({kind}): prompt {len(p)} tokens, "
            f"generated {len(toks)}")
    ttft = [h.ttft for h in handles]
    itl = [d for h in handles for d in h.inter_token_latencies()]
    log(f"smoke values, not a benchmark: {len(handles)} requests in "
        f"{wall:.3f}s; TTFT median {statistics.median(ttft) * 1e3:.1f}ms "
        f"max {max(ttft) * 1e3:.1f}ms; ITL median "
        f"{statistics.median(itl) * 1e3:.2f}ms max {max(itl) * 1e3:.2f}ms")

    backend = client.backend
    check_drained(backend, "one replica")
    check_sampling_kernel(backend)
    engine = backend.engine
    worst = 0.0
    for p, toks, g in zip(prompts, tokens, params):
        if g.temperature:
            continue
        deficit, exact = reference_deficits(engine, p, toks)
        worst = max(worst, float(deficit.max()))
        log(f"greedy vs reference: {int(exact.sum())}/{len(toks)} tokens "
            f"are the reference argmax; worst deficit "
            f"{float(deficit.max()):.4f} sigma")
    if worst > REF_TOL:
        raise RuntimeError(f"a greedy token sits {worst:.3f} sigma below "
                           f"the reference maximum (> {REF_TOL})")
    sampler_parity(SEED)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    client.close()


def four_chips(events: Counter) -> None:
    devices = jax.devices()[:4]
    t0 = time.perf_counter()
    single = TurboClient.from_arch(ARCH, init_seed=SEED, **FOUR_CHIPS)
    prompts = seeded_prompts(single.backend.engine.cfg.vocab_size, 8,
                             *FOUR_CHIP_PROMPT_LENS, SEED)
    params = [GenerationParams(max_new_tokens=NEW_TOKENS)] * len(prompts)
    _, want = serve(single, prompts, params)
    check_drained(single.backend, "single replica")
    single.close()
    log(f"single replica: {len(want)} greedy requests in "
        f"{time.perf_counter() - t0:.1f}s; {compile_note(events)}")
    del single
    gc.collect()

    t0 = time.perf_counter()
    pool = TurboClient.from_arch(ARCH, init_seed=SEED, replicas=4,
                                 **FOUR_CHIPS)
    handles, got = serve(pool, prompts, params)
    log(f"pool of {pool.num_replicas}: {len(got)} greedy requests in "
        f"{time.perf_counter() - t0:.1f}s; {compile_note(events)}")
    if pool.num_replicas != 4 or pool.healthy_replicas() != [0, 1, 2, 3]:
        raise RuntimeError(f"pool health {pool.health()}")
    routed = sorted(h.replica for h in handles)
    log(f"requests per replica: {dict(Counter(routed))}")
    if set(routed) != {0, 1, 2, 3}:
        raise RuntimeError("requests did not reach all four replicas")
    for i, dev in enumerate(devices):
        backend = pool.replica(i).backend
        st = backend.state
        where = {
            "params": jax.tree.leaves(backend.engine.params),
            "kv pool": [st.cache["k"], st.cache["v"],
                        st.cache["block_tables"]],
            "gen state": [st.cur, st.emitted, st.counts, st.done,
                          st.budget, st.eos, st.temp, st.top_k, st.top_p,
                          st.seed]}
        for name, leaves in where.items():
            placed = {d for leaf in leaves for d in leaf.devices()}
            if placed != {dev}:
                raise RuntimeError(f"replica {i} {name} on {placed}, "
                                   f"not {dev}")
        log(f"replica {i}: params, KV pool and GenState on {dev}")
        check_drained(backend, f"replica {i}")
    for i, (a, b) in enumerate(zip(got, want)):
        if a != b:
            raise RuntimeError(f"request {i}: pool tokens differ from the "
                               f"single replica")
    log(f"all {len(got)} requests: pool tokens identical to one replica")
    for dev in devices:
        stats = dev.memory_stats() or {}
        log(f"{dev}: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    pool.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the replica-pool phase, one replica "
                    "per chip, against one replica")
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    events: Counter = Counter()
    count_events(events)
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(events)
    else:
        one_chip(events)
    log(f"whole run: {compile_note(events)}; "
        f"{time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
