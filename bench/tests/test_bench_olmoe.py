"""The olmoe-1b-7b-8L configuration: its plain reference against the
program (the full forward, and the served path's packed prefill then
paged decode), its counts, its two readers, and its cell end to end at a
tiny size on the CPU."""
import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, runner, spec
from bench.harness import Pump
from bench.references import olmoe as ref
from bench.tests import tiny_root
from bench.tests.tiny_root import tiny_config

NAME = "olmoe-1b-7b-8L"
WORKLOAD = NAME + ".reasoning"
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**33 + 7


def reader(name):
    return spec.load_module(spec.BENCH / "metrics" / f"{name}.py")


def tiny(**over):
    return tiny_config(NAME, torch_dtype="float32", **over)


def test_the_cell_resolves_to_the_published_block():
    cell = spec.resolve(WORKLOAD)
    cfg = harness.program_config(NAME, cell.config)
    assert cfg.family == "moe" and cfg.num_layers == 8
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size) == (2048, 16, 16, 128, 1024, 50304)
    assert (cfg.moe.num_experts, cfg.moe.top_k) == (64, 8)
    assert not cfg.moe.norm_topk_prob
    assert cfg.qk_norm and cfg.qk_norm_span == "full"
    dm = cell.reference().dims(cell.config)
    assert (dm["experts"], dm["top_k"], dm["expert_ff"], dm["ff"]) == \
        (64, 8, 1024, 8 * 1024)
    names = [m["name"] for m in cell.per_layer]
    assert {"moe_expert_roofline", "moe_dispatch_share",
            "decode_attention_roofline"} <= set(names)
    assert cell.traffic == spec.resolve("internlm2-1.8b.reasoning").traffic


def test_reference_matches_program_forward():
    """f32 smoke widths: the program's dropless layer (sorted, grouped)
    and the reference's plain every-expert sum differ in summation order
    only."""
    from repro.models import forward_hidden
    from repro.models.layers import lm_logits
    config = tiny()
    cfg = harness.program_config(NAME, config)
    params = ref.make_params(config, SEED, dtype="float32")
    toks = np.random.default_rng(0).integers(0, config["vocab_size"], 37)
    h, _, _ = forward_hidden(cfg, params, jnp.asarray(toks[None]))
    want = np.asarray(lm_logits(cfg, params["embed"], h)[0])
    got, = ref.logits(config, SEED, [(toks, np.arange(37))],
                      dtype="float32", pad_to=16)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_served_prefill_then_paged_decode_matches_reference():
    """The served path's programs at f32 smoke widths: one packed prefill
    of a 13-token prompt, its KV scattered into a paged pool of 4-token
    blocks (as the engine's splice does), then 9 paged decode steps fed
    the sequence's next tokens. Every position's logits against the
    reference's one causal pass. Tolerance 2e-4: the three take f32 sums
    in different orders (packed attention, paged decode attention, the
    reference's HIGHEST einsums), about 1e-5 apart at these widths; a
    routing or masking fault moves logits by their own size."""
    from repro.models import decode_step, make_paged_cache, prefill_packed
    config = tiny()
    cfg = harness.program_config(NAME, config)
    params = ref.make_params(config, SEED, dtype="float32")
    seq = np.random.default_rng(1).integers(0, config["vocab_size"], 22)
    p, steps, bs = 13, 9, 4
    L, kv, dh = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    none = jnp.zeros((0,), jnp.int32)
    empty = jnp.zeros((L, 0, kv, dh), jnp.float32)
    logits0, parts, rows = prefill_packed(
        cfg, params, jnp.asarray(seq[None, :p], jnp.int32),
        jnp.zeros((p,), jnp.int32), jnp.arange(p, dtype=jnp.int32),
        jnp.asarray([p - 1]), empty, empty, none, none,
        cache_dtype=jnp.float32)
    assert int(rows.sum()) == L * p * cfg.moe.top_k
    mb = 8
    cache = make_paged_cache(cfg, 1, mb + 1, bs, mb, jnp.float32)
    table = np.arange(1, mb + 1, dtype=np.int32)        # block 0: trash
    flat = table[np.arange(p) // bs] * bs + np.arange(p) % bs
    for name in ("k", "v"):
        pool = cache[name].reshape(L, -1, kv, dh)
        cache[name] = pool.at[:, flat].set(parts[name]).reshape(
            cache[name].shape)
    cache["block_tables"] = jnp.asarray(table[None])
    cache["len"] = jnp.asarray([p], jnp.int32)
    got = [np.asarray(logits0[0])]
    for i in range(steps):
        logits, cache, rows = decode_step(
            cfg, params, cache, jnp.asarray([seq[p + i]], jnp.int32))
        assert int(rows.sum()) == L * cfg.moe.top_k
        got.append(np.asarray(logits[0]))
    want, = ref.logits(config, SEED,
                       [(seq[:p + steps], np.arange(p - 1, p + steps))],
                       dtype="float32", pad_to=16)
    np.testing.assert_allclose(np.stack(got), want, rtol=2e-4, atol=2e-4)


def test_layer_weights_equal_stacked_weights():
    config = tiny_config(NAME)
    stacked = ref.make_params(config, 9)
    assert stacked["layers"]["moe"]["router"].dtype == jnp.float32
    assert stacked["layers"]["moe"]["w_up"].dtype == jnp.bfloat16
    key = jax.random.key(ref.dense.weight_seed(9))
    ckey = ref._key(config)
    for i in range(config["num_hidden_layers"]):
        layer = ref._layer_params_fn(ckey, "bfloat16")(key, i)
        for (p, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(layer),
                jax.tree_util.tree_leaves_with_path(
                    jax.tree.map(lambda x: x[i], stacked["layers"]))):
            assert np.array_equal(np.asarray(a), np.asarray(b)), p


def test_routing_weights_zero_outside_the_top_k():
    dm = ref.dims(tiny())
    logits = jax.random.normal(jax.random.key(3), (5, dm["experts"]))
    w = np.asarray(ref.routing_weights(dm, logits))
    probs = np.asarray(jax.nn.softmax(logits, -1))
    assert ((w > 0).sum(-1) == dm["top_k"]).all()
    np.testing.assert_allclose(w[w > 0], probs[w > 0], rtol=1e-6)
    renorm = np.asarray(ref.routing_weights(dict(dm, norm_topk=True),
                                            logits))
    np.testing.assert_allclose(renorm.sum(-1), 1.0, rtol=1e-6)


# -- counts ----------------------------------------------------------------
DM = ref.dims(spec.resolve(WORKLOAD).config)
EXPERT = 3 * 2048 * 1024 * 2                     # one expert, bf16


def tick(name, t, touched):
    return {"name": name, "ts": t, "dur": 0.01,
            "args": {"batch": 16, "experts_touched": touched,
                     "max_expert_rows": 5}}


def test_experts_touched_moves_bytes_not_ops():
    p = Pump(0.0, 0.05, 16, 13_000, True, [], 0, 0, 0)
    bare = ref.count_decode_tick(DM, p, [])
    few = ref.count_decode_tick(DM, p, [tick("decode", 0.01, 8 * 20)])
    many = ref.count_decode_tick(DM, p, [tick("decode", 0.01, 8 * 57)])
    assert bare[0] == few[0] == many[0]
    assert many[1] - few[1] == 8 * 37 * EXPERT
    # the router (f32) and both qk-norm scales join where touched is known
    fixed = 8 * (2048 * 64 * 4 + 2 * 2048 * 2)
    assert few[1] - bare[1] == fixed + (8 * 20 - 8 * 8) * EXPERT
    # no event: the fewest a tick reads, 8 experts a layer, which is the
    # dense count at width 8 x 1024
    from bench import roofline
    assert bare == roofline.decode_tick(DM, 16, 13_000)
    # a prefill event does not price a decode tick
    assert ref.count_decode_tick(DM, p, [tick("prefill", 0.01, 400)]) == bare


def test_decode_tick_ops_by_hand():
    p = Pump(0.0, 0.05, 2, 100, True, [], 0, 0, 0)
    ops, _ = ref.count_decode_tick(DM, p, [])
    attn_proj = 4 * 2048 * 2048                  # q, k, v, o (16 x 128)
    experts = 8 * 3 * 2048 * 1024                # 8 experts a token
    mm = 2 * 2 * (8 * (attn_proj + experts) + 2048 * 50304)
    attn = 4 * (100 + 2) * 128 * 16 * 8
    assert ops == mm + attn


def test_moe_experts_count_by_hand():
    p = Pump(0.0, 0.05, 16, 13_000, True, [], 0, 0, 0)
    ops, nbytes = ref.count_moe_experts(DM, p, [tick("decode", 0.01, 452)])
    pairs = 16 * 8 * 8
    assert ops == 2 * 3 * 2048 * 1024 * pairs
    assert nbytes == 452 * EXPERT + pairs * 2048 * 2 * 2
    _, fewest = ref.count_moe_experts(DM, p, [])
    assert fewest == 64 * EXPERT + pairs * 2048 * 2 * 2


def test_prefill_count_shares_the_touched_experts():
    p = Pump(0.0, 0.3, 0, 0, False, [(500, 0), (300, 0)], 2, 0, 0)
    bare = ref.count_prefill(DM, p, [])
    got = ref.count_prefill(DM, p, [tick("prefill", 0.1, 1000)])
    assert [g[0] for g in got] == [b[0] for b in bare]
    fixed = 8 * (2048 * 64 * 4 + 2 * 2048 * 2)
    for g, b in zip(got, bare):
        assert g[1] - b[1] == fixed + (500 - 64) * EXPERT


# -- readers ---------------------------------------------------------------
def record(scopes, pumps, events=()):
    cell = spec.resolve(WORKLOAD)
    r = cell.reference()
    dm = r.dims(cell.config)
    return {"dims": dm, "peaks": PEAKS, "pumps": pumps,
            "counts": spec.Counts(r, dm, list(events)),
            "device": {"programs": {"jit_tick(3)": 1.0},
                       "scopes": {"jit_tick": scopes}}}


PUMPS = [Pump(0.00, 0.05, 16, 13_000, True, [], 0, 0, 0),
         Pump(0.05, 0.10, 15, 12_000, True, [], 0, 0, 0),
         Pump(0.10, 0.40, 0, 0, False, [(300, 0)], 1, 0, 0)]
EVENTS = [tick("decode", 0.01, 450), tick("decode", 0.06, 440),
          tick("prefill", 0.2, 500)]
SCOPES = {"moe/experts": 0.012, "moe/experts/gmm": 0.002,
          "moe/router": 0.001, "moe/dispatch": 0.0005,
          "moe/combine": 0.0015, "moe": 0.001,
          "paged_gather": 0.01, "(unscoped)": 0.03}


def test_moe_expert_roofline_by_hand():
    got = reader("moe_expert_roofline").read(record(SCOPES, PUMPS, EVENTS))
    rows = [(16, 450), (15, 440)]
    need = sum(max(2 * 3 * 2048 * 1024 * r * 64 / 197e12,
                   (t * EXPERT + r * 64 * 2048 * 4) / 819e9)
               for r, t in rows)
    assert got == pytest.approx(100.0 * need / 0.014, rel=1e-12)
    # bound by bytes: 450 experts are 5.7 GB, 7 ms at 819 GB/s
    assert 450 * EXPERT / 819e9 > 2 * 3 * 2048 * 1024 * 1024 / 197e12


def test_moe_dispatch_share_by_hand():
    got = reader("moe_dispatch_share").read(record(SCOPES, PUMPS))
    assert got == pytest.approx(100.0 * 0.003 / 0.018, rel=1e-12)


@pytest.mark.parametrize("name", ["moe_expert_roofline",
                                  "moe_dispatch_share"])
@pytest.mark.parametrize("device", [None, {"programs": {}, "scopes": {}},
                                    {"programs": {}, "scopes": {
                                        "jit_tick": {"ffn": 0.2}}}])
def test_readers_read_nothing_without_expert_scopes(name, device):
    """A dense program, or a program with no such scopes (the parent's),
    gives no reading and raises nothing."""
    rec = record({}, PUMPS)
    rec["device"] = device
    assert reader(name).read(rec) is None


# -- the cell end to end ---------------------------------------------------
def test_a_traced_run_is_correct_and_priced_by_the_touched_experts(
        tmp_path):
    """A whole ``--trace 1`` run of the cell at a tiny size on the CPU
    (bf16 as on the chip): correct against the reference under a limit the
    float8 control exceeds, and each decode pump priced with the program's
    tick event and its ``experts_touched``. The CPU's trace has no TPU
    plane, so the device readers read nothing."""
    events = Counter()
    harness.count_compiles(events)
    name = tiny_root.make(tmp_path, "reasoning", config=NAME)
    cell = spec.resolve(name, tmp_path)
    seen = []
    r = cell.reference()
    count = r.count_decode_tick

    def counted(dm, p, evs):
        seen.append((p, evs))
        return count(dm, p, evs)
    r.count_decode_tick = counted
    cell.reference = lambda: r
    out = runner.run_cell(cell, SEED, 2.0, trace=True, peaks=PEAKS,
                          events=events, t_start=time.perf_counter(),
                          trace_dir=tmp_path / "trace", control=True)
    check = out["extra"]["check"]
    assert out["correct"], check
    assert check["control_gap_sigma"] > \
        out["checks"]["worst_gap_sigma"]["limit"]
    assert out["metrics"]["decode_mfu"]["value"] > 0
    assert "moe_expert_roofline" not in out["metrics"]
    decoded = [evs for p, evs in seen if p.decoded]
    assert decoded
    assert all(any("experts_touched" in e.get("args", {}) for e in evs)
               for evs in decoded)
