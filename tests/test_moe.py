"""The dropless expert layer on the served one-device path, OLMoE's block,
and the routing counters the serving engine reports.

Smoke widths, float32 weights from a seed, on the CPU: the grouped
matmuls run as ``jax.lax.ragged_dot`` here (the kernel's interpret-mode
parity is in ``test_kernels.py``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import GenerationParams, TurboClient
from repro.configs import get_smoke_config
from repro.core import AnalyticCostModel
from repro.models import init_params, prefill_packed
from repro.models import layers as L
from repro.models import moe as M

CM = AnalyticCostModel(flops_per_token=1e6, bytes_per_token=1e3,
                       weight_bytes=1e6, overhead=1e-4)

# float32 rows of one token computed in a different batch or pack take
# XLA's reduction order for that shape: equal to about 1e-6, where a
# dropped expert moves an output by the size of the output itself
BATCH_TOL = 2e-5


def _moe(norm_topk: bool = False, experts: int = 8, top_k: int = 3):
    cfg = get_smoke_config("olmoe-1b-7b")
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=experts, top_k=top_k,
        norm_topk_prob=norm_topk))


def _params(cfg, seed=0):
    return M.init_moe(cfg, jax.random.key(seed), jnp.float32)


def _per_token_loop(cfg, p, x):
    """Each token alone: softmax over every expert, its top k, each of
    those experts' SwiGLU weighted by its (optionally rescaled) weight."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    out = np.zeros_like(np.asarray(x, np.float64))
    for t, xt in enumerate(np.asarray(x, np.float64)):
        logits = xt @ p["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[:cfg.moe.top_k]
        w = probs[top]
        if cfg.moe.norm_topk_prob:
            w = w / w.sum()
        for e, we in zip(top, w):
            g = xt @ p["w_gate"][e]
            u = xt @ p["w_up"][e]
            out[t] += we * ((g / (1 + np.exp(-g)) * u) @ p["w_down"][e])
    return out


@pytest.mark.parametrize("norm_topk", [False, True])
def test_dropless_matches_a_per_token_loop(norm_topk):
    cfg = _moe(norm_topk)
    p = _params(cfg)
    x = jax.random.normal(jax.random.key(1), (2, 9, cfg.d_model))
    out, _, rows = M.apply_moe(cfg, p, x)
    want = _per_token_loop(cfg, p, x.reshape(18, -1)).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                               atol=1e-4)
    assert int(rows.sum()) == 18 * cfg.moe.top_k


def test_renormalising_the_top_k_is_the_configurations_choice():
    """Without rescaling each token's output is its rescaled output times
    the softmax mass of its top k (which is below one)."""
    x = jax.random.normal(jax.random.key(2), (1, 5, 64))
    out = {}
    for norm in (False, True):
        cfg = _moe(norm)
        out[norm], _, _ = M.apply_moe(cfg, _params(cfg), x)
    cfg = _moe(False)
    weights, _, _ = M.route(cfg, _params(cfg), x[0])
    mass = np.asarray(weights.sum(-1))
    assert (mass < 1).all()
    np.testing.assert_allclose(np.asarray(out[False][0]),
                               np.asarray(out[True][0]) * mass[:, None],
                               rtol=1e-5, atol=1e-6)


def _one_hot_router(cfg, p, favourite: int):
    """A router that sends every token of positive entries to
    ``favourite`` first."""
    router = np.asarray(p["router"]).copy()
    router[:, favourite] += 50.0 / np.sqrt(cfg.d_model)
    return dict(p, router=jnp.asarray(router))


def test_a_row_is_the_same_alone_in_a_batch_and_in_a_pack():
    """Row 0 alone, in a batch of 16 and inside a packed prefill gives the
    same expert output; the capacity path drops its picks past an
    expert's capacity, so it does not (16 tokens that all want expert 0
    overflow a capacity of 8)."""
    cfg = _moe()
    p = _one_hot_router(cfg, _params(cfg), favourite=0)
    x = jnp.abs(jax.random.normal(jax.random.key(3), (16, 1, cfg.d_model)))
    alone, _, _ = M.apply_moe(cfg, p, x[15:16])
    batch, _, rows = M.apply_moe(cfg, p, x)
    packed, _, _ = M.apply_moe(cfg, p, x.reshape(1, 16, -1))
    assert int(rows[0]) == 16
    np.testing.assert_allclose(np.asarray(batch[15]), np.asarray(alone[0]),
                               rtol=BATCH_TOL, atol=BATCH_TOL)
    np.testing.assert_allclose(np.asarray(packed[0, 15]),
                               np.asarray(alone[0, 0]), rtol=BATCH_TOL,
                               atol=BATCH_TOL)
    capped, _, kept = M._apply_capacity(
        dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=1.0)), p, x)
    assert int(kept[0]) == 8
    assert not np.allclose(np.asarray(capped[15]), np.asarray(alone[0]),
                           rtol=1e-2, atol=1e-2)


def test_stacked_experts_read_in_place_equal_the_layers_slice():
    cfg = _moe()
    stack = [_params(cfg, seed) for seed in range(3)]
    experts = {n: jnp.stack([s[n] for s in stack]) for n in M.EXPERT_KEYS}
    x = jax.random.normal(jax.random.key(4), (2, 7, cfg.d_model))
    for i in range(3):
        want, _, rows = M.apply_moe(cfg, stack[i], x)
        got, _, rows_in_place = M.apply_moe(
            cfg, {"router": stack[i]["router"]}, x, experts, jnp.int32(i))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        assert np.array_equal(np.asarray(rows), np.asarray(rows_in_place))


def test_routing_stats():
    rows = jnp.asarray([[0, 3, 1, 0], [2, 0, 0, 7]], jnp.int32)
    assert np.asarray(M.routing_stats(rows)).tolist() == [4, 7]


def test_full_width_qk_norm_normalises_the_whole_projection():
    """OLMoE's qk-norm: one RMS over every head of a token's q (and k),
    which a per-head norm does not give when the heads' scales differ."""
    cfg = get_smoke_config("olmoe-1b-7b")
    assert cfg.qk_norm and cfg.qk_norm_span == "full"
    h, dh = cfg.num_heads, cfg.head_dim
    p = L.init_attention(cfg, jax.random.key(5), jnp.float32)
    assert p["q_norm"].shape == (h * dh,)
    p["q_norm"] = jax.random.normal(jax.random.key(6), (h * dh,))
    x = jax.random.normal(jax.random.key(7), (1, 3, cfg.d_model))
    pos = jnp.zeros((1, 3), jnp.int32)          # RoPE at 0 is the identity
    q, _, _ = L.qkv_project(cfg, p, x, pos)
    raw = np.asarray(jnp.einsum("bsd,dhk->bshk", x, p["wq"]))
    flat = raw.reshape(1, 3, h * dh)
    want = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + 1e-6) * \
        np.asarray(p["q_norm"])
    np.testing.assert_allclose(np.asarray(q).reshape(1, 3, -1), want,
                               rtol=1e-5, atol=1e-5)
    head = dataclasses.replace(cfg, qk_norm_span="head")
    ph = dict(p, q_norm=p["q_norm"][:dh], k_norm=p["k_norm"][:dh])
    qh, _, _ = L.qkv_project(head, ph, x, pos)
    assert not np.allclose(np.asarray(qh), np.asarray(q), atol=1e-3)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, qk_norm_span="heads")


def test_packed_prefill_reports_expert_rows():
    cfg = get_smoke_config("olmoe-1b-7b")
    params = init_params(cfg, jax.random.key(0))
    n = 12
    toks = jnp.arange(n, dtype=jnp.int32)[None] % cfg.vocab_size
    zero = jnp.zeros((cfg.num_layers, 0, cfg.num_kv_heads, cfg.head_dim))
    none = jnp.zeros((0,), jnp.int32)
    _, _, rows = prefill_packed(
        cfg, params, toks, jnp.zeros((n,), jnp.int32),
        jnp.arange(n, dtype=jnp.int32), jnp.asarray([n - 1]), zero, zero,
        none, none)
    assert rows.shape == (cfg.num_layers, cfg.moe.num_experts)
    assert (np.asarray(rows).sum(-1) == n * cfg.moe.top_k).all()
    dense = get_smoke_config("internlm2-1.8b")
    _, _, none_rows = prefill_packed(
        dense, init_params(dense, jax.random.key(0)), toks,
        jnp.zeros((n,), jnp.int32), jnp.arange(n, dtype=jnp.int32),
        jnp.asarray([n - 1]), zero[..., :dense.num_kv_heads, :],
        zero[..., :dense.num_kv_heads, :], none, none)
    assert none_rows is None


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "internlm2-1.8b"])
def test_the_engine_reports_routing_for_experts_only(arch):
    """MoE: each tick event carries what the tick's routing read gave,
    the gauges the latest read and the counter their sum; a dense model
    reports none of them."""
    client = TurboClient.from_arch(
        arch, seq_buckets=(32, 64), batch_buckets=(1, 2), max_slots=3,
        cap_new=8, warmup=False, cost_model=CM, trace=True)
    try:
        hs = [client.submit(list(range(1, 6 + i)),
                            GenerationParams(max_new_tokens=4))
              for i in range(3)]
        for h in hs:
            h.result()
        ticks = [e for e in client.trace_events()
                 if e["name"] in ("prefill", "decode") and "dur" in e]
        snap = client.metrics()
    finally:
        client.close()
    moe = arch == "olmoe-1b-7b"
    assert ticks
    for e in ticks:
        assert ("experts_touched" in e["args"]) == moe
    if not moe:
        assert not any(k.startswith("moe.") for k in snap["gauges"])
        assert not any(k.startswith("moe.") for k in snap["counters"])
        return
    cfg = get_smoke_config(arch)
    for e in ticks:
        # a prefill tick sums the reads of its dispatches
        touched = e["args"]["experts_touched"]
        assert touched >= cfg.num_layers * cfg.moe.top_k
        assert e["name"] == "prefill" or \
            touched <= cfg.num_layers * cfg.moe.num_experts
        assert e["args"]["max_expert_rows"] >= 1
    decode = [e for e in ticks if e["name"] == "decode"][-1]["args"]
    assert snap["gauges"]["moe.experts_touched"] == decode["experts_touched"]
    assert snap["gauges"]["moe.max_expert_rows"] == decode["max_expert_rows"]
    assert snap["counters"]["moe.routed_pairs"] == \
        sum(e["args"]["experts_touched"] for e in ticks)
