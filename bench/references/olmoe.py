"""Plain reference for OLMoE-style sparse decoders, and their counts.

One block type, as published (OLMoE, arXiv:2409.02060):

- ``h += W_o attn(rope(qn(W_q x)), rope(kn(W_k x)), W_v x)`` with
  ``x = rms(h)``, where ``qn`` and ``kn`` are RMSNorms over the whole
  projected q (heads x head_dim wide) and the whole projected k, not per
  head;
- ``h += sum_e w_e * W_down_e(silu(W_gate_e x2) * W_up_e x2)`` with
  ``x2 = rms(h)``, where ``w = softmax(x2 W_router)`` over every expert,
  zero outside its top ``num_experts_per_tok``, and rescaled to sum to
  one only with ``norm_topk_prob``.

The expert layer is computed plainly: every expert on every token, each
weighted by its routing weight (zero outside the token's top k), so
nothing here depends on how the program sorts, groups or drops its
picks.  Attention, RoPE, the norms, the head and the weights' seeds are
``dense_gqa``'s; everything is float32 at ``Precision.HIGHEST``, one
sequence, layer by layer.  ``lowp="fp8"`` is the control, as there: every
matrix product, the router's included, takes float8 e4m3 inputs.

The weights are made on the device from the seed in the served
program's layout (``make_params``): the router in float32, every other
matrix in the configuration's dtype.

Counts (``count_<name>(dm, pump, events)``): a token passes through
``top_k`` experts of ``expert_ff``, so its operations are the dense
counts' at ``ff = top_k * expert_ff`` (``dims``; the router's
``2 d E`` a token is left out, as the norms are).  The bytes a dispatch
reads depend on which experts it touched: the program's tick events carry
``experts_touched`` ((layer, expert) pairs with at least one row), and
where they do each touched expert's three matrices are read once, with
the router and the qk-norm scales.  Where no event says, the count takes
the fewest a dispatch can touch, ``top_k`` experts a layer: the dense
count at that width.
"""
from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import roofline
from bench.references import dense_gqa as dense

HIGHEST = dense.HIGHEST

#: tick events whose args price a decode tick, and a prefill dispatch
DECODE = ("decode", "chunk+decode")
PREFILL = ("prefill", "chunk")


# -- configuration ---------------------------------------------------------
def dims(config: dict) -> dict:
    """``dense_gqa.dims`` plus the expert sizes; ``ff`` is the width a
    token passes through, ``top_k`` experts of ``expert_ff``."""
    dm = dense.dims(config)
    k = int(config["num_experts_per_tok"])
    dm.update(experts=int(config["num_experts"]), top_k=k,
              expert_ff=dm["ff"], ff=k * dm["ff"],
              norm_topk=bool(config["norm_topk_prob"]))
    return dm


def _key(config: dict) -> tuple:
    return tuple(sorted(dims(config).items()))


# -- counts ----------------------------------------------------------------
def _expert_bytes(dm: dict) -> int:
    """One expert's three matrices, bfloat16."""
    return 3 * dm["d"] * dm["expert_ff"] * 2


def _touched(events, kinds) -> Optional[int]:
    """(layer, expert) pairs the dispatches of ``kinds`` touched, from
    their tick events; None where no event says."""
    got = [e["args"]["experts_touched"] for e in events
           if e["name"] in kinds and "experts_touched" in e.get("args", {})]
    return sum(got) if got else None


def _priced(dm: dict, nbytes: float, touched: Optional[float]) -> float:
    """``nbytes`` of a dense count at ``top_k`` experts a layer, with the
    experts read replaced by the ``touched`` ones (and the router and
    qk-norm scales added) where they are known."""
    if touched is None:
        return nbytes
    fixed = dm["layers"] * (dm["d"] * dm["experts"] * 4 +
                            (dm["heads"] + dm["kv"]) * dm["dh"] * 2)
    return nbytes + fixed + \
        (touched - dm["layers"] * dm["top_k"]) * _expert_bytes(dm)


def count_decode_tick(dm: dict, pump, events) -> Tuple[float, float]:
    """The pump's decode tick: every row's layers at ``top_k`` experts,
    the head and attention at true lengths; bytes at the experts its
    tick event says it touched."""
    ops, nbytes = roofline.decode_tick(dm, pump.rows, pump.context)
    return ops, _priced(dm, nbytes, _touched(events, DECODE))


def count_prefill(dm: dict, pump, events) -> List[Tuple[float, float]]:
    """One entry per admitted prompt (one prompt per packed prefill), its
    bytes at its share of the experts the pump's prefill events say were
    touched."""
    touched = _touched(events, PREFILL)
    share = None if touched is None else touched / max(len(pump.segments),
                                                        1)
    out = []
    for seg in pump.segments:
        ops, nbytes = roofline.prefill(dm, [seg])
        out.append((ops, _priced(dm, nbytes, share)))
    return out


def count_decode_attention(dm: dict, pump, events) -> Tuple[float, float]:
    """The attention of the pump's decode tick, at the rows' true
    lengths (multi-head: one query head a KV head)."""
    return roofline.decode_attention(dm, pump.rows, pump.context)


def count_moe_experts(dm: dict, pump, events) -> Tuple[float, float]:
    """The decode tick's expert matmuls alone: ``2 * 3 * d * expert_ff``
    ops a routed (token, expert) pair; each touched expert's three
    matrices read once, and the routed rows in and out (bfloat16)."""
    pairs = pump.rows * dm["top_k"] * dm["layers"]
    touched = _touched(events, DECODE)
    if touched is None:
        touched = dm["layers"] * dm["top_k"]
    ops = 2.0 * 3 * dm["d"] * dm["expert_ff"] * pairs
    return ops, float(touched * _expert_bytes(dm) + pairs * dm["d"] * 2 * 2)


# -- weights ---------------------------------------------------------------
def leaf_specs(dm: dict) -> List[Tuple[str, tuple, str, int, Optional[str]]]:
    """(path, per-layer shape, init, fan_in, dtype or None for the
    configuration's) of every leaf, in the served program's parameter
    layout; paths under ``layers/`` are stacked."""
    d, h, kv, dh, v = dm["d"], dm["heads"], dm["kv"], dm["dh"], dm["vocab"]
    e, f = dm["experts"], dm["expert_ff"]
    out = [("embed/tok", (1, v, d), "normal", d, None)]
    if not dm["tied"]:
        out.append(("embed/head", (1, d, v), "normal", d, None))
    out += [("layers/norm1/scale", (d,), "ones", 0, None),
            ("layers/norm2/scale", (d,), "ones", 0, None),
            ("layers/attn/wq", (d, h, dh), "normal", d, None),
            ("layers/attn/wk", (d, kv, dh), "normal", d, None),
            ("layers/attn/wv", (d, kv, dh), "normal", d, None),
            ("layers/attn/wo", (h, dh, d), "normal", h * dh, None),
            ("layers/attn/q_norm", (h * dh,), "ones", 0, None),
            ("layers/attn/k_norm", (kv * dh,), "ones", 0, None),
            ("layers/moe/router", (d, e), "normal", d, "float32"),
            ("layers/moe/w_gate", (e, d, f), "normal", d, None),
            ("layers/moe/w_up", (e, d, f), "normal", d, None),
            ("layers/moe/w_down", (e, f, d), "normal", f, None),
            ("final_norm/scale", (d,), "ones", 0, None)]
    return out


def _leaves(dm: dict, key, dtype: str, layer=None, stacked: bool = True):
    """The leaves under ``layers/`` (``layer`` given: that layer's, else
    all of them stacked) or, with ``stacked=False``, the others."""
    flat = {}
    for path, shape, init, fan, dt in leaf_specs(dm):
        inner = path.startswith("layers/")
        if inner != stacked:
            continue
        dt = dt or dtype
        if not inner:
            flat[path] = dense._leaf(key, path, shape, init, fan, dt)
        elif layer is not None:
            flat[path[len("layers/"):]] = dense._leaf(
                key, path, shape, init, fan, dt, layer)
        else:
            flat[path] = lax.map(
                lambda i, path=path, shape=shape, init=init, fan=fan, dt=dt:
                dense._leaf(key, path, shape, init, fan, dt, i),
                jnp.arange(dm["layers"]))
    return flat


@lru_cache(maxsize=None)
def _make_params_fn(ckey: tuple, dtype: str):
    dm = dict(ckey)

    @jax.jit
    def make(key):
        return dense._nest({**_leaves(dm, key, dtype),
                            **_leaves(dm, key, dtype, stacked=False)})
    return make


def make_params(config: dict, seed: int, dtype: str = "bfloat16"):
    """All weights, stacked over layers, made on the default device."""
    return _make_params_fn(_key(config), dtype)(
        jax.random.key(dense.weight_seed(seed)))


@lru_cache(maxsize=None)
def _layer_params_fn(ckey: tuple, dtype: str):
    dm = dict(ckey)

    @jax.jit
    def make(key, i):
        return dense._nest(_leaves(dm, key, dtype, layer=i))
    return make


@lru_cache(maxsize=None)
def _outer_params_fn(ckey: tuple, dtype: str):
    dm = dict(ckey)

    @jax.jit
    def make(key):
        return dense._nest(_leaves(dm, key, dtype, stacked=False))
    return make


# -- reference forward -----------------------------------------------------
def _rms_full(x, scale, eps: float):
    """RMSNorm of each token's whole projection: x (T, heads, dh)."""
    t = x.shape[0]
    flat = x.reshape(t, -1)
    flat = flat / jnp.sqrt((flat * flat).mean(-1, keepdims=True) + eps)
    return (flat * scale).reshape(x.shape)


def routing_weights(dm: dict, logits):
    """(T, E) routing weights from router logits: the softmax over every
    expert, zero outside each token's top k, rescaled to sum to one only
    with ``norm_topk``."""
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = lax.top_k(probs, dm["top_k"])
    if dm["norm_topk"]:
        top = top / top.sum(-1, keepdims=True)
    rows = jnp.arange(probs.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top)


@partial(jax.jit, static_argnames=("ckey", "lowp"))
def _layer(p, h, *, ckey: tuple, lowp: bool):
    """One decoder layer over a whole sequence h: (T, d)."""
    dm = dict(ckey)
    p = dense._f32(p)
    t = h.shape[0]
    a = p["attn"]
    x = dense._norm(dm, p["norm1"], h)
    q = _rms_full(dense._mm("td,dhk->thk", x, a["wq"], lowp), a["q_norm"],
                  dm["eps"])
    k = _rms_full(dense._mm("td,dhk->thk", x, a["wk"], lowp), a["k_norm"],
                  dm["eps"])
    q, k = dense._rope(q, dm["theta"]), dense._rope(k, dm["theta"])
    v = dense._mm("td,dhk->thk", x, a["wv"], lowp)
    groups = dm["heads"] // dm["kv"]
    k = jnp.repeat(k, groups, axis=1)
    v = jnp.repeat(v, groups, axis=1)
    s = dense._mm("qhd,khd->hqk", q, k, lowp) / math.sqrt(dm["dh"])
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = dense._mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, lowp)
    h = h + dense._mm("thk,hkd->td", o, a["wo"], lowp)
    x = dense._norm(dm, p["norm2"], h)
    m = p["moe"]
    w = routing_weights(dm, dense._mm("td,de->te", x, m["router"], lowp))

    def expert(acc, xs):
        wg, wu, wd, we = xs
        u = jax.nn.silu(dense._mm("td,df->tf", x, wg, lowp)) * \
            dense._mm("td,df->tf", x, wu, lowp)
        return acc + we[:, None] * dense._mm("tf,fd->td", u, wd, lowp), None

    out, _ = lax.scan(expert, jnp.zeros_like(h),
                      (m["w_gate"], m["w_up"], m["w_down"], w.T))
    return h + out


def logits(config: dict, seed: int, seqs: Sequence[Tuple[np.ndarray,
                                                     np.ndarray]],
           lowp: Optional[str] = None, pad_to: int = 1024,
           dtype: str = "bfloat16") -> List[np.ndarray]:
    """Reference logits (float32), one array per ``(tokens, rows)`` pair:
    the logits at positions ``rows`` of one causal pass over ``tokens``
    (as ``dense_gqa.logits``: layer by layer, each sequence padded at its
    end to a multiple of ``pad_to``; the padding is causal and routed per
    token, so it reaches no real position)."""
    if lowp not in (None, "fp8"):
        raise ValueError(f"unknown lower precision {lowp!r}")
    ckey = _key(config)
    dm = dict(ckey)
    key = jax.random.key(dense.weight_seed(seed))
    outer = _outer_params_fn(ckey, dtype)(key)
    table = outer["embed"]["tok"][0]
    hs = []
    for tokens, _ in seqs:
        t = len(tokens)
        toks = np.zeros((-(-t // pad_to) * pad_to,), np.int32)
        toks[:t] = tokens
        hs.append(table[jnp.asarray(toks)].astype(jnp.float32))
    make_layer = _layer_params_fn(ckey, dtype)
    for i in range(dm["layers"]):
        w = make_layer(key, i)
        hs = [_layer(w, h, ckey=ckey, lowp=lowp == "fp8") for h in hs]
    return [np.asarray(dense._logits(
        outer, h, jnp.asarray(np.asarray(rows, np.int32)), ckey=ckey,
        lowp=lowp == "fp8"), np.float32) for h, (_, rows) in zip(hs, seqs)]
