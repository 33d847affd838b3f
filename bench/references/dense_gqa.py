"""Plain reference for dense GQA decoders, and the weights they share.

Two block types, as published:

- ``rmsnorm`` + SwiGLU (InternLM2, arXiv:2403.17297):
  ``h += W_o attn(rope(W_q x), rope(W_k x), W_v x)`` with ``x = rms(h)``,
  then ``h += W_down(silu(W_gate x2) * W_up x2)`` with ``x2 = rms(h)``;
- ``layernorm`` + GELU (StarCoder2, arXiv:2402.19173): the same attention
  with LayerNorm, then ``h += W_down gelu_tanh(W_up x2 + b_up) + b_down``.

Attention is causal grouped-query attention: query head ``j`` reads KV
head ``j // (heads / kv_heads)``; RoPE rotates the two halves of each head
(``x1 cos - x2 sin, x2 cos + x1 sin``) with frequencies
``theta ** (-i / (head_dim / 2))``.  Logits are ``final_norm(h) @ head``.

Everything is float32 at ``Precision.HIGHEST``, one sequence, no cache,
no kernels, no batching; it runs layer by layer (each layer's weights
made from the seed when it is needed) so that it fits beside nothing
else on one chip.  Departures of the served program from the published
models are recorded in each configuration file (``departures``); this
reference computes the configuration as it is run.

The weights are the benchmark's, not the program's: ``make_params``
builds them on the device from the seed in one jitted call, in the
parameter layout the served program takes, and ``layer_params`` makes
one layer of the same values again for the reference.

``lowp="fp8"`` is the control: every matrix product takes its inputs
rounded to float8 e4m3 (per-tensor scale for weights, per-row for
activations), the precision below the configuration's bfloat16.

The counts the per-layer readers price a run by are the configuration's
too: ``count_<name>(dm, pump, events)`` for each name in
``bench.spec.COUNTS``, here ``bench/roofline.py``'s dense GQA counts.
"""
from __future__ import annotations

import math
import zlib
from functools import lru_cache, partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import roofline

HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0


# -- configuration ---------------------------------------------------------
def dims(config: dict) -> dict:
    """The sizes the reference and the weights need, from a config file."""
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return {
        "layers": int(config["num_hidden_layers"]),
        "d": d, "heads": h,
        "kv": int(config["num_key_value_heads"]),
        "dh": int(config.get("head_dim", d // h)),
        "ff": int(config["intermediate_size"]),
        "vocab": int(config["vocab_size"]),
        "norm": config["norm_type"],
        "gated": config["hidden_act"] == "silu",
        "eps": float(config.get("rms_norm_eps",
                                config.get("norm_epsilon", 1e-6))),
        "theta": float(config["rope_theta"]),
        "tied": bool(config.get("tie_word_embeddings", False)),
    }


def _key(config: dict) -> tuple:
    return tuple(sorted(dims(config).items()))


# -- counts ----------------------------------------------------------------
# (ops, bytes) of what one harness pump ran, from its true lengths;
# ``events`` are the program's trace events that began inside the pump
# (unused by dense counts, which depend on lengths alone).
def count_decode_tick(dm: dict, pump, events) -> Tuple[float, float]:
    """The pump's decode tick: every row's layers, head and attention."""
    return roofline.decode_tick(dm, pump.rows, pump.context)


def count_prefill(dm: dict, pump, events) -> List[Tuple[float, float]]:
    """One entry per prefill dispatch of the pump: each admitted prompt
    is a dispatch of its own (one prompt per packed prefill)."""
    return [roofline.prefill(dm, [seg]) for seg in pump.segments]


def count_decode_attention(dm: dict, pump, events) -> Tuple[float, float]:
    """The attention of the pump's decode tick, at the rows' true
    lengths."""
    return roofline.decode_attention(dm, pump.rows, pump.context)


# -- weights ---------------------------------------------------------------
def leaf_specs(dm: dict) -> List[Tuple[str, tuple, str, int]]:
    """(path, per-layer shape, init, fan_in) of every leaf, in the served
    program's parameter layout; paths under ``layers/`` are stacked."""
    d, h, kv, dh, ff, v = (dm["d"], dm["heads"], dm["kv"], dm["dh"],
                           dm["ff"], dm["vocab"])
    ln = dm["norm"] == "layernorm"
    out = [("embed/tok", (1, v, d), "normal", d)]
    if not dm["tied"]:
        out.append(("embed/head", (1, d, v), "normal", d))
    for n in ("norm1", "norm2"):
        out.append((f"layers/{n}/scale", (d,), "ones", 0))
        if ln:
            out.append((f"layers/{n}/bias", (d,), "zeros", 0))
    out += [("layers/attn/wq", (d, h, dh), "normal", d),
            ("layers/attn/wk", (d, kv, dh), "normal", d),
            ("layers/attn/wv", (d, kv, dh), "normal", d),
            ("layers/attn/wo", (h, dh, d), "normal", h * dh)]
    if dm["gated"]:
        out += [("layers/ffn/w_gate", (d, ff), "normal", d),
                ("layers/ffn/w_up", (d, ff), "normal", d),
                ("layers/ffn/w_down", (ff, d), "normal", ff)]
    else:
        out += [("layers/ffn/w_up", (d, ff), "normal", d),
                ("layers/ffn/b_up", (ff,), "zeros", 0),
                ("layers/ffn/w_down", (ff, d), "normal", ff),
                ("layers/ffn/b_down", (d,), "zeros", 0)]
    out.append(("final_norm/scale", (d,), "ones", 0))
    if ln:
        out.append(("final_norm/bias", (d,), "zeros", 0))
    return out


def _leaf(key, path: str, shape, init: str, fan_in: int, dtype,
          layer: Optional[jax.Array] = None) -> jax.Array:
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    x = jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
    return x.astype(dtype)


def _nest(flat: Dict[str, jax.Array]) -> dict:
    out: dict = {}
    for path, x in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = x
    return out


def weight_seed(seed: int) -> int:
    """The 32-bit weight key of a run seed of any size."""
    return int(np.random.SeedSequence([int(seed), 1]).generate_state(1)[0])


@lru_cache(maxsize=None)
def _make_params_fn(ckey: tuple, dtype: str):
    dm = dict(ckey)

    @jax.jit
    def make(key):
        flat = {}
        for path, shape, init, fan in leaf_specs(dm):
            if path.startswith("layers/"):
                flat[path] = lax.map(
                    lambda i, path=path, shape=shape, init=init, fan=fan:
                    _leaf(key, path, shape, init, fan, dtype, i),
                    jnp.arange(dm["layers"]))
            else:
                flat[path] = _leaf(key, path, shape, init, fan, dtype)
        return _nest(flat)
    return make


def make_params(config: dict, seed: int, dtype: str = "bfloat16"):
    """All weights, stacked over layers, made on the default device."""
    return _make_params_fn(_key(config), dtype)(
        jax.random.key(weight_seed(seed)))


@lru_cache(maxsize=None)
def _layer_params_fn(ckey: tuple, dtype: str):
    dm = dict(ckey)

    @jax.jit
    def make(key, i):
        return _nest({path[len("layers/"):]:
                      _leaf(key, path, shape, init, fan, dtype, i)
                      for path, shape, init, fan in leaf_specs(dm)
                      if path.startswith("layers/")})
    return make


@lru_cache(maxsize=None)
def _outer_params_fn(ckey: tuple, dtype: str):
    dm = dict(ckey)

    @jax.jit
    def make(key):
        return _nest({path: _leaf(key, path, shape, init, fan, dtype)
                      for path, shape, init, fan in leaf_specs(dm)
                      if not path.startswith("layers/")})
    return make


# -- reference forward -----------------------------------------------------
def _fp8(x: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 under a scale that maps the max to 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq: str, x, w, lowp: bool):
    if lowp:
        x = _fp8(x, axis=-1)
        w = _fp8(w, axis=None)
    return jnp.einsum(eq, x, w, precision=HIGHEST)


def _norm(dm: dict, p: dict, x):
    if dm["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + dm["eps"]) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + dm["eps"]) * \
        p["scale"]


def _rope(x, theta: float):
    """x: (T, heads, dh), positions 0..T-1."""
    t, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("ckey", "lowp"))
def _layer(p, h, *, ckey: tuple, lowp: bool):
    """One decoder layer over a whole sequence h: (T, d)."""
    dm = dict(ckey)
    p = _f32(p)
    t = h.shape[0]
    groups = dm["heads"] // dm["kv"]
    x = _norm(dm, p["norm1"], h)
    q = _rope(_mm("td,dhk->thk", x, p["attn"]["wq"], lowp), dm["theta"])
    k = _rope(_mm("td,dhk->thk", x, p["attn"]["wk"], lowp), dm["theta"])
    v = _mm("td,dhk->thk", x, p["attn"]["wv"], lowp)
    k = jnp.repeat(k, groups, axis=1)
    v = jnp.repeat(v, groups, axis=1)
    s = _mm("qhd,khd->hqk", q, k, lowp) / math.sqrt(dm["dh"])
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, lowp)
    h = h + _mm("thk,hkd->td", a, p["attn"]["wo"], lowp)
    x = _norm(dm, p["norm2"], h)
    f = p["ffn"]
    if dm["gated"]:
        u = jax.nn.silu(_mm("td,df->tf", x, f["w_gate"], lowp)) * \
            _mm("td,df->tf", x, f["w_up"], lowp)
        return h + _mm("tf,fd->td", u, f["w_down"], lowp)
    u = jax.nn.gelu(_mm("td,df->tf", x, f["w_up"], lowp) + f["b_up"],
                    approximate=True)
    return h + _mm("tf,fd->td", u, f["w_down"], lowp) + f["b_down"]


@partial(jax.jit, static_argnames=("ckey", "lowp"))
def _logits(outer, h, rows, *, ckey: tuple, lowp: bool):
    dm = dict(ckey)
    outer = _f32(outer)
    x = _norm(dm, outer["final_norm"], h[rows])
    head = outer["embed"]["tok"][0].T if dm["tied"] \
        else outer["embed"]["head"][0]
    return _mm("td,dv->tv", x, head, lowp)


def logits(config: dict, seed: int, seqs: Sequence[Tuple[np.ndarray,
                                                     np.ndarray]],
           lowp: Optional[str] = None, pad_to: int = 1024,
           dtype: str = "bfloat16") -> List[np.ndarray]:
    """Reference logits (float32), one array per ``(tokens, rows)`` pair:
    the logits at positions ``rows`` of one causal pass over ``tokens``.

    The passes run layer by layer, each layer's weights made once for all
    sequences; each sequence is padded at its end to a multiple of
    ``pad_to`` so that few lengths compile, and causality keeps the
    padding out of every real position."""
    if lowp not in (None, "fp8"):
        raise ValueError(f"unknown lower precision {lowp!r}")
    ckey = _key(config)
    dm = dict(ckey)
    key = jax.random.key(weight_seed(seed))
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
    return [np.asarray(_logits(outer, h, jnp.asarray(np.asarray(rows,
                                                                np.int32)),
                               ckey=ckey, lowp=lowp == "fp8"), np.float32)
            for h, (_, rows) in zip(hs, seqs)]
