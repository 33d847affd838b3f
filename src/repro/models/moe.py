"""Top-k mixture of experts: a dropless grouped layer on one device, and a
capacity-bounded sort-based dispatch under sharding rules (EP-shardable).

Without sharding rules (the served one-chip path) ``apply_moe`` is
dropless: every (token, expert) pick is computed, so a row's output does
not depend on its batch-mates. The picks are sorted by expert and the
experts run as grouped matmuls over the sorted rows
(``repro.kernels.ops.grouped_matmul``: megablox ``gmm`` on TPU,
``jax.lax.ragged_dot`` elsewhere), each step under a named scope:
``moe/router``, ``moe/dispatch``, ``moe/experts``, ``moe/combine``.

Under sharding rules dispatch avoids the O(T*E*C) one-hot einsum:
assignments are ranked within their expert via a stable sort, tokens
beyond capacity are dropped, and the (E, C, d) expert batch is built by
scatter. Experts are sharded over the 'expert' logical axis (mesh
'model'); GSPMD turns the gather/scatter into all-to-alls.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed import axis_size, constrain, current_rules
from repro.kernels.ops import grouped_matmul
from repro.models.layers import dense_init

Params = Dict[str, jax.Array]

#: an expert layer's matrices (the rest of its params is the router)
EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def init_moe(cfg: ModelConfig, key, dtype) -> Params:
    assert cfg.moe is not None
    e, d, f = cfg.moe.num_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, e), d, jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), d, dtype),
        "w_up": dense_init(ks[2], (e, d, f), d, dtype),
        "w_down": dense_init(ks[3], (e, f, d), f, dtype),
    }
    return p


def expert_capacity(cfg: ModelConfig, num_tokens: int) -> int:
    moe = cfg.moe
    cap = int(num_tokens * moe.top_k / moe.num_experts * moe.capacity_factor)
    return max(8, -(-cap // 8) * 8)   # round up to multiple of 8


def route(cfg: ModelConfig, p: Params, x2d: jax.Array
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x2d: (T, d) -> (weights (T,k), expert_idx (T,k), aux_loss).

    Logits in float32 at full precision, softmax over every expert, the
    top k of it; the k weights are rescaled to sum to one only where the
    configuration says so (``norm_topk_prob``)."""
    moe = cfg.moe
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), p["router"],
                        precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, moe.top_k)
    if moe.norm_topk_prob:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, moe.num_experts), axis=1), axis=0)
    aux = moe.num_experts * jnp.sum(me * ce) / moe.top_k
    return weights, idx, aux


def _dispatch_group(x2d, idx, weights, e, cap, dtype):
    """One dispatch group: tokens (Tg,d) routed to an (E, cap) buffer.

    Returns (xin (E,cap,d), slot (Tg*k,), flat_token, flat_weight, keep)."""
    tg = x2d.shape[0]
    k = idx.shape[1]
    flat_expert = idx.reshape(tg * k)
    flat_weight = weights.reshape(tg * k).astype(dtype)
    flat_token = jnp.repeat(jnp.arange(tg), k)
    # rank within expert via stable sort + cummax of run starts
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    i = jnp.arange(tg * k, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_expert[1:] != sorted_expert[:-1]])
    run_start = jax.lax.cummax(jnp.where(is_start, i, 0))
    rank = jnp.zeros_like(i).at[order].set(i - run_start)
    keep = rank < cap
    slot = jnp.where(keep, flat_expert * cap + rank, e * cap)
    table = jnp.full((e * cap + 1,), tg * k, jnp.int32)
    table = table.at[slot].set(jnp.arange(tg * k, dtype=jnp.int32))
    table = table[:-1].reshape(e, cap)
    valid = table < tg * k
    tok_for_slot = flat_token[jnp.where(valid, table, 0)]
    xin = x2d[tok_for_slot] * valid[..., None].astype(dtype)
    return xin, slot, flat_token, flat_weight, keep


def _combine_group(yflat, slot, flat_token, flat_weight, keep, tg, d,
                   dtype):
    contrib = yflat[slot] * flat_weight[:, None] * \
        keep.astype(dtype)[:, None]
    return jnp.zeros((tg, d), dtype).at[flat_token].add(contrib)


def apply_moe(cfg: ModelConfig, p: Params, x: jax.Array,
              experts: Optional[Params] = None,
              layer: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B,S,d) -> (out (B,S,d), aux_loss scalar, rows (E,) int32).

    ``rows`` is how many token rows each expert computed. Dropless
    without sharding rules (``_apply_dropless``); with them, capacity-
    bounded (``_apply_capacity``).  A scan over layers may pass every
    layer's expert matrices stacked, (L, E, ...), as ``experts``, with
    this layer's index ``layer`` (``p`` then holds the router): the
    dropless kernel reads the layer's experts in place, where slicing
    them out of the stack would copy every expert of the layer."""
    if current_rules() is None:
        b, s, d = x.shape
        out, aux, rows = _apply_dropless(cfg, p, x.reshape(b * s, d),
                                         experts, layer)
        return out.reshape(b, s, d), aux, rows
    if experts is not None:
        p = dict(p, **{n: w[layer] for n, w in experts.items()})
    return _apply_capacity(cfg, p, x)


@jax.named_scope("moe")
def _apply_dropless(cfg: ModelConfig, p: Params, x2d: jax.Array,
                    experts: Optional[Params] = None,
                    layer: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x2d: (T, d) -> (out (T, d), aux_loss, group sizes (E,) int32).

    Every pick is computed, so each token's output is what it would be
    alone: its k picks are sorted by expert (a stable sort, so rows keep
    token order within an expert), the experts run as
    grouped matmuls over the sorted rows with per-expert group sizes, and
    each token's k rows are gathered back (the inverse of the sort: a
    scatter-add to its token written as a gather) and summed with its
    routing weights in float32, in its own top-k order."""
    moe = cfg.moe
    t, d = x2d.shape
    e, k = moe.num_experts, moe.top_k
    with jax.named_scope("router"):
        weights, idx, aux = route(cfg, p, x2d)
    with jax.named_scope("dispatch"):
        flat = idx.reshape(t * k)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
        xs = x2d[order // k]                                  # (T*k, d)
        if experts is None:
            w, groups = {n: p[n] for n in EXPERT_KEYS if n in p}, sizes
        else:
            # every layer's experts as one group list; rows go to this
            # layer's groups only
            w = {n: a.reshape((-1,) + a.shape[2:])
                 for n, a in experts.items()}
            groups = lax.dynamic_update_slice(
                jnp.zeros((w["w_down"].shape[0],), jnp.int32), sizes,
                (layer * e,))
    with jax.named_scope("experts"):
        if cfg.act == "swiglu":
            h = jax.nn.silu(grouped_matmul(xs, w["w_gate"], groups)) * \
                grouped_matmul(xs, w["w_up"], groups)
        else:
            h = jax.nn.gelu(grouped_matmul(xs, w["w_up"], groups))
        ys = grouped_matmul(h.astype(x2d.dtype), w["w_down"],
                            groups)                          # (T*k, d)
    with jax.named_scope("combine"):
        back = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        out = jnp.sum(ys[back].reshape(t, k, d) * weights[..., None],
                      axis=1)
    return out.astype(x2d.dtype), aux, sizes


def routing_stats(rows: jax.Array) -> jax.Array:
    """(2,) int32 of a step's per-layer expert rows (L, E): the (layer,
    expert) pairs that got at least one row, and the largest group."""
    return jnp.stack([jnp.sum(rows > 0), jnp.max(rows)]).astype(jnp.int32)


def _apply_capacity(cfg: ModelConfig, p: Params, x: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B,S,d) -> (out (B,S,d), aux_loss scalar, kept rows (E,)).

    GShard-style grouped dispatch: tokens are split into ``G`` groups (one
    per data shard when a mesh is active) with per-group expert capacity,
    so the token->expert movement is an all-to-all between the data and
    expert axes instead of a full x all-gather. G=1 on a single device.
    """
    moe = cfg.moe
    b, s, d = x.shape
    t = b * s
    e = moe.num_experts
    k = moe.top_k
    groups = axis_size("batch")
    if t % groups != 0:
        groups = 1
    tg = t // groups
    x2d = x.reshape(t, d)
    weights, idx, aux = route(cfg, p, x2d)
    cap = expert_capacity(cfg, tg)

    x3 = x2d.reshape(groups, tg, d)
    idx3 = idx.reshape(groups, tg, k)
    w3 = weights.reshape(groups, tg, k)
    xin, slot, flat_token, flat_weight, keep = jax.vmap(
        _dispatch_group, in_axes=(0, 0, 0, None, None, None)
    )(x3, idx3, w3, e, cap, x.dtype)                    # xin: (G,E,cap,d)
    xin = jnp.swapaxes(xin, 0, 1)                        # (E,G,cap,d)
    xin = constrain(xin, "expert", "exp_cap", None, "embed")

    # Expert FFN: (E,G,C,d) x (E,d,f)
    if cfg.act == "swiglu":
        g = jnp.einsum("egcd,edf->egcf", xin, p["w_gate"])
        u = jnp.einsum("egcd,edf->egcf", xin, p["w_up"])
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(jnp.einsum("egcd,edf->egcf", xin, p["w_up"]))
    h = constrain(h, "expert", "exp_cap", None, None)
    yexp = jnp.einsum("egcf,efd->egcd", h, p["w_down"])
    yexp = constrain(yexp, "expert", "exp_cap", None, "embed")
    yexp = jnp.swapaxes(yexp, 0, 1)                      # (G,E,cap,d)
    yflat = yexp.reshape(groups, e * cap, d)
    yflat = jnp.concatenate(
        [yflat, jnp.zeros((groups, 1, d), x.dtype)], axis=1)

    out = jax.vmap(_combine_group,
                   in_axes=(0, 0, 0, 0, 0, None, None, None))(
        yflat, slot, flat_token, flat_weight, keep, tg, d, x.dtype)
    out = constrain(out, "batch", None, None).reshape(b, s, d)
    kept = jnp.zeros((e,), jnp.int32).at[idx3.reshape(-1)].add(
        keep.reshape(-1).astype(jnp.int32))
    return constrain(out, "batch", None, "embed"), aux, kept
