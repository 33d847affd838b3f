"""Pallas TPU kernel: fused temperature / top-k / top-p / Gumbel sampling.

One pass over the logits row replaces the full-vocab ``jnp.sort`` the
XLA sampling path paid per decode tick (the LightSeq observation,
arxiv 2010.13887: sampling only ever needs a small candidate set).  The
kernel keeps the whole row in VMEM and

  1. takes the greedy ``argmax`` (the short-circuit for temperature<=0
     rows — mixed batches stop paying the sampled path for them),
  2. temperature-scales and max-peels the top ``cands`` candidates
     (``cands`` iterations of max+argmax, no sort; tie order matches
     ``lax.top_k`` — lowest index first),
  3. applies the top-k mask, the nucleus (top-p) mask over the
     exclusive prefix sum of the candidate softmax, and picks via the
     Gumbel-max trick: ``argmax(vals + gumbel)`` over the kept set is an
     exact categorical draw from the renormalized kept distribution.

The Gumbel noise is generated OUTSIDE the kernel from the per-request
key (``fold_in(PRNGKey(seed), step)``) so the XLA reference and the
kernel consume identical noise and stay bit-comparable, and the
reproducibility contract lives in one place (runtime/sampling.py).

Truncation semantics: rows sample from their top ``cands`` tokens even
when ``top_k == 0`` (whole vocab) or ``top_k > cands`` — the tail mass
beyond 64 candidates is negligible for trained models and the bound is
what buys the no-sort single pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _argmax_first(x, iota):
    """Row max and the LOWEST index that holds it, both (br, 1): the tie
    order of ``jnp.argmax`` and ``lax.top_k``, spelled with a max and a
    min reduction so that it does not depend on how the TPU lowers
    argmax."""
    m = jnp.max(x, axis=-1, keepdims=True)
    big = jnp.iinfo(jnp.int32).max
    return m, jnp.min(jnp.where(x == m, iota, big), axis=-1, keepdims=True)


def _sample_kernel(x_ref, t_ref, k_ref, p_ref, g_ref, o_ref, *,
                   cols: int, cands: int):
    x = x_ref[...].astype(jnp.float32)                   # (br, Cp)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(col < cols, x, -jnp.inf)               # mask padded cols
    _, greedy = _argmax_first(x, col)                    # (br, 1)

    temp = jnp.maximum(t_ref[...], 1e-6)                 # (br, 1)
    work = x / temp
    br = x.shape[0]
    cand = jax.lax.broadcasted_iota(jnp.int32, (br, cands), 1)

    # the peeled candidates ride in the loop carry and land in lane j by
    # a select: Mosaic cannot store one lane at a dynamic offset
    def peel(j, carry):
        w, vals, idx = carry
        m, a = _argmax_first(w, col)                     # (br, 1) each
        vals = jnp.where(cand == j, m, vals)
        idx = jnp.where(cand == j, a, idx)
        return jnp.where(col == a, -jnp.inf, w), vals, idx

    _, vals, idx = jax.lax.fori_loop(
        0, cands, peel,
        (work, jnp.full((br, cands), -jnp.inf, jnp.float32),
         jnp.zeros((br, cands), jnp.int32)))             # (br, C) desc

    k = k_ref[...]                                       # (br, 1) int32
    keff = jnp.clip(jnp.where(k > 0, k, cands), 1, cands)
    keep = cand < keff
    masked = jnp.where(keep, vals, -jnp.inf)
    m = jnp.max(masked, axis=-1, keepdims=True)
    e = jnp.where(keep, jnp.exp(masked - m), 0.0)
    probs = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)

    # inclusive prefix sum as a triangular sum, one candidate column per
    # step (Mosaic has no cumsum); the one-hot lane reduce is exact
    def prefix(j, acc):
        pj = jnp.sum(jnp.where(cand == j, probs, 0.0), axis=-1,
                     keepdims=True)
        return acc + jnp.where(cand >= j, pj, 0.0)

    inclusive = jax.lax.fori_loop(0, cands, prefix, jnp.zeros_like(probs))
    exclusive = inclusive - probs
    keep = keep & (exclusive < p_ref[...])
    pert = jnp.where(keep, vals + g_ref[...], -jnp.inf)
    _, choice = _argmax_first(pert, cand)                # (br, 1)
    sampled = jnp.sum(jnp.where(cand == choice, idx, 0), axis=-1,
                      keepdims=True)
    o_ref[...] = jnp.where(t_ref[...] > 0, sampled, greedy)


def default_block_rows(cols: int, vmem_budget: int = 1 << 21) -> int:
    """Rows per VMEM tile: keep the logits tile under ~2MB of f32."""
    per_row = max(cols, 128) * 4
    rows = max(vmem_budget // per_row, 8)
    return int(min(256, pl.next_power_of_2(rows)))


def sample_pallas(logits: jax.Array, temperature: jax.Array,
                  top_k: jax.Array, top_p: jax.Array, gumbel: jax.Array,
                  *, block_rows: int = 0, interpret: bool = False
                  ) -> jax.Array:
    """logits: (B, V); temperature/top_k/top_p: (B,); gumbel: (B, C).

    Returns (B,) int32 — one token per row; temperature<=0 rows are the
    plain argmax.
    """
    r, c = logits.shape
    cands = gumbel.shape[-1]
    br = block_rows or default_block_rows(c)
    br = min(br, pl.next_power_of_2(max(r, 8)))
    t2 = temperature.astype(jnp.float32).reshape(r, 1)
    k2 = top_k.astype(jnp.int32).reshape(r, 1)
    p2 = top_p.astype(jnp.float32).reshape(r, 1)
    grid = (pl.cdiv(r, br),)
    out = pl.pallas_call(
        functools.partial(_sample_kernel, cols=c, cands=cands),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, cands), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        interpret=interpret,
        name="turbo_sample",
    )(logits, t2, k2, p2, gumbel.astype(jnp.float32))
    return out[:, 0]
