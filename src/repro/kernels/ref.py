"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors the semantics of its kernel exactly, including
variable-length masking, so kernel tests can `assert_allclose` against it
over shape/dtype sweeps.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def softmax_ref(x: jax.Array, lengths: Optional[jax.Array] = None,
                scale: float = 1.0) -> jax.Array:
    """Masked scaled softmax over the last dim. x: (R, C); lengths: (R,)."""
    xf = x.astype(jnp.float32) * scale
    if lengths is not None:
        mask = jnp.arange(x.shape[-1])[None, :] < lengths[:, None]
        xf = jnp.where(mask, xf, -jnp.inf)
    m = jnp.max(xf, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(xf - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    return (e / jnp.maximum(s, 1e-30)).astype(x.dtype)


def layernorm_ref(x: jax.Array, gamma: jax.Array, beta: jax.Array,
                  bias: Optional[jax.Array] = None,
                  residual: Optional[jax.Array] = None,
                  eps: float = 1e-6,
                  return_residual: bool = False):
    """Fused AddBias+Residual+LayerNorm. x,(residual): (R,C); bias: (C,).

    Uses the paper's Eq.1 single-pass form Var = E(x^2) - E(x)^2.
    """
    s = x.astype(jnp.float32)
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if residual is not None:
        s = s + residual.astype(jnp.float32)
    mean = jnp.mean(s, axis=-1, keepdims=True)
    mean_sq = jnp.mean(s * s, axis=-1, keepdims=True)
    var = jnp.maximum(mean_sq - mean * mean, 0.0)
    y = (s - mean) * jax.lax.rsqrt(var + eps)
    y = y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    y = y.astype(x.dtype)
    if return_residual:
        return y, s.astype(x.dtype)
    return y


def rmsnorm_ref(x: jax.Array, gamma: jax.Array,
                bias: Optional[jax.Array] = None,
                residual: Optional[jax.Array] = None,
                eps: float = 1e-6,
                return_residual: bool = False):
    s = x.astype(jnp.float32)
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if residual is not None:
        s = s + residual.astype(jnp.float32)
    ms = jnp.mean(s * s, axis=-1, keepdims=True)
    y = (s * jax.lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)
         ).astype(x.dtype)
    if return_residual:
        return y, s.astype(x.dtype)
    return y


def sample_ref(logits: jax.Array, temperature: jax.Array,
               top_k: jax.Array, top_p: jax.Array,
               gumbel: jax.Array) -> jax.Array:
    """Fused sampling oracle. logits: (B, V); temperature/top_k/top_p:
    (B,); gumbel: (B, C) pre-drawn per-row Gumbel noise.

    Candidate set = the top C = gumbel.shape[-1] temperature-scaled
    logits (``lax.top_k`` tie order: lowest index first).  top_k == 0 or
    top_k > C truncates to C.  Sampling uses the Gumbel-max trick over
    the kept candidates — an exact categorical draw from the
    renormalized top-k/top-p distribution.  Rows with temperature <= 0
    return the plain argmax (greedy), computed by the identical
    expression the greedy engine uses.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    c = gumbel.shape[-1]
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits.astype(jnp.float32) / temp
    vals, idx = jax.lax.top_k(scaled, c)                 # (B, C) desc
    cand = jnp.arange(c)[None, :]
    k = jnp.clip(jnp.where(top_k > 0, top_k, c), 1, c)[:, None]
    keep = cand < k
    masked = jnp.where(keep, vals, -jnp.inf)
    m = jnp.max(masked, axis=-1, keepdims=True)
    e = jnp.where(keep, jnp.exp(masked - m), 0.0)
    probs = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    # nucleus: keep the smallest high-probability set whose mass reaches
    # top_p (the crossing token is kept, so the set is never empty)
    exclusive = jnp.cumsum(probs, axis=-1) - probs
    keep = keep & (exclusive < top_p[:, None])
    pert = jnp.where(keep, vals + gumbel.astype(jnp.float32), -jnp.inf)
    choice = jnp.argmax(pert, axis=-1)
    sampled = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]
    return jnp.where(temperature > 0, sampled.astype(jnp.int32), greedy)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        lengths: Optional[jax.Array] = None,
                        causal: bool = True,
                        scale: Optional[float] = None) -> jax.Array:
    """q: (B,H,Sq,dh); k,v: (B,KV,Sk,dh); lengths: (B,) valid kv length.

    GQA: H = KV * G. Causal alignment assumes the queries are the *last*
    Sq positions of the kv sequence (standard prefill/extend semantics):
    q row i attends kv j  iff  j <= (Sk - Sq + i).
    """
    b, h, sq, dh = q.shape
    kv = k.shape[1]
    g = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    qg = q.reshape(b, kv, g, sq, dh)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    sk = k.shape[2]
    kpos = jnp.arange(sk)
    mask = jnp.ones((b, sq, sk), bool)
    if causal:
        qpos = jnp.arange(sq) + (sk - sq)
        mask = mask & (kpos[None, None, :] <= qpos[None, :, None])
    if lengths is not None:
        mask = mask & (kpos[None, None, :] < lengths[:, None, None])
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(s - m)
    den = jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    w = (e / den).astype(q.dtype)
    out = jnp.einsum("bkgqs,bksd->bkgqd", w, v)
    return out.reshape(b, h, sq, dh)


def grouped_matmul_ref(lhs: jax.Array, rhs: jax.Array,
                       group_sizes: jax.Array) -> jax.Array:
    """Rows of ``lhs`` (m, k), sorted by group, times their group's
    matrix: group g holds the ``group_sizes[g]`` rows after those of the
    groups before it, and multiplies ``rhs[g]`` (G, k, n).  Float32 out
    (m, n); rows past the groups' sum are zero."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)
