"""Pallas TPU kernel: blockwise (flash) attention with online softmax.

Beyond-paper extension of the C1 fusion idea: the paper fuses
mask+scale+softmax between the two attention GEMMs; on TPU we fuse the
GEMMs themselves into the same VMEM pass (QK^T -> mask -> online softmax
-> .V), which turns the O(S^2) score tensor into O(block_q * block_k)
VMEM tiles. Supports causal masking, GQA (query-head folding onto the kv
head via the k/v index_map), and per-batch variable kv lengths — the
TPU-native form of the paper's variable-length-aware serving runtime.

Grid: (B, H, num_q_blocks, num_kv_blocks); the kv dim is innermost and
sequential, with running (m, l, acc) in VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, sq: int, sk: int,
                  block_q: int, block_k: int):
    i = pl.program_id(2)        # q block
    j = pl.program_id(3)        # kv block
    nk = pl.num_programs(3)
    kv_len = jnp.minimum(len_ref[pl.program_id(0)], sk)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * block_q + (sk - sq)   # absolute kv pos of first q row
    k_start = j * block_k

    def body():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_len
        if causal:
            mask &= kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                              # (bq, 128)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)       # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)               # (bq, 128)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + \
            jnp.sum(p, axis=-1, keepdims=True)           # (bq, 128)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, dh)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, dh)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # skip kv blocks strictly above the causal diagonal
        pl.when(k_start <= q_start + block_q - 1)(body)
    else:
        body()

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           lengths=None, *, causal: bool = True,
                           scale=None, block_q: int = 512,
                           block_k: int = 512,
                           interpret: bool = False) -> jax.Array:
    """q: (B,H,Sq,dh); k,v: (B,KV,Sk,dh); lengths: (B,) valid kv lengths.

    Causal alignment: q row i sits at kv position (Sk - Sq + i), i.e. the
    queries are the last Sq positions (prefill: Sq == Sk).
    """
    b, h, sq, dh = q.shape
    kv, sk = k.shape[1], k.shape[2]
    g = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nq = pl.cdiv(sq, bq)
    nk = pl.cdiv(sk, bk)
    if lengths is None:
        lengths = jnp.full((b,), sk, jnp.int32)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, sq=sq, sk=sk,
        block_q=bq, block_k=bk)
    # lengths ride in SMEM as a scalar-prefetch operand: a (1, 1) VMEM
    # block of a (B, 1) array breaks the TPU's (8, 128) tiling rule
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh),
                         lambda b_, h_, i, j, n: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, i, j, n, g=g: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, i, j, n, g=g: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b_, h_, i, j, n: (b_, h_, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="turbo_flash_attention",
    )(lengths.astype(jnp.int32), q, k, v)
