"""Pallas TPU kernel: split-K flash *decode* attention (Sq = 1).

The serving hot loop (paper §5 / our §Perf cell C): one query token
attends to a long KV cache. The sequential flash kernel walks KV blocks
on one core; at decode batch sizes that leaves the chip idle. Split-K
parallelizes over the KV *sequence*: grid (B, H, n_splits), each split
produces a partial (max, denom, acc) over its KV range in one VMEM pass
(paper C1: mask+softmax+both GEMMs fused), and a cheap jnp combine merges
the partials with a log-sum-exp reduction.

HBM traffic per step = one bf16 read of K and V plus O(B*H*splits)
scalars — the bandwidth floor the §Perf analysis projects (~12-15 ms/step
for qwen3-32b decode_32k vs 333 ms for the best XLA path).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_scr, m_scr, l_scr, *,
                   scale: float, sk: int, block_k: int, split: int):
    j = pl.program_id(3)          # kv block within this split
    nk = pl.num_programs(3)
    s = pl.program_id(2)          # split index
    kv_len = jnp.minimum(len_ref[pl.program_id(0)], sk)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = (s * nk + j) * block_k
    q = q_ref[0, 0].astype(jnp.float32)                  # (1, dh)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, dh)
    st = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (1, bk)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
    mask = kpos < kv_len
    st = jnp.where(mask, st, NEG_INF)

    m_prev = m_scr[...]                                  # (1, 128)
    m_cur = jnp.max(st, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(st - m_new[:, :1])
    p = jnp.where(mask, p, 0.0)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, dh)
    # zero masked rows of V: padded blocks read unspecified data (NaN in
    # interpret mode) and 0 * NaN = NaN would poison the accumulator —
    # must be a select, not a multiply.  The row mask is rebuilt along
    # sublanes: Mosaic cannot transpose the (1, bk) lane mask.
    vpos = k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    v = jnp.where(vpos < kv_len, v, 0.0)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (1, dh)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0, 0] = acc_scr[...].astype(o_ref.dtype)
        m_ref[0, 0, 0] = m_scr[...].astype(m_ref.dtype)
        l_ref[0, 0, 0] = l_scr[...].astype(l_ref.dtype)


def flash_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                        lengths=None, *, scale=None, num_splits: int = 4,
                        block_k: int = 512,
                        interpret: bool = False) -> jax.Array:
    """q: (B,H,dh); k,v: (B,KV,S,dh); lengths: (B,) valid kv lengths.

    Returns (B,H,dh). GQA via the k/v index_map (H folded onto KV)."""
    b, h, dh = q.shape
    kv, sk = k.shape[1], k.shape[2]
    g = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    per_split = -(-sk // num_splits)
    bk = min(block_k, per_split)
    nk = pl.cdiv(per_split, bk)
    if lengths is None:
        lengths = jnp.full((b,), sk, jnp.int32)

    kernel = functools.partial(
        _decode_kernel, scale=scale, sk=sk, block_k=bk, split=num_splits)
    # lengths ride in SMEM as a scalar-prefetch operand (a (1, 1) VMEM
    # block breaks the TPU's (8, 128) tiling rule)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, num_splits, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, dh),
                         lambda b_, h_, s, j, n: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, s, j, n, g=g, nk=nk:
                         (b_, h_ // g, s * nk + j, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, s, j, n, g=g, nk=nk:
                         (b_, h_ // g, s * nk + j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, 1, dh),
                         lambda b_, h_, s, j, n: (b_, h_, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, 128),
                         lambda b_, h_, s, j, n: (b_, h_, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, 128),
                         lambda b_, h_, s, j, n: (b_, h_, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, dh), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
        ],
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, num_splits, 1, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, h, num_splits, 1, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, h, num_splits, 1, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="turbo_flash_decode",
    )(lengths.astype(jnp.int32), q[:, :, None, :], k, v)

    return _combine_splits(out, m, l, q.dtype)


def _combine_splits(out, m, l, dtype):
    """Merge split partials with a log-sum-exp reduction (cheap, jnp).

    The partials carry a unit axis before the lanes so each kernel block
    spans the array's last two dims (the TPU tiling rule)."""
    out, m, l = out[:, :, :, 0], m[:, :, :, 0], l[:, :, :, 0]
    m1 = m[..., 0]                                       # (B,H,S_) lanes dup
    m_star = jnp.max(m1, axis=-1, keepdims=True)         # (B,H,1)
    w = jnp.exp(m1 - m_star)                             # (B,H,S_)
    den = jnp.sum(l[..., 0] * w, axis=-1)                # (B,H)
    num = jnp.sum(out * w[..., None], axis=2)            # (B,H,dh)
    return (num / jnp.maximum(den, 1e-30)[..., None]).astype(dtype)


def _paged_decode_kernel(tables_ref, len_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr, *,
                         scale: float, block_size: int):
    """Split-K decode over a *block table*: the kv range of split ``s`` is
    a run of logical blocks whose physical pool block is chosen by the
    scalar-prefetched table (the k/v index_map does the indirection, so
    the kernel body is the contiguous kernel with block_k = block_size)."""
    j = pl.program_id(3)          # logical block within this split
    nk = pl.num_programs(3)
    s = pl.program_id(2)          # split index
    kv_len = len_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = (s * nk + j) * block_size
    q = q_ref[0, 0].astype(jnp.float32)                  # (1, dh)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bs, dh)
    st = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (1, bs)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
    # length alone bounds validity: positions past a row's length sit in
    # trash/unassigned blocks whose table entry is 0
    mask = kpos < kv_len
    st = jnp.where(mask, st, NEG_INF)

    m_prev = m_scr[...]                                  # (1, 128)
    m_cur = jnp.max(st, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(st - m_new[:, :1])
    p = jnp.where(mask, p, 0.0)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bs, dh)
    # select, not multiply: unwritten block contents are unspecified
    # (the row mask is rebuilt along sublanes, as in _decode_kernel)
    vpos = k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    v = jnp.where(vpos < kv_len, v, 0.0)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (1, dh)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0, 0] = acc_scr[...].astype(o_ref.dtype)
        m_ref[0, 0, 0] = m_scr[...].astype(m_ref.dtype)
        l_ref[0, 0, 0] = l_scr[...].astype(l_ref.dtype)


def flash_decode_paged_pallas(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, block_tables: jax.Array,
                              lengths=None, *, scale=None,
                              num_splits: int = 4,
                              interpret: bool = False) -> jax.Array:
    """Paged variant of :func:`flash_decode_pallas`.

    q: (B,H,dh); k_pool,v_pool: (NB,BS,KV,dh) — ONE pool of fixed-size
    token blocks shared by all rows; block_tables: (B,MB) int32 mapping
    each row's logical block index to a physical pool block; lengths: (B,)
    valid kv lengths.  Returns (B,H,dh).

    The kv walk follows the block table via scalar prefetch (the table is
    available before the kernel body runs, so each grid step DMAs exactly
    the pool block it needs) — HBM traffic stays one read of the *live*
    KV, never of a contiguous max-length stripe.
    """
    b, h, dh = q.shape
    nb, bs, kv = k_pool.shape[:3]
    g = h // kv
    mb = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    num_splits = max(1, min(num_splits, mb))
    nk = pl.cdiv(mb, num_splits)          # logical blocks per split
    pad = num_splits * nk - mb
    # padded table entries point at block 0; their positions are >= mb*bs
    # only when mb*bs >= every length, which the caller guarantees — they
    # are masked by the length check either way
    tables = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, pad)))
    if lengths is None:
        lengths = jnp.full((b,), mb * bs, jnp.int32)
    # (KV, NB, BS, dh): the (bs, dh) tile pallas DMAs per step is then the
    # trailing-2-dim tile TPU tiling wants
    kt = jnp.transpose(k_pool, (2, 0, 1, 3))
    vt = jnp.transpose(v_pool, (2, 0, 1, 3))

    grid = (b, h, num_splits, nk)
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, block_size=bs)
    # the lengths are a second scalar-prefetch operand (SMEM): a (1, 1)
    # VMEM block breaks the TPU's (8, 128) tiling rule
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, dh),
                         lambda b_, h_, s, j, t, n: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bs, dh),
                         lambda b_, h_, s, j, t, n, g=g, nk=nk:
                         (h_ // g, t[b_, s * nk + j], 0, 0)),
            pl.BlockSpec((1, 1, bs, dh),
                         lambda b_, h_, s, j, t, n, g=g, nk=nk:
                         (h_ // g, t[b_, s * nk + j], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, 1, dh),
                         lambda b_, h_, s, j, t, n: (b_, h_, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, 128),
                         lambda b_, h_, s, j, t, n: (b_, h_, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, 128),
                         lambda b_, h_, s, j, t, n: (b_, h_, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, dh), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
        ],
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, num_splits, 1, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, h, num_splits, 1, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, h, num_splits, 1, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="turbo_flash_decode_paged",
    )(tables, lengths.astype(jnp.int32), q[:, :, None, :], kt, vt)
    return _combine_splits(out, m, l, q.dtype)
