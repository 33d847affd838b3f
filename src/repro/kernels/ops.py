"""jit'd dispatch wrappers around the Pallas kernels.

``impl`` selects the execution path:
  "xla"       pure-jnp reference (ref.py) — default on CPU
  "pallas"    compiled Pallas TPU kernel — default on TPU
  "interpret" Pallas kernel body executed by the interpreter (CPU
              validation path; bit-accurate kernel semantics)
  "auto"      pallas on TPU, xla elsewhere
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_decode import (flash_decode_paged_pallas,
                                        flash_decode_pallas)
from repro.kernels.layernorm import norm_pallas
from repro.kernels.sampling import sample_pallas
from repro.kernels.softmax import softmax_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "xla"
    return impl


def fused_softmax(x: jax.Array, lengths: Optional[jax.Array] = None, *,
                  scale: float = 1.0, impl: str = "auto",
                  block_rows: int = 0) -> jax.Array:
    """Masked scaled softmax over the last dim of a 2-D array."""
    impl = _resolve(impl)
    if impl == "xla":
        return ref.softmax_ref(x, lengths, scale)
    return softmax_pallas(x, lengths, scale=scale, block_rows=block_rows,
                          interpret=(impl == "interpret"))


def fused_layernorm(x, gamma, beta, bias=None, residual=None, *,
                    eps: float = 1e-6, return_residual: bool = False,
                    impl: str = "auto", block_rows: int = 0):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.layernorm_ref(x, gamma, beta, bias, residual, eps,
                                 return_residual)
    return norm_pallas(x, gamma, beta, bias, residual, rms=False, eps=eps,
                       return_residual=return_residual,
                       block_rows=block_rows,
                       interpret=(impl == "interpret"))


def fused_rmsnorm(x, gamma, bias=None, residual=None, *, eps: float = 1e-6,
                  return_residual: bool = False, impl: str = "auto",
                  block_rows: int = 0):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.rmsnorm_ref(x, gamma, bias, residual, eps,
                               return_residual)
    return norm_pallas(x, gamma, None, bias, residual, rms=True, eps=eps,
                       return_residual=return_residual,
                       block_rows=block_rows,
                       interpret=(impl == "interpret"))


def fused_sample(logits, temperature, top_k, top_p, gumbel, *,
                 impl: str = "auto", block_rows: int = 0) -> jax.Array:
    """Fused temperature/top-k/top-p/Gumbel sampling over (B, V) logits.

    gumbel: (B, C) pre-drawn per-row Gumbel noise — C bounds the
    candidate set (no full-vocab sort).  Returns (B,) int32 tokens;
    temperature<=0 rows short-circuit to argmax.
    """
    impl = _resolve(impl)
    if impl == "xla":
        return ref.sample_ref(logits, temperature, top_k, top_p, gumbel)
    return sample_pallas(logits, temperature, top_k, top_p, gumbel,
                         block_rows=block_rows,
                         interpret=(impl == "interpret"))


def flash_attention(q, k, v, lengths=None, *, causal: bool = True,
                    scale=None, impl: str = "auto", block_q: int = 512,
                    block_k: int = 512) -> jax.Array:
    impl = _resolve(impl)
    if impl == "xla":
        return ref.flash_attention_ref(q, k, v, lengths, causal, scale)
    return flash_attention_pallas(
        q, k, v, lengths, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=(impl == "interpret"))


def flash_decode(q, k, v, lengths=None, *, scale=None,
                 num_splits: int = 4, block_k: int = 512,
                 impl: str = "auto") -> jax.Array:
    """Split-K decode attention. q: (B,H,dh); k,v: (B,KV,S,dh)."""
    impl = _resolve(impl)
    if impl == "xla":
        out = ref.flash_attention_ref(q[:, :, None, :], k, v, lengths,
                                      causal=False, scale=scale)
        return out[:, :, 0]
    return flash_decode_pallas(q, k, v, lengths, scale=scale,
                               num_splits=num_splits, block_k=block_k,
                               interpret=(impl == "interpret"))


def flash_decode_paged(q, k_pool, v_pool, block_tables, lengths=None, *,
                       scale=None, num_splits: int = 4,
                       impl: str = "auto") -> jax.Array:
    """Paged split-K decode attention over a block-table KV pool.

    q: (B,H,dh); k_pool,v_pool: (NB,BS,KV,dh); block_tables: (B,MB)."""
    impl = _resolve(impl)
    if impl == "xla":
        # materialize the logical view, then the contiguous oracle
        b, mb = block_tables.shape
        bs = k_pool.shape[1]
        k = k_pool[block_tables].reshape(
            (b, mb * bs) + k_pool.shape[2:]).swapaxes(1, 2)  # (B,KV,S,dh)
        v = v_pool[block_tables].reshape(
            (b, mb * bs) + v_pool.shape[2:]).swapaxes(1, 2)
        out = ref.flash_attention_ref(q[:, :, None, :], k, v, lengths,
                                      causal=False, scale=scale)
        return out[:, :, 0]
    return flash_decode_paged_pallas(q, k_pool, v_pool, block_tables,
                                     lengths, scale=scale,
                                     num_splits=num_splits,
                                     interpret=(impl == "interpret"))


#: the grouped matmul's row tile: the decode tick's 16 rows x 8 picks
GROUPED_ROWS = 128


def grouped_matmul(lhs, rhs, group_sizes, *, impl: str = "auto"
                   ) -> jax.Array:
    """Grouped matmul, ``ref.grouped_matmul_ref``'s semantics: lhs (m, k)
    rows sorted by group, rhs (G, k, n), group_sizes (G,) int32 -> (m, n)
    float32.

    The kernel is JAX's megablox ``gmm`` (a Pallas kernel with a custom
    VJP): its grid visits only the (row tile, group) pairs that hold rows,
    so a group with no rows costs nothing and its matrix is never read,
    and ``rhs`` is read in place (a caller may hand it every layer's
    experts and give rows to one layer's groups only).  Tiles: up to
    ``GROUPED_ROWS`` rows (m padded to a multiple; padding rows belong to
    no group and are cut off), all of k, 512 columns of n."""
    impl = _resolve(impl)
    if impl == "xla":
        return ref.grouped_matmul_ref(lhs, rhs, group_sizes)
    m, k = lhs.shape
    tm = min(GROUPED_ROWS, -(-m // 8) * 8)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = megablox.gmm(lhs, rhs, group_sizes, jnp.float32,
                       (tm, k, min(rhs.shape[2], 512)), None, None, False,
                       impl == "interpret")
    return out[:m]
