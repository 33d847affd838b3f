"""Core transformer layers in pure JAX: norms, RoPE/M-RoPE, GQA attention
(naive / chunked online-softmax / decode), FFN, embeddings.

All functions are pure; parameters are plain dicts of jnp arrays so they
stack cleanly along a leading layer dim for ``lax.scan``. Activation
sharding uses logical-axis annotations (`repro.distributed.constrain`).
Each layer runs under a ``jax.named_scope`` (``norm``, ``qkv``,
``attention``, ``expand_kv``, ``gqa_grouped``, ``out_proj``, ``ffn``,
``embed``, ``lm_head``), so every device operation in a profile names its
layer.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed import constrain, current_rules

Params = Dict[str, jax.Array]

# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis_size, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(max(in_axis_size, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, dim: int, dtype) -> Params:
    p = {"scale": jnp.ones((dim,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


@jax.named_scope("norm")
def apply_norm(cfg: ModelConfig, p: Params, x: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    """LayerNorm via the paper's Eq.1 single-pass form, or RMSNorm.

    Var(x) = E(x^2) - E(x)^2  (TurboTransformers Eq. 1): both moments come
    from one pass over the data; the Pallas kernel (kernels/layernorm.py)
    implements the same math tile-wise.
    """
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        mean_sq = jnp.mean(xf * xf, axis=-1, keepdims=True)
        var = jnp.maximum(mean_sq - mean * mean, 0.0)
        y = (xf - mean) * lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_headwise(scale: jax.Array, x: jax.Array,
                      eps: float = 1e-6) -> jax.Array:
    """Per-head qk-norm (Qwen3/OLMoE): normalize the trailing head_dim."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE + M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float
               ) -> jax.Array:
    """x: (B, S, H, Dh); positions: (B, S) int32."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                      # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (B,S,half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def mrope_sections(head_dim: int) -> Tuple[int, int, int]:
    """Split of half-dim across (temporal, height, width) à la Qwen2-VL."""
    half = head_dim // 2
    t = half - 2 * (half // 3)
    return (t, half // 3, half // 3)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float
                ) -> jax.Array:
    """M-RoPE: positions (3, B, S) — temporal/height/width streams."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                      # (half,)
    secs = mrope_sections(x.shape[-1])
    # angles per stream, then select stream per frequency-section
    angles = positions[..., None].astype(jnp.float32) * freqs   # (3,B,S,half)
    sel = jnp.repeat(jnp.arange(3), jnp.array(secs),
                     total_repeat_length=half)                  # (half,)
    angle = jnp.take_along_axis(
        jnp.moveaxis(angles, 0, -1), sel[None, None, :, None], axis=-1
    )[..., 0]                                                   # (B,S,half)
    cos = jnp.cos(angle)[:, :, None, :]
    sin = jnp.sin(angle)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def positions_for(cfg: ModelConfig, tokens_shape: Tuple[int, int],
                  num_prefix_patches: int = 0, offset: int = 0) -> jax.Array:
    """Build position ids. For M-RoPE returns (3, B, S); else (B, S).

    VLM convention (frontend stub): the first ``num_prefix_patches`` slots
    are a square image-patch grid with (t=0, h=row, w=col); text positions
    continue sequentially on all three streams.
    """
    b, s = tokens_shape
    base = jnp.arange(s, dtype=jnp.int32)[None, :] + offset
    base = jnp.broadcast_to(base, (b, s))
    if cfg.rope != "mrope":
        return base
    if num_prefix_patches:
        g = max(int(math.isqrt(num_prefix_patches)), 1)
        idx = jnp.arange(s, dtype=jnp.int32)
        is_img = idx < num_prefix_patches
        row = jnp.where(is_img, idx // g, idx - num_prefix_patches + 1)
        col = jnp.where(is_img, idx % g, idx - num_prefix_patches + 1)
        tpos = jnp.where(is_img, 0, idx - num_prefix_patches + 1)
        pos3 = jnp.stack([tpos, row, col])[:, None, :] + offset
        return jnp.broadcast_to(pos3, (3, b, s))
    return jnp.broadcast_to(base[None], (3, b, s))


def _rope_dispatch(cfg: ModelConfig, x, positions):
    if cfg.rope == "none":
        return x
    if cfg.rope == "mrope":
        return apply_mrope(x, positions, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta)


# ---------------------------------------------------------------------------
# Attention (GQA)
# ---------------------------------------------------------------------------


def init_attention(cfg: ModelConfig, key, dtype) -> Params:
    d, h, kv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, dh), d, dtype),
        "wk": dense_init(ks[1], (d, kv, dh), d, dtype),
        "wv": dense_init(ks[2], (d, kv, dh), d, dtype),
        "wo": dense_init(ks[3], (h, dh, d), h * dh, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


@jax.named_scope("qkv")
def qkv_project(cfg: ModelConfig, p: Params, x: jax.Array,
                positions: jax.Array):
    """x: (B,S,d) -> q (B,S,H,dh), k/v (B,S,KV,dh) with norm+rope applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm_headwise(p["q_norm"], q)
        k = rms_norm_headwise(p["k_norm"], k)
    q = _rope_dispatch(cfg, q, positions)
    k = _rope_dispatch(cfg, k, positions)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    return q, k, v


@jax.named_scope("expand_kv")
def expand_kv(x: jax.Array, groups: int,
              constrain_heads: bool = True) -> jax.Array:
    """GQA -> MHA: repeat each kv head `groups` times so the head dim stays
    a single flat axis. Crucial for TP: a (KV, G) grouped layout cannot be
    sharded when KV < tp_size (scores replicate, blowing up memory); the
    expanded H dim shards evenly and each device materializes only its own
    slice of the (broadcast) expansion. ``constrain_heads=False`` leaves
    the layout to propagation (decode: the cache may be sequence-sharded
    and must not be reshuffled onto heads every step). Prefill and decode
    under sharding rules use it; one-device decode contracts the grouped
    heads instead (``attention_decode``)."""
    if groups == 1:
        return x
    b, s, kv, dh = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, groups, dh))
    x = x.reshape(b, s, kv * groups, dh)
    if constrain_heads:
        return constrain(x, "batch", None, "heads", None)
    return x


@jax.named_scope("attention")
def attention_naive(cfg: ModelConfig, q, k, v, *, causal: bool = True,
                    q_offset: int = 0) -> jax.Array:
    """Reference attention. q:(B,Sq,H,dh), k/v:(B,Sk,KV,dh) -> (B,Sq,H,dh)."""
    b, sq, h, dh = q.shape
    kvh = k.shape[2]
    k = expand_kv(k, h // kvh)
    v = expand_kv(v, h // kvh)
    scale = 1.0 / math.sqrt(dh)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k) * scale
    scores = scores.astype(jnp.float32)
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(k.shape[1])[None, :]
        scores = jnp.where(kpos <= qpos, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v)
    return out


@jax.named_scope("attention")
def attention_packed(cfg: ModelConfig, q, k, v, *, q_seg, k_seg,
                     q_pos, k_pos) -> jax.Array:
    """Segment-masked causal attention for packed prefill.

    Many independent sequences are concatenated along the sequence axis:
    q:(B,Sq,H,dh) holds the fresh tokens of every segment back to back,
    k/v:(B,Sk,KV,dh) holds each segment's cached prefix followed by the
    fresh keys (the last Sq keys line up with the queries).  ``q_seg`` /
    ``k_seg`` (int32, (Sq,) / (Sk,)) carry the segment id per slot —
    padding uses a negative id — and ``q_pos`` / ``k_pos`` the absolute
    position within the owning sequence, so a chunk resuming at offset
    ``off`` packs with positions ``off..`` exactly like the
    ``prefill_suffix`` seam.  Key j is visible to query i iff both sit in
    the same segment and ``k_pos[j] <= q_pos[i]``; every query also sees
    its own fresh key so fully padded rows stay finite (their output is
    never gathered).
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    k = expand_kv(k, h // kvh)
    v = expand_kv(v, h // kvh)
    scale = 1.0 / math.sqrt(dh)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k) * scale
    scores = scores.astype(jnp.float32)
    same = q_seg[:, None] == k_seg[None, :]
    causal = k_pos[None, :] <= q_pos[:, None]
    self_key = (jnp.arange(sk)[None, :] - (sk - sq)) == \
        jnp.arange(sq)[:, None]
    mask = (same & causal) | self_key
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v)
    return out


@jax.named_scope("attention")
def attention_chunked(cfg: ModelConfig, q, k, v, *, causal: bool = True,
                      q_block: int = 512, kv_block: int = 1024,
                      q_offset: int = 0) -> jax.Array:
    """Memory-efficient online-softmax attention (flash-style in pure JAX).

    Scans q in blocks (outer lax.map) and kv in blocks (inner lax.scan with
    running max/denominator), so peak memory is O(q_block * kv_block) per
    (batch, kv_head) instead of O(S^2). This is the XLA execution path for
    long sequences and the oracle for kernels/flash_attention.py.

    ``q_offset`` places the queries ``q_offset`` positions into the key
    sequence (suffix prefill resuming after a cached prefix): query i is
    causal against keys 0 .. q_offset + i.
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    k = expand_kv(k, h // kvh)
    v = expand_kv(v, h // kvh)
    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    nq = -(-sq // q_block)
    nk = -(-sk // kv_block)
    pad_q = nq * q_block - sq
    pad_k = nk * kv_block - sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, nq, q_block, h, dh)
    kb = k.reshape(b, nk, kv_block, h, dh)
    vb = v.reshape(b, nk, kv_block, h, dh)

    def q_step(qi):
        qblk = qg[:, qi]                                   # (B,qb,H,dh)
        q_ids = qi * q_block + jnp.arange(q_block) + q_offset

        def kv_step(carry, ki):
            acc, m, l = carry
            kblk, vblk = kb[:, ki], vb[:, ki]              # (B,kb,H,dh)
            s = jnp.einsum("bqhd,bshd->bhqs", qblk, kblk) * scale
            s = s.astype(jnp.float32)
            k_ids = ki * kv_block + jnp.arange(kv_block)
            mask = k_ids[None, :] < sk   # mask padded kv
            if causal:
                mask = mask & (k_ids[None, :] <= q_ids[:, None])
            s = jnp.where(mask[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isinf(m_new)[..., None], 0.0, p)
            alpha = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
            l_new = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqs,bshd->bhqd", p.astype(q.dtype), vblk)
            acc_new = acc * alpha[..., None].astype(acc.dtype) + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, h, q_block, dh), q.dtype)
        m0 = jnp.full((b, h, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, q_block), jnp.float32)
        if causal:
            # only kv blocks that intersect the causal triangle
            n_used = jnp.minimum(
                nk, (qi * q_block + q_block + q_offset + kv_block - 1)
                // kv_block)
        (acc, m, l), _ = lax.scan(
            lambda c, ki: lax.cond(
                (ki < n_used) if causal else True,
                lambda: kv_step(c, ki), lambda: (c, None)),
            (acc0, m0, l0), jnp.arange(nk))
        l = jnp.maximum(l, 1e-30)
        out = acc / l[..., None].astype(acc.dtype)
        return jnp.moveaxis(out, 1, 2)                     # (B,qb,H,dh)

    out = lax.map(q_step, jnp.arange(nq))                 # (nq,B,qb,H,dh)
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * q_block, h, dh)
    return out[:, :sq]


@jax.named_scope("attention")
def attention_chunked_train(cfg: ModelConfig, q, k, v, *,
                            causal: bool = True, q_block: int = 512
                            ) -> jax.Array:
    """Training-path blockwise attention: each q block is wrapped in
    jax.checkpoint, so the backward pass rematerializes one block's
    (q_block x S) score tile at a time instead of saving every softmax
    intermediate of an online-softmax scan. Peak activation memory is
    O(q_block * S) per (batch, kv_head) regardless of layer count.
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    k = expand_kv(k, h // kvh)
    v = expand_kv(v, h // kvh)
    q_block = min(q_block, sq)
    nq = -(-sq // q_block)
    pad_q = nq * q_block - sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, nq, q_block, h, dh)

    @jax.checkpoint
    def q_step(qblk, qi):
        s = jnp.einsum("bqhd,bshd->bhqs", qblk, k) * scale
        s = s.astype(jnp.float32)
        if causal:
            q_ids = qi * q_block + jnp.arange(q_block)
            mask = jnp.arange(sk)[None, :] <= q_ids[:, None]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(qblk.dtype)
        out = jnp.einsum("bhqs,bshd->bqhd", w, v)
        return out                                        # (b,qb,H,dh)

    out = lax.map(lambda qi: q_step(qg[:, qi], qi), jnp.arange(nq))
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * q_block, h, dh)
    return out[:, :sq]


@jax.named_scope("attention")
def attention_decode(cfg: ModelConfig, q, k_cache, v_cache, cache_len
                     ) -> jax.Array:
    """Decode attention: q (B,1,H,dh) against cache (B,S,KV,dh).

    ``cache_len`` (B,) masks positions >= current length. Without sharding
    rules (one device) each KV head's keys and values are contracted with
    its G = H/KV query heads in place (``gqa_grouped``). Under rules the
    cache is expanded to H heads first (``expand_kv``), so heads shard
    even when KV < tp_size; the kv sequence dim may be sharded over
    'model' (context parallelism) — GSPMD inserts the partial
    softmax-max/sum collectives automatically.
    """
    b, _, h, dh = q.shape
    kvh = k_cache.shape[2]
    scale = 1.0 / math.sqrt(dh)
    valid = jnp.arange(k_cache.shape[1])[None, :] < cache_len[:, None]
    rules = current_rules()
    if rules is None:
        return _decode_grouped(q, k_cache, v_cache, valid, scale)
    k_full = expand_kv(k_cache, h // kvh, constrain_heads=False)
    v_full = expand_kv(v_cache, h // kvh, constrain_heads=False)
    q3 = q[:, 0]
    if rules.rules.get("kv_dh_shard"):
        # head-dim-sharded KV cache: keep q on the SAME dh sharding so the
        # q.k contraction stays a local partial dot + psum of the small
        # (B,H,S) scores — instead of all-gathering the 1GB-per-layer
        # cache to match q's head sharding.
        q3 = constrain(q3, "batch", None, "act_dh")
    s = jnp.einsum("bhd,bshd->bhs", q3, k_full) * scale
    s = s.astype(jnp.float32)
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhs,bshd->bhd", w, v_full)
    if rules.rules.get("kv_dh_shard"):
        # keep the PV product dh-sharded too (V stays local); the output
        # projection contracts (h, dh) with a psum instead of gathering V
        out = constrain(out, "batch", None, "act_dh")
    return out[:, None]


@jax.named_scope("gqa_grouped")
def _decode_grouped(q, k_cache, v_cache, valid, scale) -> jax.Array:
    """``attention_decode`` without the GQA copy: query head h = kv*G + g
    (``expand_kv``'s order) reads its KV head's cache directly."""
    b, _, h, dh = q.shape
    kvh = k_cache.shape[2]
    qg = q[:, 0].reshape(b, kvh, h // kvh, dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache) * scale
    s = s.astype(jnp.float32)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v_cache)
    return out.reshape(b, 1, h, dh)


@jax.named_scope("out_proj")
def attention_output(p: Params, attn: jax.Array) -> jax.Array:
    out = jnp.einsum("bshk,hkd->bsd", attn, p["wo"])
    return constrain(out, "batch", None, "embed")


# ---------------------------------------------------------------------------
# Feed-forward
# ---------------------------------------------------------------------------


def init_ffn(cfg: ModelConfig, key, dtype, d_ff: Optional[int] = None
             ) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "swiglu":
        return {
            "w_gate": dense_init(ks[0], (d, f), d, dtype),
            "w_up": dense_init(ks[1], (d, f), d, dtype),
            "w_down": dense_init(ks[2], (f, d), f, dtype),
        }
    return {
        "w_up": dense_init(ks[0], (d, f), d, dtype),
        "b_up": jnp.zeros((f,), dtype),
        "w_down": dense_init(ks[1], (f, d), f, dtype),
        "b_down": jnp.zeros((d,), dtype),
    }


@jax.named_scope("ffn")
def apply_ffn(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.act == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
        h = jax.nn.silu(g) * u
        h = constrain(h, "batch", None, "mlp")
        out = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    else:
        h = jnp.einsum("bsd,df->bsf", x, p["w_up"]) + p["b_up"]
        h = jax.nn.gelu(h)
        h = constrain(h, "batch", None, "mlp")
        out = jnp.einsum("bsf,fd->bsd", h, p["w_down"]) + p["b_down"]
    return constrain(out, "batch", None, "embed")


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def init_embedding(cfg: ModelConfig, key, dtype) -> Params:
    ks = jax.random.split(key, 3)
    n_embed = max(cfg.num_codebooks, 1)
    p = {"tok": dense_init(ks[0], (n_embed, cfg.vocab_size, cfg.d_model),
                           cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(
            ks[1], (n_embed, cfg.d_model, cfg.vocab_size), cfg.d_model, dtype)
    return p


@jax.named_scope("embed")
def embed_tokens(cfg: ModelConfig, p: Params, tokens: jax.Array
                 ) -> jax.Array:
    """tokens: (B,S) or (B,K,S) for multi-codebook audio -> (B,S,d)."""
    if tokens.ndim == 2:
        h = jnp.take(p["tok"][0], tokens, axis=0)
    else:
        # sum codebook embeddings per frame (MusicGen)
        embs = jax.vmap(lambda tab, t: jnp.take(tab, t, axis=0),
                        in_axes=(0, 1), out_axes=1)(p["tok"], tokens)
        h = jnp.sum(embs, axis=1)
    return constrain(h, "batch", None, "embed")


@jax.named_scope("lm_head")
def lm_logits(cfg: ModelConfig, p: Params, h: jax.Array) -> jax.Array:
    """h: (B,S,d) -> logits (B,S,V) or (B,K,S,V) for audio."""
    if cfg.tie_embeddings:
        tables = p["tok"]                                # (K,V,d)
        logits = jnp.einsum("bsd,kvd->bksv", h, tables)
    else:
        logits = jnp.einsum("bsd,kdv->bksv", h, p["head"])
    if cfg.num_codebooks:
        return constrain(logits, "batch", None, None, "vocab")
    logits = logits[:, 0]
    return constrain(logits, "batch", None, "vocab")
