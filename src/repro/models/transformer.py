"""Composable decoder model covering all assigned families.

Layers are stacked along a leading ``L`` dim and executed with
``lax.scan`` (compact HLO even for 126-layer models; lets XLA overlap
per-layer collectives with compute). Families:

  dense / vlm / audio : [norm -> GQA attn -> norm -> FFN] x L
  moe                 : [norm -> GQA attn -> norm -> MoE] x L
  ssm                 : [norm -> mamba] x L
  hybrid (Zamba-style): mamba backbone + ONE weight-shared attention+FFN
                        block applied after every ``attn_every`` layers

Three entry points: ``forward_train`` (loss), ``prefill`` (build cache),
``decode_step`` (one token with cache). Caches are functional pytrees that
the engine donates for in-place updates.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed import constrain
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

Params = Dict[str, Any]


@dataclass(frozen=True)
class ModelRuntime:
    """Execution knobs independent of the architecture."""
    attn_impl: str = "auto"        # naive | chunked | auto
    q_block: int = 512
    kv_block: int = 1024
    remat: str = "none"            # none | full | dots
    chunked_threshold: int = 2048  # auto: chunked when S >= this
    aux_loss_weight: float = 0.01
    # Megatron-style sequence parallelism for the residual stream: the
    # scan-over-layers carry (saved for backward) is sharded over 'model'
    # on its sequence dim; GSPMD inserts the gather/scatter at attention
    # boundaries. Trades ICI traffic for L*B*S*d activation memory / TP.
    seq_shard: bool = False
    # Decode: unroll the layer loop instead of lax.scan. The scan form
    # double-buffers the full KV cache (xs + ys copies); the unrolled form
    # updates each layer's slice in place via donated-buffer aliasing —
    # bigger HLO, ~3x lower decode temp memory.
    unroll_decode: bool = False


def _residual_constrain(rt: ModelRuntime, h: jax.Array) -> jax.Array:
    if rt.seq_shard:
        return constrain(h, "batch", "act_seq", "embed")
    return constrain(h, "batch", None, "embed")


DEFAULT_RUNTIME = ModelRuntime()


def _attn(cfg: ModelConfig, rt: ModelRuntime, q, k, v,
          q_offset: int = 0):
    """Prefill attention dispatch.  ``q_offset > 0`` is the suffix-prefill
    case: queries sit ``q_offset`` positions into the key sequence (k/v
    carry the cached prefix in front); impl selection then keys on the
    total attended length so a cache hit takes the same memory-bounded
    path its cache-cold twin would."""
    s = k.shape[1] if q_offset else q.shape[1]
    impl = rt.attn_impl
    if impl == "auto":
        impl = "chunked" if s >= rt.chunked_threshold else "naive"
    if impl == "chunked_train":
        if q_offset:
            raise ValueError("chunked_train is a training-path impl; "
                             "suffix prefill supports naive/chunked")
        return L.attention_chunked_train(cfg, q, k, v, causal=True,
                                         q_block=rt.q_block)
    if impl == "chunked":
        return L.attention_chunked(cfg, q, k, v, causal=True,
                                   q_block=rt.q_block, kv_block=rt.kv_block,
                                   q_offset=q_offset)
    return L.attention_naive(cfg, q, k, v, causal=True, q_offset=q_offset)


def _num_shared_apps(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.attn_every if cfg.attn_every else 0


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array,
                param_dtype: Optional[str] = None) -> Params:
    dtype = jnp.dtype(param_dtype or cfg.dtype)
    k_embed, k_layers, k_shared, k_final = jax.random.split(key, 4)
    params: Params = {"embed": L.init_embedding(cfg, k_embed, dtype)}

    def init_block(k) -> Params:
        if cfg.family in ("ssm", "hybrid"):
            k1, k2 = jax.random.split(k)
            return {"norm1": L.init_norm(cfg, cfg.d_model, dtype),
                    "mamba": S.init_mamba(cfg, k2, dtype)}
        k1, k2 = jax.random.split(k)
        blk = {"norm1": L.init_norm(cfg, cfg.d_model, dtype),
               "attn": L.init_attention(cfg, k1, dtype),
               "norm2": L.init_norm(cfg, cfg.d_model, dtype)}
        if cfg.family == "moe":
            blk["moe"] = M.init_moe(cfg, k2, dtype)
        else:
            blk["ffn"] = L.init_ffn(cfg, k2, dtype)
        return blk

    keys = jax.random.split(k_layers, cfg.num_layers)
    blocks = [init_block(k) for k in keys]
    params["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)

    if cfg.family == "hybrid":
        k1, k2 = jax.random.split(k_shared)
        params["shared"] = {
            "norm1": L.init_norm(cfg, cfg.d_model, dtype),
            "attn": L.init_attention(cfg, k1, dtype),
            "norm2": L.init_norm(cfg, cfg.d_model, dtype),
            "ffn": L.init_ffn(cfg, k2, dtype),
        }
    params["final_norm"] = L.init_norm(cfg, cfg.d_model, dtype)
    return params


def abstract_params(cfg: ModelConfig, param_dtype: Optional[str] = None
                    ) -> Params:
    """ShapeDtypeStruct param tree (no allocation) for dry-runs."""
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0), param_dtype))


# ---------------------------------------------------------------------------
# Embedding frontends
# ---------------------------------------------------------------------------


def embed_inputs(cfg: ModelConfig, params: Params, tokens: jax.Array,
                 embeds_override: Optional[jax.Array] = None) -> jax.Array:
    h = L.embed_tokens(cfg, params["embed"], tokens)
    if embeds_override is not None:
        # VLM stub frontend: precomputed patch embeddings occupy the first
        # N_img sequence slots.
        n_img = embeds_override.shape[1]
        h = lax.dynamic_update_slice(
            h, embeds_override.astype(h.dtype), (0, 0, 0))
    return h


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def _block_attn_full(cfg, rt, blk, h, positions, collect_cache,
                     prefix_kv=None, q_offset: int = 0):
    """One attention block over a full (or suffix) sequence.  With
    ``prefix_kv`` = (pk, pv), attention runs over [cached prefix, fresh
    k/v] at query offset ``q_offset`` (suffix prefill); the collected
    cache parts stay suffix-only — the prefix is already in the pool."""
    hn = L.apply_norm(cfg, blk["norm1"], h)
    q, k, v = L.qkv_project(cfg, blk["attn"], hn, positions)
    if prefix_kv is not None:
        pk, pv = prefix_kv
        k_all = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
    else:
        k_all, v_all = k, v
    attn = _attn(cfg, rt, q, k_all, v_all, q_offset=q_offset)
    h = h + L.attention_output(blk["attn"], attn)
    hn2 = L.apply_norm(cfg, blk["norm2"], h)
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "moe":
        out, aux = M.apply_moe(cfg, blk["moe"], hn2)
    else:
        out = L.apply_ffn(cfg, blk["ffn"], hn2)
    h = _residual_constrain(rt, h + out)
    cache = (k, v) if collect_cache else None
    return h, aux, cache


def _maybe_remat(fn, rt: ModelRuntime):
    if rt.remat == "none":
        return fn
    if rt.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward_hidden(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   *, rt: ModelRuntime = DEFAULT_RUNTIME,
                   embeds_override: Optional[jax.Array] = None,
                   num_prefix_patches: int = 0,
                   collect_cache: bool = False):
    """Returns (h_final, aux_loss, cache_parts).

    cache_parts (when collect_cache): per-family pytree of per-layer states
    stacked on a leading L dim (attention k/v or mamba conv/ssm states).
    """
    h = embed_inputs(cfg, params, tokens, embeds_override)
    bsz, seq = h.shape[:2]
    positions = L.positions_for(cfg, (bsz, seq), num_prefix_patches)

    if cfg.family in ("ssm", "hybrid"):
        return _forward_hidden_ssm(cfg, params, h, positions, rt,
                                   collect_cache)

    def block(carry, blk):
        h, aux = carry
        h, aux_l, cache = _block_attn_full(cfg, rt, blk, h, positions,
                                           collect_cache)
        return (h, aux + aux_l), cache

    block = _maybe_remat(block, rt)
    (h, aux), caches = lax.scan(block, (h, jnp.zeros((), jnp.float32)),
                                params["layers"])
    h = L.apply_norm(cfg, params["final_norm"], h)
    cache_parts = None
    if collect_cache:
        cache_parts = {"k": caches[0], "v": caches[1]}
    return h, aux, cache_parts


def _forward_hidden_ssm(cfg, params, h, positions, rt, collect_cache):
    """Mamba backbone; hybrid adds the weight-shared attention block."""
    n_apps = _num_shared_apps(cfg)
    shared = params.get("shared")

    def shared_block(h, collect):
        hn = L.apply_norm(cfg, shared["norm1"], h)
        q, k, v = L.qkv_project(cfg, shared["attn"], hn, positions)
        attn = _attn(cfg, rt, q, k, v)
        h = h + L.attention_output(shared["attn"], attn)
        hn2 = L.apply_norm(cfg, shared["norm2"], h)
        h = h + L.apply_ffn(cfg, shared["ffn"], hn2)
        return h, (k, v) if collect else None

    def block(carry, xs):
        h, layer_idx, shared_kv, app_idx = carry
        blk = xs
        hn = L.apply_norm(cfg, blk["norm1"], h)
        out, conv_st, ssm_st = S.apply_mamba_with_state(
            cfg, blk["mamba"], hn, None)
        h = _residual_constrain(rt, h + out)
        if cfg.attn_every:
            def do_attn(h, shared_kv, app_idx):
                h, kv = shared_block(h, collect_cache)
                if collect_cache:
                    k, v = kv
                    shared_kv = (
                        lax.dynamic_update_slice(
                            shared_kv[0], k[None].astype(shared_kv[0].dtype),
                            (app_idx, 0, 0, 0, 0)),
                        lax.dynamic_update_slice(
                            shared_kv[1], v[None].astype(shared_kv[1].dtype),
                            (app_idx, 0, 0, 0, 0)))
                return h, shared_kv, app_idx + 1

            trigger = (layer_idx % cfg.attn_every) == cfg.attn_every - 1
            h, shared_kv, app_idx = lax.cond(
                trigger, do_attn,
                lambda h, skv, ai: (h, skv, ai),
                h, shared_kv, app_idx)
        ys = (conv_st, ssm_st) if collect_cache else None
        return (h, layer_idx + 1, shared_kv, app_idx), ys

    bsz, seq = h.shape[:2]
    if cfg.attn_every and collect_cache:
        kv, dh = cfg.num_kv_heads, cfg.head_dim
        shared_kv0 = (jnp.zeros((n_apps, bsz, seq, kv, dh), h.dtype),
                      jnp.zeros((n_apps, bsz, seq, kv, dh), h.dtype))
    else:
        shared_kv0 = (jnp.zeros((), h.dtype),) * 2

    block = _maybe_remat(block, rt)
    carry0 = (h, jnp.zeros((), jnp.int32), shared_kv0,
              jnp.zeros((), jnp.int32))
    (h, _, shared_kv, _), states = lax.scan(block, carry0, params["layers"])
    h = L.apply_norm(cfg, params["final_norm"], h)
    cache_parts = None
    if collect_cache:
        cache_parts = {"conv": states[0], "state": states[1]}
        if cfg.attn_every:
            cache_parts["shared_k"] = shared_kv[0]
            cache_parts["shared_v"] = shared_kv[1]
    return h, jnp.zeros((), jnp.float32), cache_parts


# ---------------------------------------------------------------------------
# Training loss
# ---------------------------------------------------------------------------


def forward_train(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
                  *, rt: ModelRuntime = DEFAULT_RUNTIME):
    """batch: tokens (B,S) or (B,K,S); labels same; optional embeds_override.

    Returns (loss, metrics dict).
    """
    tokens = batch["tokens"]
    labels = batch["labels"]
    h, aux, _ = forward_hidden(
        cfg, params, tokens, rt=rt,
        embeds_override=batch.get("embeds_override"),
        num_prefix_patches=(batch["embeds_override"].shape[1]
                            if batch.get("embeds_override") is not None
                            else 0))
    logits = L.lm_logits(cfg, params["embed"], h).astype(jnp.float32)
    # dense: (B,S,V) vs (B,S); audio: (B,K,S,V) vs (B,K,S)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    total = loss + rt.aux_loss_weight * aux
    return total, {"loss": loss, "aux_loss": aux,
                   "perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def make_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Allocate an empty decode cache pytree."""
    Lc = cfg.num_layers
    cache: Dict[str, jax.Array] = {
        "len": jnp.zeros((batch,), jnp.int32),
        # rope position of the next token = len + pos_offset (M-RoPE text
        # positions restart after the image-patch prefix).
        "pos_offset": jnp.zeros((batch,), jnp.int32)}
    if cfg.family in ("ssm", "hybrid"):
        conv_s, state_s = S.ssm_state_shapes(cfg, batch)
        cache["conv"] = jnp.zeros((Lc,) + conv_s, dtype)
        cache["state"] = jnp.zeros((Lc,) + state_s, jnp.float32)
        if cfg.attn_every:
            n_apps = _num_shared_apps(cfg)
            kv, dh = cfg.num_kv_heads, cfg.head_dim
            cache["shared_k"] = jnp.zeros(
                (n_apps, batch, max_len, kv, dh), dtype)
            cache["shared_v"] = jnp.zeros(
                (n_apps, batch, max_len, kv, dh), dtype)
    else:
        kv, dh = cfg.num_kv_heads, cfg.head_dim
        cache["k"] = jnp.zeros((Lc, batch, max_len, kv, dh), dtype)
        cache["v"] = jnp.zeros((Lc, batch, max_len, kv, dh), dtype)
    return cache


def cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: make_cache(cfg, batch, max_len, dtype))


def make_paged_cache(cfg: ModelConfig, batch: int, num_blocks: int,
                     block_size: int, max_blocks: int,
                     dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Allocate an empty *paged* decode cache.

    K/V live in one pool of ``num_blocks`` fixed-size token blocks shared
    by every sequence; ``block_tables`` (B, max_blocks) maps each row's
    logical block index to a physical pool block.  Table entries default
    to 0 — the reserved trash block — so unassigned logical blocks read
    (masked) garbage and absorb stray writes instead of corrupting live
    sequences.  Unlike the contiguous layout there is no per-row
    ``max_len`` stripe: a row grows by appending table entries, and the
    footprint is bounded by the pool, not by rows x horizon.
    """
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError("paged KV applies to attention-family caches "
                         "only (SSM state is O(1) per sequence)")
    kv, dh = cfg.num_kv_heads, cfg.head_dim
    return {
        "len": jnp.zeros((batch,), jnp.int32),
        "pos_offset": jnp.zeros((batch,), jnp.int32),
        "k": jnp.zeros((cfg.num_layers, num_blocks, block_size, kv, dh),
                       dtype),
        "v": jnp.zeros((cfg.num_layers, num_blocks, block_size, kv, dh),
                       dtype),
        "block_tables": jnp.zeros((batch, max_blocks), jnp.int32),
    }


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            max_len: int, rt: ModelRuntime = DEFAULT_RUNTIME,
            embeds_override: Optional[jax.Array] = None,
            true_lengths: Optional[jax.Array] = None,
            cache_dtype=jnp.bfloat16):
    """Process a full prompt; returns (last-token logits, populated cache).

    ``true_lengths`` (B,) supports right-padded ragged batches for
    attention-family models: logits are gathered at each request's own last
    token and the cache length is per-request (trailing pad K/V is masked
    out by decode attention). SSM/hybrid models carry state across pad
    positions, so ragged prefill is only valid for attention families.
    """
    seq = tokens.shape[-1]
    bsz = tokens.shape[0]
    if true_lengths is not None and cfg.family in ("ssm", "hybrid"):
        raise ValueError("ragged prefill unsupported for SSM state "
                         "(group requests by exact length instead)")
    h, _, parts = forward_hidden(
        cfg, params, tokens, rt=rt, embeds_override=embeds_override,
        num_prefix_patches=(embeds_override.shape[1]
                            if embeds_override is not None else 0),
        collect_cache=True)
    if true_lengths is None:
        h_last = h[:, -1:]
    else:
        idx = (true_lengths - 1).astype(jnp.int32)
        h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
    logits = L.lm_logits(cfg, params["embed"], h_last)
    cache = make_cache(cfg, bsz, max_len, cache_dtype)
    cache["len"] = (jnp.full((bsz,), seq, jnp.int32) if true_lengths is None
                    else true_lengths.astype(jnp.int32))
    if cfg.rope == "mrope" and embeds_override is not None:
        n_img = embeds_override.shape[1]
        cache["pos_offset"] = jnp.full((bsz,), -(n_img - 1), jnp.int32)
    if cfg.family in ("ssm", "hybrid"):
        cache["conv"] = parts["conv"].astype(cache["conv"].dtype)
        cache["state"] = parts["state"]
        if cfg.attn_every:
            pad = max_len - seq
            cache["shared_k"] = jnp.pad(
                parts["shared_k"].astype(cache_dtype),
                ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            cache["shared_v"] = jnp.pad(
                parts["shared_v"].astype(cache_dtype),
                ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        pad = max_len - seq
        cache["k"] = jnp.pad(parts["k"].astype(cache_dtype),
                             ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        cache["v"] = jnp.pad(parts["v"].astype(cache_dtype),
                             ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    if cfg.num_codebooks:
        return logits[:, :, 0], cache       # (B,K,V)
    return logits[:, 0], cache              # (B,V)


def prefill_suffix(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   prefix_k: jax.Array, prefix_v: jax.Array, *,
                   prefix_len: int, rt: ModelRuntime = DEFAULT_RUNTIME,
                   true_lengths: Optional[jax.Array] = None,
                   cache_dtype=jnp.bfloat16):
    """Resume a prompt pass after ``prefix_len`` cached tokens (the
    prefix-sharing KV cache's suffix prefill, and the per-chunk pass of
    chunked prefill — each chunk resumes at the previous chunk's seam,
    with the prefix KV read back from the request's own paged blocks).

    ``tokens`` (B, S_suffix) holds the right-padded *uncached* remainder
    of each prompt; ``prefix_k``/``prefix_v`` (L, B, prefix_len, KV, dh)
    is the shared prefix KV gathered from the paged pool.  Queries run at
    positions ``prefix_len ..`` (the paged path's position offset) and
    each layer attends over [prefix, suffix] with the causal mask
    continued across the seam, so the result is the same computation a
    full-prompt prefill would have done for the suffix positions — only
    the prefix's quadratic work is skipped.  ``prefix_len == 0`` (the
    first chunk of a cold prompt) degenerates to a plain prompt pass:
    the empty prefix arrays are ignored rather than concatenated, so the
    compiled HLO matches the cold path exactly.

    Returns ``(last-token logits, {"k", "v"})`` where k/v are the
    *suffix-only* cache parts (L, B, S_suffix, KV, dh): the caller
    scatters them into its own (copy-on-write) blocks; the shared prefix
    blocks are never written.  Attention families only — the paged
    serving path this feeds already excludes SSM state and codebook
    models.
    """
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError("suffix prefill resumes attention KV only; SSM "
                         "state cannot restart mid-sequence")
    if cfg.num_codebooks:
        raise ValueError("suffix prefill does not support codebook models")
    if prefix_len < 0:
        raise ValueError(f"prefix_len must be >= 0, got {prefix_len}")
    bsz, seq = tokens.shape
    h = embed_inputs(cfg, params, tokens)
    positions = L.positions_for(cfg, (bsz, seq), 0, offset=prefix_len)
    use_prefix = prefix_len > 0

    def block(carry, xs):
        h = carry
        blk, pk, pv = xs
        h, _, kv = _block_attn_full(cfg, rt, blk, h, positions, True,
                                    prefix_kv=(pk, pv) if use_prefix
                                    else None,
                                    q_offset=prefix_len)
        return h, kv

    h, (k_suf, v_suf) = lax.scan(
        block, h, (params["layers"], prefix_k, prefix_v))
    h = L.apply_norm(cfg, params["final_norm"], h)
    if true_lengths is None:
        h_last = h[:, -1:]
    else:
        idx = (true_lengths - 1).astype(jnp.int32)
        h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
    logits = L.lm_logits(cfg, params["embed"], h_last)
    return logits[:, 0], {"k": k_suf.astype(cache_dtype),
                          "v": v_suf.astype(cache_dtype)}


def prefill_packed(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   seg_ids: jax.Array, positions: jax.Array,
                   last_idx: jax.Array, prefix_k: jax.Array,
                   prefix_v: jax.Array, prefix_seg: jax.Array,
                   prefix_pos: jax.Array, *,
                   rt: ModelRuntime = DEFAULT_RUNTIME,
                   cache_dtype=jnp.bfloat16):
    """Prefill many independent sequences in ONE dispatch.

    ``tokens`` (1, P) concatenates every segment's fresh (uncached)
    tokens back to back, right-padded to the pack bucket; ``seg_ids``
    (P,) carries the owning segment per slot (negative = padding) and
    ``positions`` (P,) the absolute position within that segment — a
    chunk resuming after ``off`` cached tokens contributes positions
    ``off..``, composing with the ``prefill_suffix`` position-offset
    seam so prefix-cache hits and resumable chunks pack alongside cold
    prompts.  ``prefix_k``/``prefix_v`` (L, P_pre, KV, dh) concatenate
    every segment's cached prefix KV (gathered from the paged pool) with
    ``prefix_seg``/``prefix_pos`` (P_pre,) labelling those key slots the
    same way; ``P_pre == 0`` is the all-cold case and skips the concat so
    the compiled HLO matches.  Attention is causal *within* segments
    (`attention_packed`), so each segment computes exactly what its own
    sequential prefill would have.

    Returns ``(logits, {"k", "v"})``: ``logits`` (N, V) gathered at
    ``last_idx`` (N,) — each segment's last fresh token, padded entries
    point anywhere harmless — and suffix-only cache parts
    (L, P, KV, dh) for the caller to scatter into per-segment paged
    blocks.  Attention families only, like ``prefill_suffix``.
    """
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError("packed prefill is attention-only; SSM state "
                         "rolls through padding and cannot pack")
    if cfg.num_codebooks:
        raise ValueError("packed prefill does not support codebook models")
    h = embed_inputs(cfg, params, tokens)
    pos_in = positions[None]                                  # (1, P)
    if cfg.rope == "mrope":
        pos_in = jnp.broadcast_to(pos_in[None], (3,) + pos_in.shape)
    use_prefix = prefix_k.shape[1] > 0
    if use_prefix:
        k_seg = jnp.concatenate([prefix_seg, seg_ids])
        k_pos = jnp.concatenate([prefix_pos, positions])
    else:
        k_seg, k_pos = seg_ids, positions

    def block(carry, xs):
        h = carry
        blk, pk, pv = xs
        hn = L.apply_norm(cfg, blk["norm1"], h)
        q, k, v = L.qkv_project(cfg, blk["attn"], hn, pos_in)
        if use_prefix:
            k_all = jnp.concatenate([pk[None].astype(k.dtype), k], axis=1)
            v_all = jnp.concatenate([pv[None].astype(v.dtype), v], axis=1)
        else:
            k_all, v_all = k, v
        attn = L.attention_packed(cfg, q, k_all, v_all, q_seg=seg_ids,
                                  k_seg=k_seg, q_pos=positions, k_pos=k_pos)
        h = h + L.attention_output(blk["attn"], attn)
        hn2 = L.apply_norm(cfg, blk["norm2"], h)
        if cfg.family == "moe":
            out, _ = M.apply_moe(cfg, blk["moe"], hn2)
        else:
            out = L.apply_ffn(cfg, blk["ffn"], hn2)
        h = _residual_constrain(rt, h + out)
        return h, (k[0], v[0])

    h, (k_suf, v_suf) = lax.scan(
        block, h, (params["layers"], prefix_k, prefix_v))
    h = L.apply_norm(cfg, params["final_norm"], h)
    h_last = h[:, last_idx.astype(jnp.int32)]                 # (1, N, d)
    logits = L.lm_logits(cfg, params["embed"], h_last)
    return logits[0], {"k": k_suf.astype(cache_dtype),
                       "v": v_suf.astype(cache_dtype)}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def decode_step(cfg: ModelConfig, params: Params, cache: Dict[str, Any],
                tokens_t: jax.Array, *, rt: ModelRuntime = DEFAULT_RUNTIME):
    """One decode step.

    tokens_t: (B,) or (B,K) for audio. Uses cache['len'] as the write
    position (per-batch uniform). Returns (logits (B,V)|(B,K,V), cache).
    """
    bsz = tokens_t.shape[0]
    toks = tokens_t[:, None] if tokens_t.ndim == 1 else tokens_t[..., None]
    h = L.embed_tokens(cfg, params["embed"], toks)        # (B,1,d)
    pos = cache["len"] + cache["pos_offset"]              # (B,)
    positions = pos[:, None]
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(positions[None], (3, bsz, 1))

    if cfg.family in ("ssm", "hybrid"):
        new_cache, h = _decode_ssm(cfg, params, cache, h, positions, rt)
    elif "block_tables" in cache:
        new_cache, h = _decode_attn_paged(cfg, params, cache, h,
                                          positions, rt)
    else:
        new_cache, h = _decode_attn(cfg, params, cache, h, positions, rt)
    h = L.apply_norm(cfg, params["final_norm"], h)
    logits = L.lm_logits(cfg, params["embed"], h)
    new_cache["len"] = cache["len"] + 1
    if cfg.num_codebooks:
        return logits[:, :, 0], new_cache
    return logits[:, 0], new_cache


@jax.named_scope("kv_write")
def _write_kv(k_cache, v_cache, k, v, pos):
    """k_cache: (B,S,KV,dh); k: (B,1,KV,dh); pos: (B,) uniform write index."""
    def upd(cache, new):
        return jax.vmap(
            lambda c, n, p: lax.dynamic_update_slice(c, n, (p, 0, 0)))(
                cache, new.astype(cache.dtype), pos)
    return upd(k_cache, k), upd(v_cache, v)


def _decode_attn(cfg, params, cache, h, positions, rt):
    if rt.unroll_decode:
        return _decode_attn_unrolled(cfg, params, cache, h, positions, rt)

    def block(carry, xs):
        h = carry
        blk, k_c, v_c = xs
        hn = L.apply_norm(cfg, blk["norm1"], h)
        q, k, v = L.qkv_project(cfg, blk["attn"], hn, positions)
        k_c, v_c = _write_kv(k_c, v_c, k, v, cache["len"])
        attn = L.attention_decode(cfg, q, k_c, v_c, cache["len"] + 1)
        h = h + L.attention_output(blk["attn"], attn)
        hn2 = L.apply_norm(cfg, blk["norm2"], h)
        if cfg.family == "moe":
            out, _ = M.apply_moe(cfg, blk["moe"], hn2)
        else:
            out = L.apply_ffn(cfg, blk["ffn"], hn2)
        return h + out, (k_c, v_c)

    h, (k_new, v_new) = lax.scan(
        block, h, (params["layers"], cache["k"], cache["v"]))
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = k_new, v_new
    return new_cache, h


@jax.named_scope("kv_write")
def _paged_write_kv(k_pool, v_pool, k, v, tables, pos):
    """Scatter one new token per row into the paged pool.

    k_pool: (NB, BS, KV, dh); k: (B, 1, KV, dh); tables: (B, MB);
    pos: (B,) logical write position.  Rows whose position runs past the
    table (a finished row frozen at its final length) are clamped — their
    table entry is the trash block by then, so the write is absorbed
    without touching any live sequence's blocks.
    """
    nb, bs = k_pool.shape[0], k_pool.shape[1]
    mb = tables.shape[1]
    pos_c = jnp.minimum(pos, mb * bs - 1)
    blk = jnp.take_along_axis(tables, (pos_c // bs)[:, None], axis=1)[:, 0]
    flat = blk * bs + pos_c % bs                          # (B,)

    def upd(pool, new):
        fp = pool.reshape((nb * bs,) + pool.shape[2:])
        fp = fp.at[flat].set(new[:, 0].astype(pool.dtype))
        return fp.reshape(pool.shape)
    return upd(k_pool, k), upd(v_pool, v)


@jax.named_scope("paged_gather")
def _paged_gather(pool, tables):
    """Materialize each row's logical KV view from the pool:
    (NB, BS, KV, dh) x (B, MB) -> (B, MB*BS, KV, dh).  Positions beyond a
    row's length land in trash/unassigned blocks and are masked by
    ``attention_decode``'s length mask."""
    g = pool[tables]                                      # (B,MB,BS,KV,dh)
    b, mb, bs = g.shape[:3]
    return g.reshape((b, mb * bs) + g.shape[3:])


def _decode_attn_paged(cfg, params, cache, h, positions, rt):
    tables = cache["block_tables"]

    def block(carry, xs):
        h = carry
        blk, k_p, v_p = xs
        hn = L.apply_norm(cfg, blk["norm1"], h)
        q, k, v = L.qkv_project(cfg, blk["attn"], hn, positions)
        k_p, v_p = _paged_write_kv(k_p, v_p, k, v, tables, cache["len"])
        k_seq = _paged_gather(k_p, tables)
        v_seq = _paged_gather(v_p, tables)
        attn = L.attention_decode(cfg, q, k_seq, v_seq, cache["len"] + 1)
        h = h + L.attention_output(blk["attn"], attn)
        hn2 = L.apply_norm(cfg, blk["norm2"], h)
        if cfg.family == "moe":
            out, _ = M.apply_moe(cfg, blk["moe"], hn2)
        else:
            out = L.apply_ffn(cfg, blk["ffn"], hn2)
        return h + out, (k_p, v_p)

    h, (k_new, v_new) = lax.scan(
        block, h, (params["layers"], cache["k"], cache["v"]))
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = k_new, v_new
    return new_cache, h


def _layer_block(cfg, rt, blk, cache, h, positions, k_c, v_c):
    """One unrolled decode layer; returns (h, updated k_c, v_c)."""
    hn = L.apply_norm(cfg, blk["norm1"], h)
    q, k, v = L.qkv_project(cfg, blk["attn"], hn, positions)
    k_c, v_c = _write_kv(k_c, v_c, k, v, cache["len"])
    attn = L.attention_decode(cfg, q, k_c, v_c, cache["len"] + 1)
    h = h + L.attention_output(blk["attn"], attn)
    hn2 = L.apply_norm(cfg, blk["norm2"], h)
    if cfg.family == "moe":
        out, _ = M.apply_moe(cfg, blk["moe"], hn2)
    else:
        out = L.apply_ffn(cfg, blk["ffn"], hn2)
    return h + out, k_c, v_c


def _decode_attn_unrolled(cfg, params, cache, h, positions, rt):
    k_full, v_full = cache["k"], cache["v"]
    for i in range(cfg.num_layers):
        blk = jax.tree.map(lambda x: x[i], params["layers"])
        h, k_c, v_c = _layer_block(cfg, rt, blk, cache, h, positions,
                                   k_full[i], v_full[i])
        k_full = lax.dynamic_update_index_in_dim(k_full, k_c, i, 0)
        v_full = lax.dynamic_update_index_in_dim(v_full, v_c, i, 0)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = k_full, v_full
    return new_cache, h


def _decode_ssm(cfg, params, cache, h, positions, rt):
    if rt.unroll_decode:
        return _decode_ssm_unrolled(cfg, params, cache, h, positions, rt)
    shared = params.get("shared")
    n_apps = _num_shared_apps(cfg)

    def shared_step(h, sk, sv, app_idx):
        hn = L.apply_norm(cfg, shared["norm1"], h)
        q, k, v = L.qkv_project(cfg, shared["attn"], hn, positions)
        sk_l = lax.dynamic_index_in_dim(sk, app_idx, 0, keepdims=False)
        sv_l = lax.dynamic_index_in_dim(sv, app_idx, 0, keepdims=False)
        sk_l, sv_l = _write_kv(sk_l, sv_l, k, v, cache["len"])
        attn = L.attention_decode(cfg, q, sk_l, sv_l, cache["len"] + 1)
        h = h + L.attention_output(shared["attn"], attn)
        hn2 = L.apply_norm(cfg, shared["norm2"], h)
        h = h + L.apply_ffn(cfg, shared["ffn"], hn2)
        sk = lax.dynamic_update_index_in_dim(sk, sk_l, app_idx, 0)
        sv = lax.dynamic_update_index_in_dim(sv, sv_l, app_idx, 0)
        return h, sk, sv

    def block(carry, xs):
        h, layer_idx, sk, sv, app_idx = carry
        blk, conv_l, state_l = xs
        hn = L.apply_norm(cfg, blk["norm1"], h)
        out, conv_l, state_l = S.apply_mamba_step(
            cfg, blk["mamba"], hn[:, 0], conv_l, state_l)
        h = h + out[:, None]
        if cfg.attn_every:
            def do_attn(h, sk, sv, ai):
                h, sk, sv = shared_step(h, sk, sv, ai)
                return h, sk, sv, ai + 1

            trigger = (layer_idx % cfg.attn_every) == cfg.attn_every - 1
            h, sk, sv, app_idx = lax.cond(
                trigger, do_attn,
                lambda h, sk, sv, ai: (h, sk, sv, ai),
                h, sk, sv, app_idx)
        return (h, layer_idx + 1, sk, sv, app_idx), (conv_l, state_l)

    sk0 = cache.get("shared_k", jnp.zeros((), h.dtype))
    sv0 = cache.get("shared_v", jnp.zeros((), h.dtype))
    carry0 = (h, jnp.zeros((), jnp.int32), sk0, sv0, jnp.zeros((), jnp.int32))
    (h, _, sk, sv, _), (conv_new, state_new) = lax.scan(
        block, carry0, (params["layers"], cache["conv"], cache["state"]))
    new_cache = dict(cache)
    new_cache["conv"], new_cache["state"] = conv_new, state_new
    if cfg.attn_every:
        new_cache["shared_k"], new_cache["shared_v"] = sk, sv
    return new_cache, h


def _decode_ssm_unrolled(cfg, params, cache, h, positions, rt):
    shared = params.get("shared")
    conv_full, state_full = cache["conv"], cache["state"]
    sk = cache.get("shared_k")
    sv = cache.get("shared_v")
    app_idx = 0
    for i in range(cfg.num_layers):
        blk = jax.tree.map(lambda x: x[i], params["layers"])
        hn = L.apply_norm(cfg, blk["norm1"], h)
        out, conv_l, state_l = S.apply_mamba_step(
            cfg, blk["mamba"], hn[:, 0], conv_full[i], state_full[i])
        h = h + out[:, None]
        conv_full = lax.dynamic_update_index_in_dim(conv_full, conv_l, i, 0)
        state_full = lax.dynamic_update_index_in_dim(state_full, state_l,
                                                     i, 0)
        if cfg.attn_every and (i % cfg.attn_every) == cfg.attn_every - 1:
            hn = L.apply_norm(cfg, shared["norm1"], h)
            q, k, v = L.qkv_project(cfg, shared["attn"], hn, positions)
            sk_l, sv_l = _write_kv(sk[app_idx], sv[app_idx], k, v,
                                   cache["len"])
            attn = L.attention_decode(cfg, q, sk_l, sv_l, cache["len"] + 1)
            h = h + L.attention_output(shared["attn"], attn)
            hn2 = L.apply_norm(cfg, shared["norm2"], h)
            h = h + L.apply_ffn(cfg, shared["ffn"], hn2)
            sk = lax.dynamic_update_index_in_dim(sk, sk_l, app_idx, 0)
            sv = lax.dynamic_update_index_in_dim(sv, sv_l, app_idx, 0)
            app_idx += 1
    new_cache = dict(cache)
    new_cache["conv"], new_cache["state"] = conv_full, state_full
    if cfg.attn_every:
        new_cache["shared_k"], new_cache["shared_v"] = sk, sv
    return new_cache, h
