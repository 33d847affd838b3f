"""Operations and bytes the serving programs need, from true lengths.

These are the dense GQA counts, and ``least_time``, which every count
shares. A configuration owns its counts: its plain reference
(``bench/references/<reference>.py``) exports ``count_<name>`` for each
name in ``bench.spec.COUNTS``, and the per-layer readers call those
through the record, never this module's counts. The dense reference
(``bench/references/dense_gqa.py``) takes them from here; an
architecture these counts do not price (sparse experts, window layers)
brings a reference of its own with its own counts, as new files.

Counts are from the configuration file (see
``bench/references/dense_gqa.dims``), never from padded shapes:

- a matrix product of a token with an ``m x n`` weight is ``2 m n`` ops;
- attention of one query over ``c`` keys is ``4 c head_dim`` ops per
  head (scores and the weighted sum);
- a dispatch reads every layer weight and the output head once (the
  embedding table is gathered by rows, so only the rows read count),
  reads the KV of the context it attends over and writes the KV of the
  tokens it adds.

The least time of a dispatch is the larger of ops over peak ops/s and
bytes over peak bytes/s; a program's roofline share is the summed least
time over its summed device time.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def layer_matmul_params(dm: dict) -> int:
    d, h, kv, dh, ff = dm["d"], dm["heads"], dm["kv"], dm["dh"], dm["ff"]
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    ffn = (3 if dm["gated"] else 2) * d * ff
    return attn + ffn


def kv_bytes_per_token(dm: dict, dtype_bytes: int = 2) -> int:
    return 2 * dm["layers"] * dm["kv"] * dm["dh"] * dtype_bytes


def weight_bytes(dm: dict, dtype_bytes: int = 2) -> int:
    """Weights one dispatch reads: every layer and the output head."""
    norms = (4 if dm["norm"] == "layernorm" else 2) * dm["d"]
    biases = 0 if dm["gated"] else dm["ff"] + dm["d"]
    per_layer = layer_matmul_params(dm) + norms + biases
    return (dm["layers"] * per_layer + dm["d"] * dm["vocab"]) * dtype_bytes


def attention_ops(dm: dict, queries: int, keys_total: int) -> float:
    """Ops of ``queries`` queries that attend over ``keys_total`` keys
    between them, summed over heads and layers."""
    return 4.0 * keys_total * dm["dh"] * dm["heads"] * dm["layers"] \
        if queries else 0.0


def decode_tick(dm: dict, rows: int, context: int) -> Tuple[float, float]:
    """(ops, bytes) of one decode tick over ``rows`` sequences whose KV
    contexts sum to ``context`` tokens (each row also attends to the
    token it adds)."""
    mm = 2.0 * rows * (dm["layers"] * layer_matmul_params(dm) +
                       dm["d"] * dm["vocab"])
    ops = mm + attention_ops(dm, rows, context + rows)
    kvb = kv_bytes_per_token(dm)
    nbytes = weight_bytes(dm) + kvb * (context + rows) + \
        rows * dm["d"] * 2
    return ops, float(nbytes)


def prefill(dm: dict, segments: Iterable[Tuple[int, int]]
            ) -> Tuple[float, float]:
    """(ops, bytes) of one prefill dispatch over ``(fresh, cached)``
    segments: fresh tokens run through every layer and attend causally
    over the cached prefix and themselves; the head runs on each
    segment's last token."""
    ops = 0.0
    nbytes = float(weight_bytes(dm))
    kvb = kv_bytes_per_token(dm)
    for fresh, cached in segments:
        ops += 2.0 * fresh * dm["layers"] * layer_matmul_params(dm)
        ops += 2.0 * dm["d"] * dm["vocab"]
        keys = fresh * cached + fresh * (fresh + 1) // 2
        ops += attention_ops(dm, fresh, keys)
        nbytes += kvb * (fresh + cached) + fresh * dm["d"] * 2
    return ops, nbytes


def decode_attention(dm: dict, rows: int, context: int
                     ) -> Tuple[float, float]:
    """(ops, bytes) of the attention of one decode tick over ``rows``
    sequences whose KV contexts sum to ``context`` tokens: each row's
    query reads the K and V of its context and of the token it adds,
    once, and each layer reads q and writes the output of every row."""
    keys = context + rows
    qo = 2 * rows * dm["heads"] * dm["dh"] * dm["layers"] * 2
    return attention_ops(dm, rows, keys), \
        float(kv_bytes_per_token(dm) * keys + qo)


def least_time(ops: float, nbytes: float, peaks: dict) -> float:
    return max(ops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
