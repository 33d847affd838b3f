"""Decode attention on one device contracts each KV head's cache with its
grouped query heads (``gqa_grouped``) instead of expanding the cache to
every query head (``expand_kv``).  The grouped form must match the
expanded one, which sharded decode still runs, and the paged decode step
must lower without any (B, S, H, dh)-sized expansion of the gathered K/V.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.distributed.sharding import make_rules, sharding_rules
from repro.models import decode_step, init_params
from repro.models import layers as L
from repro.models.transformer import make_paged_cache

CFG = get_smoke_config("internlm2-1.8b")
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _expanded(q, k, v, lens):
    """``attention_decode`` under a one-device rules context: the
    expanded (``expand_kv``) form."""
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    with sharding_rules(make_rules(mesh)):
        return L.attention_decode(CFG, q, k, v, lens)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("groups", [1, 2, 12])
def test_grouped_decode_matches_expanded(groups, dtype):
    b, s, kvh, dh = 5, 40, 2, 16
    h = kvh * groups
    kq, kk, kv = jax.random.split(jax.random.key(groups), 3)
    q = jax.random.normal(kq, (b, 1, h, dh), dtype)
    k = jax.random.normal(kk, (b, s, kvh, dh), dtype)
    v = jax.random.normal(kv, (b, s, kvh, dh), dtype)
    lens = jnp.array([1, s, 7, 23, 2], jnp.int32)    # unequal, 1 and full
    got = L.attention_decode(CFG, q, k, v, lens)
    want = _expanded(q, k, v, lens)
    assert got.shape == want.shape == (b, 1, h, dh)
    assert got.dtype == want.dtype == dtype
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    # a length-1 row attends to its first key alone: the output is v[0]
    # of each query head's KV head (head h = kv * G + g)
    np.testing.assert_allclose(
        np.asarray(got[0, 0], np.float32),
        np.asarray(jnp.repeat(v[0, 0], groups, axis=0), np.float32),
        rtol=tol, atol=tol)


def test_paged_decode_lowers_without_gqa_expansion():
    b, nb, bs, mb = 2, 9, 4, 5                       # gathered S = 20
    s, h, kvh, dh = mb * bs, CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
    assert h > kvh, "the smoke config must be grouped-query"
    params = init_params(CFG, jax.random.key(0))
    cache = make_paged_cache(CFG, b, nb, bs, mb, jnp.float32)
    toks = jnp.zeros((b,), jnp.int32)
    lowered = jax.jit(lambda p, c, t: decode_step(CFG, p, c, t)).lower(
        params, cache, toks)
    text = lowered.as_text(debug_info=True)
    assert "gqa_grouped" in text
    assert "expand_kv" not in text
    g = h // kvh
    expanded = [f"{b}x{s}x{h}x{dh}x", f"{b}x{s}x{kvh}x{g}x{dh}x"]
    broadcasts = re.findall(r"stablehlo\.broadcast_in_dim.*-> tensor<([^>]*)>",
                            text)
    assert not [t for t in broadcasts
                if any(t.startswith(e) for e in expanded)], broadcasts
    assert not any(f"tensor<{e}" in text for e in expanded)
