"""Per-kernel validation: shape/dtype sweeps, interpret-mode Pallas vs the
pure-jnp oracle in ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _assert_close(got, want, dtype):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **TOL[dtype])


# ---------------------------------------------------------------------------
# fused softmax
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,cols", [(8, 128), (16, 256), (33, 200),
                                       (7, 1000), (128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_lengths", [False, True])
def test_softmax_kernel(rows, cols, dtype, with_lengths):
    x = jax.random.normal(jax.random.key(rows * cols), (rows, cols)
                          ).astype(dtype)
    lengths = None
    if with_lengths:
        lengths = jax.random.randint(jax.random.key(7), (rows,), 1,
                                     cols + 1)
    want = ref.softmax_ref(x, lengths, 0.7)
    got = ops.fused_softmax(x, lengths, scale=0.7, impl="interpret")
    _assert_close(got, want, dtype)
    # rows sum to one over the valid region
    s = np.asarray(got, np.float32).sum(-1)
    np.testing.assert_allclose(s, 1.0, rtol=1e-2)


@pytest.mark.parametrize("rows,block_rows", [(100, 16), (33, 8), (5, 0)])
def test_softmax_rows_not_multiple_of_block(rows, block_rows):
    """Regression for the dead block-row clamp: row counts that do not
    tile the grid exactly (ragged tail block, or fewer rows than the
    minimum tile) must still match the oracle."""
    cols = 64
    x = jax.random.normal(jax.random.key(3), (rows, cols))
    lengths = jax.random.randint(jax.random.key(5), (rows,), 1, cols + 1)
    want = ref.softmax_ref(x, lengths, 1.0)
    got = ops.fused_softmax(x, lengths, impl="interpret",
                            block_rows=block_rows)
    _assert_close(got, want, jnp.float32)
    assert not np.isnan(np.asarray(got)).any()


def test_softmax_xla_path_matches():
    x = jax.random.normal(jax.random.key(0), (16, 96))
    got = ops.fused_softmax(x, impl="xla")
    want = jax.nn.softmax(x, axis=-1)
    _assert_close(got, want, jnp.float32)


# ---------------------------------------------------------------------------
# fused layernorm / rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,cols", [(8, 128), (10, 100), (64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("with_residual", [False, True])
def test_layernorm_kernel(rows, cols, dtype, with_bias, with_residual):
    ks = jax.random.split(jax.random.key(rows + cols), 5)
    x = jax.random.normal(ks[0], (rows, cols)).astype(dtype)
    g = jax.random.normal(ks[1], (cols,)).astype(dtype)
    b = jax.random.normal(ks[2], (cols,)).astype(dtype)
    bias = jax.random.normal(ks[3], (cols,)).astype(dtype) \
        if with_bias else None
    res = jax.random.normal(ks[4], (rows, cols)).astype(dtype) \
        if with_residual else None
    want, want_s = ref.layernorm_ref(x, g, b, bias, res, 1e-6, True)
    got, got_s = ops.fused_layernorm(x, g, b, bias, res,
                                     return_residual=True,
                                     impl="interpret")
    _assert_close(got, want, dtype)
    _assert_close(got_s, want_s, dtype)


@pytest.mark.parametrize("rows,cols", [(8, 128), (12, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel(rows, cols, dtype):
    ks = jax.random.split(jax.random.key(cols), 2)
    x = jax.random.normal(ks[0], (rows, cols)).astype(dtype)
    g = jax.random.normal(ks[1], (cols,)).astype(dtype)
    want = ref.rmsnorm_ref(x, g)
    got = ops.fused_rmsnorm(x, g, impl="interpret")
    _assert_close(got, want, dtype)


def test_layernorm_single_pass_variance_matches_two_pass():
    """Paper Eq. 1: E(x^2)-E(x)^2 must equal E((x-E x)^2) numerically for
    well-scaled inputs."""
    x = jax.random.normal(jax.random.key(5), (32, 777))
    g = jnp.ones((777,))
    b = jnp.zeros((777,))
    got = ref.layernorm_ref(x, g, b)
    xf = np.asarray(x, np.float64)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    want = (xf - mean) / np.sqrt(var + 1e-6)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,kv,s,dh,bq,bk", [
    (2, 4, 2, 128, 32, 32, 32),     # GQA
    (1, 2, 2, 96, 64, 32, 32),      # MHA, ragged block edge
    (2, 8, 1, 64, 16, 16, 32),      # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(b, h, kv, s, dh, bq, bk, dtype):
    ks = jax.random.split(jax.random.key(s + h), 3)
    q = jax.random.normal(ks[0], (b, h, s, dh)).astype(dtype)
    k = jax.random.normal(ks[1], (b, kv, s, dh)).astype(dtype)
    v = jax.random.normal(ks[2], (b, kv, s, dh)).astype(dtype)
    lengths = jnp.array([s] + [s // 2] * (b - 1))
    want = ref.flash_attention_ref(q, k, v, lengths, True)
    got = ops.flash_attention(q, k, v, lengths, causal=True,
                              impl="interpret", block_q=bq, block_k=bk)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_flash_attention_decode_shape():
    """Sq < Sk (extend/decode): queries sit at the end of the kv window."""
    b, h, kv, sk, sq, dh = 2, 4, 4, 128, 8, 32
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, h, sq, dh))
    k = jax.random.normal(ks[1], (b, kv, sk, dh))
    v = jax.random.normal(ks[2], (b, kv, sk, dh))
    want = ref.flash_attention_ref(q, k, v, None, True)
    got = ops.flash_attention(q, k, v, causal=True, impl="interpret",
                              block_q=8, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("b,h,kv,s,dh,splits,bk", [
    (2, 4, 2, 250, 32, 3, 64),      # uneven split + partial block
    (2, 4, 2, 256, 32, 4, 64),      # exact cover
    (1, 8, 1, 512, 64, 4, 128),     # MQA long cache
    (2, 2, 2, 128, 32, 1, 128),     # single split == sequential flash
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_kernel(b, h, kv, s, dh, splits, bk, dtype):
    """Split-K decode attention (the serving hot loop; §Perf cell C's
    projected kernel) vs the oracle, incl. variable cache lengths."""
    ks = jax.random.split(jax.random.key(s + splits), 3)
    q = jax.random.normal(ks[0], (b, h, dh)).astype(dtype)
    k = jax.random.normal(ks[1], (b, kv, s, dh)).astype(dtype)
    v = jax.random.normal(ks[2], (b, kv, s, dh)).astype(dtype)
    lengths = jnp.array([s] + [max(s // 3, 1)] * (b - 1))
    want = ref.flash_attention_ref(q[:, :, None, :], k, v, lengths,
                                   causal=False)[:, :, 0]
    got = ops.flash_decode(q, k, v, lengths, num_splits=splits,
                           block_k=bk, impl="interpret")
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("b,h,kv,dh,bs,mb,nb,splits", [
    (2, 4, 2, 32, 16, 4, 12, 2),    # GQA, shuffled pool, uneven lengths
    (1, 4, 4, 64, 16, 3, 8, 4),     # splits > blocks-per-split coverage
    (2, 2, 1, 32, 32, 2, 6, 1),     # MQA, single split
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_paged_kernel(b, h, kv, dh, bs, mb, nb, splits,
                                   dtype):
    """Paged split-K decode: the kv walk follows a per-row block table
    through a shared pool instead of a contiguous stripe.  Must match
    both the oracle and the contiguous kernel run on the materialized
    logical view."""
    rng = np.random.default_rng(bs + mb)
    ks = jax.random.split(jax.random.key(b * h + dh), 3)
    q = jax.random.normal(ks[0], (b, h, dh)).astype(dtype)
    k_pool = jax.random.normal(ks[1], (nb, bs, kv, dh)).astype(dtype)
    v_pool = jax.random.normal(ks[2], (nb, bs, kv, dh)).astype(dtype)
    # disjoint physical blocks per row; block 0 stays trash
    perm = rng.permutation(np.arange(1, nb))[:b * mb]
    tables = jnp.asarray(perm.reshape(b, mb).astype(np.int32))
    lengths = jnp.asarray(
        rng.integers(1, mb * bs + 1, size=(b,)).astype(np.int32))
    want = ops.flash_decode_paged(q, k_pool, v_pool, tables, lengths,
                                  impl="xla")
    got = ops.flash_decode_paged(q, k_pool, v_pool, tables, lengths,
                                 num_splits=splits, impl="interpret")
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)
    # token-for-token with the contiguous kernel over the same KV
    k_c = k_pool[tables].reshape(b, mb * bs, kv, dh).swapaxes(1, 2)
    v_c = v_pool[tables].reshape(b, mb * bs, kv, dh).swapaxes(1, 2)
    contiguous = ops.flash_decode(q, k_c, v_c, lengths,
                                  num_splits=splits, impl="interpret")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(contiguous, np.float32), **tol)


# ---------------------------------------------------------------------------
# fused sampling
# ---------------------------------------------------------------------------

def _sampling_inputs(rows, cols, key, temps, ks, ps):
    """Per-row params cycling through the given grids + shared Gumbel
    noise drawn exactly the way runtime.sampling draws it."""
    keys = jax.random.split(jax.random.key(key), 2)
    logits = 4.0 * jax.random.normal(keys[0], (rows, cols))
    temperature = jnp.array([temps[i % len(temps)] for i in range(rows)],
                            jnp.float32)
    top_k = jnp.array([ks[i % len(ks)] for i in range(rows)], jnp.int32)
    top_p = jnp.array([ps[i % len(ps)] for i in range(rows)], jnp.float32)
    cands = min(64, cols)
    gumbel = jax.random.gumbel(keys[1], (rows, cands), jnp.float32)
    return logits, temperature, top_k, top_p, gumbel


@pytest.mark.parametrize("rows,cols", [(1, 64), (4, 256), (8, 1000),
                                       (33, 128), (5, 37)])
def test_sample_kernel_parity(rows, cols):
    """Interpret-mode kernel vs the jnp oracle: identical token ids for
    every mix of greedy/sampled rows and top-k/top-p settings (0
    disables k; k > C and p = 1.0 exercise the truncation edges)."""
    logits, t, k, p, g = _sampling_inputs(
        rows, cols, rows * cols, temps=(0.0, 0.7, 1.3),
        ks=(0, 1, 5, 64, 10_000), ps=(0.3, 0.95, 1.0))
    want = ops.fused_sample(logits, t, k, p, g, impl="xla")
    got = ops.fused_sample(logits, t, k, p, g, impl="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.int32


def test_sample_greedy_rows_match_argmax():
    """temperature <= 0 rows take the exact argmax regardless of the
    noise or filter params — the greedy-stream bit-identity contract."""
    logits, _, k, p, g = _sampling_inputs(16, 512, 3, temps=(0.0,),
                                          ks=(0, 3), ps=(0.5, 1.0))
    t = jnp.zeros((16,), jnp.float32)
    want = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for impl in ("xla", "interpret"):
        got = ops.fused_sample(logits, t, k, p, g, impl=impl)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sample_topk1_is_greedy():
    """top_k = 1 collapses the candidate set to the argmax: sampled rows
    become deterministic greedy rows whatever the temperature/noise."""
    logits, _, _, _, g = _sampling_inputs(12, 300, 9, temps=(1.0,),
                                          ks=(1,), ps=(1.0,))
    t = jnp.full((12,), 0.9, jnp.float32)
    k = jnp.ones((12,), jnp.int32)
    p = jnp.ones((12,), jnp.float32)
    want = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for impl in ("xla", "interpret"):
        got = ops.fused_sample(logits, t, k, p, g, impl=impl)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sample_tiny_topp_is_greedy():
    """A nucleus smaller than the top token's own mass keeps only the
    top token (the exclusive-cumsum mask never drops rank 0)."""
    logits, _, _, _, g = _sampling_inputs(8, 128, 11, temps=(0.8,),
                                          ks=(0,), ps=(1.0,))
    t = jnp.full((8,), 0.8, jnp.float32)
    k = jnp.zeros((8,), jnp.int32)
    p = jnp.full((8,), 1e-6, jnp.float32)
    want = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for impl in ("xla", "interpret"):
        got = ops.fused_sample(logits, t, k, p, g, impl=impl)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sample_one_hot_logits():
    """A one-hot row (one finite spike) must return the spike for every
    param combination — sampled or greedy."""
    rows, cols = 10, 200
    hot = np.arange(3, 3 + rows * 7, 7) % cols
    logits = np.full((rows, cols), -30.0, np.float32)
    logits[np.arange(rows), hot] = 30.0
    logits = jnp.asarray(logits)
    t = jnp.array([0.0, 0.5, 1.0, 1.5, 0.7] * 2, jnp.float32)
    k = jnp.array([0, 1, 4, 64, 7] * 2, jnp.int32)
    p = jnp.array([0.1, 0.9, 1.0, 0.5, 0.99] * 2, jnp.float32)
    g = jax.random.gumbel(jax.random.key(0), (rows, 64), jnp.float32)
    for impl in ("xla", "interpret"):
        got = ops.fused_sample(logits, t, k, p, g, impl=impl)
        np.testing.assert_array_equal(np.asarray(got), hot)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sample_tie_order_lowest_index(dtype):
    """Rows full of exact ties (as bf16 logits are) peel candidates and
    pick the greedy token lowest index first — the order of jnp.argmax
    and lax.top_k — so kernel and oracle draw the same token."""
    rows, cols = 16, 512
    logits, t, k, p, g = _sampling_inputs(
        rows, cols, 5, temps=(0.0, 0.8, 1.2), ks=(0, 3, 40),
        ps=(0.9, 1.0))
    logits = (jnp.round(logits) / 2).astype(dtype)    # few distinct values
    want = ops.fused_sample(logits, t, k, p, g, impl="xla")
    got = ops.fused_sample(logits, t, k, p, g, impl="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("rows,block_rows", [(33, 8), (5, 0), (100, 16)])
def test_sample_rows_not_multiple_of_block(rows, block_rows):
    """Ragged tail blocks (rows not tiling the grid) must still match
    the oracle token-for-token."""
    logits, t, k, p, g = _sampling_inputs(
        rows, 96, rows + 1, temps=(0.0, 1.1), ks=(0, 2), ps=(0.9, 1.0))
    want = ops.fused_sample(logits, t, k, p, g, impl="xla")
    got = ops.fused_sample(logits, t, k, p, g, impl="interpret",
                           block_rows=block_rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sample_tokens_reproducible_and_batch_independent():
    """The runtime wrapper's PRNG contract: same (seed, step) -> same
    token, independent of batch composition or row position."""
    from repro.runtime.sampling import sample_tokens
    v = 256
    logits = 3.0 * jax.random.normal(jax.random.key(42), (4, v))
    logits = logits.at[2].set(logits[0])   # rows 0/2: identical draws
    t = jnp.full((4,), 0.8, jnp.float32)
    k = jnp.zeros((4,), jnp.int32)
    p = jnp.full((4,), 0.95, jnp.float32)
    seed = jnp.array([7, 9, 7, 11], jnp.int32)
    step = jnp.array([0, 3, 0, 5], jnp.int32)
    a = sample_tokens(logits, temperature=t, top_k=k, top_p=p, seed=seed,
                      step=step)
    b = sample_tokens(logits, temperature=t, top_k=k, top_p=p, seed=seed,
                      step=step)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # rows 0 and 2 share logits/params/seed/step -> same token
    assert int(a[0]) == int(a[2])
    # a row alone draws the same token it drew co-batched
    solo = sample_tokens(logits[1:2], temperature=t[1:2], top_k=k[1:2],
                         top_p=p[1:2], seed=seed[1:2], step=step[1:2])
    assert int(solo[0]) == int(a[1])


def test_flash_matches_model_chunked_attention():
    """The kernel agrees with the model's XLA chunked-attention path."""
    from repro.configs import get_smoke_config
    from repro.models.layers import attention_chunked
    cfg = get_smoke_config("qwen3-32b")
    b, s, h, kvh, dh = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, kvh, dh))
    v = jax.random.normal(ks[2], (b, s, kvh, dh))
    want = attention_chunked(cfg, q, k, v, q_block=16, kv_block=16)
    got = ops.flash_attention(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
        causal=True, impl="interpret", block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(got, 1, 2)),
                               np.asarray(want), rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# grouped matmul (the dropless expert layer's)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes,groups", [
    ((5, 0, 12, 7), 4),             # an empty group; rows to every other
    ((3, 0, 9, 4), 12),             # the last 8 groups hold no rows
    ((0, 40, 0, 0, 0, 0, 0, 0), 8),  # one group, rows past one row tile
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_kernel(sizes, groups, dtype):
    """Interpret-mode megablox gmm against ragged_dot; ``groups`` beyond
    ``sizes`` hold no rows (a stack of layers read in place)."""
    k, n = 256, 192
    m = sum(sizes)
    gs = jnp.asarray(list(sizes) + [0] * (groups - len(sizes)), jnp.int32)
    lhs = jax.random.normal(jax.random.key(m), (m, k)).astype(dtype)
    rhs = (jax.random.normal(jax.random.key(groups), (groups, k, n)) /
           16).astype(dtype)
    got = ops.grouped_matmul(lhs, rhs, gs, impl="interpret")
    want = ref.grouped_matmul_ref(lhs, rhs, gs)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    _assert_close(got, want, dtype)
