"""The plain float32 reference agrees with the program's forward pass on
smoke widths of both block types, and its per-layer weights are the ones
the program is served."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.references import dense_gqa as ref
from bench.tests.tiny_root import tiny_config

FAMILIES = ["internlm2-1.8b", "starcoder2-15b-8L"]


@pytest.mark.parametrize("name", FAMILIES)
def test_reference_matches_program_forward(name):
    from repro.models import forward_hidden
    from repro.models.layers import lm_logits
    config = tiny_config(name, torch_dtype="float32")
    cfg = harness.program_config(name, config)
    seed = 2**33 + 5
    params = ref.make_params(config, seed, dtype="float32")
    toks = np.random.default_rng(0).integers(0, config["vocab_size"], 37)
    h, _, _ = forward_hidden(cfg, params, jnp.asarray(toks[None]))
    want = np.asarray(lm_logits(cfg, params["embed"], h)[0])
    rows = np.arange(37)
    got, = ref.logits(config, seed, [(toks, rows)], dtype="float32",
                      pad_to=16)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", FAMILIES)
def test_layer_weights_equal_stacked_weights(name):
    config = tiny_config(name)
    stacked = ref.make_params(config, 9)
    key = jax.random.key(ref.weight_seed(9))
    ckey = ref._key(config)
    for i in range(config["num_hidden_layers"]):
        layer = ref._layer_params_fn(ckey, "bfloat16")(key, i)
        for (p, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(layer),
                jax.tree_util.tree_leaves_with_path(
                    jax.tree.map(lambda x: x[i], stacked["layers"]))):
            assert np.array_equal(np.asarray(a), np.asarray(b)), p
    outer = ref._outer_params_fn(ckey, "bfloat16")(key)
    assert np.array_equal(np.asarray(outer["embed"]["tok"]),
                          np.asarray(stacked["embed"]["tok"]))


def test_weights_follow_the_seed():
    config = tiny_config("internlm2-1.8b")
    a = ref.make_params(config, 2**33)
    b = ref.make_params(config, 2**33 + 1)
    assert a["layers"]["attn"]["wq"].dtype == jnp.bfloat16
    assert not np.array_equal(np.asarray(a["embed"]["tok"]),
                              np.asarray(b["embed"]["tok"]))


def test_padding_does_not_reach_real_positions():
    config = tiny_config("starcoder2-15b-8L", torch_dtype="float32")
    toks = np.arange(20) % config["vocab_size"]
    seqs = [(toks, np.arange(20)), (toks[:7], np.arange(3, 7))]
    a = ref.logits(config, 3, seqs, dtype="float32", pad_to=16)
    b = ref.logits(config, 3, seqs, dtype="float32", pad_to=64)
    np.testing.assert_allclose(a[1], a[0][3:7], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(a[0], b[0], rtol=1e-5, atol=1e-5)
