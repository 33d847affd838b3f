"""Per-architecture smoke tests: reduced same-family configs, one forward /
train step / prefill+decode on CPU, asserting output shapes and no NaNs
(deliverable f)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ARCH_IDS, get_config, get_smoke_config,
                           shapes_for)
from repro.models import (decode_step,
                          init_params, prefill)
from repro.models.io import synthetic_prompts, synthetic_train_batch
from repro.models.layers import lm_logits
from repro.models import forward_hidden
from repro.training import (OptimizerConfig, TrainConfig, init_state,
                            make_train_step)


@pytest.fixture(scope="module")
def smoke(request):
    return {}


def _params(cfg):
    return init_params(cfg, jax.random.key(0))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    assert cfg.d_model == 64   # genuinely reduced
    tc = TrainConfig(optimizer=OptimizerConfig(learning_rate=1e-3),
                     compute_dtype="float32")
    state = init_state(cfg, tc, 0)
    step = jax.jit(make_train_step(cfg, tc))
    batch = synthetic_train_batch(cfg, jax.random.key(1), 2, 32)
    # output shape checks
    if cfg.num_codebooks:
        assert batch["tokens"].shape == (2, cfg.num_codebooks, 32)
    else:
        assert batch["tokens"].shape == (2, 32)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    state2, metrics2 = step(state, batch)
    assert float(metrics2["loss"]) < float(metrics["loss"])  # it learns


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    params = _params(cfg)
    pr = synthetic_prompts(cfg, jax.random.key(2), 2, 17)
    logits_p, cache = prefill(
        cfg, params, pr["tokens"], max_len=24,
        embeds_override=pr.get("embeds_override"),
        cache_dtype=jnp.float32)
    expect = (2, cfg.num_codebooks, cfg.vocab_size) if cfg.num_codebooks \
        else (2, cfg.vocab_size)
    assert logits_p.shape == expect
    nxt = jnp.argmax(logits_p, -1)
    logits_d, cache, _ = decode_step(cfg, params, cache, nxt)
    assert np.isfinite(np.asarray(logits_d)).all()
    # oracle: full forward over the extended sequence
    if cfg.num_codebooks:
        toks2 = jnp.concatenate([pr["tokens"], nxt[:, :, None]], axis=-1)
    else:
        toks2 = jnp.concatenate([pr["tokens"], nxt[:, None]], axis=-1)
    h, _, _ = forward_hidden(
        cfg, params, toks2, embeds_override=pr.get("embeds_override"),
        num_prefix_patches=(pr["embeds_override"].shape[1]
                            if "embeds_override" in pr else 0))
    ref = lm_logits(cfg, params["embed"], h[:, -1:])
    ref = ref[:, :, 0] if cfg.num_codebooks else ref[:, 0]
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact published dimensions (exercised
    via dry-run only; this test checks the numbers, not allocation)."""
    cfg = get_config(arch)
    expected = {
        "qwen3-32b": (64, 5120, 64, 8, 25600, 151936),
        "llama3-405b": (126, 16384, 128, 8, 53248, 128256),
        "internlm2-1.8b": (24, 2048, 16, 8, 8192, 92544),
        "starcoder2-15b": (40, 6144, 48, 4, 24576, 49152),
        "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
        "falcon-mamba-7b": (64, 4096, 0, 0, 0, 65024),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected
    # family-specific structure
    if arch == "olmoe-1b-7b":
        assert cfg.moe.num_experts == 64 and cfg.moe.top_k == 8
    if arch == "phi3.5-moe-42b-a6.6b":
        assert cfg.moe.num_experts == 16 and cfg.moe.top_k == 2
    if arch == "falcon-mamba-7b":
        assert cfg.ssm.variant == "mamba1" and cfg.ssm.state_dim == 16
    if arch == "zamba2-1.2b":
        assert cfg.ssm.variant == "mamba2" and cfg.ssm.state_dim == 64
        assert cfg.attn_every > 0
    if arch == "musicgen-large":
        assert cfg.num_codebooks == 4
    if arch == "qwen2-vl-7b":
        assert cfg.rope == "mrope" and cfg.frontend == "vision"
    if arch == "qwen3-32b":
        assert cfg.qk_norm


def test_long_500k_assignment_follows_family_rule():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        names = {s.name for s in shapes_for(cfg)}
        if cfg.family in ("ssm", "hybrid"):
            assert "long_500k" in names
        else:
            assert "long_500k" not in names


def test_vlm_frontend_stub_changes_output():
    cfg = get_smoke_config("qwen2-vl-7b")
    params = _params(cfg)
    pr = synthetic_prompts(cfg, jax.random.key(3), 1, 24)
    h1, _, _ = forward_hidden(cfg, params, pr["tokens"],
                              embeds_override=pr["embeds_override"],
                              num_prefix_patches=pr["embeds_override"
                                                    ].shape[1])
    h2, _, _ = forward_hidden(cfg, params, pr["tokens"])
    assert float(jnp.max(jnp.abs(h1 - h2))) > 1e-3


def test_mamba2_ssd_matmul_matches_scan():
    """The SSD block-matmul form (§Perf cell D) is numerically equivalent
    to the associative-scan form, forward and backward."""
    import dataclasses
    cfg = get_smoke_config("zamba2-1.2b")
    cfg_ssd = dataclasses.replace(
        cfg, ssm=dataclasses.replace(cfg.ssm, ssd_matmul=True))
    params = _params(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 67), 0,
                              cfg.vocab_size)
    h1, _, _ = forward_hidden(cfg, params, toks)
    h2, _, _ = forward_hidden(cfg_ssd, params, toks)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-4, atol=1e-4)
    g1 = jax.grad(lambda p: jnp.sum(forward_hidden(cfg, p, toks)[0] ** 2)
                  )(params)
    g2 = jax.grad(lambda p: jnp.sum(forward_hidden(cfg_ssd, p, toks)[0]
                                    ** 2))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-2, atol=1e-3)


def test_musicgen_delay_pattern_roundtrip():
    from repro.models.frontend import apply_delay_pattern, undelay_pattern
    toks = jax.random.randint(jax.random.key(0), (2, 4, 16), 0, 100)
    delayed = apply_delay_pattern(toks)
    # codebook k shifted right by k
    assert (np.asarray(delayed[:, 1, 1:]) ==
            np.asarray(toks[:, 1, :-1])).all()
    rec = undelay_pattern(delayed)
    assert (np.asarray(rec[:, :, :12]) == np.asarray(toks[:, :, :12])).all()
