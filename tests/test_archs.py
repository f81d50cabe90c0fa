"""Per-architecture smoke tests (reduced configs, CPU).

For every assigned architecture: instantiate a reduced variant of the same
family, run one forward and one train(-grad) step, assert output shapes and
absence of NaNs; plus decode-vs-full-forward logit consistency.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)


def make_batch(cfg, B=2, S=32, key=KEY):
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "positions": jnp.arange(S)[None].repeat(B, 0),
        "targets": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }
    if cfg.family == "audio":
        batch["encoder_embeds"] = jax.random.normal(key, (B, 16, cfg.d_model))
    if cfg.frontend == "vision" and cfg.frontend_tokens:
        batch["vision_embeds"] = jax.random.normal(
            key, (B, cfg.frontend_tokens, cfg.d_model)
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch):
    cfg = get_reduced(arch)
    params = T.init_params(cfg, KEY)
    B, S = 2, 64
    batch = make_batch(cfg, B, S)
    logits, aux, _ = T.apply(cfg, params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))

    loss, metrics = T.loss(cfg, params, batch)
    grads = jax.grad(lambda p: T.loss(cfg, p, batch)[0])(params)
    gnorm = jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(grads)))
    assert bool(jnp.isfinite(loss)) and bool(jnp.isfinite(gnorm))
    # one SGD step must change the loss
    new_params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
    loss2, _ = T.loss(cfg, new_params, batch)
    assert float(loss2) < float(loss)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_full_forward(arch):
    cfg = get_reduced(arch)
    params = T.init_params(cfg, KEY)
    B, S = 2, 32
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full = {"tokens": tokens, "positions": jnp.arange(S)[None].repeat(B, 0)}
    enc = None
    if cfg.family == "audio":
        enc = jax.random.normal(KEY, (B, 16, cfg.d_model))
        full["encoder_embeds"] = enc
    logits_full, _, _ = T.apply(cfg, params, full)

    caches = T.init_cache(cfg, B, S)
    pre = {"tokens": tokens[:, : S - 1], "positions": jnp.arange(S - 1)[None].repeat(B, 0)}
    if enc is not None:
        pre["encoder_embeds"] = enc
    _, _, caches = T.apply(cfg, params, pre, caches=caches, cache_index=0)
    dec = {"tokens": tokens[:, S - 1 :], "positions": jnp.full((B, 1), S - 1)}
    if enc is not None:
        dec["encoder_embeds"] = enc
    logits_dec, _, _ = T.apply(cfg, params, dec, caches=caches, cache_index=S - 1)
    err = float(jnp.max(jnp.abs(logits_dec[:, 0] - logits_full[:, -1])))
    assert err < 2e-3, f"{arch}: decode/full mismatch {err}"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_is_exact(arch):
    """Full configs carry the assigned dimensions (spot checks)."""
    cfg = get_config(arch)
    expected = {
        "gemma2_9b": (42, 3584, 16, 8, 14336, 256_000),
        "phi3_medium_14b": (40, 5120, 40, 10, 17920, 100_352),
        "zamba2_1p2b": (38, 2048, 32, 32, 8192, 32_000),
        "mamba2_2p7b": (64, 2560, 0, 0, 0, 50_280),
        "chameleon_34b": (48, 8192, 64, 8, 22016, 65_536),
        "llama4_maverick_400b_a17b": (48, 5120, 40, 8, 8192, 202_048),
        "seamless_m4t_medium": (12, 1024, 16, 16, 4096, 256_206),
        "grok1_314b": (64, 6144, 48, 8, 32768, 131_072),
        "minitron_8b": (32, 4096, 32, 8, 16384, 256_000),
        "gemma3_27b": (62, 5376, 32, 16, 21504, 262_144),
        "qwen_1p5b": (28, 1536, 12, 2, 8960, 151_936),
        "deepseek_v2_lite": (27, 2048, 16, 16, 10944, 102_400),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected


def test_param_counts_in_band():
    """Approximate param counts should land near the nameplate sizes."""
    bands = {
        "gemma2_9b": (7e9, 11e9),
        "phi3_medium_14b": (12e9, 16e9),
        "zamba2_1p2b": (0.9e9, 1.7e9),
        "mamba2_2p7b": (2.2e9, 3.2e9),
        "chameleon_34b": (30e9, 38e9),
        "llama4_maverick_400b_a17b": (350e9, 450e9),
        "grok1_314b": (280e9, 350e9),
        "minitron_8b": (7e9, 10e9),
        "gemma3_27b": (23e9, 31e9),
        "qwen_1p5b": (1.2e9, 2.1e9),
        "deepseek_v2_lite": (14e9, 17e9),  # published 15.7 B
    }
    for arch, (lo, hi) in bands.items():
        n = get_config(arch).num_params()
        assert lo <= n <= hi, f"{arch}: {n / 1e9:.2f}B outside [{lo / 1e9}, {hi / 1e9}]"


def test_moe_active_params():
    cfg = get_config("llama4_maverick_400b_a17b")
    active = cfg.num_active_params()
    assert active < 0.12 * cfg.num_params()  # top-1 of 128 experts
    cfg = get_config("grok1_314b")
    assert cfg.num_active_params() < 0.4 * cfg.num_params()  # top-2 of 8
