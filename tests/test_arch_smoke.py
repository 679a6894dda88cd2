"""Per-architecture smoke tests (deliverable f).

For each assigned architecture: instantiate the REDUCED same-family variant
(<=2 layers, d_model<=512, <=4 experts) and run one forward + one train
step on CPU, asserting output shapes and no NaNs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ALL_ARCHS, ASSIGNED_ARCHS, get_config
from repro.models.init import init_params, padded_vocab, count_params
from repro.models.model import forward_full, lm_loss
from repro.training.optimizer import AdamW

B, S = 2, 64


def _inputs(cfg, rng):
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    kw = {}
    if cfg.modality == "vision":
        kw["modality_embeds"] = 0.02 * jax.random.normal(
            rng, (B, cfg.num_modality_tokens, cfg.d_model)
        ).astype(jnp.bfloat16)
    if cfg.is_encoder_decoder:
        kw["encoder_embeds"] = 0.02 * jax.random.normal(
            rng, (B, cfg.encoder_seq_len, cfg.d_model)
        ).astype(jnp.bfloat16)
    return tokens, kw


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_config_limits(arch):
    cfg = get_config(arch, smoke=True)
    assert cfg.num_layers <= 2 or (
        cfg.arch_type in ("ssm", "hybrid") and cfg.num_layers <= 4
    ), f"{arch}: smoke num_layers={cfg.num_layers}"
    assert cfg.d_model <= 512
    assert cfg.num_experts <= 4


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch, smoke=False)
    expected = {
        "granite-20b": (52, 6144, 48, 1, 24576, 49152),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "deepseek-v2-236b": (60, 5120, 128, 128, 1536, 102400),
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
        "mamba2-2.7b": (64, 2560, 0, 0, 0, 50280),
        "starcoder2-3b": (30, 3072, 24, 2, 12288, 49152),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
        "deepseek-v2-lite": (27, 2048, 16, 16, 1408, 102400),
    }
    if arch not in expected:
        pytest.skip("paper-model config, not an assigned arch")
    L, D, H, KVH, FF, V = expected[arch]
    assert cfg.num_layers == L
    assert cfg.d_model == D
    assert cfg.num_heads == H
    assert cfg.num_kv_heads == KVH
    if arch == "deepseek-v2-236b":
        assert cfg.moe_d_ff == FF
        assert cfg.num_experts == 160 and cfg.num_experts_per_tok == 6
        assert cfg.kv_lora_rank == 512 and cfg.num_shared_experts == 2
    elif arch == "deepseek-v2-lite":
        assert cfg.moe_d_ff == FF and cfg.d_ff == 10944
        assert cfg.num_experts == 64 and cfg.num_experts_per_tok == 6
        assert cfg.num_experts_held == 64 and cfg.first_k_dense == 1
        assert cfg.kv_lora_rank == 512 and cfg.q_lora_rank == 0
        assert cfg.num_shared_experts == 2 and not cfg.norm_topk_prob
        assert cfg.rope_scaling_factor == 40 and cfg.yarn_mscale_all_dim == 0.707
    elif arch == "mixtral-8x7b":
        assert cfg.moe_d_ff == FF
        assert cfg.num_experts == 8 and cfg.num_experts_per_tok == 2
    elif arch == "mamba2-2.7b":
        assert cfg.ssm_state_size == 128
    else:
        assert cfg.d_ff == FF
    assert cfg.vocab_size == V
    if arch == "zamba2-2.7b":
        assert cfg.ssm_state_size == 64


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_smoke(arch):
    cfg = get_config(arch, smoke=True)
    rng = jax.random.PRNGKey(0)
    params = init_params(cfg, rng)
    tokens, kw = _inputs(cfg, rng)
    out = forward_full(params, cfg, tokens, **kw)
    V = padded_vocab(cfg)
    assert out["logits"].shape == (B, S, V)
    assert out["hidden"].shape == (B, S, cfg.d_model)
    assert np.all(np.isfinite(np.asarray(out["logits"], np.float32)))
    assert np.all(np.isfinite(np.asarray(out["hidden"], np.float32)))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_smoke(arch):
    """One optimizer step; loss finite and decreases over 3 steps."""
    cfg = get_config(arch, smoke=True)
    rng = jax.random.PRNGKey(1)
    params = init_params(cfg, rng)
    tokens, kw = _inputs(cfg, rng)
    labels = jnp.roll(tokens, -1, axis=1)

    opt = AdamW(learning_rate=1e-3)
    opt_state = opt.init(params)

    def loss_fn(p):
        return lm_loss(p, cfg, tokens, labels, **kw)

    first = None
    for _ in range(3):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        assert np.isfinite(float(loss)), f"{arch}: loss not finite"
        if first is None:
            first = float(loss)
        params, opt_state = opt.update(grads, opt_state, params)
    final = float(loss_fn(params))
    assert final < first, f"{arch}: loss did not decrease ({first}->{final})"


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_count_sane(arch):
    """Smoke param count is small enough for CPU and nonzero."""
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    n = count_params(params)
    assert 1e4 < n < 2e8, f"{arch}: {n} params"
