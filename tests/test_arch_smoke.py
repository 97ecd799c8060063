"""Per assigned architecture: REDUCED same-family variant runs one forward
and one train step on CPU; output shapes + no NaNs (deliverable f)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)


def _batch(cfg, batch=2, seq=16):
    shape = (batch, seq) if cfg.n_codebooks == 1 else (batch, seq, cfg.n_codebooks)
    tok = jax.random.randint(KEY, shape, 0, cfg.vocab_size)
    b = {"tokens": tok, "labels": tok}
    if cfg.n_prefix_embeds:
        b["prefix_embeds"] = jax.random.normal(
            KEY, (batch, cfg.n_prefix_embeds, cfg.d_model)) * 0.02
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_reduced_variant(arch):
    cfg = get_smoke_config(arch)
    # a hybrid keeps one whole period of its layer pattern
    assert cfg.n_layers <= max(4, cfg.hybrid_period) and cfg.d_model <= 512
    if cfg.is_moe:
        assert cfg.moe.num_experts <= 4
    params = T.init(KEY, cfg)
    batch = _batch(cfg)
    logits, _ = T.forward(params, batch, cfg)
    B, S = batch["tokens"].shape[:2]
    if cfg.n_codebooks > 1:
        assert logits.shape == (B, S, cfg.n_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any()), "NaN logits"

    # one SGD train step must reduce nothing NaN and change params
    (loss, _), grads = jax.value_and_grad(T.loss_fn, has_aux=True)(
        params, batch, cfg)
    assert jnp.isfinite(loss)
    new_params = jax.tree.map(lambda p, g: p - 1e-2 * g, params, grads)
    (loss2, _) = T.loss_fn(new_params, batch, cfg)[0], None
    assert jnp.isfinite(loss2[0] if isinstance(loss2, tuple) else loss2)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_metadata(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    assert cfg.citation, "configs must cite their source"
    expected = {
        "jamba-1.5-large-398b": dict(n_layers=72, d_model=8192, n_heads=64,
                                     n_kv_heads=8, d_ff=24576, vocab_size=65536),
        "internvl2-26b": dict(n_layers=48, d_model=6144, n_heads=48,
                              n_kv_heads=8, d_ff=16384),
        "mamba2-2.7b": dict(n_layers=64, d_model=2560),
        "chatglm3-6b": dict(n_layers=28, d_model=4096, n_heads=32,
                            n_kv_heads=2, d_ff=13696, vocab_size=65024),
        "mistral-nemo-12b": dict(n_layers=40, d_model=5120, n_heads=32,
                                 n_kv_heads=8, d_ff=14336, vocab_size=131072),
        "musicgen-medium": dict(n_layers=48, d_model=1536, n_heads=24,
                                n_kv_heads=24, d_ff=6144, vocab_size=2048),
        "llama4-scout-17b-a16e": dict(n_layers=48, d_model=5120, n_heads=40,
                                      n_kv_heads=8, d_ff=8192),
        "qwen3-moe-235b-a22b": dict(n_layers=94, d_model=4096, n_heads=64,
                                    n_kv_heads=4, d_ff=1536, vocab_size=151936),
        "llama3-405b": dict(n_layers=126, d_model=16384, n_heads=128,
                            n_kv_heads=8, d_ff=53248, vocab_size=128256),
        "mistral-large-123b": dict(n_layers=88, d_model=12288, n_heads=96,
                                   n_kv_heads=8, d_ff=28672, vocab_size=32768),
        "granite-4.0-h-micro": dict(n_layers=40, d_model=2048, n_heads=32,
                                    n_kv_heads=8, d_ff=8192,
                                    vocab_size=100352),
    }[arch]
    for k, v in expected.items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


def test_moe_expert_counts():
    assert get_config("jamba-1.5-large-398b").moe.num_experts == 16
    assert get_config("jamba-1.5-large-398b").moe.top_k == 2
    assert get_config("llama4-scout-17b-a16e").moe.num_experts == 16
    assert get_config("llama4-scout-17b-a16e").moe.top_k == 1
    assert get_config("qwen3-moe-235b-a22b").moe.num_experts == 128
    assert get_config("qwen3-moe-235b-a22b").moe.top_k == 8


def test_param_counts_in_expected_range():
    """Analytic parameter counts should land near the advertised sizes."""
    def pc(a):
        return get_config(a).param_count()
    assert 380e9 < pc("jamba-1.5-large-398b") < 440e9
    assert 18e9 < pc("internvl2-26b") < 26e9      # language backbone only
    assert 2.4e9 < pc("mamba2-2.7b") < 3.1e9
    assert 5.5e9 < pc("chatglm3-6b") < 7.5e9
    assert 11e9 < pc("mistral-nemo-12b") < 14e9
    assert 1.2e9 < pc("musicgen-medium") < 2.2e9
    assert 380e9 < pc("llama3-405b") < 430e9
    assert 115e9 < pc("mistral-large-123b") < 130e9
    q = get_config("qwen3-moe-235b-a22b")
    assert 200e9 < q.param_count() < 260e9
    assert 18e9 < q.active_param_count() < 28e9
    s = get_config("llama4-scout-17b-a16e")
    assert 95e9 < s.param_count() < 120e9         # 16 full experts
    # top-1 of 16 experts, no shared expert modelled -> ~11B active
    assert 9e9 < s.active_param_count() < 20e9
