"""granite-4.0-h-micro as a federated LoRA payload, against the plain
reference (``bench/reference_lm.py``) at smoke size on the CPU.

The reference runs in float32 at ``default_matmul_precision("highest")``
on the same seeded weights (adapters' ``B`` drawn small and nonzero, so
that both ``A`` and ``B`` get gradients); the program runs the smoke
config in float32. What differs is the order of operations only: the
chunked SSD against the token-by-token recurrence, additive against
``where`` masking, fused against separate projections. Tolerances:

- logits: rtol 2e-4, atol 2e-5 (float32 round-off through 10 layers);
- one local step, one round: the adapters' change agrees to 1e-3 of
  the change's norm, per leaf (the change is the difference of two
  nearby float32 numbers, so its relative round-off is larger).

The payload's path through the engines is pinned in
``test_lora_engine.py``.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import program_lm, reference_lm  # noqa: E402

ARCH = "granite-4.0-h-micro"
H, M, DMAX, SEQ, C = 4, 2, 3, 32, 5


def _dims():
    from repro.configs.registry import get_smoke_config
    cfg = get_smoke_config(ARCH)
    kinds = ["attention" if cfg.layer_kind(i) == "attn" else "mamba"
             for i in range(cfg.n_layers)]
    config = {
        "layer_types": kinds, "num_hidden_layers": cfg.n_layers,
        "hidden_size": cfg.d_model, "vocab_size": cfg.vocab_size,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "shared_intermediate_size": cfg.d_ff,
        "mamba_n_heads": cfg.ssm.n_heads(cfg.d_model),
        "mamba_d_head": cfg.ssm.head_dim, "mamba_d_state": cfg.ssm.d_state,
        "mamba_n_groups": cfg.ssm.n_groups, "mamba_d_conv": cfg.ssm.conv_width,
        "attention_multiplier": cfg.attn_scale,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "rms_norm_eps": cfg.norm_eps, "lora": {"rank": 16, "alpha": 32.0},
        "data": {"n_classes": C}}
    return reference_lm.dims(config)


@pytest.fixture(scope="module")
def setup():
    """Seeded weights in both layouts, and a 4-lane, 2-edge cohort."""
    from repro.configs.registry import get_hfl_spec
    m = _dims()
    spec = get_hfl_spec(ARCH)
    weights = reference_lm.init(jax.random.PRNGKey(1), m)
    ad = reference_lm.adapters_init(jax.random.PRNGKey(2), m, b_std=0.05)
    frozen = program_lm.frozen(weights, m, jnp.float32)
    params = program_lm.params(ad, m)
    rng = np.random.default_rng(3)
    X = rng.integers(0, m.vocab, (H, DMAX, SEQ)).astype(np.int32)
    y = rng.integers(0, C, (H, DMAX)).astype(np.int32)
    mask = np.ones((H, DMAX), np.float32)
    mask[0, 2] = mask[3, 1:] = 0.0
    sizes = mask.sum(axis=1)
    assign = np.array([0, 1, 0, 1], np.int32)
    return dict(m=m, spec=spec, weights=weights, ad=ad, frozen=frozen,
                params=params, X=X, y=y, mask=mask, sizes=sizes,
                assign=assign)


def _close_change(prog, ref, start, m, rtol=1e-3):
    """Per leaf: |change_prog - change_ref| <= rtol * |change_ref|."""
    got = reference_lm.flat(program_lm.adapters(prog, m))
    want, p0 = reference_lm.flat(ref), reference_lm.flat(start)
    for k in want:
        d_ref = want[k] - p0[k]
        err = np.linalg.norm(got[k] - want[k])
        assert err <= rtol * np.linalg.norm(d_ref) + 1e-7, (k, err)
        assert np.linalg.norm(d_ref) > 0, k


def test_granite_config_is_published_and_stage_is_one_period():
    from repro.configs.registry import get_config, get_hfl_spec
    from repro.configs import granite_4_0_h_micro as g
    cfg = get_config(ARCH)
    attn = [i for i in range(cfg.n_layers) if cfg.layer_kind(i) == "attn"]
    assert attn == [5, 15, 25, 35]
    assert cfg.ssm.n_heads(cfg.d_model) == 64 and cfg.ssm.conv_bias
    assert not cfg.use_rope and cfg.attn_scale == 0.015625
    stage = g.stage_config()
    assert stage.n_layers == 10 and stage.d_model == cfg.d_model
    spec = get_hfl_spec(ARCH, "stage")
    assert spec is get_hfl_spec(ARCH, "stage")

    class Fed:
        n_classes = 20
    key = jax.random.PRNGKey(0)
    n = lambda t: sum(a.size for a in jax.tree.leaves(t))  # noqa: E731
    frozen = jax.eval_shape(lambda k: spec.frozen_init_fn(k, Fed), key)
    params = jax.eval_shape(lambda k: spec.init_fn(k, Fed), key)
    assert n(params) == 8_713_216 + 2048 * 20      # adapters + head
    assert n(frozen["embed"]) == 100352 * 2048
    # 9 x 76,182,976 (Mamba-2 + MLP) + 60,821,504 (attention + MLP)
    # + the final norm, as the published layout counts them
    assert n(frozen) - n(frozen["embed"]) == 746_470_336
    assert {a.dtype for a in jax.tree.leaves(frozen["blocks"])} == {
        jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}
    assert {a.dtype for a in jax.tree.leaves(params)} == {
        jnp.dtype(jnp.float32)}


def test_logits_match_the_reference(setup):
    s = setup
    X = jnp.asarray(s["X"][0])
    got = s["spec"].apply_fn(s["params"], X, s["frozen"])
    with jax.default_matmul_precision("highest"):
        want = reference_lm.logits(s["weights"], s["ad"], X, s["m"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_mamba_convolves_with_bias_then_applies_silu(setup):
    """The published order, conv(xBC) + bias then SiLU, against the
    reference's Mamba-2 mixer (adapters zero)."""
    from repro.configs.registry import get_smoke_config
    from repro.models import mamba2
    s, m = setup, setup["m"]
    cfg = get_smoke_config(ARCH)
    w = s["weights"]["layers"][0]
    zero = jax.tree.map(jnp.zeros_like, s["ad"]["layers"][0])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, m.d))
    mix = jax.tree.map(lambda a: a[0], s["frozen"]["blocks"][0]["mix"])
    got = mamba2.mamba2_forward(mix, x, cfg)
    with jax.default_matmul_precision("highest"):
        want = reference_lm._mamba(w, zero, x, m)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # the former order, SiLU then conv, is another function
    swapped = dict(mix, conv_x_bias=jnp.zeros_like(mix["conv_x_bias"]))
    assert not np.allclose(mamba2.mamba2_forward(swapped, x, cfg), want,
                           atol=1e-3)
    # decode token by token through the cache gives the same outputs
    cache = mamba2.init_ssm_cache(cfg, 2)
    outs = []
    for t in range(SEQ):
        o, cache = mamba2.mamba2_decode(mix, x[:, t:t + 1], cache, cfg)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), got,
                               rtol=2e-4, atol=2e-5)


def test_one_local_step_matches_the_reference(setup):
    from repro.core.local_train import local_sgd
    s, m = setup, setup["m"]
    X, y, mask = (jnp.asarray(s[k][0]) for k in ("X", "y", "mask"))
    got = jax.jit(local_sgd, static_argnums=(0, 5, 6))(
        s["spec"].apply_fn, s["params"], X, y, mask, 1, 0.5, s["frozen"])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference_lm.local_gd, static_argnums=(5, 6, 7))(
            s["ad"], s["weights"], X, y, mask, 1, 0.5, m)
    _close_change(got, want, s["ad"], m)


def test_one_round_matches_the_reference(setup):
    from repro.core.hfl import hfl_global_iteration
    s, m = setup, setup["m"]
    args = [jnp.asarray(s[k]) for k in ("X", "y", "mask", "sizes",
                                        "assign")]
    got = hfl_global_iteration(s["spec"].apply_fn, s["params"], *args,
                               M=M, L=1, Q=2, lr=0.5, frozen=s["frozen"])
    with jax.default_matmul_precision("highest"):
        want = reference_lm.hfl_round(s["weights"], s["ad"], *args, M=M,
                                      L=1, Q=2, lr=0.5, block=2, m=m)
    _close_change(got, want, s["ad"], m)


def test_ssd_gradients_stay_finite_when_a_chunk_decays_past_float32(setup):
    """Decays of e^-200 across a chunk: the chunked SSD's gradients stay
    finite (the masked upper triangle once overflowed exp and gave
    inf * 0 = NaN) and match the reference recurrence's."""
    from repro.configs.registry import get_smoke_config
    from repro.models import mamba2
    s, m = setup, setup["m"]
    cfg = get_smoke_config(ARCH)
    mix = jax.tree.map(lambda a: a[0], s["frozen"]["blocks"][0]["mix"])
    mix = dict(mix, dt_bias=jnp.full_like(mix["dt_bias"], 3.0),
               A_log=jnp.full_like(mix["A_log"], 1.0))
    w = dict(s["weights"]["layers"][0], dt_bias=mix["dt_bias"],
             A_log=mix["A_log"])
    zero = jax.tree.map(jnp.zeros_like, s["ad"]["layers"][0])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, m.d))
    got = jax.grad(lambda a: mamba2.mamba2_forward(
        dict(mix, A_log=a), x, cfg).sum())(mix["A_log"])
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda a: reference_lm._mamba(
            dict(w, A_log=a), zero, x, m).sum())(w["A_log"])
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
