"""Model-zoo payloads: ModelSpec resolution, engine parity, accounting.

Coverage map:

* ``arch="hfl-cnn"`` (the default) — bitwise parity with the pre-spec
  engines: same init leaves, same ``cnn_apply`` function object (same
  static-jit cache key), identical one-round output when the old inline
  recipe is replayed against ``round_step`` directly.
* n_classes threading — the clustering auxiliary models take
  ``fed.n_classes`` (a 4-class world prices ``aux_bits`` from a 4-class
  head; an earlier revision silently defaulted to 10).
* every registry smoke arch — one ``HFLFramework`` round (the
  ``round_step`` engine) plus a fused single-dispatch sweep matching the
  per-round host loop, on the synthetic sequence task. The
  ``HFL_SMOKE_ARCHS`` families (dense/ssm/moe) run in tier-1; the rest
  of the registry is slow-marked for the weekly model-zoo-parity lane.
* ``evaluate_in_batches`` — padded-tail chunking is exact (one traced
  program per chunk shape, chunked == unchunked accuracy).
* ``message_bits()`` / codecs on embedding and MoE leaf shapes.
"""
import numpy as np
import pytest

_N, _M, _H = 8, 2, 4
_TIER1_ARCHS = ("mistral-nemo-12b", "mamba2-2.7b", "qwen3-moe-235b-a22b")


def _image_world(n_classes=10, seed=0):
    from repro.core.cost_model import SystemParams, sample_population
    from repro.data import make_dataset, partition_noniid

    sp = SystemParams(n_devices=_N, n_edges=_M)
    pop = sample_population(sp, seed=seed)
    if n_classes == 10:
        X, y, Xt, yt = make_dataset("fmnist_syn", n_train=240, n_test=64,
                                    seed=seed)
    else:   # random pixels are fine: these worlds only pin shapes/pricing
        rng = np.random.default_rng(seed)
        X = rng.random((240, 28, 28, 1)).astype(np.float32)
        y = rng.integers(0, n_classes, 240).astype(np.int32)
        Xt = rng.random((32, 28, 28, 1)).astype(np.float32)
        yt = rng.integers(0, n_classes, 32).astype(np.int32)
    fed = partition_noniid(X, y, Xt, yt, n_devices=_N, size_range=(6, 10),
                           n_classes=n_classes, seed=seed)
    return sp, pop, fed


def _seq_world(arch, seed=0):
    from repro.configs.registry import get_smoke_config
    from repro.core.cost_model import SystemParams, sample_population
    from repro.data import make_seq_dataset, partition_noniid

    vocab = min(257, get_smoke_config(arch).vocab_size)
    sp = SystemParams(n_devices=_N, n_edges=_M)
    pop = sample_population(sp, seed=seed)
    X, y, Xt, yt = make_seq_dataset(n_train=240, n_test=64, seed=seed,
                                    vocab_size=vocab)
    fed = partition_noniid(X, y, Xt, yt, n_devices=_N, size_range=(6, 10),
                           seed=seed)
    return sp, pop, fed


# ------------------------------------------------- hfl-cnn bitwise parity

def test_registry_spec_identity_and_default():
    from repro.configs.registry import (ARCH_IDS, HFL_SMOKE_ARCHS,
                                        get_hfl_spec)
    from repro.models import cnn

    spec = get_hfl_spec("hfl-cnn")
    assert spec is get_hfl_spec("hfl-cnn")          # cached: same object
    assert spec.apply_fn is cnn.cnn_apply           # same jit cache key
    assert spec.mini_apply_fn is cnn.mini_apply
    for arch in ARCH_IDS:
        s = get_hfl_spec(arch)
        assert s is get_hfl_spec(arch)
        assert s.apply_fn == get_hfl_spec(arch).apply_fn
    assert set(HFL_SMOKE_ARCHS) <= {"hfl-cnn", *ARCH_IDS}
    with pytest.raises(KeyError):
        get_hfl_spec("no-such-arch")


def test_hfl_cnn_bitwise_parity_with_pre_spec_engines():
    """The default arch replays the pre-spec construction bit for bit:
    identical init leaves in all three engines, identical one-round
    params when the old inline recipe drives ``round_step`` directly."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core.assignment import GeoAssigner
    from repro.core.async_engine import AsyncConfig, AsyncHFLEngine
    from repro.core.framework import (FrameworkConfig, HFLFramework,
                                      round_step)
    from repro.core.hfl import evaluate_in_batches, pad_device_data
    from repro.core.scheduling import FedAvgScheduler
    from repro.core.sweep import SweepRunner
    from repro.models import cnn
    from repro.utils import tree_bytes

    sp, pop, fed = _image_world()
    cfg = FrameworkConfig(scheduler="fedavg", assigner="geo", H=_H,
                          alloc_steps=25, max_iters=1)
    fw = HFLFramework(sp, pop, fed, cfg)
    rec = fw.run_round(1)

    # --- init parity (framework / async / sweep), pre-spec recipes inline
    key = jax.random.PRNGKey(cfg.seed)
    k_model, _, _ = jax.random.split(key, 3)
    hw, ch = fed.X_test.shape[1:3], fed.X_test.shape[3]
    ref = cnn.cnn_init(k_model, hw, ch, fed.n_classes)

    fw2 = HFLFramework(sp, pop, fed, cfg)
    for a, b in zip(jax.tree.leaves(ref),
                    jax.tree.leaves(fw2.model_params)):
        assert (a == b).all()
    assert fw2.apply_fn is cnn.cnn_apply

    eng = AsyncHFLEngine(sp, pop, fed, AsyncConfig(H=_H, alloc_steps=25))
    for a, b in zip(jax.tree.leaves(ref),
                    jax.tree.leaves(eng.model_params)):
        assert (a == b).all()

    runner = SweepRunner(sp, [(pop, fed)] * 2, alloc_steps=25)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[cnn.cnn_init(k, hw, ch, fed.n_classes) for k in keys])
    for a, b in zip(jax.tree.leaves(stacked),
                    jax.tree.leaves(runner.params0)):
        assert (a == b).all()
    assert runner.apply_fn is cnn.cnn_apply

    # --- one-round parity: the old engine's exact call, replayed
    sp_r = dataclasses.replace(sp, model_bits=float(tree_bytes(ref) * 8))
    rng = np.random.default_rng(cfg.seed)
    sched = np.asarray(FedAvgScheduler(fed.n_devices, _H).schedule(rng))
    assign, _ = GeoAssigner(sp_r).assign(pop, sched, rng)
    X, y, mask = pad_device_data(fed)
    (new_params, _), (T_i, E_i, _, _, _, _) = round_step(
        cnn.cnn_apply, sp_r, (ref, (None, None)), pop.u[sched],
        pop.D[sched], pop.p[sched], pop.g[sched], pop.g_cloud, pop.B_m,
        X[sched], y[sched], mask[sched],
        pop.D[sched], jnp.asarray(np.asarray(assign)), cfg.lr,
        M=pop.n_edges, L=sp.L, Q=sp.Q, alloc_steps=cfg.alloc_steps)
    for a, b in zip(jax.tree.leaves(new_params),
                    jax.tree.leaves(fw.model_params)):
        assert (a == b).all()
    assert rec["T_i"] == float(T_i) and rec["E_i"] == float(E_i)
    assert rec["acc"] == evaluate_in_batches(cnn.cnn_apply, new_params,
                                             fed.X_test, fed.y_test)


# ----------------------------------------------- n_classes bug regression

def test_clustering_aux_models_take_fed_n_classes():
    """4-class world: clustering heads and aux_bits pricing follow
    ``fed.n_classes`` (the pre-fix path silently built 10-class heads)."""
    import jax

    from repro.configs.registry import get_hfl_spec
    from repro.core.sweep import build_scheduler
    from repro.core.scheduling import IKCScheduler, VKCScheduler
    from repro.models import cnn
    from repro.utils import tree_bytes

    sp, pop, fed4 = _image_world(n_classes=4)
    spec = get_hfl_spec("hfl-cnn")
    key = jax.random.PRNGKey(0)

    assert spec.init_fn(key, fed4)["fc2"].shape == (226, 4)
    assert spec.mini_init_fn(key, fed4)["fc"].shape[1] == 4

    sched_i, stats_i = build_scheduler("ikc", fed4, sp, _H, pop=pop)
    assert isinstance(sched_i, IKCScheduler)
    assert stats_i["aux_bits"] == tree_bytes(cnn.mini_init(key, 4)) * 8
    assert stats_i["aux_bits"] != tree_bytes(cnn.mini_init(key, 10)) * 8

    sched_v, stats_v = build_scheduler("vkc", fed4, sp, _H, pop=pop)
    assert isinstance(sched_v, VKCScheduler)
    full4 = cnn.cnn_init(key, (28, 28), 1, 4)
    full10 = cnn.cnn_init(key, (28, 28), 1, 10)
    assert stats_v["aux_bits"] == tree_bytes(full4) * 8
    assert stats_v["aux_bits"] != tree_bytes(full10) * 8


# --------------------------------------------- per-arch engine coverage

def _zoo_round_and_fused_parity(arch):
    """One framework round (the ``round_step`` engine) + fused-vs-host
    sweep parity on the synthetic sequence task."""
    from repro.core.framework import FrameworkConfig, HFLFramework
    from repro.core.sweep import SweepRunner, build_scheduler

    sp, pop, fed = _seq_world(arch)
    cfg = FrameworkConfig(arch=arch, scheduler="fedavg", assigner="geo",
                          H=_H, lr=0.3, alloc_steps=25, max_iters=1)
    fw = HFLFramework(sp, pop, fed, cfg)
    rec = fw.run_round(1)
    assert np.isfinite(rec["T_i"]) and np.isfinite(rec["E_i"])
    assert 0.0 <= rec["acc"] <= 1.0

    def run(fused):
        runner = SweepRunner(sp, [(pop, fed)], lr=0.3, alloc_steps=25,
                             arch=arch)
        scheds = [build_scheduler("fedavg", fed, sp, _H, seed=0)]
        return runner.run(scheds, 2, assign="geo", fused=fused)

    host, fused = run(False), run(True)
    assert fused["n_dispatches"] == 1
    for k in ("T_i", "E_i", "obj"):
        np.testing.assert_allclose(host[k], fused[k], rtol=1e-4, atol=1e-6,
                                   err_msg=k)
    np.testing.assert_allclose(host["acc"], fused["acc"], atol=0.09)
    # the task is learnable: two rounds beat the 10-class chance rate
    assert host["acc"][0, -1] > 0.2


@pytest.mark.parametrize("arch", _TIER1_ARCHS)
def test_zoo_arch_round_and_fused_parity(arch):
    _zoo_round_and_fused_parity(arch)


@pytest.mark.slow
@pytest.mark.parametrize("arch", [
    a for a in ("jamba-1.5-large-398b", "internvl2-26b", "chatglm3-6b",
                "musicgen-medium", "llama4-scout-17b-a16e", "llama3-405b",
                "mistral-large-123b")])
def test_zoo_arch_round_and_fused_parity_weekly(arch):
    _zoo_round_and_fused_parity(arch)


def test_async_engine_seq_arch():
    """The event-driven engine trains a non-CNN payload."""
    from repro.core.async_engine import AsyncConfig, AsyncHFLEngine

    sp, pop, fed = _seq_world("mamba2-2.7b")
    eng = AsyncHFLEngine(sp, pop, fed,
                         AsyncConfig(arch="mamba2-2.7b", H=_H, lr=0.3,
                                     alloc_steps=25))
    r1, r2 = eng.step_round(), eng.step_round()
    assert 0.0 <= r1["acc"] <= 1.0 and r2["acc"] > 0.15


def test_seq_ikc_clustering_recovers_majority_classes():
    """IKC's sequence mini model clusters devices by majority class."""
    from repro.core.sweep import build_scheduler
    from repro.core.scheduling import IKCScheduler

    sp, pop, fed = _seq_world("mamba2-2.7b")
    sched, stats = build_scheduler("ikc", fed, sp, _H, pop=pop,
                                   arch="mamba2-2.7b")
    assert isinstance(sched, IKCScheduler)
    assert stats["ari"] > 0.3
    assert 0 < stats["aux_bits"] < 1e6


# ------------------------------------------------- evaluate_in_batches

def test_evaluate_in_batches_padded_tail():
    """Chunked == unchunked accuracy, exactly; the ragged tail reuses the
    full-chunk program instead of tracing a second one."""
    import jax

    from repro.core.hfl import evaluate_accuracy, evaluate_in_batches
    from repro.models import cnn

    rng = np.random.default_rng(0)
    X = rng.random((130, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, 130).astype(np.int32)
    params = cnn.cnn_init(jax.random.PRNGKey(0), (28, 28), 1, 10)

    shapes = []

    def apply(p, x):
        shapes.append(x.shape)      # appended once per trace
        return cnn.cnn_apply(p, x)

    acc_chunked = evaluate_in_batches(apply, params, X, y, batch=64)
    assert shapes == [(64, 28, 28, 1)]      # one trace, tail included
    acc_full = evaluate_in_batches(cnn.cnn_apply, params, X, y, batch=130)
    assert acc_chunked == acc_full          # integer counting: exact
    ref = float(evaluate_accuracy(cnn.cnn_apply, params, X, y))
    np.testing.assert_allclose(acc_chunked, ref, atol=1e-6)
    # batch > n must clamp, not pad a mostly-dead chunk
    assert evaluate_in_batches(cnn.cnn_apply, params, X[:5], y[:5],
                               batch=512) == \
        evaluate_in_batches(cnn.cnn_apply, params, X[:5], y[:5], batch=5)


# ------------------------------------- codec accounting on zoo payloads

def test_message_bits_on_embedding_and_moe_leaves():
    """message_bits() prices embedding/MoE leaf shapes exactly: raw =
    32 bits/elem, bf16 = 16 bits/elem, int8 = 8 bits/elem + one f32
    scale per leaf, topk = k * (32 + ceil(log2 n)) per leaf."""
    import math

    import jax

    from repro.configs.registry import get_hfl_spec
    from repro.core import compression as comp

    spec = get_hfl_spec("qwen3-moe-235b-a22b")
    sp, pop, fed = _seq_world("qwen3-moe-235b-a22b")
    params = spec.init_fn(jax.random.PRNGKey(0), fed)
    leaves = jax.tree.leaves(params)
    sizes = [leaf.size for leaf in leaves]
    n_elem = sum(sizes)
    # the payload really has embedding + stacked-expert leaves
    assert any(leaf.ndim >= 4 for leaf in leaves)           # MoE stacks
    assert params["embed"].shape[0] >= 256                  # vocab rows

    raw = comp.message_bits(comp.CompressionConfig(), params)
    assert raw == 32 * n_elem
    bf16 = comp.message_bits(comp.CompressionConfig(codec="bf16_delta"),
                             params)
    assert bf16 == 16 * n_elem and raw / bf16 == 2.0
    int8 = comp.message_bits(comp.CompressionConfig(codec="int8"), params)
    assert int8 == 8 * n_elem + 32 * len(leaves)
    frac = 0.05
    topk = comp.message_bits(
        comp.CompressionConfig(codec="topk", topk_frac=frac), params)
    expect = sum(min(n, max(1, int(round(frac * n)))) *
                 (32 + max(1, math.ceil(math.log2(n)))) for n in sizes)
    assert topk == expect


def test_int8_roundtrip_on_embedding_leaf():
    """The int8 codec's decode error is bounded by one quantisation step
    per row on an embedding-shaped leaf."""
    import jax

    from repro.core import compression as comp

    cfg = comp.CompressionConfig(codec="int8")
    key = jax.random.PRNGKey(0)
    emb = jax.random.normal(key, (257, 64)) * 0.02
    rows = np.asarray(emb).reshape(4, -1)       # 4 messages
    q, scale = comp.encode_rows(cfg, key, rows)
    dec = np.asarray(comp.decode_rows(cfg, q, scale))
    err = np.abs(dec - rows).max(axis=1)
    assert (err <= np.asarray(scale) * (1 + 1e-6)).all()
