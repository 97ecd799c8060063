"""granite-4.0-h-micro through the HFL engines at smoke size on the CPU:
the compiled round holds no per-lane or per-edge copy of the frozen base;
only the trainable tree (adapters and head) is differentiated, trained,
aggregated and priced; the base is bitwise unchanged by a round; lanes
trained in chunks give what one vmap gives; the engines that cannot take
a frozen base refuse it. Weights and cohort as in ``test_lora_payload``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_lora_payload import ARCH, H, M, setup  # noqa: F401


def test_round_holds_no_copy_of_the_base_per_lane_or_edge(setup):
    """The lowered round program has no (H, ...) or (M, ...) array
    shaped like a leaf of the base (one not shaped like an adapter)."""
    import re

    from repro.core import cost_model as cm
    from repro.core.framework import round_step
    s = setup
    f32 = jnp.float32
    sp = cm.SystemParams(n_devices=8, n_edges=M, L=2, Q=2)
    g = jnp.ones((H, M), f32)
    text = round_step.lower(
        s["spec"].apply_fn, sp, (s["params"], (None, None)), jnp.ones(H, f32),
        jnp.ones(H, f32), jnp.ones(H, f32), g, jnp.ones(M, f32),
        jnp.ones(M, f32), jnp.asarray(s["X"]), jnp.asarray(s["y"]),
        jnp.asarray(s["mask"]), jnp.asarray(s["sizes"]),
        jnp.asarray(s["assign"]), 0.5, M=M, L=2, Q=2, alloc_steps=5,
        frozen=s["frozen"]).as_text()
    trainable = {a.shape[1:] for a in jax.tree.leaves(s["params"])}
    base = {a.shape for a in jax.tree.leaves(s["frozen"])
            if a.ndim >= 2 and a.shape[1:] not in trainable}
    assert (s["m"].vocab, s["m"].d) in base
    types = set(re.findall(r"tensor<([0-9x]+)x[fi]\d+>", text))
    for shape in base:
        for lead in (H, M):
            key = "x".join(map(str, (lead,) + shape))
            assert key not in types, key
    # the base itself does enter the program, once
    assert "x".join(map(str, (s["m"].vocab, s["m"].d))) in types


def _world(seed=0):
    from repro.core.cost_model import SystemParams, sample_population
    from repro.data import make_seq_dataset, partition_noniid
    sp = SystemParams(n_devices=8, n_edges=M)
    pop = sample_population(sp, seed=seed)
    X, y, Xt, yt = make_seq_dataset(n_train=240, n_test=32, seed=seed,
                                    vocab_size=257)
    fed = partition_noniid(X, y, Xt, yt, n_devices=8, size_range=(2, 4),
                           seed=seed)
    return sp, pop, fed


@pytest.fixture(scope="module")
def framework():
    from repro.core.framework import FrameworkConfig, HFLFramework
    sp, pop, fed = _world()
    cfg = FrameworkConfig(arch=ARCH, scheduler="fedavg", assigner="geo",
                          H=H, lr=0.3, alloc_steps=25, max_iters=1)
    return HFLFramework(sp, pop, fed, cfg)


def test_round_trains_and_prices_the_adapters_alone(framework):
    from repro.utils import tree_bytes
    fw = framework
    base0 = jax.tree.map(np.array, fw.frozen)
    p0 = jax.tree.map(np.array, fw.model_params)
    rec = fw.run_round(1)
    # the base is bitwise unchanged; every trainable leaf moved
    for a, b in zip(jax.tree.leaves(base0), jax.tree.leaves(fw.frozen)):
        assert (a == np.asarray(b)).all()
    moved = [not np.array_equal(a, np.asarray(b)) for a, b in
             zip(jax.tree.leaves(p0), jax.tree.leaves(fw.model_params))]
    assert all(moved[:-1]) or sum(moved) > len(moved) // 2
    assert jax.tree.structure(fw.model_params) == jax.tree.structure(p0)
    # priced and sent: the adapters and the head, not the base
    n_train = sum(a.size for a in jax.tree.leaves(fw.model_params))
    assert fw.model_bits == 32 * n_train == tree_bytes(fw.model_params) * 8
    assert fw.sp.model_bits == fw.model_bits
    assert rec["uplink_bits"] == fw.model_bits
    assert rec["frozen_bytes"] == tree_bytes(fw.frozen) > fw.model_bits / 8
    S = fw.X.shape[2]
    assert rec["tokens_padded"] == fw.sp.L * fw.sp.Q * H * fw.X.shape[1] * S
    assert 0 < rec["tokens_valid"] <= rec["tokens_padded"]
    assert np.isfinite(rec["T_i"]) and 0.0 <= rec["acc"] <= 1.0


def test_local_sgd_differentiates_the_trainable_tree_only(setup):
    """The gradient tree is the trainable tree's; the base is an
    argument the gradient does not reach."""
    from repro.core.local_train import masked_loss
    s = setup
    X, y, mask = (jnp.asarray(s[k][0]) for k in ("X", "y", "mask"))
    grad = jax.grad(functools.partial(masked_loss, s["spec"].apply_fn))
    g = jax.eval_shape(grad, s["params"], X, y, mask, s["frozen"])
    assert jax.tree.structure(g) == jax.tree.structure(s["params"])
    jaxpr = jax.make_jaxpr(grad)(s["params"], X, y, mask, s["frozen"])
    outs = {tuple(v.aval.shape) for v in jaxpr.jaxpr.outvars}
    emb = tuple(s["frozen"]["embed"].shape)
    assert emb not in outs


@pytest.mark.parametrize("chunk", [2, 3])
def test_lanes_train_in_chunks_as_one_vmap_would(setup, chunk):
    """Lanes trained ``chunk`` at a time (3: the cohort padded up to
    whole chunks) give what the whole cohort gives in one vmap."""
    from repro.core.local_train import cohort_local_sgd
    s = setup
    apply_fn = s["spec"].apply_fn
    p = jax.tree.map(lambda a: jnp.broadcast_to(a, (H,) + a.shape),
                     s["params"])
    args = [jnp.asarray(s[k]) for k in ("X", "y", "mask")]
    train = jax.jit(cohort_local_sgd, static_argnums=(0, 5, 6, 8))
    a = train(apply_fn, p, *args, 1, 0.5, s["frozen"], 0)
    b = train(apply_fn, p, *args, 1, 0.5, s["frozen"], chunk)
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert u.shape == v.shape
        np.testing.assert_allclose(u, v, rtol=1e-5, atol=1e-6)


def test_framework_holds_the_callers_base_and_adapters(setup):
    """A spec handed to ``HFLFramework`` is the payload: its base and
    trainable tree are held as given, none is drawn, and the priced
    payload is the given trainable tree."""
    import dataclasses

    from repro.core.framework import FrameworkConfig, HFLFramework
    from repro.utils import tree_bytes
    s = setup
    sp, pop, fed = _world()
    spec = dataclasses.replace(
        s["spec"], frozen_init_fn=lambda key, fed: s["frozen"],
        init_fn=lambda key, fed: s["params"])
    fw = HFLFramework(sp, pop, fed, FrameworkConfig(
        arch=ARCH, scheduler="fedavg", assigner="geo", H=H), spec=spec)
    assert fw.spec is spec and fw.apply_fn is s["spec"].apply_fn
    for a, b in zip(jax.tree.leaves(fw.frozen), jax.tree.leaves(s["frozen"])):
        assert a is b
    assert fw.model_params is s["params"]
    assert fw.model_bits == tree_bytes(s["params"]) * 8


def test_engines_without_a_frozen_base_refuse_the_payload():
    from repro.core.async_engine import AsyncConfig, AsyncHFLEngine
    from repro.core.framework import FrameworkConfig, HFLFramework
    from repro.core.sweep import SweepRunner
    sp, pop, fed = _world()
    with pytest.raises(ValueError, match="frozen base"):
        AsyncHFLEngine(sp, pop, fed, AsyncConfig(arch=ARCH, H=H))
    with pytest.raises(ValueError, match="frozen base"):
        SweepRunner(sp, [(pop, fed)], arch=ARCH)
    with pytest.raises(ValueError, match="frozen base"):
        HFLFramework(sp, pop, fed, FrameworkConfig(arch=ARCH, scheduler="vkc",
                                                   H=H))
