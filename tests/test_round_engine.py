"""Fused batched round engine: parity against the sequential oracles.

The batched allocator (``allocate_all_edges``) and the fused
``round_step`` replace the seed's per-edge Python loops; these tests pin
them to the original per-edge path on identical inputs.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cost_model as cm
from repro.core import resource as ra
from repro.core.framework import (FrameworkConfig, HFLFramework,
                                  round_step)
from repro.core.hfl import hfl_global_iteration
from repro.core.sweep import SweepRunner

ALLOC_STEPS = 120


def _per_edge_oracle(sp, pop, sched, assign, steps):
    """The seed's sequential loop: M separate allocate calls."""
    outs = []
    for m in range(pop.n_edges):
        mask = jnp.asarray(assign == m)
        outs.append(ra.allocate(sp, pop.u[sched], pop.D[sched],
                                pop.p[sched], pop.g[sched, m],
                                pop.B_m[m], mask, steps=steps))
    return outs


@pytest.mark.parametrize("seed", [1, 7])
def test_allocate_all_edges_matches_per_edge_loop(seed):
    """Batched solve == per-edge loop on b, f, T_edge, E_edge to 1e-5,
    including populations where some edges receive no devices."""
    sp = cm.SystemParams(n_devices=18, n_edges=4)
    pop = cm.sample_population(sp, seed=seed)
    sched = np.arange(18)
    rng = np.random.default_rng(seed)
    # only 3 of 4 edges used -> edge 3 is empty
    assign = rng.integers(0, 3, 18)

    seq = _per_edge_oracle(sp, pop, sched, assign, ALLOC_STEPS)
    bat = ra.allocate_all_edges(sp, pop, sched, assign, steps=ALLOC_STEPS)

    np.testing.assert_allclose(np.stack([np.asarray(r.b) for r in seq]),
                               np.asarray(bat.b), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.stack([np.asarray(r.f) for r in seq]),
                               np.asarray(bat.f), rtol=1e-5)
    np.testing.assert_allclose([float(r.T_edge) for r in seq],
                               np.asarray(bat.T_edge), rtol=1e-5)
    np.testing.assert_allclose([float(r.E_edge) for r in seq],
                               np.asarray(bat.E_edge), rtol=1e-5)
    # empty edge contributes nothing
    assert float(bat.T_edge[3]) == 0.0 and float(bat.obj[3]) == 0.0


def test_select_device_allocation_routes_rows():
    sp = cm.SystemParams(n_devices=10, n_edges=3)
    pop = cm.sample_population(sp, seed=2)
    sched = np.arange(10)
    assign = np.asarray([0, 1, 2, 0, 1, 2, 0, 1, 2, 0])
    bat = ra.allocate_all_edges(sp, pop, sched, assign, steps=60)
    b, f = ra.select_device_allocation(bat, assign)
    for h in range(10):
        assert float(b[h]) == float(bat.b[assign[h], h])
        assert float(f[h]) == float(bat.f[assign[h], h])


def _linear_apply(params, X):
    return X.reshape(X.shape[0], -1) @ params["w"]


def test_fused_round_step_matches_sequential_components():
    """One fused round_step == the sequential composition (per-edge
    allocate loop -> round_cost -> hfl_global_iteration) on T_i, E_i and
    the trained parameters, at fixed seed."""
    sp = cm.SystemParams(n_devices=12, n_edges=3)
    pop = cm.sample_population(sp, seed=4)
    rng = np.random.default_rng(4)
    sched = np.arange(12)
    assign = rng.integers(0, 3, 12)
    H, Dmax = 12, 6
    X = jnp.asarray(rng.normal(0, 1, (H, Dmax, 2, 2, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 3, (H, Dmax)).astype(np.int32))
    mask = jnp.ones((H, Dmax), jnp.float32)
    w0 = {"w": jnp.asarray(rng.normal(0, 0.1, (4, 3)).astype(np.float32))}

    # sequential oracle
    seq = _per_edge_oracle(sp, pop, sched, assign, ALLOC_STEPS)
    b = np.zeros(H)
    f = np.zeros(H)
    for m, res in enumerate(seq):
        sel = assign == m
        b[sel] = np.asarray(res.b)[sel]
        f[sel] = np.asarray(res.f)[sel]
    T_i, E_i, _, _ = cm.round_cost(sp, pop, jnp.asarray(sched),
                                   jnp.asarray(assign), jnp.asarray(b),
                                   jnp.asarray(f))
    w_seq, _ = hfl_global_iteration(_linear_apply, w0, X, y, mask,
                                    pop.D[sched], jnp.asarray(assign),
                                    M=3, L=2, Q=2, lr=0.05)

    # fused engine
    (w_fused, _), (T_f, E_f, _, _, b_f, f_f) = round_step(
        _linear_apply, sp, (w0, (None, None)), pop.u[sched], pop.D[sched],
        pop.p[sched], pop.g[sched], pop.g_cloud, pop.B_m, X, y, mask,
        pop.D[sched], jnp.asarray(assign), 0.05, M=3, L=2, Q=2,
        alloc_steps=ALLOC_STEPS)

    np.testing.assert_allclose(float(T_i), float(T_f), rtol=1e-5)
    np.testing.assert_allclose(float(E_i), float(E_f), rtol=1e-5)
    np.testing.assert_allclose(b, np.asarray(b_f), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(f, np.asarray(f_f), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w_seq["w"]),
                               np.asarray(w_fused["w"]), rtol=1e-5,
                               atol=1e-7)


def _toy_round_inputs(seed=4, H=12, M=3, Dmax=6):
    sp = cm.SystemParams(n_devices=H, n_edges=M)
    pop = cm.sample_population(sp, seed=seed)
    rng = np.random.default_rng(seed)
    sched = np.arange(H)
    # leave edge M-1 empty: the kernel path must reproduce the einsum
    # path's empty-edge semantics (edge keeps its model, cloud weight 0)
    assign = rng.integers(0, M - 1, H)
    X = jnp.asarray(rng.normal(0, 1, (H, Dmax, 2, 2, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 3, (H, Dmax)).astype(np.int32))
    mask = jnp.ones((H, Dmax), jnp.float32)
    w0 = {"w": jnp.asarray(rng.normal(0, 0.1, (4, 3)).astype(np.float32))}
    return sp, pop, sched, assign, X, y, mask, w0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hfl_iteration_agg_kernel_matches_einsum(dtype):
    """Algorithm 1 with agg_kernel=True == the einsum oracle, including
    an empty edge and both model dtypes."""
    sp, pop, sched, assign, X, y, mask, w0 = _toy_round_inputs()
    w0 = {"w": w0["w"].astype(dtype)}
    outs = {}
    for ak in (False, True):
        w, _ = hfl_global_iteration(_linear_apply, w0, X.astype(dtype), y,
                                    mask, pop.D[sched], jnp.asarray(assign),
                                    M=3, L=2, Q=2, lr=0.05, agg_kernel=ak)
        outs[ak] = np.asarray(w["w"], np.float32)
    tol = 1e-5 if dtype == jnp.float32 else 0.03
    np.testing.assert_allclose(outs[True], outs[False], rtol=tol, atol=tol)


def test_round_step_agg_kernel_matches_einsum_oracle():
    """Fused round_step with the Pallas aggregation backend == the
    einsum backend on trained params AND the cost outputs (which must be
    untouched by the aggregation route)."""
    sp, pop, sched, assign, X, y, mask, w0 = _toy_round_inputs()
    outs = {}
    for ak in (False, True):
        (w, _), (T_i, E_i, _, _, b, f) = round_step(
            _linear_apply, sp, (w0, (None, None)), pop.u[sched], pop.D[sched],
            pop.p[sched], pop.g[sched], pop.g_cloud, pop.B_m, X, y, mask,
            pop.D[sched], jnp.asarray(assign), 0.05, M=3, L=2, Q=2,
            alloc_steps=ALLOC_STEPS, agg_kernel=ak)
        outs[ak] = (np.asarray(w["w"]), float(T_i), float(E_i),
                    np.asarray(b), np.asarray(f))
    np.testing.assert_allclose(outs[True][0], outs[False][0],
                               rtol=1e-5, atol=1e-6)
    # the cost subgraph is identical, but the two agg_kernel traces are
    # separate XLA compilations — tight tolerance, not bitwise equality
    np.testing.assert_allclose(outs[True][1], outs[False][1], rtol=1e-6)
    np.testing.assert_allclose(outs[True][2], outs[False][2], rtol=1e-6)
    np.testing.assert_allclose(outs[True][3], outs[False][3],
                               rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(outs[True][4], outs[False][4], rtol=1e-6)


def test_sweep_round_agg_kernel_vmapped_lanes():
    """The vmapped multi-lane round with agg_kernel=True (one lane-
    batched kernel launch per aggregation) == the einsum lanes."""
    from repro.core.sweep import sweep_round
    sp, pop, sched, assign, X, y, mask, w0 = _toy_round_inputs()
    S = 2
    rng = np.random.default_rng(11)
    stack = lambda a: jnp.stack([jnp.asarray(a)] * S)  # noqa: E731
    params_b = {"w": jnp.asarray(
        rng.normal(0, 0.1, (S, 4, 3)).astype(np.float32))}
    assign_b = jnp.asarray(np.stack([assign,
                                     rng.integers(0, 3, len(sched))]))
    args = (_linear_apply, sp, params_b, stack(pop.u), stack(pop.D),
            stack(pop.p), stack(pop.g), stack(pop.g_cloud), stack(pop.B_m),
            stack(X), stack(y), stack(mask), stack(pop.D), stack(sched),
            assign_b, 0.05)
    kw = dict(M=3, L=2, Q=2, alloc_steps=60)
    p_k, (T_k, E_k), _ = sweep_round(*args, **kw, agg_kernel=True)
    p_e, (T_e, E_e), _ = sweep_round(*args, **kw, agg_kernel=False)
    np.testing.assert_allclose(np.asarray(p_k["w"]), np.asarray(p_e["w"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(T_k), np.asarray(T_e), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(E_k), np.asarray(E_e), rtol=1e-6)


def test_sweep_round_done_mask_freezes_lane():
    """Per-lane early stop: a done lane's params pass through unchanged
    and its training-compute costs (T_i, E_i) come back zero, while live
    lanes are untouched by the mask."""
    from repro.core.sweep import sweep_round
    sp, pop, sched, assign, X, y, mask, w0 = _toy_round_inputs()
    S = 2
    rng = np.random.default_rng(13)
    stack = lambda a: jnp.stack([jnp.asarray(a)] * S)  # noqa: E731
    params_b = {"w": jnp.asarray(
        rng.normal(0, 0.1, (S, 4, 3)).astype(np.float32))}
    assign_b = jnp.asarray(np.stack([assign, assign]))
    args = (_linear_apply, sp, params_b, stack(pop.u), stack(pop.D),
            stack(pop.p), stack(pop.g), stack(pop.g_cloud), stack(pop.B_m),
            stack(X), stack(y), stack(mask), stack(pop.D), stack(sched),
            assign_b, 0.05)
    kw = dict(M=3, L=2, Q=2, alloc_steps=60)
    p_all, (T_all, E_all), _ = sweep_round(*args, **kw)
    p_msk, (T_msk, E_msk), _ = sweep_round(
        *args, **kw, done_b=jnp.asarray([True, False]))
    # lane 0 frozen: params unchanged, zero costs
    np.testing.assert_array_equal(np.asarray(p_msk["w"][0]),
                                  np.asarray(params_b["w"][0]))
    assert float(T_msk[0]) == 0.0 and float(E_msk[0]) == 0.0
    # lane 1 live: identical to the unmasked round
    np.testing.assert_allclose(np.asarray(p_msk["w"][1]),
                               np.asarray(p_all["w"][1]), rtol=1e-6)
    np.testing.assert_allclose(float(T_msk[1]), float(T_all[1]), rtol=1e-6)
    np.testing.assert_allclose(float(E_msk[1]), float(E_all[1]), rtol=1e-6)


@pytest.mark.slow
def test_sweep_runner_per_lane_early_stop(small_world):
    """target_acc=0 marks every lane done after round 1: the run stops
    early and later rows never accrue costs (here there are none)."""
    sp, pop, fed = small_world
    from repro.core.scheduling import FedAvgScheduler
    runner = SweepRunner(sp, [(pop, fed), (pop, fed)], lr=0.01,
                         alloc_steps=50, model_seed=0)
    scheds = [FedAvgScheduler(fed.n_devices, 8) for _ in range(2)]
    out = runner.run(scheds, n_rounds=4, assign="geo", seeds=[0, 1],
                     target_acc=0.0)
    assert out["acc"].shape == (2, 1)          # stopped after one round
    np.testing.assert_array_equal(out["iters"], [1, 1])


@pytest.mark.slow
def test_sweep_runner_agg_kernel_matches_einsum(small_world):
    """End-to-end SweepRunner lane sweep: agg_kernel=True reproduces the
    einsum runner's accuracy/cost trajectories at fixed seeds."""
    sp, pop, fed = small_world
    from repro.core.scheduling import FedAvgScheduler
    outs = {}
    for ak in (False, True):
        runner = SweepRunner(sp, [(pop, fed), (pop, fed)], lr=0.01,
                             alloc_steps=50, model_seed=0, agg_kernel=ak)
        scheds = [FedAvgScheduler(fed.n_devices, 8) for _ in range(2)]
        outs[ak] = runner.run(scheds, n_rounds=2, assign="geo",
                              seeds=[0, 1])
    np.testing.assert_allclose(outs[True]["acc"], outs[False]["acc"],
                               atol=1e-6)
    np.testing.assert_allclose(outs[True]["T_i"], outs[False]["T_i"],
                               rtol=1e-6)
    np.testing.assert_allclose(outs[True]["E_i"], outs[False]["E_i"],
                               rtol=1e-6)


@pytest.mark.slow
def test_fused_framework_round_matches_sequential_record(small_world):
    """Framework-level regression: engine='fused' reproduces the
    sequential run_round record (T_i, E_i, acc) at fixed seed."""
    sp, pop, fed = small_world
    recs = {}
    for engine in ("sequential", "fused"):
        cfg = FrameworkConfig(scheduler="fedavg", assigner="geo", H=10,
                              K=10, target_acc=0.99, max_iters=1,
                              alloc_steps=60, seed=0, engine=engine)
        fw = HFLFramework(sp, pop, fed, cfg)
        recs[engine] = fw.run_round(1)
    for k in ("T_i", "E_i"):
        np.testing.assert_allclose(recs["sequential"][k],
                                   recs["fused"][k], rtol=1e-5)
    np.testing.assert_allclose(recs["sequential"]["acc"],
                               recs["fused"]["acc"], atol=1e-6)


@pytest.mark.slow
def test_sweep_runner_matches_fused_framework(small_world):
    """A 2-lane SweepRunner run is finite, shape-correct, and its lane-0
    records match a standalone fused framework driven by the same
    schedule/assignment/model-init stream."""
    sp, pop, fed = small_world
    runner = SweepRunner(sp, [(pop, fed), (pop, fed)], lr=0.01,
                         alloc_steps=50, model_seed=0)
    from repro.core.scheduling import FedAvgScheduler
    scheds = [FedAvgScheduler(fed.n_devices, 8) for _ in range(2)]
    out = runner.run(scheds, n_rounds=2, assign="geo", seeds=[0, 1])
    assert out["acc"].shape == (2, 2)
    assert out["T_i"].shape == (2, 2) and out["E_i"].shape == (2, 2)
    assert np.isfinite(out["T_i"]).all() and np.isfinite(out["E_i"]).all()
    assert (out["T_i"] > 0).all() and (out["E_i"] > 0).all()
    assert ((out["acc"] >= 0) & (out["acc"] <= 1)).all()
    assert out["H"] == 8
    assert out["msg_bits_per_round"] > 0
