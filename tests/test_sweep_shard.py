"""Sharded SweepRunner: parity against the single-device vmapped oracle.

The multi-device cases run in spawned subprocesses (``multidevice``
fixture) because ``--xla_force_host_platform_device_count`` must be set
before jax import: each ``_payload_*`` function below is executed in a
fresh interpreter with 8 emulated CPU devices and asserts parity
internally (exit code carries the verdict). Lane independence makes the
two paths float-identical per round up to XLA partitioning
reassociation (~1 ulp/round on params, measured), which compounds
through training — so params/costs compare tightly, test-set accuracy
with a couple-of-samples tolerance, and early-stop targets sit ≥3
test-samples away from the per-round accuracies they gate.

The geo payload (non-slow) doubles as tier-1's sharding smoke — one
subprocess per run, ~40 s; the hfel/drl/chunked payloads are marked
slow and run in the weekly sharded-parity CI lane. Single-device cases
(1-lane mesh plumbing, lane_chunk parity, the done-mask freeze
property) run inline in tier-1.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

# world shared by all payloads: small enough that both engines compile
# in seconds, big enough that lanes diverge (per-lane model inits).
_N, _M, _H = 12, 3, 8
_ROUNDS = 4
_TARGET = 0.35


def _make_world():
    from repro.core.cost_model import SystemParams, sample_population
    from repro.data import make_dataset, partition_noniid

    sp = SystemParams(n_devices=_N, n_edges=_M)
    pop = sample_population(sp, seed=0)
    X, y, Xt, yt = make_dataset("fmnist_syn", n_train=240, n_test=60,
                                seed=0)
    fed = partition_noniid(X, y, Xt, yt, n_devices=_N,
                           size_range=(10, 16), seed=0)
    return sp, pop, fed


def _run_one(S, assign, shard, n_rounds=_ROUNDS, target_acc=_TARGET,
             shard_kw=None, **run_kw):
    """One sweep through either engine (sharded runner asserted to pad S
    up to the emulated device count). shard_kw: extra ctor kwargs for
    the sharded runner only (e.g. lane_chunk)."""
    import jax

    from repro.core.sweep import SweepRunner, build_scheduler

    sp, pop, fed = _make_world()
    worlds = [(pop, fed)] * S
    runner = SweepRunner(sp, worlds, lr=0.02, alloc_steps=25,
                         model_seed=0, shard=shard,
                         **(shard_kw if shard and shard_kw else {}))
    if shard:
        n_dev = len(jax.devices())
        assert runner.S_pad == -(-S // n_dev) * n_dev, (
            runner.S_pad, S, n_dev)
    scheds = [build_scheduler("fedavg", fed, sp, _H, seed=s)
              for s in range(S)]
    a = assign() if callable(assign) else assign
    return runner.run(scheds, n_rounds, assign=a, seeds=list(range(S)),
                      target_acc=target_acc, **run_kw)


def _run_pair(S, assign, n_rounds=_ROUNDS, target_acc=_TARGET,
              shard_kw=None, **run_kw):
    """Run the same sweep through the single-device and sharded engines
    and return both result dicts."""
    return [_run_one(S, assign, shard, n_rounds=n_rounds,
                     target_acc=target_acc, shard_kw=shard_kw, **run_kw)
            for shard in (False, True)]


def _assert_parity(o0, o1, acc_atol=0.09):
    """Allclose parity between the vmapped oracle (o0) and the sharded
    run (o1). Round costs depend only on (sched, assign, done) — all
    host-side and parity-exact — so T/E/obj compare tightly and FIRST;
    accuracy rides the trained params, where XLA partitioning drift
    (~1 ulp/round) amplifies chaotically through training, so it
    tolerates a few flipped test samples."""
    assert o0["acc"].shape == o1["acc"].shape
    np.testing.assert_array_equal(o0["iters"], o1["iters"])
    for k in ("T_i", "E_i", "obj"):
        np.testing.assert_allclose(o0[k], o1[k], rtol=1e-4, atol=1e-6,
                                   err_msg=k)
    np.testing.assert_allclose(o0["acc"], o1["acc"], atol=acc_atol)
    assert o0["H"] == o1["H"]


# ------------------------------------------------- multidevice payloads

def _payload_geo():
    """Geo assignment, S=5 lanes on 8 devices (non-divisible: 3 dead pad
    lanes) with per-lane early stop firing at different rounds.

    The early-stop target is picked from a no-stop probe of the oracle
    rather than hardcoded: pre-stop trajectories are identical across
    engines, so under a target t every lane stops at the first probe
    round with acc >= t — choosing the candidate threshold with the
    largest margin to every probe accuracy (while still making lanes
    stop at different rounds) keeps the iters-equality assert off the
    knife edge where tolerated float drift could flip a stopping round.
    """
    import jax

    assert len(jax.devices()) == 8, jax.devices()
    probe = _run_one(5, "geo", shard=False, target_acc=None)
    accs = probe["acc"]                                  # (S, R)
    vals = np.unique(accs)
    best, best_margin, best_iters = None, 0.0, None
    for t in (vals[:-1] + vals[1:]) / 2:
        reached = accs >= t
        iters = np.where(reached.any(axis=1),
                         reached.argmax(axis=1) + 1, _ROUNDS)
        if iters.min() < _ROUNDS and len(set(iters.tolist())) > 1:
            margin = float(np.abs(accs - t).min())
            if margin > best_margin:
                best, best_margin, best_iters = float(t), margin, iters
    assert best is not None, f"no divergent early-stop target in {accs}"
    assert best_margin >= 0.04, (best, best_margin, accs)

    o0, o1 = _run_pair(5, "geo", target_acc=best)
    _assert_parity(o0, o1, acc_atol=min(0.09, best_margin))
    # the early stop actually exercised per-lane divergence, exactly as
    # the probe predicted
    np.testing.assert_array_equal(o0["iters"], best_iters)


def _payload_hfel():
    """Batched K-candidate HFEL search as the per-round assigner (host
    search between sharded rounds), S=3 on 8 devices. No early-stop
    target: search/allocation parity is exact, and keeping every lane
    live avoids threshold knife-edges on the chaotic accuracy (the geo
    payload owns early-stop coverage)."""

    def make_assign():
        from repro.core.sweep import make_hfel_assign

        sp, _, _ = _make_world()
        return make_hfel_assign(sp, n_transfer=6, n_exchange=6,
                                alloc_steps=25, n_candidates=4)

    o0, o1 = _run_pair(3, make_assign, n_rounds=2, target_acc=None)
    _assert_parity(o0, o1, acc_atol=0.15)


def _payload_drl():
    """Greedy D3QN deployment assigner (jitted Q eval on the default
    device between sharded rounds), S=3 on 8 devices. Untrained-net
    assignments are deterministic, so like the hfel payload this skips
    the early-stop target and leans on exact cost parity."""
    import jax

    def make_assign():
        from repro.core.sweep import make_drl_assign
        from repro.drl.d3qn import d3qn_init
        from repro.drl.train import drl_features

        sp, pop, _ = _make_world()
        feats = drl_features(pop, np.arange(_H))
        params = d3qn_init(jax.random.PRNGKey(0), feats.shape[-1], _M)
        return make_drl_assign(sp, params)

    o0, o1 = _run_pair(3, make_assign, n_rounds=2, target_acc=None)
    _assert_parity(o0, o1, acc_atol=0.15)


def _payload_geo_chunked():
    """lane_chunk=1 cache-blocked execution inside the sharded blocks
    (the bench's fastest CPU variant) against the plain vmapped
    single-device oracle."""
    o0, o1 = _run_pair(5, "geo", shard_kw={"lane_chunk": 1})
    _assert_parity(o0, o1)


# ------------------------------------------------------------ the tests

@pytest.mark.multidevice
def test_sharded_parity_geo_nondivisible_early_stop(multidevice):
    multidevice("test_sweep_shard:_payload_geo")


@pytest.mark.multidevice
@pytest.mark.slow
def test_sharded_parity_hfel(multidevice):
    multidevice("test_sweep_shard:_payload_hfel")


@pytest.mark.multidevice
@pytest.mark.slow
def test_sharded_parity_lane_chunked(multidevice):
    multidevice("test_sweep_shard:_payload_geo_chunked")


@pytest.mark.multidevice
@pytest.mark.slow
def test_sharded_parity_drl(multidevice):
    multidevice("test_sweep_shard:_payload_drl")


def test_shard_single_device_mesh_matches_vmap(small_world):
    """shard=True on a 1-device ('lane',) mesh is the same program
    modulo shard_map plumbing — exact parity, runs in tier-1 without
    emulation (S_pad == S, no dead lanes)."""
    from repro.core.sweep import SweepRunner, build_scheduler
    from repro.launch.mesh import sweep_mesh

    sp, pop, fed = small_world
    worlds = [(pop, fed)] * 2
    outs = []
    for shard in (False, True):
        runner = SweepRunner(sp, worlds, lr=0.02, alloc_steps=20,
                             model_seed=0, shard=shard,
                             mesh=sweep_mesh(1) if shard else None)
        scheds = [build_scheduler("fedavg", fed, sp, 6, seed=s)
                  for s in range(2)]
        outs.append(runner.run(scheds, 2, assign="geo", seeds=[0, 1],
                               target_acc=0.9))
    _assert_parity(outs[0], outs[1], acc_atol=1e-6)


def test_lane_chunk_matches_vmap(small_world):
    """Single-device lane_chunk=1 (sequential lax.map over lanes) is the
    same per-lane computation as the whole-axis vmap — parity to float
    reassociation, runs in tier-1."""
    from repro.core.sweep import SweepRunner, build_scheduler

    sp, pop, fed = small_world
    worlds = [(pop, fed)] * 2
    outs = []
    for chunk in (None, 1):
        runner = SweepRunner(sp, worlds, lr=0.02, alloc_steps=20,
                             model_seed=0, lane_chunk=chunk)
        scheds = [build_scheduler("fedavg", fed, sp, 6, seed=s)
                  for s in range(2)]
        outs.append(runner.run(scheds, 2, assign="geo", seeds=[0, 1]))
    _assert_parity(outs[0], outs[1], acc_atol=0.05)


def test_sweep_mesh_shape_and_validation():
    from repro.core.sweep import SweepRunner
    from repro.launch.mesh import make_debug_mesh, sweep_mesh
    from repro.parallel.sharding import pad_lanes

    mesh = sweep_mesh()
    assert mesh.axis_names == ("lane",)
    # Auto axes: lane-sharded arrays keep their sharding out of the type,
    # so the CNN's pooling reshape stays legal inside the sweep
    from jax.sharding import AxisType
    assert mesh.axis_types == (AxisType.Auto,)
    assert set(make_debug_mesh().axis_types) == {AxisType.Auto}
    with pytest.raises(ValueError):
        sweep_mesh(10_000)
    assert pad_lanes(5, 8) == 8
    assert pad_lanes(8, 8) == 8
    assert pad_lanes(9, 8) == 16
    assert pad_lanes(1, 1) == 1
    # a non-lane mesh is rejected up front
    sp, pop, fed = _make_world()
    with pytest.raises(ValueError):
        SweepRunner(sp, [(pop, fed)], shard=True,
                    mesh=make_debug_mesh())
    # lane_chunk must divide the per-device lane block
    with pytest.raises(ValueError):
        SweepRunner(sp, [(pop, fed)] * 2, lane_chunk=3)


# -------------------------------------- done-mask freeze (property test)

_world_cache = {}


def _cached_sweep_inputs():
    """One tiny compiled-once sweep_round input set for the freeze
    property (module-level cache: the shim draws ~20 examples)."""
    if _world_cache:
        return _world_cache["inputs"]
    import dataclasses

    import jax.numpy as jnp

    from repro.core.sweep import SweepRunner, build_scheduler

    sp, pop, fed = _make_world()
    runner = SweepRunner(sp, [(pop, fed)] * 3, lr=0.02, alloc_steps=20,
                         model_seed=0)
    sched = np.stack([np.asarray(
        build_scheduler("fedavg", fed, sp, _H, seed=s).schedule(
            np.random.default_rng(s)))
        for s in range(3)])
    assign = sched % _M
    spp = dataclasses.replace(sp, model_bits=float(runner.model_bits))
    _world_cache["inputs"] = (runner, spp, jnp.asarray(sched),
                              jnp.asarray(assign))
    return _world_cache["inputs"]


@settings(max_examples=8, deadline=None)
@given(mask_bits=st.integers(min_value=1, max_value=6),
       n_rounds=st.integers(min_value=1, max_value=2))
def test_done_mask_freeze_invariant(mask_bits, n_rounds):
    """Frozen lanes are *exactly* constant: across any subsequent
    rounds, a done lane's params are bitwise-unchanged and its per-round
    T_i/E_i are exactly zero, while at least one live lane trains."""
    import jax
    import jax.numpy as jnp

    from repro.core.sweep import sweep_round

    runner, spp, sched, assign = _cached_sweep_inputs()
    done = np.array([(mask_bits >> i) & 1 == 1 for i in range(3)])
    params = runner.params0
    for _ in range(n_rounds):
        new_params, (T_i, E_i) = sweep_round(
            runner.apply_fn, spp, params, runner.u_b, runner.D_b,
            runner.p_b, runner.g_b, runner.g_cloud_b, runner.B_m_b,
            runner.X_b, runner.y_b, runner.mask_b, runner.D_b, sched,
            assign, 0.02, M=_M, L=spp.L, Q=spp.Q, alloc_steps=20,
            done_b=jnp.asarray(done))
        for old, new in zip(jax.tree.leaves(params),
                            jax.tree.leaves(new_params)):
            np.testing.assert_array_equal(np.asarray(old)[done],
                                          np.asarray(new)[done])
            if not done.all():
                assert not np.array_equal(np.asarray(old)[~done],
                                          np.asarray(new)[~done])
        assert np.all(np.asarray(T_i)[done] == 0.0)
        assert np.all(np.asarray(E_i)[done] == 0.0)
        assert np.all(np.asarray(T_i)[~done] > 0.0)
        params = new_params
