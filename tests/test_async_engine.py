"""Event-driven async engine: degenerate-trace parity against the
synchronous ``round_step`` oracle, staleness/dropout/arrival behaviour,
and the availability-trace samplers (ISSUE 8 tentpole).

The parity contract (documented in ``docs/async.md``): with
``AvailabilityTrace.always_on`` + wait-for-all buffers + no jitter, the
event loop IS the synchronous round — allocations and per-task costs
bitwise, T_i/E_i to float-accumulation-order tolerance, trained params
and accuracy to ulp-level tolerance.
"""
import functools

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from repro.core import async_engine as ae  # noqa: E402
from repro.core import compression as comp  # noqa: E402
from repro.core import cost_model as cm  # noqa: E402
from repro.core.async_engine import AsyncConfig, AsyncHFLEngine  # noqa: E402
from repro.core.framework import round_step  # noqa: E402
from repro.core.hfl import evaluate_in_batches  # noqa: E402
from repro.core.local_train import cohort_local_sgd  # noqa: E402
from repro.core.traffic import TrafficGenerator, TrafficParams  # noqa: E402
from repro.data import make_dataset, partition_noniid  # noqa: E402

N_DEV, N_EDGE, H = 10, 3, 6
ALLOC_STEPS = 60


class _FixedSched:
    """Deterministic cohort — isolates the event loop from scheduler RNG."""

    def __init__(self, sel):
        self.sel = np.asarray(sel)

    def schedule(self, rng):
        return self.sel


class _ModAssigner:
    """Round-robin assignment: guarantees every edge a known member set."""

    def assign(self, pop, sched, rng):
        return np.asarray(sched) % pop.n_edges, None


def _world(seed=0):
    # small Q/L keep the event loop fast; the loop structure is identical
    sp = cm.SystemParams(n_devices=N_DEV, n_edges=N_EDGE,
                         d_range=(30, 60), L=2, Q=3)
    pop = cm.sample_population(sp, seed=seed)
    X, y, Xt, yt = make_dataset("fmnist_syn", n_train=300, n_test=120,
                                seed=seed)
    fed = partition_noniid(X, y, Xt, yt, n_devices=N_DEV,
                           size_range=(15, 25), seed=seed)
    return sp, pop, fed


# ------------------------------------------------------------- samplers

def test_always_on_trace_is_degenerate():
    tr = cm.AvailabilityTrace.always_on(5)
    for t in (0.0, 1.0, 1e9):
        assert tr.up_at(t).all()
    assert (tr.latency_scale == 1.0).all()
    assert tr.toggles_after(0, 0.0).size == 0


def test_default_params_sample_degenerate_trace():
    tr = cm.sample_availability(cm.AvailabilityParams(), 8, seed=1)
    assert tr.init_up.all()
    assert np.isinf(tr.toggles).all()
    assert (tr.latency_scale == 1.0).all()


def test_sampled_toggles_ascend_and_replay():
    ap = cm.AvailabilityParams(p_offline0=0.3, mean_up_s=50.0,
                               mean_down_s=10.0)
    tr = cm.sample_availability(ap, 64, seed=7, max_toggles=16)
    fin = np.where(np.isfinite(tr.toggles), tr.toggles, np.inf)
    assert (np.diff(fin, axis=1) >= 0).all()
    assert np.isfinite(tr.toggles).any()
    tr2 = cm.sample_availability(ap, 64, seed=7, max_toggles=16)
    np.testing.assert_array_equal(tr.toggles, tr2.toggles)
    np.testing.assert_array_equal(tr.init_up, tr2.init_up)


def test_straggler_scales_two_valued():
    ap = cm.AvailabilityParams(straggler_frac=0.5, straggler_scale=7.0)
    s = np.asarray(cm.sample_straggler_scales(
        jax.random.PRNGKey(0), ap, 200))
    assert set(np.unique(s)) == {1.0, 7.0}


def test_up_at_counts_flips():
    tr = cm.AvailabilityTrace(init_up=np.array([True]),
                              toggles=np.array([[1.0, 2.0, np.inf]]),
                              latency_scale=np.ones(1))
    assert tr.up_at(0.5)[0] and not tr.up_at(1.5)[0] and tr.up_at(2.5)[0]
    np.testing.assert_array_equal(tr.toggles_after(0, 0.5),
                                  np.array([1.0, 2.0]))


# ----------------------------------------------------- oracle parity

def _one_ulp_response(round_fn, params):
    """Per-leaf max |round_fn(p ± 1 ulp) - round_fn(p)|: how far the
    oracle round itself moves when its input moves by one ulp. ReLU and
    max-pool kinks make this far larger than an ulp of the output."""
    base = jax.tree.leaves(round_fn(params))
    resp = [0.0] * len(base)
    for direction in (np.inf, -np.inf):
        moved = jax.tree.leaves(round_fn(jax.tree.map(
            lambda x, d=direction: jnp.nextafter(x, d), params)))
        resp = [max(r, float(jnp.max(jnp.abs(a - b))))
                for r, a, b in zip(resp, moved, base)]
    return resp


def test_degenerate_trace_matches_round_step_oracle():
    """Zero-latency-skew/zero-dropout async == synchronous round_step:
    allocations bitwise, costs to accumulation-order tolerance, params
    and accuracy within the summation-order bound below — every round,
    from the same start state.

    The two engines differ only in how eq. (2) is summed: each edge
    flush is a (H,) @ (H, P) gemv, the sync round one (M, H) @ (H, P)
    gemm. With at most 3 members per edge (H=6, M=3) the two sums differ
    by at most 2 ulp per aggregation, Q aggregations per round, so the
    params may differ by up to 2·Q times the oracle's own response to a
    one-ulp change of its input (``_one_ulp_response``)."""
    sp, pop, fed = _world(seed=0)
    cfg = AsyncConfig(H=H, scheduler="fedavg", alloc_steps=ALLOC_STEPS,
                      seed=3)
    eng = AsyncHFLEngine(sp, pop, fed, cfg)
    spp = eng.sp                       # model_bits-patched params

    for _ in range(2):
        params_start = eng.model_params
        rec = eng.step_round()
        sched, assign = eng.last_sched, eng.last_assign

        def sync_round(params):
            return round_step(
                eng.apply_fn, spp, params,
                pop.u[sched], pop.D[sched], pop.p[sched], pop.g[sched],
                pop.g_cloud, pop.B_m,
                eng.X[sched], eng.y[sched], eng.mask[sched],
                pop.D[sched], jnp.asarray(assign, jnp.int32), cfg.lr,
                M=pop.n_edges, L=spp.L, Q=spp.Q,
                alloc_steps=cfg.alloc_steps)

        params_sync, (T, E, _, _, b, f) = sync_round(params_start)

        b_a, f_a = eng.last_alloc[:2]
        np.testing.assert_array_equal(np.asarray(b_a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(f_a), np.asarray(f))
        assert rec["T_i"] == pytest.approx(float(T), rel=1e-5)
        assert rec["E_i"] == pytest.approx(float(E), rel=1e-5)
        assert rec["n_updates"] == spp.Q * H
        assert rec["n_stale"] == 0 and rec["n_aborted"] == 0
        assert rec["forced_flushes"] == 0
        assert rec["msg_bits"] == pytest.approx(
            (spp.Q * H + pop.n_edges) * spp.model_bits)
        resp = _one_ulp_response(lambda p: sync_round(p)[0], params_start)
        for pa, pb, r in zip(jax.tree.leaves(eng.model_params),
                             jax.tree.leaves(params_sync), resp):
            np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                       rtol=2e-6,
                                       atol=max(2e-7, 2 * spp.Q * r))
        acc_sync = evaluate_in_batches(eng.apply_fn, params_sync,
                                       fed.X_test, fed.y_test)
        assert rec["acc"] == pytest.approx(acc_sync, abs=1e-6)


# ------------------------------------------- chunked dispatch

H_CHUNK = 12                       # a cohort of several chunks
C = ae.DISPATCH_CHUNK
# dispatch sizes around one chunk and the whole cohort, on lanes drawn
# at random (so with gaps between them), and one more mask with gaps
DISPATCHES = {
    name: sorted(np.random.default_rng(k).choice(H_CHUNK, k, replace=False))
    for name, k in (("1", 1), ("C-1", C - 1), ("C", C), ("C+1", C + 1),
                    ("H", H_CHUNK))}
DISPATCHES["gaps"] = [1, 4, 5, 9, 10]


@functools.partial(jax.jit, static_argnames=("apply_fn", "L"))
def _masked_oracle(apply_fn, cohort, edge, assign, dmask, X, y, mask, lr,
                   *, L):
    """The whole-cohort masked dispatch the chunked one replaced: every
    lane trains, dispatched lanes from their edge model. Returns
    ``(pulled, trained)`` for all H lanes."""
    def bmask(leaf):
        return dmask.reshape((-1,) + (1,) * (leaf.ndim - 1))

    pulled = jax.tree.map(lambda e: jnp.take(e, assign, axis=0), edge)
    src = jax.tree.map(lambda c, q: jnp.where(bmask(c), q, c), cohort,
                       pulled)
    return pulled, cohort_local_sgd(apply_fn, src, X, y, mask, L, lr)


@pytest.fixture(scope="module")
def chunk_world():
    """An H=12 cohort whose lanes and edges all hold different models."""
    sp, pop, fed = _world(seed=2)
    eng = AsyncHFLEngine(sp, pop, fed, AsyncConfig(H=H, seed=2))
    rows = np.arange(H_CHUNK) % N_DEV

    def spread(n, seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), n)
        return jax.tree.map(
            lambda p: p[None] + 0.01 * jax.vmap(
                lambda k: jax.random.normal(k, p.shape))(keys),
            eng.model_params)

    return dict(apply_fn=eng.apply_fn, L=sp.L, lr=0.01,
                cohort=spread(H_CHUNK, 0), edge=spread(N_EDGE, 1),
                assign=jnp.asarray(rows % N_EDGE, jnp.int32),
                X=eng.X[rows], y=eng.y[rows], mask=eng.mask[rows])


def _dispatch_mask(name):
    dmask = np.zeros(H_CHUNK, bool)
    dmask[DISPATCHES[name]] = True
    return jnp.asarray(dmask)


def _assert_rows(got, want, old, dmask):
    """Dispatched rows close to ``want`` (unless it is None); the others
    bitwise ``old``."""
    on = np.asarray(dmask)
    for g, o, w in zip(jax.tree.leaves(got), jax.tree.leaves(old),
                       jax.tree.leaves(want) if want is not None
                       else jax.tree.leaves(got)):
        g, w, o = np.asarray(g), np.asarray(w), np.asarray(o)
        np.testing.assert_allclose(g[on], w[on], rtol=1e-6)
        np.testing.assert_array_equal(g[~on], o[~on])


def test_a_chunk_is_never_wider_than_the_cohort():
    assert 1 < ae.dispatch_chunk(H_CHUNK) == C < H_CHUNK
    assert ae.dispatch_chunk(C - 1) == C - 1


@pytest.mark.parametrize("name", list(DISPATCHES))
def test_chunked_dispatch_matches_the_masked_oracle(chunk_world, name):
    w, dmask = chunk_world, _dispatch_mask(name)
    args = (w["apply_fn"], w["cohort"], w["edge"], w["assign"], dmask,
            w["X"], w["y"], w["mask"], w["lr"])
    got = ae._train_dispatched(*args, L=w["L"])
    _, trained = _masked_oracle(*args, L=w["L"])
    _assert_rows(got, trained, w["cohort"], dmask)


@pytest.mark.parametrize("codec", ["bf16_delta", "topk"])
def test_chunked_compressed_dispatch_matches_the_masked_oracle(
        chunk_world, codec):
    """Deterministic codecs: the reconstruction and the residual rows of
    the dispatched lanes as the whole-cohort encode gives them."""
    w = chunk_world
    cc = comp.CompressionConfig(codec=codec)
    resid = jax.tree.map(lambda c: 1e-3 * jnp.cos(c), w["cohort"])
    key = jax.random.PRNGKey(4)
    for name in DISPATCHES:
        dmask = _dispatch_mask(name)
        args = (w["apply_fn"], w["cohort"], w["edge"], w["assign"], dmask,
                w["X"], w["y"], w["mask"], w["lr"])
        got, got_resid = ae._train_dispatched_compressed(
            *args, resid, key, L=w["L"], codec=cc)
        pulled, trained = _masked_oracle(*args, L=w["L"])
        delta = jax.tree.map(lambda t, q: t - q, trained, pulled)
        dec, want_resid = comp.encode_decode(cc, key, delta, resid)
        recon = jax.tree.map(jnp.add, pulled, dec)
        _assert_rows(got, recon, w["cohort"], dmask)
        _assert_rows(got_resid, want_resid, resid, dmask)


def test_chunked_int8_dispatch_keeps_error_feedback_exact(chunk_world):
    """Stochastic rounding draws per chunk, so the rows are checked
    by what holds for any draw: reconstruction plus new residual is the
    trained model plus the old residual, and each new residual lies
    within one quantisation level of its row."""
    w = chunk_world
    cc = comp.CompressionConfig(codec="int8")
    resid = jax.tree.map(lambda c: 1e-3 * jnp.cos(c), w["cohort"])
    for name in DISPATCHES:
        dmask = _dispatch_mask(name)
        on = np.asarray(dmask)
        args = (w["apply_fn"], w["cohort"], w["edge"], w["assign"], dmask,
                w["X"], w["y"], w["mask"], w["lr"])
        got, got_resid = ae._train_dispatched_compressed(
            *args, resid, jax.random.PRNGKey(4), L=w["L"], codec=cc)
        pulled, trained = _masked_oracle(*args, L=w["L"])
        for g, r, t, q, o in zip(*(jax.tree.leaves(a) for a in (
                got, got_resid, trained, pulled, resid))):
            # a handful of f32 roundings of numbers the size of the params
            np.testing.assert_allclose(
                np.asarray(g + r)[on], np.asarray(t + o)[on], rtol=1e-6,
                atol=8 * np.finfo(np.float32).eps * float(jnp.abs(q).max()))
            x = np.asarray(t - q + o)[on].reshape(int(on.sum()), -1)
            step = np.abs(x).max(axis=1, keepdims=True) / 127.0
            nr = np.asarray(r)[on].reshape(x.shape)
            assert (np.abs(nr) <= step * (1 + 1e-5)).all()
        _assert_rows(got, None, w["cohort"], dmask)
        _assert_rows(got_resid, None, resid, dmask)


# -------------------------------------------------- async behaviour

def _straggler_trace(sp, pop, fed, seed):
    """Latency scales making slots 3..5 deliver at 1.5x their edge's
    fast member — after the first buffered flush, before the edge's Q-th
    — so staleness >= 1 is guaranteed, not timing-dependent."""
    probe = AsyncHFLEngine(sp, pop, fed,
                           AsyncConfig(H=H, alloc_steps=ALLOC_STEPS,
                                       seed=seed),
                           scheduler=_FixedSched(np.arange(H)),
                           assigner=_ModAssigner())
    probe.step_round(collect_eval=False)
    tc = np.asarray(probe.last_alloc[2], np.float64)
    scale = np.ones(N_DEV)
    for s in range(3, 6):              # slot s shares an edge with s-3
        scale[s] = 1.5 * tc[s - 3] / tc[s]
    return cm.AvailabilityTrace(init_up=np.ones(N_DEV, bool),
                                toggles=np.full((N_DEV, 1), np.inf),
                                latency_scale=scale)


def test_stragglers_with_small_buffer_cause_staleness_and_finish_early():
    sp, pop, fed = _world(seed=1)
    tr = _straggler_trace(sp, pop, fed, seed=5)

    def build(buffer_size):
        cfg = AsyncConfig(H=H, alloc_steps=ALLOC_STEPS, seed=5,
                          buffer_size=buffer_size, staleness_exp=0.5)
        return AsyncHFLEngine(sp, pop, fed, cfg, trace=tr,
                              scheduler=_FixedSched(np.arange(H)),
                              assigner=_ModAssigner())

    rec_buf = build(1).step_round(collect_eval=False)
    rec_all = build(None).step_round(collect_eval=False)
    # FedBuff-style flushes aggregate late updates at staleness >= 1 ...
    assert rec_buf["n_stale"] > 0 and rec_buf["max_staleness"] >= 1
    # ... and stop waiting on the stragglers' critical path
    assert rec_buf["T_i"] < rec_all["T_i"]
    # wait-for-all never sees staleness, only a longer round
    assert rec_all["n_stale"] == 0


def test_all_offline_round_terminates_and_keeps_model():
    sp, pop, fed = _world(seed=2)
    tr = cm.AvailabilityTrace(init_up=np.zeros(N_DEV, bool),
                              toggles=np.full((N_DEV, 1), np.inf),
                              latency_scale=np.ones(N_DEV))
    cfg = AsyncConfig(H=H, alloc_steps=ALLOC_STEPS, seed=0)
    eng = AsyncHFLEngine(sp, pop, fed, cfg, trace=tr)
    before = jax.tree.map(np.asarray, eng.model_params)
    rec = eng.step_round(collect_eval=False)
    assert rec["n_updates"] == 0
    assert rec["forced_flushes"] > 0
    for pa, pb in zip(jax.tree.leaves(before),
                      jax.tree.leaves(eng.model_params)):
        np.testing.assert_allclose(pa, np.asarray(pb), rtol=1e-6,
                                   atol=1e-7)


def _task_latencies(sp, pop, fed, seed):
    """Per-slot task latency tc of the fixed cohort ``arange(H)`` under
    round-robin assignment (the allocation ignores the trace)."""
    probe = AsyncHFLEngine(sp, pop, fed,
                           AsyncConfig(H=H, alloc_steps=ALLOC_STEPS,
                                       seed=seed),
                           scheduler=_FixedSched(np.arange(H)),
                           assigner=_ModAssigner())
    probe.step_round(collect_eval=False)
    return np.asarray(probe.last_alloc[2], np.float64)


def test_late_arrivals_still_deliver_full_round():
    """Whole fleet offline at t=0; Exp arrivals then stay up — the round
    starts late but every edge still drains Q full buffers, because every
    member is up before any task can finish. The mean arrival delay is
    tied to the fastest task, min(tc)/50, so the chance that any of the H
    arrivals comes later than min(tc) is at most H·exp(-50); the drawn
    trace is checked against that premise. (A member that arrives after
    its edge's first delivery misses that flush by design: see the next
    test.)"""
    sp, pop, fed = _world(seed=3)
    tc = _task_latencies(sp, pop, fed, seed=4)
    ap = cm.AvailabilityParams(p_offline0=1.0, mean_down_s=tc.min() / 50,
                               mean_up_s=float("inf"))
    tr = cm.sample_availability(ap, N_DEV, seed=11)
    assert not tr.init_up.any()
    assert tr.toggles[:H, 0].max() < tc.min()
    cfg = AsyncConfig(H=H, alloc_steps=ALLOC_STEPS, seed=4)
    eng = AsyncHFLEngine(sp, pop, fed, cfg, trace=tr,
                         scheduler=_FixedSched(np.arange(H)),
                         assigner=_ModAssigner())
    rec = eng.step_round(collect_eval=False)
    assert rec["n_updates"] == sp.Q * H
    assert rec["forced_flushes"] == 0


def test_member_arriving_after_first_delivery_misses_that_flush():
    """Slot 4 shares edge 1 with slot 1 (round-robin, M=3) and comes up
    at 1.5·tc[1], after slot 1's first delivery. Nothing is in flight
    then, so edge 1 flushes slot 1 alone; slot 4 joins the next two
    flushes. The round aggregates Q·H - 1 updates, none of them stale."""
    sp, pop, fed = _world(seed=3)
    tc = _task_latencies(sp, pop, fed, seed=4)
    toggles = np.full((N_DEV, 1), np.inf)
    toggles[4, 0] = 1.5 * tc[1]
    init_up = np.ones(N_DEV, bool)
    init_up[4] = False
    tr = cm.AvailabilityTrace(init_up=init_up, toggles=toggles,
                              latency_scale=np.ones(N_DEV))
    cfg = AsyncConfig(H=H, alloc_steps=ALLOC_STEPS, seed=4)
    eng = AsyncHFLEngine(sp, pop, fed, cfg, trace=tr,
                         scheduler=_FixedSched(np.arange(H)),
                         assigner=_ModAssigner())
    rec = eng.step_round(collect_eval=False)
    assert rec["n_updates"] == sp.Q * H - 1
    assert rec["n_stale"] == 0
    assert rec["forced_flushes"] == 0


def test_churny_round_terminates_with_sane_accounting():
    sp, pop, fed = _world(seed=4)
    cfg0 = AsyncConfig(H=H, alloc_steps=ALLOC_STEPS, seed=6)
    probe = AsyncHFLEngine(sp, pop, fed, cfg0)
    T_deg = probe.step_round(collect_eval=False)["T_i"]

    ap = cm.AvailabilityParams(p_offline0=0.2, mean_up_s=T_deg / 5,
                               mean_down_s=T_deg / 10)
    tr = cm.sample_availability(ap, N_DEV, seed=13, max_toggles=256)
    cfg = AsyncConfig(H=H, alloc_steps=ALLOC_STEPS, seed=6,
                      buffer_size=1)
    eng = AsyncHFLEngine(sp, pop, fed, cfg, trace=tr)
    summary = eng.run(n_rounds=2, eval_every=2)
    assert summary["rounds"] == 2
    assert summary["n_updates"] <= 2 * sp.Q * H
    assert summary["wasted_j"] >= 0.0
    assert eng.t > 0.0
    assert summary["final_acc"] is not None


def test_staleness_weight_decay_dampens_stale_updates():
    """A stale delivery moves the edge model less than a fresh one:
    larger a => stronger decay => smaller parameter step."""
    sp, pop, fed = _world(seed=5)
    tr = _straggler_trace(sp, pop, fed, seed=7)

    def run(a):
        cfg = AsyncConfig(H=H, alloc_steps=ALLOC_STEPS, seed=7,
                          buffer_size=1, staleness_exp=a)
        eng = AsyncHFLEngine(sp, pop, fed, cfg, trace=tr,
                             scheduler=_FixedSched(np.arange(H)),
                             assigner=_ModAssigner())
        rec = eng.step_round(collect_eval=False)
        assert rec["n_stale"] > 0      # decay actually exercised
        return jax.tree.leaves(jax.tree.map(np.asarray, eng.model_params))

    base = run(0.0)
    damped = run(4.0)
    diff = sum(float(np.abs(a - b).sum()) for a, b in zip(base, damped))
    assert diff > 0.0                  # a changes the aggregate


# ------------------------------------------------------------ traffic

def test_traffic_trace_respects_horizon_and_seeds():
    tp = TrafficParams(join_rate=0.5, mean_session_s=20.0, p_online0=0.3)
    gen = TrafficGenerator(tp, n_devices=12, seed=9)
    tr = gen.make_trace(horizon_s=100.0)
    fin = tr.toggles[np.isfinite(tr.toggles)]
    assert fin.size > 0 and (fin >= 0).all() and (fin <= 100.0).all()
    np.testing.assert_array_equal(tr.up_at(0.0), tr.init_up)
    tr2 = TrafficGenerator(tp, n_devices=12, seed=9).make_trace(100.0)
    np.testing.assert_array_equal(tr.toggles, tr2.toggles)


def test_traffic_rate_modulation():
    tp = TrafficParams(join_rate=1.0, diurnal_amp=0.5,
                       diurnal_period_s=100.0, burst_mult=4.0,
                       burst_every_s=50.0, burst_len_s=5.0)
    gen = TrafficGenerator(tp, n_devices=4, seed=0)
    assert gen.rate(25.0) == pytest.approx(1.5)      # diurnal peak
    assert gen.rate(75.0) == pytest.approx(0.5)      # diurnal trough
    assert gen.rate(51.0) == pytest.approx(
        4.0 * (1.0 + 0.5 * np.sin(2 * np.pi * 51.0 / 100.0)))
    assert gen.rate(0.0) == pytest.approx(4.0)       # burst at t=0


def test_traffic_trace_drives_engine():
    sp, pop, fed = _world(seed=6)
    probe = AsyncHFLEngine(sp, pop, fed,
                           AsyncConfig(H=H, alloc_steps=ALLOC_STEPS))
    T_deg = probe.step_round(collect_eval=False)["T_i"]
    tp = TrafficParams(join_rate=2.0 / T_deg, mean_session_s=T_deg,
                       p_online0=0.5)
    tr = TrafficGenerator(tp, N_DEV, seed=3).make_trace(5 * T_deg)
    eng = AsyncHFLEngine(sp, pop, fed,
                         AsyncConfig(H=H, alloc_steps=ALLOC_STEPS,
                                     buffer_size=2), trace=tr)
    rec = eng.step_round(collect_eval=False)
    assert rec["round"] == 1 and rec["T_i"] > 0.0
