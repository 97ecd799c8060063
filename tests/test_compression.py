"""Uplink update codecs: round-trip properties, error feedback, cost
accounting, and codec="none" parity across all three engines.

The identity codec is a codec like the others, not a bypass: it sends
the model itself, carries no state and draws no key, so every engine's
one round body traces the uncompressed program with it. Active codecs
are pinned on (a) the stochastic-rounding/topk math itself, (b) the
error-feedback residual telescope, and (c) host-loop vs fused-scan
lockstep (both derive their codec keys from ``compression.round_key``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import compression as comp
from repro.core import cost_model as cm
from repro.data import make_dataset, partition_noniid

KEY = jax.random.PRNGKey(0)


def _cfg(codec, **kw):
    return comp.CompressionConfig(codec=codec, **kw)


# -------------------------------------------------------------- config

def test_unknown_codec_rejected():
    with pytest.raises(ValueError):
        comp.CompressionConfig(codec="gzip")
    with pytest.raises(ValueError):
        comp.CompressionConfig(codec="topk", topk_frac=0.0)


def test_config_is_hashable_static():
    a, b = _cfg("int8"), _cfg("int8")
    assert hash(a) == hash(b) and a == b
    assert not _cfg("none").active and _cfg("topk").active


# -------------------------------------------------- message accounting

def test_message_bits_none_is_raw_bytes():
    params = {"w": jnp.zeros((7, 11), jnp.float32),
              "b": jnp.zeros((11,), jnp.float32)}
    assert comp.message_bits(_cfg("none"), params) == (7 * 11 + 11) * 32


def test_message_bits_ratios():
    params = {"w": jnp.zeros((64, 256), jnp.float32)}
    raw = comp.message_bits(_cfg("none"), params)
    assert comp.message_bits(_cfg("bf16_delta"), params) == raw / 2
    # int8: 4x minus the per-leaf scale overhead
    int8 = comp.message_bits(_cfg("int8"), params)
    assert raw / int8 > 3.9
    # topk at 5%: > 4x despite charging value+index per kept entry
    topk = comp.message_bits(_cfg("topk", topk_frac=0.05), params)
    assert raw / topk > 4.0
    # denser topk costs more bits
    assert comp.message_bits(_cfg("topk", topk_frac=0.5), params) > topk


def test_round_msg_bits_helper():
    sp = cm.SystemParams(n_devices=10, n_edges=3)
    # default: sp.model_bits per message (the pre-codec accounting)
    assert cm.round_msg_bits(sp, 40, 3) == (40 + 3) * sp.model_bits
    # codec override prices every uplink with the compressed size
    assert cm.round_msg_bits(sp, 40, 3, msg_bits=100.0) == 4300.0


# --------------------------------------------------- codec round trips

def test_identity_passthrough_is_exact():
    """codec="none" must not even enter delta space: the uplink hands
    the model and the residual back untouched and the receiver takes the
    message as it is (f32 ``a + (b - a) != b``, so a delta round-trip
    would change the engines' programs); it draws no key."""
    cfg = _cfg("none")
    new = {"w": jax.random.normal(KEY, (4, 8))}
    ref = {"w": jnp.zeros((4, 8))}
    msg, nr = comp.uplink(cfg, None, ref, new, None)
    assert msg is new and nr is None
    assert comp.receive(cfg, msg, ref) is msg
    assert comp.init_state(cfg, new, 3) is None
    assert comp.round_key(cfg, 7, 2) is None
    assert comp.split(None, 3) is None and comp.fold_in(None, 1) is None


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=1, max_value=6),
       st.integers(min_value=1, max_value=200),
       st.floats(min_value=1e-3, max_value=10.0))
def test_int8_roundtrip_error_bounded_by_one_level(R, p, scale_mag):
    """Stochastic rounding lands on one of the two adjacent levels, so
    the per-element error is below one quantization step (≈ absmax/127),
    and the wire format really is int8."""
    x = scale_mag * jax.random.normal(jax.random.PRNGKey(R * 1000 + p),
                                      (R, p))
    q, sc = comp.encode_rows(_cfg("int8"), KEY, x)
    assert q.dtype == jnp.int8 and sc.shape == (R,)
    err = np.abs(np.asarray(comp.decode_rows(_cfg("int8"), q, sc) - x))
    assert err.max() <= np.asarray(sc).max() * (1 + 1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=2, max_value=5),
       st.integers(min_value=8, max_value=100))
def test_topk_keeps_largest_magnitudes(R, p):
    cfg = _cfg("topk", topk_frac=0.25)
    x = jax.random.normal(jax.random.PRNGKey(R * 77 + p), (R, p))
    q, sc = comp.encode_rows(cfg, KEY, x)
    k = comp._topk_k(cfg, p)
    qn = np.asarray(q)
    xn = np.abs(np.asarray(x))
    for r in range(R):
        kept = np.flatnonzero(qn[r])
        assert len(kept) == k
        # every kept entry >= every dropped entry (ties aside)
        dropped = np.setdiff1d(np.arange(p), kept)
        if len(dropped):
            assert xn[r, kept].min() >= xn[r, dropped].max() - 1e-6


def test_bf16_roundtrip_relative_error():
    x = jax.random.normal(KEY, (3, 50))
    q, sc = comp.encode_rows(_cfg("bf16_delta"), KEY, x)
    assert q.dtype == jnp.bfloat16
    dec = comp.decode_rows(_cfg("bf16_delta"), q, sc)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(x),
                               rtol=1e-2, atol=1e-3)


@pytest.mark.parametrize("codec", ["bf16_delta", "int8", "topk"])
def test_error_feedback_bias_vanishes_over_rounds(codec):
    """The EF telescope: summing the decoded transmissions over R rounds
    of a constant true delta d gives R*d - resid_R, so the mean
    compressed update's bias is |resid_R|/R -> 0. Checked against the
    single-shot (no-EF) bias, which it must beat."""
    cfg = _cfg(codec, topk_frac=0.1)
    d = jax.random.normal(KEY, (3, 64))
    resid = jnp.zeros_like(d)
    R = 30
    total = np.zeros(d.shape, np.float64)
    for r in range(R):
        q, sc, resid = comp.encode_leaf(cfg, jax.random.PRNGKey(r), d,
                                        resid)
        total += np.asarray(comp.decode_rows(cfg, q, sc), np.float64)
    bias = np.abs(total / R - np.asarray(d, np.float64)).mean()
    # telescope bound: mean bias == |resid_R| / R elementwise
    np.testing.assert_allclose(bias,
                               np.abs(np.asarray(resid)).mean() / R,
                               rtol=1e-3, atol=1e-7)
    q1, sc1, _ = comp.encode_leaf(
        dataclasses.replace(cfg, error_feedback=False), KEY, d,
        jnp.zeros_like(d))
    one_shot = np.abs(
        np.asarray(comp.decode_rows(cfg, q1, sc1)) - np.asarray(d)).mean()
    if codec != "bf16_delta":        # bf16 cast is near-exact one-shot
        assert bias < one_shot


def test_round_key_deterministic_and_distinct():
    cfg = _cfg("int8", seed=3)
    k1 = comp.round_key(cfg, 7, 2)
    k2 = comp.round_key(cfg, 7, 2)
    assert np.array_equal(np.asarray(k1), np.asarray(k2))
    assert not np.array_equal(np.asarray(k1),
                              np.asarray(comp.round_key(cfg, 7, 3)))
    assert not np.array_equal(np.asarray(k1),
                              np.asarray(comp.round_key(cfg, 8, 2)))


# ------------------------------------------------------- engine parity

def _world(seed=0, N=8, M=3):
    sp = cm.SystemParams(n_devices=N, n_edges=M, d_range=(50, 90),
                         L=2, Q=2)
    pop = cm.sample_population(sp, seed=seed)
    X, y, Xt, yt = make_dataset("fmnist_syn", n_train=240, n_test=100,
                                seed=seed)
    fed = partition_noniid(X, y, Xt, yt, n_devices=N, size_range=(20, 40),
                           seed=seed)
    return sp, pop, fed


@pytest.fixture(scope="module")
def world():
    return _world()


def test_framework_codec_none_is_bitwise_oracle(world):
    """round_step with codec="none" trains as Algorithm 1 alone does
    (``hfl_global_iteration`` on the cohort and assignment the round
    chose, no codec), carries no codec state, and prices raw
    payloads."""
    from repro.core.framework import FrameworkConfig, HFLFramework
    from repro.core.hfl import hfl_global_iteration
    sp, pop, fed = world
    cfg = FrameworkConfig(H=5, engine="fused", seed=0, alloc_steps=30,
                          compression=_cfg("none"))
    fw = HFLFramework(sp, pop, fed, cfg)
    params0 = fw.model_params
    rec = fw.run_round(1)
    sched, assign = fw.last_sched, fw.last_assign
    want, state = hfl_global_iteration(
        fw.apply_fn, params0, fw.X[sched], fw.y[sched], fw.mask[sched],
        pop.D[sched], jnp.asarray(assign), M=pop.n_edges, L=sp.L, Q=sp.Q,
        lr=cfg.lr)
    for a, b in zip(jax.tree.leaves(fw.model_params),
                    jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    assert state == (None, None) and fw.codec_state == (None, None)
    assert fw.uplink_bits == fw.model_bits and fw.sp_round == fw.sp
    assert rec["codec"] == "none"
    assert rec["msg_bits"] == cm.round_msg_bits(
        fw.sp, sp.Q * rec["H"], pop.n_edges)
    # per-cluster scheduling can round the cohort up past the requested
    # H, so size the expectation off the record's actual cohort
    assert rec["uplink_bytes"] * 8 == pytest.approx(
        sp.Q * rec["H"] * fw.uplink_bits)


def test_framework_compressed_round_cuts_msg_bits(world):
    """int8 end-to-end: training still progresses, msg_bits and the
    cost-model energy E_i drop with the compressed payload, and the EF
    residuals become non-zero."""
    from repro.core.framework import FrameworkConfig, HFLFramework
    sp, pop, fed = world

    def run(codec):
        cfg = FrameworkConfig(H=5, engine="fused", seed=0, alloc_steps=30,
                              compression=_cfg(codec))
        fw = HFLFramework(sp, pop, fed, cfg)
        recs = [fw.run_round(i) for i in range(2)]
        return fw, recs

    fw_n, recs_n = run("none")
    fw_c, recs_c = run("int8")
    assert recs_n[-1]["msg_bits"] / recs_c[-1]["msg_bits"] > 3.9
    # same channel realisations, smaller payload -> strictly cheaper round
    assert recs_c[-1]["E_i"] < recs_n[-1]["E_i"]
    assert recs_c[-1]["T_i"] < recs_n[-1]["T_i"]
    assert np.isfinite(recs_c[-1]["acc"])
    dev_resid, edge_resid = fw_c.codec_state
    assert any(float(jnp.abs(r).max()) > 0
               for r in jax.tree.leaves(dev_resid))


def test_sweep_codec_none_parity_and_compressed_lockstep(world):
    """SweepRunner: codec="none" reproduces the uncompressed sweep
    exactly; an active codec keeps host-loop, fused scan and the oracle
    host loop over the traced step in lockstep (same round_key stream)."""
    from repro.core.sweep import SweepRunner, build_scheduler
    sp, pop, fed = world
    _, pop1, fed1 = _world(seed=1)
    worlds = [(pop, fed), (pop1, fed1)]
    scheds = lambda: [build_scheduler("fedavg", f, sp, 4, seed=s)  # noqa: E731
                      for s, (_, f) in enumerate(worlds)]

    ref = SweepRunner(sp, worlds, alloc_steps=25).run(scheds(), 2)
    none = SweepRunner(sp, worlds, alloc_steps=25,
                       compression=_cfg("none")).run(scheds(), 2)
    assert np.array_equal(ref["acc"], none["acc"])
    assert ref["msg_bits_per_round"] == none["msg_bits_per_round"]

    r_c = SweepRunner(sp, worlds, alloc_steps=25,
                      compression=_cfg("int8"))
    host = r_c.run(scheds(), 2)
    fused = r_c.run(scheds(), 2, fused=True)
    oracle = r_c.run(scheds(), 2, fused="oracle")
    assert np.array_equal(host["acc"], fused["acc"])
    assert np.array_equal(oracle["acc"], fused["acc"])
    assert host["codec"] == "int8"
    assert ref["msg_bits_per_round"] / host["msg_bits_per_round"] > 3.9


def test_async_codec_none_parity_and_compressed_smoke(world):
    """AsyncHFLEngine: codec="none" is bitwise the pre-codec engine on a
    churny trace; int8 trains with ~4x smaller messages and streams the
    codec fields into its per-round record."""
    from repro.core.async_engine import AsyncConfig, AsyncHFLEngine
    sp, pop, fed = world
    ap = cm.AvailabilityParams(p_offline0=0.1, mean_up_s=900.0,
                               mean_down_s=120.0, straggler_frac=0.25,
                               straggler_scale=3.0)
    trace = cm.sample_availability(ap, pop.n_devices, seed=5)

    def run(compression):
        cfg = AsyncConfig(H=5, seed=0, alloc_steps=25, buffer_size=2,
                          compression=compression)
        eng = AsyncHFLEngine(sp, pop, fed, cfg, trace=trace)
        recs = [eng.step_round() for _ in range(2)]
        return eng, recs

    eng_ref, recs_ref = run(comp.CompressionConfig())
    eng_none, recs_none = run(_cfg("none"))
    for a, b in zip(jax.tree.leaves(eng_ref.model_params),
                    jax.tree.leaves(eng_none.model_params)):
        assert bool((a == b).all())
    assert recs_ref[-1]["msg_bits"] == recs_none[-1]["msg_bits"]

    eng_c, recs_c = run(_cfg("int8"))
    assert recs_c[-1]["codec"] == "int8"
    assert recs_none[-1]["msg_bits"] / recs_c[-1]["msg_bits"] > 3.9
    assert recs_c[-1]["uplink_bytes"] * 8 == pytest.approx(
        (recs_c[-1]["n_updates"] + pop.n_edges) * eng_c.uplink_bits)
    assert np.isfinite(recs_c[-1]["acc"])
    assert any(float(jnp.abs(r).max()) > 0
               for r in jax.tree.leaves(eng_c.dev_resid))


def test_sequential_engine_rejects_codec(world):
    from repro.core.framework import FrameworkConfig, HFLFramework
    sp, pop, fed = world
    with pytest.raises(ValueError, match="fused"):
        HFLFramework(sp, pop, fed,
                     FrameworkConfig(H=5, engine="sequential",
                                     compression=_cfg("int8")))
