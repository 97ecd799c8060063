"""Bring-up smoke of the HFL system on TPU, at the paper's width.

    python chip_smoke.py              # one chip: the five phases below
    python chip_smoke.py --chips 4    # only the lane-sharded fused sweep

One chip (the paper's setting: N=100 devices, M=5 edges, H=50 scheduled,
D_n in 400..700, L=Q=5, the 114,383-parameter CNN):

  device   jax.devices()[0] must be a TPU; otherwise exit 2 before any
           work (no CPU fallback, no interpret-mode kernels).
  kernels  hier_agg masked / int8 masked-decode aggregation and the
           K-means distance kernel, compiled for the chip (the compiled
           program must hold a ``tpu_custom_call``) and compared with
           ``kernels/*/ref.py`` run at ``default_matmul_precision
           ("highest")`` and with float64 truth computed on the host.
  main     a short Algorithm-5 D3QN training (batched trainer, H=50),
           then 3 ``HFLFramework`` rounds with IKC + DRL and both Pallas
           kernels on, and the same 3 rounds with both off as the
           reference.
  sweep    ``SweepRunner.run(fused=True)`` on the same world with as many
           lanes as a compile says fit one chip, against
           ``fused="oracle"``.
  serve    ``launch.serve.run_serve``: N=100, M=5, diurnal traffic, H=50
           (or the largest H whose async dispatch program fits), 3 rounds.

Four chips (``--chips 4``): S=4 paper-width lanes of different worlds on
``sweep_mesh(4)``, one lane per chip, and lanes 0 and 3 re-run unsharded
as S=1 on one chip, which must match.

Each phase prints one JSON line: its checks, backend-compile and
trace/lower seconds, wall seconds and the device's ``peak_bytes_in_use``.
The last line is ``{"ok": true, "device": {...}}``; any failed check
exits 1 without it. Data and weights are made from ``--seed``. The
script runs in one process and starts none.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# Kernel tolerances, relative to the operands' scale (max |operand| for
# the aggregations, ||x||^2 + ||c||^2 for the K-means distances).
# - Against float64 truth (numpy, on the host): 2^-18. A kernel that
#   computes in f32 is within ~1e-6 (the distance kernel's blocked
#   accumulation over the P=114,383 weights, measured in interpret mode);
#   one bf16 pass of the same dot, the TPU's default for f32 operands, is
#   off by ~3e-5 on the distances and more on the aggregations, so it
#   fails.
# - Against ``kernels/*/ref.py`` at ``default_matmul_precision
#   ("highest")``, which is f32 itself: over the H=50 terms of an
#   aggregation the two agree to 2^-16. The reference's distances
#   subtract 2 x.c from ||x||^2 + ||c||^2, sums of P terms whose f32
#   rounding reaches ~2^-24 * sqrt(P) = 2^-15.6 of the scale, so they are
#   held to 2^-13 (that comparison cannot tell f32 from bf16; the
#   float64 one does).
KERNEL_TOL_F64 = 2.0 ** -18
AGG_TOL_REF = 2.0 ** -16
DIST_TOL_REF = 2.0 ** -13
# Cost parity between two compiles of the same allocation: the repo's
# fused/oracle contract (tests/test_sweep_fused.py).
COST_RTOL = 1e-4
# Accuracy parity between two runs that differ only in the aggregation
# backend or the dispatch layout: f32 rounding differences in the
# trained parameters may flip a handful of the 2,000 test predictions;
# 2% is 40 of them.
ACC_ATOL = 0.02
CHANCE = 0.1                 # 10 classes


@dataclasses.dataclass(frozen=True)
class Setting:
    """The paper's setting (``SystemParams`` defaults, Section VI)."""
    n_devices: int = 100
    n_edges: int = 5
    H: int = 50
    K: int = 10
    d_range: tuple = (400, 700)
    L: int = 5
    Q: int = 5
    n_train: int = 20_000
    n_test: int = 2_000
    rounds: int = 3
    episodes: int = 16           # D3QN episodes (two batched waves)
    alloc_steps: int = 200       # FrameworkConfig default
    P: int = 114_383             # paper CNN parameters (448 KB message)
    seed: int = 0


class CompileClock:
    """Seconds JAX spends compiling, from its monitoring events:
    ``backend`` is XLA compilation, persistent-cache reads included (what
    a warm cache saves); ``trace`` is tracing and lowering to MLIR."""

    def __init__(self, jax):
        self.backend = 0.0
        self.trace = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += duration
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.trace += duration


class Phase:
    """Checks and facts one phase reports on its JSON line."""

    def __init__(self):
        self.checks = {}
        self.info = {}

    def check(self, name, ok, **detail):
        self.checks[name] = {"ok": bool(ok), **detail}

    @property
    def ok(self):
        return all(c["ok"] for c in self.checks.values())


def _device_or_exit(n_chips):
    """The first device must be a TPU and there must be ``n_chips`` of
    them; otherwise exit 2 before any work."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"device check failed: {e}", file=sys.stderr)
        sys.exit(2)
    if devs[0].platform != "tpu" or len(devs) < n_chips:
        print(f"device check failed: need {n_chips} TPU chip(s), JAX "
              f"sees {len(devs)} x {devs[0].platform} "
              f"({devs[0].device_kind})", file=sys.stderr)
        sys.exit(2)
    return devs


def _peak_bytes(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return [s.get("peak_bytes_in_use") for s in stats]


def _free_bytes(device):
    s = device.memory_stats() or {}
    return s.get("bytes_limit", 0) - s.get("bytes_in_use", 0)


def _compile_fits(lowered, device, args_resident):
    """Compile ``lowered`` for the chip and say whether it fits the memory
    the device has left: ``(fits, bytes needed or the compiler's
    refusal)``. The TPU compiler itself refuses a program larger than
    the chip (RESOURCE_EXHAUSTED)."""
    import jax
    try:
        m = lowered.compile().memory_analysis()
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        return False, str(e).splitlines()[0]
    need = m.temp_size_in_bytes + m.output_size_in_bytes
    if not args_resident:
        need += m.argument_size_in_bytes
    return need <= _free_bytes(device), need


def _finite(x):
    import numpy as np
    return bool(np.all(np.isfinite(np.asarray(x, dtype=np.float64))))


def _build_world(st, seed):
    from repro.core import cost_model as cm
    from repro.data import make_dataset, partition_noniid
    sp = cm.SystemParams(n_devices=st.n_devices, n_edges=st.n_edges,
                         d_range=st.d_range, L=st.L, Q=st.Q)
    pop = cm.sample_population(sp, seed=seed)
    X, y, Xt, yt = make_dataset("fmnist_syn", n_train=st.n_train,
                                n_test=st.n_test, seed=seed)
    fed = partition_noniid(X, y, Xt, yt, n_devices=st.n_devices,
                           size_range=sp.d_range, seed=seed)
    return sp, pop, fed


# ------------------------------------------------------------- phases

def phase_kernels(st, ph, state):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.hier_agg import ref as agg_ref
    from repro.kernels.hier_agg.hier_agg import (
        masked_aggregate_batched_pallas,
        masked_decode_aggregate_batched_pallas)
    from repro.kernels.kmeans_dist.kmeans_dist import (
        pairwise_sq_dists_pallas)
    from repro.kernels.kmeans_dist.ref import pairwise_sq_dists_ref

    M, H, P, N = st.n_edges, st.H, st.P, st.n_devices
    k = jax.random.split(jax.random.PRNGKey(st.seed), 7)
    mask = jax.nn.one_hot(jax.random.randint(k[0], (H,), 0, M), M).T
    sizes = jax.random.randint(k[1], (H,), st.d_range[0],
                               st.d_range[1] + 1).astype(jnp.float32)
    params = 0.1 * jax.random.normal(k[2], (H, P))
    scales = jax.random.uniform(k[3], (H,), minval=1e-4, maxval=1e-3)
    q = jax.random.randint(k[4], (H, P), -127, 128).astype(jnp.int8)
    x = jax.random.normal(k[5], (N, P))
    c = x[:st.K] + 0.1 * jax.random.normal(k[6], (st.K, P))

    # float64 truth on the host, from the same inputs
    m64, s64, p64, sc64, q64, x64, c64 = (
        np.asarray(a, np.float64)
        for a in (mask, sizes, params, scales, q, x, c))

    def agg64(d):
        w = m64 * s64[None, :]
        return w / np.maximum(w.sum(1, keepdims=True), 1.0) @ d

    xx, cc = (x64 ** 2).sum(1)[:, None], (c64 ** 2).sum(1)[None, :]
    cases = (
        ("masked_aggregate", masked_aggregate_batched_pallas,
         (mask[None], sizes[None], params[None]), True,
         lambda: agg_ref.masked_aggregate_ref(mask, sizes, params),
         agg64(p64), np.abs(p64).max(), AGG_TOL_REF),
        ("masked_decode_aggregate_int8",
         masked_decode_aggregate_batched_pallas,
         (mask[None], sizes[None], scales[None], q[None]), True,
         lambda: agg_ref.masked_decode_aggregate_ref(mask, sizes, scales,
                                                     q),
         agg64(sc64[:, None] * q64), 127.0 * sc64.max(), AGG_TOL_REF),
        ("kmeans_pairwise_sq_dists", pairwise_sq_dists_pallas, (x, c),
         False, lambda: pairwise_sq_dists_ref(x, c),
         np.maximum(xx + cc - 2.0 * x64 @ c64.T, 0.0), xx + cc,
         DIST_TOL_REF),
    )
    for name, fn, args, batched, ref_fn, truth, scale, ref_tol in cases:
        t0 = time.perf_counter()
        compiled = fn.lower(*args, interpret=False).compile()
        t_compile = time.perf_counter() - t0
        ph.check(f"{name}_is_mosaic_kernel",
                 "tpu_custom_call" in compiled.as_text())
        out = compiled(*args)
        out = out[0] if batched else out
        out.block_until_ready()
        runs = []
        for _ in range(5):
            t0 = time.perf_counter()
            compiled(*args).block_until_ready()
            runs.append(time.perf_counter() - t0)
        with jax.default_matmul_precision("highest"):
            ref = ref_fn()
        out64 = np.asarray(out, np.float64)
        for against, want, tol in (("ref", np.asarray(ref, np.float64),
                                    ref_tol),
                                   ("float64", truth, KERNEL_TOL_F64)):
            err = np.abs(out64 - want)
            rel = float(np.max(err / scale))
            ph.check(f"{name}_vs_{against}", rel <= tol,
                     max_abs_err=float(err.max()), max_err_over_scale=rel,
                     tol=tol, shape=list(out.shape))
        ref_err = np.abs(np.asarray(ref, np.float64) - truth)
        ph.info[f"{name}_ref_vs_float64_max_err_over_scale"] = float(
            np.max(ref_err / scale))
        ph.info[f"{name}_compile_s"] = t_compile
        ph.info[f"{name}_run_ms_median"] = float(np.median(runs)) * 1e3


def phase_main(st, ph, state):
    import jax
    import numpy as np

    from repro.core.framework import FrameworkConfig, HFLFramework
    from repro.drl.train import D3QNTrainer
    from repro.kernels.hier_agg import ops as agg_ops
    from repro.kernels.kmeans_dist import ops as km_ops

    # under this script the kernels run compiled, never interpreted
    ph.check("kernels_compiled_not_interpreted",
             not agg_ops._default_interpret()
             and not km_ops._default_interpret())
    sp, pop, fed = _build_world(st, st.seed)
    state["world"] = (sp, pop, fed)

    t0 = time.perf_counter()
    trainer = D3QNTrainer(sp, H=st.H, hfel_transfer=30, hfel_exchange=60,
                          alloc_steps=60,
                          eps_decay_episodes=max(1, st.episodes // 2),
                          seed=st.seed)
    trainer.train(max_episodes=st.episodes, verbose=False)
    ph.info["d3qn_train_s"] = time.perf_counter() - t0
    ph.check("d3qn_trained", trainer.step > 0
             and all(_finite(x) for x in jax.tree.leaves(trainer.params)),
             episodes=trainer.episode, td_updates=trainer.step,
             avg_return=float(np.mean(trainer.reward_history)))
    state["drl_params"] = trainer.params

    runs = {}
    for label, kernel in (("kernel", True), ("reference", False)):
        cfg = FrameworkConfig(scheduler="ikc", assigner="drl", H=st.H,
                              K=st.K, alloc_steps=st.alloc_steps,
                              seed=st.seed, agg_kernel=kernel,
                              use_kernel=kernel)
        t0 = time.perf_counter()
        fw = HFLFramework(sp, pop, fed, cfg, drl_params=trainer.params)
        ph.info[f"{label}_setup_s"] = time.perf_counter() - t0
        recs, scheds, assigns, secs = [], [], [], []
        for i in range(1, st.rounds + 1):
            t0 = time.perf_counter()
            recs.append(fw.run_round(i))
            secs.append(time.perf_counter() - t0)
            scheds.append(fw.last_sched)
            assigns.append(fw.last_assign)
        ph.info[f"{label}_round_s"] = secs
        ph.info[f"{label}_acc"] = [r["acc"] for r in recs]
        ph.info[f"{label}_ari"] = fw.clustering_stats.get("ari")
        runs[label] = (recs, scheds, assigns)

    (rk, sk, ak), (rr, sr, ar) = runs["kernel"], runs["reference"]
    ph.check("same_schedules", all(np.array_equal(a, b)
                                   for a, b in zip(sk, sr)))
    ph.check("same_assignments", all(np.array_equal(a, b)
                                     for a, b in zip(ak, ar)))
    for key in ("T_i", "E_i"):
        a = np.array([r[key] for r in rk])
        b = np.array([r[key] for r in rr])
        dev = float(np.max(np.abs(a - b) / np.abs(b)))
        ph.check(f"{key}_kernel_vs_reference", dev <= COST_RTOL,
                 kernel=a.tolist(), reference=b.tolist(), max_rel=dev,
                 tol=COST_RTOL)
    acc_k = np.array([r["acc"] for r in rk])
    acc_r = np.array([r["acc"] for r in rr])
    ph.check("acc_finite_above_chance",
             _finite(acc_k) and acc_k[-1] > CHANCE and acc_r[-1] > CHANCE,
             chance=CHANCE)
    gap = float(np.max(np.abs(acc_k - acc_r)))
    ph.check("acc_kernel_vs_reference", gap <= ACC_ATOL, max_abs=gap,
             tol=ACC_ATOL)


def _lower_fused(runner, schedulers, rounds, run_kw):
    """Lower (not run) the one-dispatch sweep program that
    ``runner.run(fused=True)`` would dispatch, going through ``run``
    itself: the dispatch is intercepted where it would execute."""
    import repro.core.sweep as sw

    class _Lowered(Exception):
        pass

    orig = sw.sweep_scan

    def lower_instead(*a, **k):
        raise _Lowered(orig.lower(*a, **k))

    sw.sweep_scan = lower_instead
    try:
        runner.run(schedulers, rounds, fused=True, **run_kw)
    except _Lowered as e:
        return e.args[0]
    finally:
        sw.sweep_scan = orig
    raise RuntimeError("run(fused=True) did not reach sweep_scan")


def phase_sweep(st, ph, state):
    import jax
    import numpy as np

    from repro.core.sweep import SweepRunner, build_scheduler

    if "drl_params" not in state:
        raise RuntimeError("needs the main phase's world and D3QN params")
    sp, pop, fed = state["world"]
    run_kw = dict(assign="drl", drl_params=state["drl_params"])

    def schedulers(S):
        # IKC clustering per lane seed (host state machines: deep-copied
        # before each run so every run starts from the same state)
        return [build_scheduler("ikc", fed, sp, st.H, K=st.K, seed=s,
                                use_kernel=True) for s in range(S)]

    def runner(S):
        return SweepRunner(sp, [(pop, fed)] * S, alloc_steps=st.alloc_steps,
                           agg_kernel=True)

    # lanes: the largest S in 1, 2, 4, ... whose program fits the chip
    S, sizes = 1, {}
    scheds = schedulers(2)
    while S < 8:
        scheds += schedulers(2 * S)[len(scheds):]
        t0 = time.perf_counter()
        lowered = _lower_fused(runner(2 * S), copy.deepcopy(scheds[:2 * S]),
                               st.rounds, run_kw)
        fits, need = _compile_fits(lowered, jax.devices()[0], True)
        sizes[2 * S] = {"fits": fits, "temp_plus_out_bytes": need,
                        "compile_s": time.perf_counter() - t0}
        if not fits:
            break
        S *= 2
    ph.info["lanes_probed"] = sizes
    ph.info["lanes"] = S

    r = runner(S)
    out = {}
    for mode in (True, "oracle"):
        t0 = time.perf_counter()
        out[mode] = r.run(copy.deepcopy(scheds[:S]), st.rounds,
                          fused=mode, **run_kw)
        np.asarray(out[mode]["acc"])
        ph.info[f"fused_{mode}_s"] = time.perf_counter() - t0
    f, o = out[True], out["oracle"]
    ph.check("fused_one_dispatch", f["n_dispatches"] == 1
             and o["n_dispatches"] == st.rounds,
             fused=f["n_dispatches"], oracle=o["n_dispatches"])
    for key in ("T_i", "E_i"):
        dev = float(np.max(np.abs(f[key] - o[key]) / np.abs(o[key])))
        ph.check(f"{key}_fused_vs_oracle", dev <= COST_RTOL, max_rel=dev,
                 tol=COST_RTOL)
    gap = float(np.max(np.abs(f["acc"] - o["acc"])))
    ph.check("acc_fused_vs_oracle", gap <= ACC_ATOL, max_abs=gap,
             tol=ACC_ATOL)
    ph.check("acc_finite_above_chance",
             _finite(f["acc"]) and bool(np.all(f["acc"][:, -1] > CHANCE)),
             acc=f["acc"].tolist())


def phase_serve(st, ph, state):
    import jax
    import jax.numpy as jnp

    from repro.core.async_engine import _train_dispatched
    from repro.core.compression import CompressionConfig
    from repro.launch.serve import run_serve
    from repro.models.cnn import cnn_init
    from repro.models.spec import cnn_spec

    # the async engine's per-dispatch program carries the whole (H, ...)
    # cohort and trains the dispatched lanes in chunks: compile it for
    # the chip before serving
    f32 = jnp.float32
    params = jax.eval_shape(lambda k: cnn_init(k, (28, 28), 1),
                            jax.random.PRNGKey(0))
    Dmax = st.d_range[1]
    H, M, sizes = st.H, st.n_edges, {}
    while True:
        def rows(n, tree=params):
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype),
                tree)
        t0 = time.perf_counter()
        lowered = _train_dispatched.lower(
            cnn_spec().apply_fn, (rows(H), None), rows(M),
            jax.ShapeDtypeStruct((H,), jnp.int32),
            jax.ShapeDtypeStruct((H,), jnp.bool_),
            jax.ShapeDtypeStruct((H, Dmax, 28, 28, 1), f32),
            jax.ShapeDtypeStruct((H, Dmax), jnp.int32),
            jax.ShapeDtypeStruct((H, Dmax), f32), 0.01, None,
            L=st.L, codec=CompressionConfig())
        fits, need = _compile_fits(lowered, jax.devices()[0], False)
        sizes[H] = {"fits": fits, "bytes": need,
                    "compile_s": time.perf_counter() - t0}
        if fits or H <= 1:
            break
        H = max(1, H * 4 // 5)
    ph.info["dispatch_program"] = sizes
    ph.check("serves_at_paper_H", H == st.H, H=H)

    lines = []
    t0 = time.perf_counter()
    summary = run_serve(n_devices=st.n_devices, n_edges=st.n_edges, H=H,
                        rounds=st.rounds, traffic="diurnal",
                        d_range=st.d_range, L=st.L, Q=st.Q,
                        n_train=st.n_train, n_test=st.n_test, seed=st.seed,
                        log=lines.append)
    ph.info["serve_s"] = time.perf_counter() - t0
    recs = [json.loads(x) for x in lines]
    ph.info["acc"] = [r["acc"] for r in recs]
    ph.info["t_virtual_s"] = summary["t_virtual"]
    ph.check("served_rounds", summary["rounds"] == st.rounds
             and len(recs) == st.rounds, rounds=summary["rounds"])
    ph.check("updates_aggregated", summary["n_updates"] > 0,
             n_updates=summary["n_updates"], n_stale=summary["n_stale"],
             n_aborted=summary["n_aborted"])
    acc = summary["final_acc"]
    ph.check("acc_finite_above_chance",
             acc is not None and math.isfinite(acc) and acc > CHANCE,
             acc=acc)
    ph.check("costs_finite", _finite([r["T_i"] for r in recs]
                                     + [r["E_i"] for r in recs]))


def phase_sharded_sweep(st, ph, state):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.sweep import SweepRunner, build_scheduler
    from repro.launch.mesh import sweep_mesh

    n = 4
    worlds = [_build_world(st, s) for s in range(n)]
    sp = worlds[0][0]
    scheds = [build_scheduler("ikc", fed, sp, st.H, K=st.K, seed=s)
              for s, (_, _, fed) in enumerate(worlds)]
    pairs = [(pop, fed) for _, pop, fed in worlds]
    kw = dict(alloc_steps=st.alloc_steps, agg_kernel=True)
    run_kw = dict(assign="geo", fused=True)

    mesh = sweep_mesh(n)
    sharded = SweepRunner(sp, pairs, shard=True, mesh=mesh, **kw)
    placed = [(sh.device.id, sh.data.shape[0])
              for sh in sharded.X_b.addressable_shards]
    ph.check("one_lane_per_chip",
             sorted(d for d, _ in placed) == sorted(
                 d.id for d in mesh.devices.flat)
             and all(rows == 1 for _, rows in placed), placement=placed)
    t0 = time.perf_counter()
    out4 = sharded.run(copy.deepcopy(scheds), st.rounds,
                       seeds=list(range(n)), **run_kw)
    ph.info["sharded_s"] = time.perf_counter() - t0
    ph.info["sharded_acc"] = out4["acc"].tolist()
    ph.info["peak_bytes_per_chip"] = _peak_bytes(mesh.devices.flat)
    params0 = jax.device_get(sharded.params0)

    for lane in (0, n - 1):
        single = SweepRunner(sp, [pairs[lane]], **kw)
        # the S=1 runner would draw lane 0's init; give it this lane's
        single.params0 = jax.tree.map(
            lambda x, s=lane: jnp.asarray(x[s:s + 1]), params0)
        t0 = time.perf_counter()
        out1 = single.run([copy.deepcopy(scheds[lane])], st.rounds,
                          seeds=[lane], **run_kw)
        ph.info[f"lane{lane}_single_s"] = time.perf_counter() - t0
        for key in ("T_i", "E_i"):
            a, b = out4[key][lane], out1[key][0]
            dev = float(np.max(np.abs(a - b) / np.abs(b)))
            ph.check(f"lane{lane}_{key}_sharded_vs_single",
                     dev <= COST_RTOL, max_rel=dev, tol=COST_RTOL)
        gap = float(np.max(np.abs(out4["acc"][lane] - out1["acc"][0])))
        ph.check(f"lane{lane}_acc_sharded_vs_single", gap <= ACC_ATOL,
                 sharded=out4["acc"][lane].tolist(),
                 single=out1["acc"][0].tolist(), max_abs=gap, tol=ACC_ATOL)
    ph.check("acc_finite_above_chance", _finite(out4["acc"])
             and bool(np.all(out4["acc"][:, -1] > CHANCE)))


ONE_CHIP = (("kernels", phase_kernels), ("main", phase_main),
            ("sweep", phase_sweep), ("serve", phase_serve))
FOUR_CHIPS = (("sharded_sweep", phase_sharded_sweep),)


def run_phases(phases, st, clock, devices):
    """Run each phase, print its JSON line; returns the failed names."""
    failed, state = [], {}
    for name, fn in phases:
        ph = Phase()
        b0, t0, w0 = clock.backend, clock.trace, time.perf_counter()
        try:
            fn(st, ph, state)
        except Exception:  # report it and go on to the next phase
            traceback.print_exc()
            ph.check("completed", False)
        line = {"phase": name, "ok": ph.ok,
                "seconds": time.perf_counter() - w0,
                "backend_compile_s": clock.backend - b0,
                "trace_lower_s": clock.trace - t0,
                "peak_bytes_in_use": _peak_bytes(devices[:1])[0],
                "checks": ph.checks, **ph.info}
        print(json.dumps(line, default=float), flush=True)
        if not ph.ok:
            failed.append(name)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = _device_or_exit(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock(jax)
    st = Setting(seed=args.seed)
    dev = devices[0]
    print(json.dumps({"phase": "device", "ok": True,
                      "platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices), "jax": jax.__version__,
                      "compile_cache": cache_dir}), flush=True)
    w0 = time.perf_counter()
    phases = FOUR_CHIPS if args.chips == 4 else ONE_CHIP
    failed = run_phases(phases, st, clock, devices)
    print(json.dumps({"total_s": time.perf_counter() - w0,
                      "backend_compile_s": clock.backend,
                      "trace_lower_s": clock.trace}), flush=True)
    if failed:
        print(f"FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
