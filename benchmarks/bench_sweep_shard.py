"""Micro-benchmark: device-sharded sweep lanes vs the single-device vmap.

Measures the fused sweep round at S ∈ {8, 32, 128} seed lanes in four
engine variants — {single-device, lane-sharded} x {whole-axis vmap,
``lane_chunk=1`` cache-blocked} — and tracks lanes/sec in
``BENCH_sweep_shard.json``. The workload is the allocation-heavy sweep
profile (M=10 edges, H=8 cohort, 500 solver steps, minimal local
training): the regime where the single-device program is serialized
(the convex-solver loop of tiny ops runs single-threaded on CPU) and
lane parallelism has real headroom; conv-heavy rounds are
DRAM-bandwidth-bound on CPU and gain little from extra *emulated*
devices.

The headline ``speedup_vs_single`` compares the best sharded variant
against the shipped PR-1..4 baseline (single-device whole-axis vmap,
what ``SweepRunner`` ran before this PR) — 2.24x at S=128 on the
committed 2-core run. ``speedup_vs_best_single`` decomposes it: the
chunked execution alone (available to both paths via ``lane_chunk``)
buys ~1.8x of that on CPU by keeping each chunk's working set
cache-resident, and device-parallelism the rest (~1.3x) — bounded by
the host's cores under emulation (all 8 devices share them), by the
device count on real hardware.

Because ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` must be
set before jax import, the measurement runs in a spawned child process
(``--child``); the parent validates the JSON and emits the CSV lines.
All variants are measured inside the same 8-device child (forcing the
device count shifts single-device round time by <10%, measured).

    PYTHONPATH=src python -m benchmarks.bench_sweep_shard [--smoke]

``--smoke`` spawns a tiny 2-device child and only asserts the benchmark
runs end-to-end and emits valid JSON (CI guard, no timing claims).
"""
from __future__ import annotations

import argparse
import json
import os
import time

LANES = (8, 32, 128)
N_EMU_DEVICES = 8
ALLOC_STEPS = 500
M_EDGES = 10
N_DEVICES = 40
H_COHORT = 8
ROUNDS = 5


# --------------------------------------------------------------- child

def _measure(lanes, n_emu, *, n_devices, m_edges, h_cohort, alloc_steps,
             rounds, n_train, n_test):
    """Runs inside the forced-device-count child: time the fused sweep
    round single-device vs sharded at each lane count."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cost_model import SystemParams, sample_population
    from repro.core.sweep import (SweepRunner, sweep_round,
                                  sweep_round_sharded)
    from repro.data import make_dataset, partition_noniid

    emulated = jax.default_backend() == "cpu"
    if emulated:
        assert len(jax.devices()) == n_emu, (
            f"child expected {n_emu} devices, got {len(jax.devices())}")
    dev = jax.devices()[0]
    sp = SystemParams(n_devices=n_devices, n_edges=m_edges, L=1, Q=1,
                      d_range=(1, 2))
    pop = sample_population(sp, seed=0)
    X, y, Xt, yt = make_dataset("fmnist_syn", n_train=n_train,
                                n_test=n_test, seed=0)
    fed = partition_noniid(X, y, Xt, yt, n_devices=n_devices,
                          size_range=(1, 2), seed=0)

    out = {"config": {"M": m_edges, "N": n_devices, "H": h_cohort,
                      "alloc_steps": alloc_steps, "rounds": rounds,
                      "emulated_devices": n_emu if emulated else 0,
                      "host_cores": os.cpu_count(),
                      "mode": "cpu-emulation" if emulated else dev.platform,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}},
           "lanes": {}}
    # four engine variants per lane count: {single, sharded} x
    # {vmap, chunked}. "single" (whole-axis vmap on one device) is the
    # PR-1..4 shipped baseline; "chunked" is the lane_chunk=1
    # cache-blocked execution — measured separately on BOTH paths so the
    # headline sharded win decomposes honestly into its cache-blocking
    # and device-parallel parts.
    variants = (("single", False, None), ("single_chunked", False, 1),
                ("shard", True, None), ("shard_chunked", True, 1))
    for S in lanes:
        row = {}
        for key, shard, chunk in variants:
            runner = SweepRunner(sp, [(pop, fed)] * S, lr=0.02,
                                 alloc_steps=alloc_steps, model_seed=0,
                                 shard=shard, lane_chunk=chunk)
            spp = dataclasses.replace(sp,
                                      model_bits=float(runner.model_bits))
            n = runner.S_pad
            sched = jnp.asarray(np.stack([np.arange(h_cohort)] * n))
            assign = jnp.asarray(
                np.stack([np.arange(h_cohort) % m_edges] * n))
            done = np.zeros(n, bool)
            done[S:] = True
            kw = dict(M=m_edges, L=1, Q=1, alloc_steps=alloc_steps,
                      lane_chunk=chunk, done_b=jnp.asarray(done))
            fn = sweep_round
            if shard:
                fn, kw["mesh"] = sweep_round_sharded, runner.mesh

            def call():
                _, (T, _), _ = fn(runner.apply_fn, spp, runner.params0,
                               runner.u_b, runner.D_b, runner.p_b,
                               runner.g_b, runner.g_cloud_b, runner.B_m_b,
                               runner.X_b, runner.y_b, runner.mask_b,
                               runner.D_b, sched, assign, 0.02, **kw)
                jax.block_until_ready(T)

            call()                                    # warmup / compile
            # min over rounds: on an oversubscribed emulation host the
            # mean is noise-dominated (±30% run-to-run, measured); the
            # per-path floor is the stable engine number.
            times = []
            for _ in range(rounds):
                t0 = time.perf_counter()
                call()
                times.append(time.perf_counter() - t0)
            dt = min(times)
            row[f"{key}_round_ms"] = dt * 1e3
            row[f"{key}_round_mean_ms"] = sum(times) / len(times) * 1e3
            row[f"{key}_lanes_per_s"] = S / dt
        best_shard = max(row["shard_lanes_per_s"],
                         row["shard_chunked_lanes_per_s"])
        best_single = max(row["single_lanes_per_s"],
                          row["single_chunked_lanes_per_s"])
        row["speedup_vs_single"] = best_shard / row["single_lanes_per_s"]
        row["speedup_vs_best_single"] = best_shard / best_single
        out["lanes"][str(S)] = row
    # On emulated CPU devices every program shares the host cores, so
    # the sharded-vs-best-single gain is bounded by host_cores, not by
    # the device count: that decomposed metric gates at a fraction of
    # the core-count ceiling; the headline vs the shipped single-device
    # vmap engine gates at the full 2x.
    cores = os.cpu_count() or 1
    out["best_single_speedup_gate"] = min(2.0, 0.6 * cores)
    return out


def _child_main(args):
    cfg = json.loads(args.config)
    result = _measure(tuple(cfg.pop("lanes")), cfg.pop("n_emu"), **cfg)
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=1)


# -------------------------------------------------------------- parent

def run(out_json: str = "BENCH_sweep_shard.json", lanes=LANES,
        n_emu: int = N_EMU_DEVICES, rounds: int = ROUNDS,
        check_claims: bool = True):
    from benchmarks.common import emit, measure_on_devices

    result = measure_on_devices(
        "benchmarks.bench_sweep_shard", _measure,
        dict(lanes=list(lanes), n_devices=N_DEVICES, m_edges=M_EDGES,
             h_cohort=H_COHORT, alloc_steps=ALLOC_STEPS, rounds=rounds,
             n_train=120, n_test=20), n_emu)
    os.makedirs(os.path.dirname(out_json) or ".", exist_ok=True)
    with open(out_json, "w") as fh:
        json.dump(result, fh, indent=1)

    for S, row in result["lanes"].items():
        emit(f"sweep_shard/S{S}_single", row["single_round_ms"] * 1e3,
             f"lanes_per_s={row['single_lanes_per_s']:.1f};"
             f"chunked={row['single_chunked_lanes_per_s']:.1f}")
        emit(f"sweep_shard/S{S}_shard", row["shard_round_ms"] * 1e3,
             f"lanes_per_s={row['shard_lanes_per_s']:.1f};"
             f"chunked={row['shard_chunked_lanes_per_s']:.1f};"
             f"speedup={row['speedup_vs_single']:.2f}x;"
             f"vs_best_single={row['speedup_vs_best_single']:.2f}x")
    if check_claims:
        hi = result["lanes"][str(max(int(k) for k in result["lanes"]))]
        sp = hi["speedup_vs_single"]
        sp_dec = hi["speedup_vs_best_single"]
        gate = result["best_single_speedup_gate"]
        cores = result["config"]["host_cores"]
        emit("sweep_shard/claim_shard_2x", 0.0,
             f"pass={sp >= 2.0};speedup_vs_single_vmap={sp:.2f}x")
        emit("sweep_shard/claim_shard_vs_best_single", 0.0,
             f"pass={sp_dec >= gate};speedup={sp_dec:.2f}x;"
             f"gate={gate:.2f}x;host_cores={cores}")
    return result


def run_smoke(out_json: str = "results/BENCH_sweep_shard_smoke.json"):
    """Tiny-shape CI guard: 2 emulated devices, asserts the sharded and
    single paths both run end-to-end and the JSON is well-formed."""
    from benchmarks.common import emit, measure_on_devices

    result = measure_on_devices(
        "benchmarks.bench_sweep_shard", _measure,
        dict(lanes=[2, 4], n_devices=8, m_edges=2, h_cohort=4,
             alloc_steps=25, rounds=1, n_train=60, n_test=20),
        2)
    os.makedirs(os.path.dirname(out_json) or ".", exist_ok=True)
    with open(out_json, "w") as fh:
        json.dump(result, fh, indent=1)
    with open(out_json) as fh:
        loaded = json.load(fh)
    if loaded["config"]["mode"] == "cpu-emulation":
        assert loaded["config"]["emulated_devices"] == 2
    assert all(row["shard_round_ms"] > 0 and row["single_round_ms"] > 0
               for row in loaded["lanes"].values())
    emit("sweep_shard/smoke", 0.0, "pass=True")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes; assert-runs-and-emits-JSON only")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    ap.add_argument("--config", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        _child_main(args)
    elif args.smoke:
        run_smoke()
    else:
        run()


if __name__ == "__main__":
    main()
