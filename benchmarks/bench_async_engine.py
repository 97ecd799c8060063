"""Micro-benchmark: event-driven async HFL engine vs the sync round.

Sweeps fleet-fault scenarios at the paper's 50% / 30% scheduling ratios:

  * ``sync``      — degenerate always-on trace, wait-for-all buffers
                    (== the synchronous ``round_step`` by the parity
                    contract pinned in ``tests/test_async_engine.py``);
  * ``dropout``   — alternating-renewal churn with mean session length
                    tuned to the degenerate round makespan;
  * ``straggler`` — 30% of the fleet at 5x latency, FedBuff-style
                    partial buffers so flushes stop waiting on them.

For each case it records the accuracy-vs-virtual-wall-clock curve
(``acc_curve``: [t_virtual_s, accuracy] per round), the staleness/waste
accounting, and the *host* wall time per round (the event loop +
dispatch overhead — the perf-tracked ``*_ms`` fields), plus a direct
``round_step`` timing as the sync engine reference. Writes
``BENCH_async_engine.json`` so future PRs track the trajectory.

    PYTHONPATH=src python -m benchmarks.bench_async_engine [--smoke]

``--smoke`` runs tiny shapes and only asserts the benchmark runs
end-to-end and emits valid JSON (CI guard, no timing claims).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core import cost_model as cm
from repro.core.async_engine import AsyncConfig, AsyncHFLEngine
from repro.core.framework import round_step
from repro.data import make_dataset, partition_noniid

N_DEVICES = 20
N_EDGES = 4
ROUNDS = 4
ALLOC_STEPS = 100


def _world(n_devices, n_edges, n_train, n_test, L, Q, seed=0):
    sp = cm.SystemParams(n_devices=n_devices, n_edges=n_edges,
                         d_range=(30, 60), L=L, Q=Q)
    pop = cm.sample_population(sp, seed=seed)
    X, y, Xt, yt = make_dataset("fmnist_syn", n_train=n_train,
                                n_test=n_test, seed=seed)
    fed = partition_noniid(X, y, Xt, yt, n_devices=n_devices,
                           size_range=(15, 30), seed=seed)
    return sp, pop, fed


def _trace_for(case, sp, pop, fed, H, T_deg, seed):
    n = pop.n_devices
    if case == "sync":
        return cm.AvailabilityTrace.always_on(n), None
    if case == "dropout":
        ap = cm.AvailabilityParams(p_offline0=0.1, mean_up_s=T_deg,
                                   mean_down_s=T_deg / 4)
        return cm.sample_availability(ap, n, seed=seed,
                                      max_toggles=256), None
    if case == "straggler":
        ap = cm.AvailabilityParams(straggler_frac=0.3,
                                   straggler_scale=5.0)
        buf = max(1, H // (2 * pop.n_edges))
        return cm.sample_availability(ap, n, seed=seed), buf
    raise ValueError(case)


def _run_case(case, ratio, sp, pop, fed, rounds, T_deg, seed=0):
    H = max(2, int(round(ratio * pop.n_devices)))
    trace, buf = _trace_for(case, sp, pop, fed, H, T_deg, seed)
    cfg = AsyncConfig(H=H, scheduler="fedavg", alloc_steps=ALLOC_STEPS,
                      seed=seed, buffer_size=buf, staleness_exp=0.5)
    eng = AsyncHFLEngine(sp, pop, fed, cfg, trace=trace)
    t0 = time.perf_counter()
    for _ in range(rounds):
        eng.step_round(collect_eval=True)
    wall = (time.perf_counter() - t0) / rounds
    s = eng.summary()
    return {
        "case": case, "ratio": ratio, "H": H, "rounds": rounds,
        "buffer_size": buf,
        "acc_curve": [[r["t"], r["acc"]] for r in s["history"]],
        "final_acc": s["final_acc"], "t_virtual": s["t_virtual"],
        "T": s["T"], "E": s["E"], "n_updates": s["n_updates"],
        "n_stale": s["n_stale"], "n_aborted": s["n_aborted"],
        "wasted_j": s["wasted_j"],
        "wall_per_round_ms": wall * 1e3,
    }


def _sync_round_ms(sp, pop, fed, H, repeat=3, seed=0):
    """Direct fused ``round_step`` timing — the sync engine reference."""
    probe = AsyncHFLEngine(sp, pop, fed,
                           AsyncConfig(H=H, alloc_steps=ALLOC_STEPS,
                                       seed=seed))
    sched = np.arange(H)
    assign = jnp.asarray(sched % pop.n_edges, jnp.int32)
    spp = probe.sp

    def one(params):
        (out, _), _ = round_step(
            probe.apply_fn, spp, (params, (None, None)),
            pop.u[sched], pop.D[sched], pop.p[sched], pop.g[sched],
            pop.g_cloud, pop.B_m,
            probe.X[sched], probe.y[sched], probe.mask[sched],
            pop.D[sched], assign, 0.01,
            M=pop.n_edges, L=spp.L, Q=spp.Q, alloc_steps=ALLOC_STEPS)
        return jax.block_until_ready(out)

    params = one(probe.model_params)                 # warmup / compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        params = one(params)
    return (time.perf_counter() - t0) / repeat * 1e3


def run(out_json: str = "BENCH_async_engine.json",
        n_devices: int = N_DEVICES, n_edges: int = N_EDGES,
        rounds: int = ROUNDS, n_train: int = 1200, n_test: int = 300,
        L: int = 3, Q: int = 3):
    sp, pop, fed = _world(n_devices, n_edges, n_train, n_test, L, Q)

    result = {"N": n_devices, "M": n_edges, "rounds": rounds,
              "L": L, "Q": Q, "cases": []}
    for ratio in (0.5, 0.3):
        # degenerate probe pins the round makespan the churn scales from
        H = max(2, int(round(ratio * n_devices)))
        probe = AsyncHFLEngine(sp, pop, fed,
                               AsyncConfig(H=H, alloc_steps=ALLOC_STEPS))
        T_deg = probe.step_round(collect_eval=False)["T_i"]
        for case in ("sync", "dropout", "straggler"):
            r = _run_case(case, ratio, sp, pop, fed, rounds, T_deg)
            result["cases"].append(r)
            acc = "-" if r["final_acc"] is None else f"{r['final_acc']:.3f}"
            emit(f"async_engine/{case}_r{int(ratio * 100)}",
                 r["wall_per_round_ms"] * 1e3,
                 f"acc={acc};T_virtual={r['t_virtual']:.0f}s;"
                 f"stale={r['n_stale']};aborted={r['n_aborted']}")
        result[f"sync_round_r{int(ratio * 100)}_ms"] = _sync_round_ms(
            sp, pop, fed, H)

    # the event loop costs more host time than one fused dispatch; track
    # the overhead ratio so it can't silently explode
    sync_ms = result["sync_round_r50_ms"]
    async_ms = next(c["wall_per_round_ms"] for c in result["cases"]
                    if c["case"] == "sync" and c["ratio"] == 0.5)
    result["async_overhead_x"] = async_ms / max(sync_ms, 1e-9)
    emit("async_engine/overhead", 0.0,
         f"async={async_ms:.0f}ms;sync={sync_ms:.0f}ms;"
         f"x={result['async_overhead_x']:.1f}")

    os.makedirs(os.path.dirname(out_json) or ".", exist_ok=True)
    with open(out_json, "w") as fh:
        json.dump(result, fh, indent=1)
    return result


def run_smoke(out_json: str = "results/BENCH_async_engine_smoke.json"):
    """Tiny-shape CI guard: runs end-to-end, validates the emitted JSON."""
    result = run(out_json=out_json, n_devices=10, n_edges=3, rounds=2,
                 n_train=300, n_test=120, L=2, Q=2)
    with open(out_json) as fh:
        loaded = json.load(fh)
    assert loaded["N"] == 10 and len(loaded["cases"]) == 6
    for c in loaded["cases"]:
        assert c["wall_per_round_ms"] > 0
        assert len(c["acc_curve"]) == c["rounds"]
    sync = [c for c in loaded["cases"] if c["case"] == "sync"]
    assert all(c["n_stale"] == 0 and c["n_aborted"] == 0 for c in sync)
    emit("async_engine/smoke", 0.0, "pass=True")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes; assert-runs-and-emits-JSON only")
    args = ap.parse_args()
    if args.smoke:
        run_smoke()
    else:
        run()


if __name__ == "__main__":
    main()
