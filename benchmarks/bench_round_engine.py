"""Micro-benchmark: sequential per-edge round vs the fused round engine.

Measures, at the acceptance scale (M=10 edges, H=50 devices, CPU):
  * the seed's sequential path — M separate ``allocate`` jit calls with
    per-edge host round-trips, then ``round_cost`` and Algorithm-1
    training as separate dispatches;
  * the fused ``round_step`` — one jitted program for the whole round;
  * the allocation stage alone (per-edge loop vs ``allocate_all_edges``).

Emits CSV lines (benchmarks.common.emit) and writes
``BENCH_round_engine.json`` so future PRs can track the perf trajectory.

    PYTHONPATH=src python -m benchmarks.bench_round_engine [--smoke]

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
from repro.core import resource as ra
from repro.core.framework import round_step
from repro.core.hfl import hfl_global_iteration

M_EDGES = 10
H_DEVICES = 50
ALLOC_STEPS = 300
REPEAT = 5


def _linear_apply(params, X):
    return X.reshape(X.shape[0], -1) @ params["w"]


def _world(seed: int = 0, m_edges: int = M_EDGES,
           h_devices: int = H_DEVICES):
    sp = cm.SystemParams(n_devices=h_devices, n_edges=m_edges)
    pop = cm.sample_population(sp, seed=seed)
    rng = np.random.default_rng(seed)
    sched = np.arange(h_devices)
    assign = rng.integers(0, m_edges, h_devices)
    Dmax = 8
    X = jnp.asarray(rng.normal(0, 1, (h_devices, Dmax, 2, 2, 1))
                    .astype(np.float32))
    y = jnp.asarray(rng.integers(0, 3, (h_devices, Dmax)).astype(np.int32))
    mask = jnp.ones((h_devices, Dmax), jnp.float32)
    w0 = {"w": jnp.asarray(rng.normal(0, 0.1, (4, 3)).astype(np.float32))}
    return sp, pop, sched, assign, X, y, mask, w0


def sequential_alloc(sp, pop, sched, assign, alloc_steps: int = ALLOC_STEPS):
    """Seed-style per-edge loop with host round-trips."""
    H = len(sched)
    b = np.zeros(H)
    f = np.zeros(H)
    for m in range(pop.n_edges):
        mask = jnp.asarray(assign == m)
        res = ra.allocate(sp, pop.u[sched], pop.D[sched], pop.p[sched],
                          pop.g[sched, m], pop.B_m[m], mask,
                          steps=alloc_steps)
        sel = assign == m
        b[sel] = np.asarray(res.b)[sel]
        f[sel] = np.asarray(res.f)[sel]
    return b, f


def sequential_round(sp, pop, sched, assign, X, y, mask, w0,
                     alloc_steps: int = ALLOC_STEPS):
    b, f = sequential_alloc(sp, pop, sched, assign, alloc_steps)
    T_i, E_i, _, _ = cm.round_cost(sp, pop, jnp.asarray(sched),
                                   jnp.asarray(assign), jnp.asarray(b),
                                   jnp.asarray(f))
    w, _ = hfl_global_iteration(_linear_apply, w0, X, y, mask, pop.D[sched],
                                jnp.asarray(assign), M=pop.n_edges, L=sp.L,
                                Q=sp.Q, lr=0.05)
    jax.block_until_ready((w, T_i, E_i))
    return float(T_i), float(E_i)


def fused_round(sp, pop, sched, assign, X, y, mask, w0,
                alloc_steps: int = ALLOC_STEPS):
    (w, _), (T_i, E_i, _, _, _, _) = round_step(
        _linear_apply, sp, (w0, (None, None)), pop.u[sched], pop.D[sched],
        pop.p[sched], pop.g[sched], pop.g_cloud, pop.B_m, X, y, mask,
        pop.D[sched], jnp.asarray(assign), 0.05, M=pop.n_edges, L=sp.L,
        Q=sp.Q, alloc_steps=alloc_steps)
    jax.block_until_ready((w, T_i, E_i))
    return float(T_i), float(E_i)


def _time(fn, *args, repeat: int = REPEAT):
    fn(*args)                                        # warmup / compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
    return out, (time.perf_counter() - t0) / repeat


def run(out_json: str = "BENCH_round_engine.json", m_edges: int = M_EDGES,
        h_devices: int = H_DEVICES, alloc_steps: int = ALLOC_STEPS,
        repeat: int = REPEAT, check_speedup: bool = True):
    sp, pop, sched, assign, X, y, mask, w0 = _world(
        m_edges=m_edges, h_devices=h_devices)

    # --- allocation stage only
    _, t_seq_alloc = _time(
        lambda: sequential_alloc(sp, pop, sched, assign, alloc_steps),
        repeat=repeat)
    _, t_fus_alloc = _time(lambda: jax.block_until_ready(
        ra.allocate_all_edges(sp, pop, sched, assign, steps=alloc_steps)),
        repeat=repeat)

    # --- full round
    (T_seq, E_seq), t_seq_round = _time(
        lambda: sequential_round(sp, pop, sched, assign, X, y, mask, w0,
                                 alloc_steps), repeat=repeat)
    (T_fus, E_fus), t_fus_round = _time(
        lambda: fused_round(sp, pop, sched, assign, X, y, mask, w0,
                            alloc_steps), repeat=repeat)

    assert abs(T_seq - T_fus) / T_seq < 1e-4, (T_seq, T_fus)
    assert abs(E_seq - E_fus) / E_seq < 1e-4, (E_seq, E_fus)

    result = {
        "M": m_edges, "H": h_devices, "alloc_steps": alloc_steps,
        "repeat": repeat,
        "sequential_alloc_ms": t_seq_alloc * 1e3,
        "fused_alloc_ms": t_fus_alloc * 1e3,
        "alloc_speedup": t_seq_alloc / t_fus_alloc,
        "sequential_round_ms": t_seq_round * 1e3,
        "fused_round_ms": t_fus_round * 1e3,
        "round_speedup": t_seq_round / t_fus_round,
        "fused_allocations_per_s": m_edges / t_fus_alloc,
    }
    os.makedirs(os.path.dirname(out_json) or ".", exist_ok=True)
    with open(out_json, "w") as fh:
        json.dump(result, fh, indent=1)

    emit("round_engine/alloc_sequential", t_seq_alloc * 1e6,
         f"M={m_edges};H={h_devices}")
    emit("round_engine/alloc_fused", t_fus_alloc * 1e6,
         f"speedup={result['alloc_speedup']:.1f}x;"
         f"allocs_per_s={result['fused_allocations_per_s']:.0f}")
    emit("round_engine/round_sequential", t_seq_round * 1e6,
         f"T_i={T_seq:.2f};E_i={E_seq:.2f}")
    emit("round_engine/round_fused", t_fus_round * 1e6,
         f"speedup={result['round_speedup']:.1f}x")
    if check_speedup:
        emit("round_engine/claim_fused_3x", 0.0,
             f"pass={result['round_speedup'] >= 3.0};"
             f"round={result['round_speedup']:.1f}x;"
             f"alloc={result['alloc_speedup']:.1f}x")
    return result


def run_smoke(out_json: str = "results/BENCH_round_engine_smoke.json"):
    """Tiny-shape CI guard: runs end-to-end, validates the emitted JSON."""
    result = run(out_json=out_json, m_edges=3, h_devices=8, alloc_steps=25,
                 repeat=1, check_speedup=False)
    with open(out_json) as fh:
        loaded = json.load(fh)
    assert loaded["fused_round_ms"] > 0 and loaded["M"] == 3
    emit("round_engine/smoke", 0.0, "pass=True")
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
