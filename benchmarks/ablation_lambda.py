"""Ablation: the λ delay/energy trade-off (paper §III-C: "the value of λ
is determined based on the specific requirements of the practical
scenarios") and non-IID severity (majority_frac) sensitivity of IKC.

Standalone (not part of benchmarks.run defaults):
    PYTHONPATH=src python -m benchmarks.ablation_lambda
"""
from __future__ import annotations

import json
import os

import numpy as np

from benchmarks.common import emit
from repro.core import cost_model as cm
from repro.core.assignment import HFELAssigner
from repro.core.assignment.hfel import total_objective
from repro.drl.train import make_training_population


def lambda_sweep(lams=(0.1, 1.0, 10.0), H=20, n_pops=4):
    """Higher λ must never increase optimised delay T_i (and generally
    trades energy for it) — the allocator/assigner react to λ."""
    rows = {}
    for lam in lams:
        sp = cm.SystemParams(n_edges=5, lam=lam)
        hfel = HFELAssigner(sp, n_transfer=60, n_exchange=120,
                            alloc_steps=100)
        Ts, Es = [], []
        for p in range(n_pops):
            pop = make_training_population(sp, H, seed=900 + p)
            rng = np.random.default_rng(p)
            a, _ = hfel.assign(pop, np.arange(H), rng)
            _, T_m, E_m = total_objective(sp, pop, np.arange(H),
                                          np.asarray(a), alloc_steps=100)
            Ts.append(T_m.max())
            Es.append(E_m.sum())
        rows[lam] = (float(np.mean(Ts)), float(np.mean(Es)))
        emit(f"ablation/lambda_{lam}", 0.0,
             f"T_i={rows[lam][0]:.1f};E_i={rows[lam][1]:.2f}")
    lam_sorted = sorted(rows)
    t_monotone = all(rows[a][0] >= rows[b][0] * 0.9
                     for a, b in zip(lam_sorted, lam_sorted[1:]))
    emit("ablation/lambda_tradeoff", 0.0,
         f"delay_nonincreasing_with_lambda={t_monotone}")
    os.makedirs("results", exist_ok=True)
    with open("results/ablation_lambda.json", "w") as f:
        json.dump({str(k): v for k, v in rows.items()}, f, indent=1)
    return rows


def noniid_severity(fracs=(0.3, 0.8), iters=5, H=20):
    """IKC's edge over FedAvg should GROW with non-IID severity (the
    whole point of class-balanced scheduling)."""
    import jax
    from repro.core.hfl import (evaluate_in_batches, hfl_global_iteration,
                                pad_device_data)
    from repro.core.scheduling import (FedAvgScheduler, IKCScheduler,
                                       run_device_clustering)
    from repro.data import make_dataset, partition_noniid
    from repro.models import cnn

    out = {}
    for frac in fracs:
        X, y, Xt, yt = make_dataset("fmnist_syn", n_train=5000, n_test=800,
                                    seed=1)
        fed = partition_noniid(X, y, Xt, yt, n_devices=40,
                               size_range=(50, 90), majority_frac=frac,
                               seed=1)
        Xp, yp, mask = pad_device_data(fed)
        key = jax.random.PRNGKey(0)
        sp = cm.SystemParams(n_devices=40, n_edges=5)
        accs = {}
        for method in ("ikc", "fedavg"):
            if method == "ikc":
                mini = cnn.mini_init(key)
                crop = jax.vmap(cnn.mini_preprocess)(
                    Xp[:, :, :, :, :1], jax.random.split(key, 40))
                labels, _ = run_device_clustering(
                    key, cnn.mini_apply, mini, crop, yp, mask, 10, sp.L, 0.01)
                sched = IKCScheduler(labels, max(1, H // 10))
            else:
                sched = FedAvgScheduler(40, H)
            params = cnn.cnn_init(key, (28, 28), 1)
            rng = np.random.default_rng(0)
            acc = 0.0
            for i in range(iters):
                sel = np.asarray(sched.schedule(rng))
                assign = np.asarray(sel % sp.n_edges)
                params, _ = hfl_global_iteration(
                    cnn.cnn_apply, params, Xp[sel], yp[sel], mask[sel],
                    np.asarray(fed.sizes[sel], np.float32), assign,
                    M=sp.n_edges, L=sp.L, Q=sp.Q, lr=0.03)
                acc = evaluate_in_batches(cnn.cnn_apply, params,
                                          fed.X_test, fed.y_test)
            accs[method] = float(acc)
        gap = accs["ikc"] - accs["fedavg"]
        out[frac] = {"ikc": accs["ikc"], "fedavg": accs["fedavg"],
                     "gap": gap}
        emit(f"ablation/noniid_{frac}", 0.0,
             f"ikc={accs['ikc']:.3f};fedavg={accs['fedavg']:.3f};gap={gap:+.3f}")
    with open("results/ablation_noniid.json", "w") as f:
        json.dump({str(k): v for k, v in out.items()}, f, indent=1)
    return out


if __name__ == "__main__":
    print("name,us_per_call,derived")
    lambda_sweep()
    noniid_severity()
