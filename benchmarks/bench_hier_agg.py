"""Micro-benchmark: Pallas ``hier_agg`` aggregation vs the XLA einsum.

Two measurements, tracked in ``BENCH_hier_agg.json``:

* **raw aggregate** — the fused masked-weight kernel
  (``masked_aggregate``: one-hot + sizes in, normalised panel built
  in-kernel) against the einsum oracle that materialises the (M, H)
  weight panel, sweeping the flattened model size P from 10^4 to 10^7
  at the paper's cohort shapes (M=5/H=50 reduced scale, M=10/H=100
  HFEL-comparison scale). P is the axis that matters: the kernel's
  whole point is streaming the (H, P) delta matrix through VMEM once
  in 512-lane blocks.
* **end-to-end round** — the fused ``round_step`` with
  ``agg_kernel=True`` vs ``False`` at a model large enough for the
  aggregation to register (a wide linear probe), pinning that the route
  stays plumbed through the real engine and that both backends return
  identical costs.

On the CPU container the kernel runs in Pallas *interpret* mode, so the
absolute kernel timings are emulation overhead, not TPU bandwidth — the
JSON records them anyway (layout-ready for a TPU run, where the same
sweep exercises the MXU path).

    PYTHONPATH=src python -m benchmarks.bench_hier_agg [--smoke]

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
from repro.core.framework import round_step
from repro.kernels.hier_agg.ops import masked_aggregate
from repro.kernels.hier_agg.ref import masked_aggregate_ref

SHAPES = ((5, 50), (10, 100))              # (M, H): paper / HFEL scales
P_SWEEP = (10_000, 100_000, 300_000, 1_000_000, 10_000_000)
# Pallas interpret mode emulates the grid step-by-step, so its wall time
# grows superlinearly in P/BP on CPU (measured: ~30 ms at P=1e4, ~2.2 s
# at P=1e5 for M=10/H=100). Off-TPU the sweep stops at this cap and
# records the larger P rows as skipped — the sweep axis (and the JSON
# layout) stays intact for a TPU run, where the compiled kernel streams
# all five points.
P_CAP_INTERPRET = 300_000
REPEAT = 3
ROUND_FEATS = 512                          # linear-probe width for e2e


def _time(fn, *args, repeat: int = REPEAT):
    jax.block_until_ready(fn(*args))             # warmup / compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeat


def _agg_inputs(M: int, H: int, P: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    assign = rng.integers(0, M, H)
    mask = jnp.asarray(
        (assign[None, :] == np.arange(M)[:, None]).astype(np.float32))
    sizes = jnp.asarray(rng.uniform(50, 90, H).astype(np.float32))
    d = jnp.asarray(rng.normal(0, 1, (H, P)).astype(np.float32))
    return mask, sizes, d


def _sweep_raw(shapes, p_sweep, repeat):
    ref_jit = jax.jit(masked_aggregate_ref)
    interpret = jax.default_backend() != "tpu"
    rows = []
    for M, H in shapes:
        for P in p_sweep:
            if interpret and P > P_CAP_INTERPRET:
                rows.append({"M": M, "H": H, "P": P,
                             "skipped": "interpret-mode emulation too "
                                        f"slow past P={P_CAP_INTERPRET}"})
                emit(f"hier_agg/raw_M{M}_H{H}_P{P}", 0.0,
                     "skipped=interpret")
                continue
            mask, sizes, d = _agg_inputs(M, H, P)
            rep = repeat if P <= 100_000 else 1
            t_k = _time(lambda: masked_aggregate(mask, sizes, d),
                        repeat=rep)
            t_e = _time(lambda: ref_jit(mask, sizes, d), repeat=rep)
            gb = (H * P + M * P) * 4 / 1e9   # streamed bytes, f32
            rows.append({
                "M": M, "H": H, "P": P,
                "kernel_ms": t_k * 1e3, "einsum_ms": t_e * 1e3,
                "kernel_over_einsum": t_k / t_e,
                "kernel_gbps": gb / t_k, "einsum_gbps": gb / t_e,
            })
            emit(f"hier_agg/raw_M{M}_H{H}_P{P}", t_k * 1e6,
                 f"einsum_us={t_e * 1e6:.1f};ratio={t_k / t_e:.2f}")
    return rows


def _linear_apply(params, X):
    return X.reshape(X.shape[0], -1) @ params["w"]


def _round_world(M, H, feats, seed=0):
    sp = cm.SystemParams(n_devices=H, n_edges=M)
    pop = cm.sample_population(sp, seed=seed)
    rng = np.random.default_rng(seed)
    sched = np.arange(H)
    assign = rng.integers(0, M, H)
    Dmax = 8
    X = jnp.asarray(rng.normal(0, 1, (H, Dmax, feats)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 3, (H, Dmax)).astype(np.int32))
    mask = jnp.ones((H, Dmax), jnp.float32)
    w0 = {"w": jnp.asarray(rng.normal(0, 0.1, (feats, 3))
                           .astype(np.float32))}
    return sp, pop, sched, assign, X, y, mask, w0


def _e2e_round(M, H, feats, alloc_steps, repeat):
    sp, pop, sched, assign, X, y, mask, w0 = _round_world(M, H, feats)

    def one(agg_kernel):
        (w, _), (T_i, E_i, _, _, _, _) = round_step(
            _linear_apply, sp, (w0, (None, None)), pop.u[sched], pop.D[sched],
            pop.p[sched], pop.g[sched], pop.g_cloud, pop.B_m, X, y, mask,
            pop.D[sched], jnp.asarray(assign), 0.05, M=M, L=sp.L, Q=sp.Q,
            alloc_steps=alloc_steps, agg_kernel=agg_kernel)
        return w, T_i, E_i

    t_kernel = _time(lambda: one(True), repeat=repeat)
    t_einsum = _time(lambda: one(False), repeat=repeat)
    w_k, T_k, _ = one(True)
    w_e, T_e, _ = one(False)
    np.testing.assert_allclose(np.asarray(w_k["w"]), np.asarray(w_e["w"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(T_k), float(T_e), rtol=1e-6)
    return {
        "M": M, "H": H, "model_params": feats * 3,
        "alloc_steps": alloc_steps,
        "round_kernel_ms": t_kernel * 1e3,
        "round_einsum_ms": t_einsum * 1e3,
        "kernel_over_einsum": t_kernel / t_einsum,
    }


def run(out_json: str = "BENCH_hier_agg.json", shapes=SHAPES,
        p_sweep=P_SWEEP, repeat: int = REPEAT, round_feats: int = ROUND_FEATS,
        alloc_steps: int = 100):
    result = {
        "backend": jax.default_backend(),
        "interpret": jax.default_backend() != "tpu",
        "repeat": repeat,
        "raw_aggregate": _sweep_raw(shapes, p_sweep, repeat),
        "round_step": _e2e_round(shapes[0][0], shapes[0][1], round_feats,
                                 alloc_steps, repeat),
    }
    os.makedirs(os.path.dirname(out_json) or ".", exist_ok=True)
    with open(out_json, "w") as fh:
        json.dump(result, fh, indent=1)

    rs = result["round_step"]
    emit("hier_agg/round_kernel", rs["round_kernel_ms"] * 1e3,
         f"einsum_ms={rs['round_einsum_ms']:.1f};"
         f"ratio={rs['kernel_over_einsum']:.2f};"
         f"params={rs['model_params']}")
    return result


def run_smoke(out_json: str = "results/BENCH_hier_agg_smoke.json"):
    """Tiny-shape CI guard: runs end-to-end, validates the emitted JSON."""
    result = run(out_json=out_json, shapes=((3, 8),), p_sweep=(4096,),
                 repeat=1, round_feats=16, alloc_steps=25)
    with open(out_json) as fh:
        loaded = json.load(fh)
    assert loaded["raw_aggregate"][0]["kernel_ms"] > 0
    assert loaded["round_step"]["round_kernel_ms"] > 0
    emit("hier_agg/smoke", 0.0, "pass=True")
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
