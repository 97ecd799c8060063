"""Shared benchmark utilities + the reduced-scale world used by the paper
experiments (CPU container: scales recorded in EXPERIMENTS.md; relative
orderings are what we validate against the paper)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Callable

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from repro.core.cost_model import SystemParams, sample_population
from repro.data import make_dataset, partition_noniid

# Reduced-scale defaults (paper: N=100, M=5, D_n in [400,700], 5 repeats)
N_DEVICES = 40
N_EDGES = 5
SIZE_RANGE = (50, 90)
REPEATS = 2


def timed(fn: Callable, *args, repeat: int = 3, **kw):
    fn(*args, **kw)
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeat
    return out, dt * 1e6  # us


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def measure_on_devices(module: str, measure: Callable, cfg: dict,
                       n_emu: int) -> dict:
    """``measure(lanes, n_devices, **cfg)`` where the devices are.

    On a TPU host it runs in this process on ``jax.devices()``: a chip
    belongs to one process, so no child may take it. Elsewhere the run is
    a CPU rehearsal in a ``python -m <module> --child`` process pinned to
    ``n_emu`` emulated host devices (the device-count flag must be set
    before jax import); the child writes its result JSON to ``--out``."""
    import jax

    from repro.utils import forced_device_env

    if jax.default_backend() == "tpu":
        cfg = dict(cfg)
        return measure(tuple(cfg.pop("lanes")), len(jax.devices()), **cfg)
    env = forced_device_env(
        n_emu, pythonpath=(os.path.join(REPO_ROOT, "src"), REPO_ROOT))
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tf:
        out_path = tf.name
    try:
        proc = subprocess.run(
            [sys.executable, "-m", module, "--child", "--out", out_path,
             "--config", json.dumps({**cfg, "n_emu": n_emu})],
            env=env, cwd=REPO_ROOT, capture_output=True, text=True,
            timeout=3600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"{module} child failed:\n{proc.stdout}\n{proc.stderr}")
        with open(out_path) as fh:
            return json.load(fh)
    finally:
        os.unlink(out_path)


def make_world(dataset: str = "fmnist_syn", seed: int = 0,
               n_devices: int = N_DEVICES):
    sp = SystemParams(n_devices=n_devices, n_edges=N_EDGES,
                      d_range=SIZE_RANGE)
    pop = sample_population(sp, seed=seed)
    X, y, Xt, yt = make_dataset(dataset, n_train=6000, n_test=1000, seed=seed)
    fed = partition_noniid(X, y, Xt, yt, n_devices=n_devices,
                           size_range=SIZE_RANGE, seed=seed)
    return sp, pop, fed
