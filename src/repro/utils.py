"""Shared small utilities: pytree math, rng splitting, shape helpers."""
from __future__ import annotations

import functools
import os
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


def tree_zeros_like(tree: Pytree) -> Pytree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(tree: Pytree, s) -> Pytree:
    return jax.tree.map(lambda x: x * s, tree)


def tree_axpy(alpha, x: Pytree, y: Pytree) -> Pytree:
    """alpha * x + y, leaf-wise."""
    return jax.tree.map(lambda a, b: alpha * a + b, x, y)


def tree_weighted_sum(trees: Iterable[Pytree], weights) -> Pytree:
    """sum_i w_i * tree_i (weights need not be normalised)."""
    trees = list(trees)
    weights = list(weights)
    assert len(trees) == len(weights) and trees, "empty weighted sum"
    out = tree_scale(trees[0], weights[0])
    for t, w in zip(trees[1:], weights[1:]):
        out = tree_axpy(w, t, out)
    return out


def tree_dot(a: Pytree, b: Pytree):
    leaves = jax.tree.map(lambda x, y: jnp.vdot(x, y), a, b)
    return functools.reduce(jnp.add, jax.tree.leaves(leaves))


def tree_norm(tree: Pytree):
    return jnp.sqrt(tree_dot(tree, tree))


def tree_size(tree: Pytree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree: Pytree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_flatten_to_vector(tree: Pytree) -> jnp.ndarray:
    """Concatenate all leaves into a single f32 vector (for clustering)."""
    leaves = jax.tree.leaves(tree)
    return jnp.concatenate([jnp.ravel(x).astype(jnp.float32) for x in leaves])


def tree_cast(tree: Pytree, dtype) -> Pytree:
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )


def split_keys(key: jax.Array, n: int):
    return list(jax.random.split(key, n))


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, multiple: int) -> int:
    return ceil_div(x, multiple) * multiple


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024 or unit == "PiB":
            return f"{n:.2f} {unit}"
        n /= 1024
    return f"{n:.2f} PiB"


def human_flops(n: float) -> str:
    for unit in ("FLOP", "KFLOP", "MFLOP", "GFLOP", "TFLOP", "PFLOP", "EFLOP"):
        if abs(n) < 1000 or unit == "EFLOP":
            return f"{n:.2f} {unit}"
        n /= 1000
    return f"{n:.2f} EFLOP"


def dbm_to_watt(dbm: float) -> float:
    return 10 ** (dbm / 10.0) / 1000.0


def db_to_linear(db) -> float:
    return 10 ** (db / 10.0)


def stable_hash(s: str) -> int:
    """Deterministic (non-salted) string hash for seeding."""
    h = 2166136261
    for c in s.encode():
        h = (h ^ c) * 16777619 & 0xFFFFFFFF
    return h


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to the fixed
    ``<repo>/.jax_cache``, so later processes find what earlier ones
    compiled. Call from ``main``, never at import.
    Returns the cache directory in use.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache_dir = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def forced_device_env(n_devices: int, pythonpath=()) -> dict:
    """Child-process env for N emulated host devices.

    ``--xla_force_host_platform_device_count`` only takes effect before
    jax import, so multi-device CPU work runs in spawned children — this
    builds their env in ONE place (the ``multidevice`` test fixture and
    ``benchmarks/bench_sweep_shard`` both use it): any pre-existing
    device-count flag in the inherited XLA_FLAGS is stripped (last-flag
    -wins would otherwise depend on the caller's environment), the CPU
    platform is pinned, and ``pythonpath`` entries are prepended.
    """
    env = os.environ.copy()
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={n_devices}"])
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [*pythonpath] + ([env["PYTHONPATH"]]
                         if env.get("PYTHONPATH") else []))
    return env
